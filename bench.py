#!/usr/bin/env python
"""Headline benchmark: gossip-steps/sec at 256 virtual workers.

Measures the MATCHA hot path of BASELINE.json's north star — 256 virtual
workers, ResNet-20-sized flat parameter state, MATCHA schedule at budget 0.5 —
and prints ONE final JSON line:

    {"metric": ..., "value": N, "unit": "gossip_steps_per_sec",
     "vs_baseline": N, "value_chunked": ..., "achieved_tflops": ..., "mfu": ...}

``value`` is the **per-step (training-regime) rate**: the fused Pallas kernel
with ``chunk=1``, i.e. every gossip step executes its own ``W_t @ x`` exactly
as a training loop that interleaves one gossip step per SGD step would
(/root/reference/communicator.py:133-158 is the per-iteration hot path this
models).  ``vs_baseline`` is value / 5000 (the ≥5k steps/sec north-star
target; the reference publishes no numbers of its own — BASELINE.md).
``value_chunked`` is the secondary consensus-only-chain rate where runs of
``chunk`` mixing matrices are pre-composed (exact by associativity but the
intermediate iterates are never materialized, so it does not apply to
training).  The roofline fields report the kernel's position against the
chip's peak MXU throughput and HBM bandwidth.

The measurement runs in this process, on the device JAX finds, and every
record names that device.  Without a TPU it exits non-zero and prints no
metric, unless ``--smoke`` or ``--platform cpu`` asked for the CPU.  Every
timing stops on a scalar the host has read back (dispatch is asynchronous).

Flags:
  --smoke        tiny sizes for a CPU sanity run
  --backend B    fused|dense|gather|shard_map|choco   (default fused —
                 the Pallas VMEM-resident multi-step W-stack kernel; dense
                 is the per-step MXU path)
  --dtype D      bf16|f32                     (default bf16)
  --steps N      scan length per timing rep
  --chunk S      chain-composition chunk for the secondary chunked number
                 (default 256; 0 disables the chunked measurement)
  --block-d B    Pallas D-block size (0 = sweep {2048, 4096, 8192} on the
                 per-step kernel and keep the best)
  --workers N    virtual workers (default 256)
  --platform P   cpu|tpu: pin the JAX platform (default: JAX's own choice)
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

NORTH_STAR = 5000.0


def build(args):
    import jax
    import jax.numpy as jnp

    from matcha_tpu import topology as tp
    from matcha_tpu.models import ResNet
    from matcha_tpu.schedule import matcha_schedule

    n = args.workers
    if args.smoke:
        n, dim, steps = 16, 4096, 50
    else:
        # flat dimension = actual ResNet-20/CIFAR-10 parameter count.
        # eval_shape: the count needs shapes only — an actual init would
        # compile and run the whole init program for four numbers
        model = ResNet(depth=20, num_classes=10)
        variables = jax.eval_shape(
            lambda k: model.init(k, jnp.zeros((1, 32, 32, 3)), train=False),
            jax.random.PRNGKey(0))
        dim = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(variables["params"]))
        steps = args.steps

    # the 256-worker build (CVX solve + decomposition) is minutes of host
    # time: set-up, outside every timed window
    edges = tp.make_graph("geometric", n, seed=1)
    sched = matcha_schedule(tp.decompose(edges, n, seed=1), n,
                            iterations=steps, budget=0.5, seed=0)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(n, dim)).astype(np.float32))
    return sched, x, steps, dim


def time_backend(backend, sched, x, steps, dtype, chunk=1, block_d=None,
                 w_window=1, reps=3, return_rates=False):
    import jax
    import jax.numpy as jnp

    from matcha_tpu.communicator import make_choco, make_decen

    compute_dtype = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    mesh = None
    if backend == "shard_map":
        from matcha_tpu.parallel import worker_mesh

        mesh = worker_mesh()  # all local devices; workers fold onto them
    if backend == "choco":
        # compressed gossip at the reference ratio (BASELINE config 4)
        comm = make_choco(sched, ratio=0.9, consensus_lr=0.1)
    else:
        comm = make_decen(sched, backend=backend, mesh=mesh,
                          compute_dtype=compute_dtype, chunk=chunk,
                          block_d=block_d, w_window=w_window)
    flags = jnp.asarray(sched.flags, jnp.float32)
    if backend in ("dense", "fused"):
        x = x.astype(compute_dtype)  # state rides in the wire dtype end-to-end

    # Timing stops on a (tiny) device->host readback: dispatch is
    # asynchronous, and a clock that stops at the enqueue inflates
    # throughput 100x+.  Summing an 8-column slice of the result keeps the
    # transfer negligible while serializing on the whole chain (every
    # output column depends on all T steps).
    run = jax.jit(lambda x: jnp.sum(comm.run(x, flags)[0][:, :8].astype(jnp.float32)))
    float(run(x))  # compile + warmup, forced to completion
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(run(x))
        rates.append(steps / (time.perf_counter() - t0))
    if return_rates:
        return max(rates), rates
    return max(rates)


def overlap_wire_grid(sched, x, steps, n, dim, backend="dense", reps=2):
    """The overlap × wire-dtype grid (ISSUE 4 tentpole): gossip-chain rate
    and wire bytes for every (eager|pipelined) × (f32|bf16) cell.

    ``overlap="1step"`` drives ``Communicator.run_overlapped`` — the exact
    software-pipelined schedule the train loop runs (issue at t, consume at
    t+1), arithmetically the same W-chain after its drain.  On a single
    chip the pipeline cannot buy wall-clock (there is no ICI to hide), so
    these cells check mechanics and the bytes accounting; a speed-up on
    several chips is not measured.  ``bytes_per_step`` is the dense
    roofline traffic model at the cell's wire width — bf16 halves it; the
    state rides in the wire dtype end-to-end like every dense/fused bench
    measurement (master-params-f32 is a *training-loop* property, modeled
    there, not in the chain microbench).
    """
    import jax
    import jax.numpy as jnp

    from matcha_tpu.communicator import make_decen

    steps = min(steps, len(sched.flags))
    flags = jnp.asarray(np.asarray(sched.flags)[:steps], jnp.float32)
    cells = []
    for wire in ("f32", "bf16"):
        comm = make_decen(sched, backend=backend, wire_dtype=wire)
        xw = x.astype(jnp.bfloat16 if wire == "bf16" else jnp.float32)
        for overlap in ("off", "1step"):
            runner = comm.run if overlap == "off" else comm.run_overlapped
            run = jax.jit(lambda v, r=runner: jnp.sum(
                r(v, flags)[0][:, :8].astype(jnp.float32)))
            float(run(xw))  # compile + warmup (forced readback, see above)
            rates = []
            for _ in range(reps):
                t0 = time.perf_counter()
                float(run(xw))
                rates.append(steps / (time.perf_counter() - t0))
            bytes_el = 2 if wire == "bf16" else 4
            cells.append({
                "overlap": overlap, "wire_dtype": wire,
                "value": round(max(rates), 1),
                "unit": "gossip_steps_per_sec",
                "bytes_per_step": (2.0 * n * dim + n * n) * bytes_el,
            })
    return cells


def staleness_grid(sched, x, steps, n, dim, backend="dense",
                   ks=(1, 2, 4), local_steps=(1, 4), reps=2):
    """The bounded-staleness grid (ISSUE 14): cells for staleness k ×
    local_steps L, each carrying

    * the *measured* k-deep pipelined gossip-chain rate
      (``Communicator.run_pipelined`` over the L-thinned flag stream — the
      exact ring arithmetic the async train loop runs; on a single chip
      this validates mechanics and ring overhead, not a wall-clock win),
    * the *modeled* fleet wall-clock under a planted period-4 straggler
      (``plan.cost.straggler_step_times`` → ``simulate_fleet_wallclock``):
      barrier-executor seconds vs bounded-staleness seconds, and the
      straggler tax recovered, and
    * the barrier tax priced through the attribution plane's own
      ``critical_path_report`` (per-epoch gate/median/tax over synthetic
      per-worker heartbeats) — the same pricing PR 11 applies to real
      runs, so the recovered fraction is stated in its currency.

    The k=1, L=1 cell IS the barrier model (one outstanding exchange =
    wait on every peer's previous round), which anchors the comparison.
    """
    import jax
    import jax.numpy as jnp

    from matcha_tpu.communicator import make_decen
    from matcha_tpu.obs.attribution import critical_path_report
    from matcha_tpu.plan import simulate_fleet_wallclock, \
        straggler_step_times

    steps = min(steps, len(sched.flags))
    comm = make_decen(sched, backend=backend)
    rounds = 64
    # the straggler scenario and its critical-path pricing are grid-level
    # facts (they do not depend on k or L): per-worker round times with
    # the planted period-4 straggler, and the barrier tax in the
    # attribution plane's own currency — critical_path_report over
    # synthetic per-worker heartbeats (8 rounds per "epoch"), exactly the
    # PR 11 pricing path
    t_rounds = straggler_step_times(n, rounds, straggler=0, period=4,
                                    slowdown=4.0, seed=1)
    spe = 8
    beats = {f"w{i}": [
        {"epoch": e,
         "comp_time": float(t_rounds[e * spe:(e + 1) * spe, i].sum()),
         "comm_time": 0.0}
        for e in range(rounds // spe)] for i in range(n)}
    cp = critical_path_report((), heartbeats_by_host=beats)
    cells = []
    for k in ks:
        for L in local_steps:
            flags = np.asarray(sched.flags, np.float32)[:steps].copy()
            if L > 1:
                flags[np.arange(steps) % L != 0] = 0.0
            fj = jnp.asarray(flags)
            run = jax.jit(lambda v, kk=k: jnp.sum(
                comm.run_pipelined(v, fj, staleness=kk)[0][:, :8]
                .astype(jnp.float32)))
            float(run(x))  # compile + warmup (forced readback, see above)
            rates = []
            for _ in range(reps):
                t0 = time.perf_counter()
                float(run(x))
                rates.append(steps / (time.perf_counter() - t0))
            # modeled fleet wall-clock of this cell's execution contract
            model = simulate_fleet_wallclock(t_rounds, staleness=k,
                                             local_steps=L)
            cells.append({
                "staleness": k, "local_steps": L,
                "value": round(max(rates), 1),
                "unit": "gossip_steps_per_sec",
                "model": {kk: (round(v, 4) if isinstance(v, float) else v)
                          for kk, v in model.items()},
                "barrier_tax_priced_seconds":
                    round(cp["total_tax_seconds"], 4),
            })
    return cells


def elision_grid(sched, x, steps, n, dim, backends=("skip", "dense"),
                 local_steps=(1, 4), reps=2):
    """The universal-elision A/B (ISSUE 19): backend × local_every cells,
    each carrying the *measured* chain rate and the compiled-cost ledger's
    per-epoch gossip-attributed boundary bytes
    (``obs.costs.elision_epoch_costs``).

    The A/B by construction: ``skip`` runs its historical flag-thinned
    stream through ``Communicator.run`` — thinning at the flag level, the
    only backend that elided before the restructure — while ``dense``
    runs ``Communicator.run_elided``, the chain-level twin of the
    restructured epoch's cond-in-body scan.  At L=4 every backend's bytes
    column must show the thinned steps' traffic *gone* (≥2× vs L=1, the
    acceptance pin), and the measured column shows what that buys in
    steps/s on this chip.
    """
    import jax
    import jax.numpy as jnp

    from matcha_tpu.communicator import make_decen
    from matcha_tpu.obs.costs import elision_epoch_costs

    steps = min(steps, len(sched.flags))
    cells = []
    for backend in backends:
        comm = make_decen(sched, backend=backend)
        for L in local_steps:
            flags = np.asarray(sched.flags, np.float32)[:steps].copy()
            if backend == "skip":
                # skip's own semantics: thin the flag stream, run it all
                if L > 1:
                    flags[np.arange(steps) % L != 0] = 0.0
                fj = jnp.asarray(flags)
                run = jax.jit(lambda v: jnp.sum(
                    comm.run(v, fj)[0][:, :8].astype(jnp.float32)))
            else:
                fj = jnp.asarray(flags)
                run = jax.jit(lambda v, LL=L: jnp.sum(
                    comm.run_elided(v, fj, LL)[0][:, :8]
                    .astype(jnp.float32)))
            float(run(x))  # compile + warmup (forced readback)
            rates = []
            for _ in range(reps):
                t0 = time.perf_counter()
                float(run(x))
                rates.append(steps / (time.perf_counter() - t0))
            try:
                costs = elision_epoch_costs(n, dim, sched.decomposed,
                                            backend=backend, t_steps=steps,
                                            local_every=L)
                ledger = {
                    "hbm_bytes_per_epoch":
                        costs["gossip_hbm_bytes_per_epoch"],
                    "hbm_bytes_per_step": costs["gossip_hbm_bytes_per_step"],
                    "exec_steps": costs["exec_steps"],
                }
            except Exception as e:  # noqa: BLE001 — ledger is a refinement
                print(f"# elision ledger failed ({backend}, L={L}): "
                      f"{type(e).__name__}: {str(e)[:200]}", file=sys.stderr)
                ledger = {}
            cells.append({
                "backend": backend, "local_every": L,
                "value": round(max(rates), 1),
                "unit": "gossip_steps_per_sec",
                **ledger,
            })
    return cells


def roofline(backend, value, n, dim, dtype, block_d=2048, chunk=1):
    """Per-step FLOP and HBM-byte model for the Pallas/MXU backends,
    evaluated at the measured rate.  The fused kernel's traffic model is
    derived in matcha_tpu/parallel/pallas_gossip.py:1-23: per chain of T
    steps the state moves once (2·N·D) and the W_t stack streams per
    D-block ((D/block_d)·T·N²); per step that amortizes to
    2·N·D/T + ceil(D/bd)·N².  The dense backend re-materializes the state
    every step (2·N·D + N²).

    With chunked composition (chunk=S > 1) each *original* step costs
    2·N²·D/S apply-FLOPs on the MXU plus ~2·N³ f32 compose-FLOPs (the
    [N,N]×[N,N] chunk products), and the streamed-W traffic shrinks ×S —
    FLOPs/bytes below count the work actually executed, so MFU stays an
    honest utilization figure, not an algorithmic speedup claim.

    Utilization is against the device's row of the one chip table
    (``obs.costs.CHIP_PEAKS``); a device that is not in it raises.  An
    explicit CPU run has no peaks and carries no utilization."""
    import jax

    from matcha_tpu.obs.costs import chip_peaks

    bytes_el = 2 if dtype == "bf16" else 4
    flops_per_step = 2.0 * n * n * dim
    d_blocks = -(-dim // block_d)
    if backend == "fused":
        bytes_per_step = d_blocks * n * n * bytes_el  # + 2·N·D/T ≈ 0 at T≫1
        if chunk > 1:
            flops_per_step = flops_per_step / chunk + 2.0 * n**3
            # compose reads the full f32 W stack once and writes 1/S of it
            bytes_per_step = bytes_per_step / chunk + (1 + 1 / chunk) * n * n * 4
    else:
        bytes_per_step = (2.0 * n * dim + n * n) * bytes_el
    achieved_tflops = flops_per_step * value / 1e12
    achieved_gbps = bytes_per_step * value / 1e9
    out = {
        "flops_per_step": flops_per_step,
        "bytes_per_step": bytes_per_step,
        "achieved_tflops": round(achieved_tflops, 2),
        "achieved_gbps": round(achieved_gbps, 2),
    }
    device = jax.devices()[0]
    if device.platform != "cpu":
        peak_tflops, peak_gbps = chip_peaks(device.device_kind)
        out["mfu"] = round(achieved_tflops / peak_tflops, 4)
        out["hbm_frac"] = round(achieved_gbps / peak_gbps, 4)
    return out


def _device_record():
    """The device every record names, as JAX reports it."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _refine(record, key, grid_fn, *grid_args):
    """Attach one grid refinement to an already-printed record and print
    the superset; a grid that fails is reported and costs only itself."""
    try:
        record[key] = grid_fn(*grid_args)
    except Exception as e:  # noqa: BLE001 — grid is a refinement
        print(f"# {key} failed: {type(e).__name__}: {str(e)[:200]}",
              file=sys.stderr)
        return
    print(json.dumps(record))
    sys.stdout.flush()


def measure(args, device) -> int:
    """The measurement; prints JSON records on stdout, each a superset of
    the one before, so the last line is the most complete.  ``device`` is
    :func:`_device_record`'s, named in every record."""
    sched, x, steps, dim = build(args)
    n = x.shape[0]

    if args.backend != "fused":
        # single-backend mode (diagnostics): time it per-step and report
        value = time_backend(args.backend, sched, x, steps, args.dtype)
        record = {
            "metric": f"gossip-steps/sec @ {n} virtual workers, "
                      f"D={dim} (ResNet-20), MATCHA budget 0.5, {args.dtype}, "
                      f"backend={args.backend}",
            "value": round(value, 1),
            "unit": "gossip_steps_per_sec",
            "vs_baseline": round(value / NORTH_STAR, 4),
            "backend": args.backend,
            "device": device,
        }
        if args.backend == "dense":
            record.update(roofline("dense", value, n, dim, args.dtype))
        print(json.dumps(record))
        sys.stdout.flush()
        if args.backend == "dense":
            # grid chain lengths follow the rate just measured, so that
            # each grid (its cells x (warm-up + 2 reps), a grid chain ~2-3x
            # slower than the plain one) stays near a minute
            def grid_steps(asked, floor, chains):
                return max(floor, min(asked, steps,
                                      int(value * 60.0 / chains)))

            if args.overlap_grid_steps:
                _refine(record, "overlap_grid", overlap_wire_grid, sched, x,
                        grid_steps(args.overlap_grid_steps, 2, 36), n, dim)
            if args.staleness_grid_steps:
                _refine(record, "staleness_grid", staleness_grid, sched, x,
                        grid_steps(args.staleness_grid_steps, 4, 54), n, dim)
            if args.elision_grid_steps:
                _refine(record, "elision_grid", elision_grid, sched, x,
                        grid_steps(args.elision_grid_steps, 4, 54), n, dim)
        _journal_record(args, record)
        return 0

    # --- primary: per-step (training-regime) fused kernel, chunk=1 ---------
    # VMEM budget: the kernel keeps [N, block_d] in+out blocks resident
    # (16 MiB scoped); 8192 is sized for bf16 — halve it for f32 so
    # `--dtype f32` still fits instead of being refused
    if args.dtype == "f32" and args.block_d > 4096:
        args.block_d = 4096
    if args.block_d == 0:
        # f32 blocks are twice the bytes, so the sweep stops at 4096 there
        # (same guard as the explicit --block-d clamp above)
        candidates = (2048, 4096, 8192) if args.dtype == "bf16" else (2048, 4096)
        sweep = {}
        for bd in candidates:
            # a candidate the kernel refuses for VMEM is sweep data, not a
            # reason to lose the configs already timed
            try:
                sweep[bd] = time_backend("fused", sched, x, steps, args.dtype,
                                         chunk=1, block_d=bd,
                                         w_window=args.w_window, reps=5,
                                         return_rates=True)
            except Exception as e:  # noqa: BLE001
                print(f"# block_d={bd} failed: {type(e).__name__}: "
                      f"{str(e)[:200]}", file=sys.stderr)
        if not sweep:
            raise RuntimeError("no block_d candidate compiled")
        block_d = max(sweep, key=lambda b: sweep[b][0])
        per_step, trials = sweep[block_d]
        print(f"# block_d sweep: { {b: round(v[0], 1) for b, v in sweep.items()} } "
              f"-> {block_d}", file=sys.stderr)
    else:
        block_d = args.block_d
        per_step, trials = time_backend("fused", sched, x, steps, args.dtype,
                                        chunk=1, block_d=block_d,
                                        w_window=args.w_window, reps=5,
                                        return_rates=True)

    def _make_record(value, w_win, rates):
        return {
            "metric": f"per-step gossip-steps/sec @ {n} virtual workers, "
                      f"D={dim} (ResNet-20), MATCHA budget 0.5, {args.dtype}",
            "value": round(value, 1), "unit": "gossip_steps_per_sec",
            "vs_baseline": round(value / NORTH_STAR, 4), "backend": "fused",
            "device": device,
            # the trial spread travels in the primary record: value is
            # best-of-reps; stddev/trials show the run's own noise
            "value_stddev": round(float(np.std(rates)), 1),
            "value_trials": [round(r, 1) for r in rates],
            "chunk": 1, "block_d": block_d, "w_window": w_win,
            **roofline("fused", value, n, dim, args.dtype,
                       block_d=block_d, chunk=1),
        }

    print(json.dumps(_make_record(per_step, args.w_window, trials)))
    sys.stdout.flush()

    # small w_window autotune: same per-step arithmetic at every candidate,
    # so this is tuning, not a metric change.  Stops once the north star is
    # reached.
    w_window = args.w_window
    if args.w_sweep:
        # tolerate sloppy lists ("4,16," / "4,,16")
        cands = [int(w) for w in args.w_sweep.split(",") if w.strip().isdigit()]
        for cand in cands:
            if cand <= 0 or cand == args.w_window or per_step >= NORTH_STAR:
                continue
            try:
                v, r = time_backend("fused", sched, x, steps, args.dtype,
                                    chunk=1, block_d=block_d,
                                    w_window=cand, reps=5, return_rates=True)
            except Exception as e:  # noqa: BLE001
                print(f"# w_window={cand} failed: {type(e).__name__}: "
                      f"{str(e)[:200]}", file=sys.stderr)
                continue
            print(f"# w_window={cand}: {v:.1f}", file=sys.stderr)
            if v > per_step:
                per_step, w_window, trials = v, cand, r

    record = _make_record(per_step, w_window, trials)
    print(json.dumps(record))
    sys.stdout.flush()

    # --- overlap × wire-dtype grid (pipelined schedule + narrowed wire) ----
    # dense per-step cells: the regime the overlapped *training* loop runs
    # (one W_t @ x per SGD step); the bf16 cells must show bytes_per_step
    # halved, the 1step cells validate the pipelined chain end-to-end
    if args.overlap_grid_steps:
        _refine(record, "overlap_grid", overlap_wire_grid, sched, x,
                args.overlap_grid_steps, n, dim)
    # --- bounded-staleness grid (ISSUE 14): k × local_steps cells --------
    # measured k-deep ring-chain rate + the modeled barrier-vs-bounded
    # fleet wall-clock under a planted period-4 straggler
    if args.staleness_grid_steps:
        _refine(record, "staleness_grid", staleness_grid, sched, x,
                args.staleness_grid_steps, n, dim)
    # --- universal-elision grid (ISSUE 19): backend × local_every cells ---
    # measured elided-chain rate + the ledger's per-epoch gossip bytes
    if args.elision_grid_steps:
        _refine(record, "elision_grid", elision_grid, sched, x,
                args.elision_grid_steps, n, dim)

    # --- secondary: chunked chain composition (consensus-only regime) ------
    if args.chunk > 1:
        from matcha_tpu.parallel import canonical_chunk

        chunk = canonical_chunk(args.chunk)
        # the chunked regime's optimum block differs from per-step (W stream
        # is amortized ×chunk, so smaller resident blocks win)
        chunked = time_backend("fused", sched, x, steps, args.dtype,
                               chunk=chunk, block_d=args.chunk_block_d)
        record["value_chunked"] = round(chunked, 1)
        record["chunk_chunked"] = chunk
        # the top-level "w_window" applies to the per-step number only; the
        # chunked measurement always runs at window 1 (composition already
        # amortizes the W stream)
        record["chunked_w_window"] = 1
        record["chunked_block_d"] = args.chunk_block_d
        cr = roofline("fused", chunked, n, dim, args.dtype,
                      block_d=args.chunk_block_d, chunk=chunk)
        record["chunked_mfu"] = cr.get("mfu")

    print(json.dumps(record))
    _journal_record(args, record)
    return 0


def _journal_record(args, record) -> None:
    """Mirror the final bench record into a run journal (``--journal``).

    The JSON line on stdout stays the contract; the journal copy is what
    ``obs_tpu.py compare`` reads, so bench runs become comparable with
    training runs (and with each other) without scraping stdout.
    """
    if not args.journal:
        return
    from matcha_tpu.obs import append_journal_record

    append_journal_record(args.journal, "bench", record=record,
                          status="measured")


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--backend", default="fused",
                   help="fused|dense|gather|shard_map|choco; gather and "
                        "choco run "
                        "orders of magnitude slower per step — pair them "
                        "with --steps 200 or a rep takes minutes")
    p.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    # the chain must be long enough that the fixed launch/dispatch overhead
    # is noise on the marginal rate
    p.add_argument("--steps", type=int, default=2000)
    p.add_argument("--chunk", type=int, default=256,
                   help="chunk for the secondary consensus-only number "
                        "(value_chunked): runs of S mixing matrices are "
                        "pre-multiplied (exact by associativity); 0/1 skips "
                        "the chunked measurement")
    p.add_argument("--block-d", type=int, default=4096,
                   help="Pallas D-block size; 0 sweeps {2048,4096,8192} on "
                        "the per-step kernel and keeps the best.  A block "
                        "whose in+out buffers overrun the 16 MiB scoped "
                        "VMEM is refused by name (bf16 8192 at N=256)")
    p.add_argument("--chunk-block-d", type=int, default=2048,
                   help="Pallas D-block size for the chunked secondary "
                        "measurement (composition amortizes the W stream, "
                        "so smaller resident blocks win)")
    p.add_argument("--w-window", type=int, default=8,
                   help="consecutive W_t per D-block grid visit in the "
                        "per-step kernel; exact per-step arithmetic (unlike "
                        "--chunk) — amortizes grid overhead and batches W "
                        "DMAs")
    p.add_argument("--w-sweep", default="4,16",
                   help="comma-separated extra w_window candidates the "
                        "per-step primary tries after --w-window, keeping "
                        "the best rate (stops once the north star is "
                        "reached; identical per-step arithmetic at every "
                        "candidate). Empty string disables.")
    p.add_argument("--overlap-grid-steps", type=int, default=200,
                   dest="overlap_grid_steps",
                   help="chain length per overlap × wire-dtype grid cell "
                        "(the pipelined/bf16-wire sweep; 0 disables). The "
                        "grid rides the dense per-step regime — the one the "
                        "overlapped training loop runs")
    p.add_argument("--staleness-grid-steps", type=int, default=120,
                   dest="staleness_grid_steps",
                   help="chain length per bounded-staleness grid cell "
                        "(k in {1,2,4} x local_steps in {1,4}; 0 disables): "
                        "measured k-deep ring-chain rate + the modeled "
                        "barrier-vs-bounded fleet wall-clock under a "
                        "planted period-4 straggler, with the straggler "
                        "tax priced through critical_path_report")
    p.add_argument("--elision-grid-steps", type=int, default=120,
                   dest="elision_grid_steps",
                   help="chain length per universal-elision grid cell "
                        "(backend in {skip,dense} x local_every in "
                        "{1,4}; 0 disables): measured elided-chain rate + "
                        "the compiled-cost ledger's per-epoch gossip-"
                        "attributed boundary bytes (the ISSUE 19 A/B)")
    p.add_argument("--workers", type=int, default=256)
    p.add_argument("--journal", default=None,
                   help="append the final record as a `bench` event to this "
                        "run-journal JSONL (obs_tpu.py compare reads it); "
                        "the stdout JSON line is unchanged")
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                   help="pin the JAX platform before first use; cpu is the "
                        "explicit request for a CPU run (no utilization "
                        "fields)")
    args = p.parse_args(argv)

    from matcha_tpu.utils import pin_platform

    pin_platform(args.platform)
    device = _device_record()
    if device["platform"] != "tpu" and not (args.smoke
                                            or args.platform == "cpu"):
        print(f"bench: no TPU: jax.devices()[0] is {device}; a device "
              f"metric is measured on the device or not at all (--smoke "
              f"or --platform cpu ask for the CPU explicitly)",
              file=sys.stderr)
        return 1
    return measure(args, device)


if __name__ == "__main__":
    sys.exit(main())
