#!/usr/bin/env python
"""The standing proof that the train path starts, learns and compiles on the
chip — one process, normal entry points, every device the host has.

    python chip_smoke.py            # on a TPU host: exit 0 and a last line
                                    # {"ok": true, "device": {...}}

Phases, each of which fails the run if it fails:

1. **train** — ``train_tpu.main(argv)``: ResNet-20 at its published widths
   (16/32/64 channels, D = 273,258) on CIFAR-shaped synthetic images, 16
   workers on the paper's 16-node Erdős–Rényi graph (``--graphid 4``),
   MATCHA budget 0.5, per-worker batch 32, ``--backend auto``, default
   telemetry and comm-split timer, 2 epochs of 4 steps.  Asserts 8 steps
   taken, finite falling loss, finite replica disagreement, no ``retrace``
   event, and that ``auto`` used every device: ``dense`` on one chip,
   ``shard_map`` with one parameter shard per chip on several.
2. **kernels** — the exchange ``train()`` runs, ``make_decen(backend=
   "dense")`` compiled for the device (never the interpreter on an
   accelerator), on a 20-step flag stream through ``Communicator.run`` at
   16 x 273,258 (the streamed Pallas pass) and 256 x 273,258 (the MXU
   product), f32 state with f32 and bf16 wire, against the per-matching
   ``gather`` chain as the oracle (run ``ORACLE_COLS`` columns at a time:
   whole, it keeps 15.5 GB of temporaries at 256 rows).
3. **fold** (more than one device) — one gossip step of the worker-folded
   ``shard_map`` plan: ``collective-permute`` in its compiled HLO, and the
   result equal to the single-chip ``dense`` step.

Without a TPU whose ``device_kind`` is in ``obs.costs.CHIP_PEAKS`` it
exits non-zero and prints no result.  ``--cpu-dry-run`` walks the same
phases at a tiny size on the CPU for debugging; its last line says
``"ok": false`` and names ``cpu``, and it never exits 0.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

FLAT_DIM = 273_258  # ResNet-20 / CIFAR-10 flat parameter count
EPOCHS, STEPS = 2, 8  # 2,048 samples / (16 workers x batch 32) = 4 a epoch
CHAIN_STEPS = 20
# Columns of the state the gather oracle mixes in one call.  The exchange
# mixes rows, so columns are independent; XLA keeps every matching's
# gathered copy of the state, and the 20-step chain at 256 x 273,258 (27
# matchings) compiled for a described v5e with 15.45 GB of temporaries
# (PR 45), 1.9 GB at this width.
ORACLE_COLS = 32_768

# Tolerances, relative to the oracle's largest magnitude.  f32 wire: both
# sides are the same f32 arithmetic up to the order of a sum (MXU passes at
# HIGHEST, FMA contraction), a few ulps a step.  bf16 wire: an ulp of
# difference before a step's quantization can move a value one bf16 step
# (2^-8 relative), so the chain carries the repo's own per-step wire budget
# (tests/test_overlap.py).
TOL_F32 = 1e-5
TOL_BF16 = CHAIN_STEPS * 2.0 ** -8


def train_phase(tiny: bool, workdir: str) -> dict:
    import jax
    import numpy as np

    import train_tpu
    from matcha_tpu.obs.journal import read_journal
    from matcha_tpu.train import build_schedule

    argv = ["--name", "chip-smoke",
            "--model", "mlp" if tiny else "resnet20",
            "--dataset", "synthetic" if tiny else "synthetic_image",
            "--graphid", "4", "--numworkers", "16", "--budget", "0.5",
            "--bs", "32", "--backend", "auto", "--epoch", str(EPOCHS),
            "--lr", "0.02", "--save", "--savePath", workdir]
    t0 = time.perf_counter()
    build_schedule(train_tpu.parse_args(argv), STEPS + 1)
    setup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    result = train_tpu.main(argv)
    wall_s = time.perf_counter() - t0

    losses = [h["loss"] for h in result.history]
    assert int(np.asarray(result.state.step)) == STEPS, result.state.step
    assert len(losses) == EPOCHS and all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert all(np.isfinite(h["disagreement"]) for h in result.history)

    events = read_journal(os.path.join(
        workdir, f"chip-smoke_{'mlp' if tiny else 'resnet20'}",
        "events.jsonl"))
    retraces = [e for e in events if e["kind"] == "retrace"]
    assert not retraces, retraces
    backend = next(e for e in events if e["kind"] == "backend")
    devices = jax.devices()
    leaves = jax.tree_util.tree_leaves(result.state.params)
    if len(devices) == 1:
        assert backend["chosen"] == "dense", backend
    else:
        assert backend["chosen"] == "shard_map", backend
        assert str(len(devices)) in backend["reason"], backend
        for leaf in leaves:
            homes = [s.device for s in leaf.addressable_shards]
            assert len(homes) == len(devices) == len(set(homes)), homes
            assert leaf.addressable_shards[0].data.shape[0] \
                == 16 // len(devices)
        scan = next(e for e in events if e["kind"] == "compile"
                    and e["label"] == "epoch_scan")
        # the batch stack meets the worker-sharded state already sharded:
        # no operand of the epoch program waits on a copy out of chip 0
        assert any("None, 'workers'" in s for s in scan["arg_shardings"]), \
            scan["arg_shardings"]
    compiles = [e for e in events if e["kind"] == "compile"]
    return {
        "backend": backend["chosen"],
        "steps": STEPS,
        "loss": [round(float(v), 4) for v in losses],
        "disagreement": [float(h["disagreement"]) for h in result.history],
        "setup_seconds_schedule_build": round(setup_s, 3),
        "compile_seconds": {
            label: round(sum(e["compile_seconds"] for e in compiles
                             if e["label"] == label), 2)
            for label in sorted({e["label"] for e in compiles})},
        "first_epoch_seconds": round(result.history[0]["epoch_time"], 3),
        # epoch 1 runs the program epoch 0 compiled; epoch_time stops on
        # the metrics readback (dispatch is asynchronous)
        "steady_step_seconds": round(
            result.history[-1]["epoch_time"] / (STEPS // EPOCHS), 4),
        "train_wall_seconds": round(wall_s, 1),
    }


def _chain_schedule(n: int):
    """A ``CHAIN_STEPS``-step flag stream for ``n`` workers.  16: the train
    phase's own MATCHA schedule.  256: a geometric graph with
    every matching drawn at p = 0.5 — MATCHA's solved probabilities cost a
    ~200 s CVX solve there, and the kernels see only flags and alpha."""
    from matcha_tpu import topology as tp
    from matcha_tpu.schedule import fixed_schedule, matcha_schedule

    if n == 16:
        return matcha_schedule(tp.select_graph(4), n, CHAIN_STEPS,
                               budget=0.5, seed=9001)
    dec = tp.decompose(tp.make_graph("geometric", n, seed=1), n, seed=1)
    return fixed_schedule(dec, n, CHAIN_STEPS, budget=0.5,
                          mode="bernoulli", seed=0)


#: ``(workers, wire)`` of the kernel phase: one worker count on each side of
#: ``parallel.STREAM_MAX_WORKERS`` (the streamed pass, the MXU product)
KERNEL_CASES = [(16, "f32"), (16, "bf16"), (256, "f32"), (256, "bf16")]


def kernel_phase(dim: int, cases=KERNEL_CASES) -> list:
    import warnings

    import jax
    import jax.numpy as jnp

    from matcha_tpu.communicator import make_decen
    from matcha_tpu.parallel import dense_exchange_form

    def timed(fn, x):
        """(result, first-call seconds, second-call seconds), both calls
        timed to a scalar readback."""
        out = None
        secs = []
        for _ in range(2):
            t0 = time.perf_counter()
            out = fn(x)
            float(out[0, 0])
            secs.append(time.perf_counter() - t0)
        return out, secs[0], secs[1]

    rows = []
    scheds = {n: _chain_schedule(n) for n in sorted({n for n, _ in cases})}
    for n, wire in cases:
        sched = scheds[n]
        flags = jnp.asarray(sched.flags[:CHAIN_STEPS], jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(n), (n, dim), jnp.float32)
        with warnings.catch_warnings():
            # make_decen warns that gather is slow at 256 rows: as the
            # oracle it runs twice
            warnings.simplefilter("ignore", UserWarning)
            dense, gather = (make_decen(sched, backend=b, wire_dtype=wire)
                             for b in ("dense", "gather"))
        got, compile_s, run_s = timed(
            jax.jit(lambda v: dense.run(v, flags)[0]), x)
        oracle_run = jax.jit(lambda v: gather.run(v, flags)[0])
        want, _, oracle_s = timed(
            lambda v: jnp.concatenate(
                [oracle_run(v[:, at:at + ORACLE_COLS])
                 for at in range(0, dim, ORACLE_COLS)], axis=1), x)
        err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        moved = float(jnp.max(jnp.abs(want - x)))
        tol = TOL_F32 if wire == "f32" else TOL_BF16
        row = {"kernel": dense_exchange_form(n)["form"],
               "oracle": "gather", "n": n,
               "dim": dim, "wire": wire, "rel_err": err, "tol": tol,
               "first_call_seconds": round(compile_s, 2),
               "chain_seconds": round(run_s, 4),
               "oracle_chain_seconds": round(oracle_s, 4)}
        rows.append(row)
        print(f"# kernel {json.dumps(row)}", flush=True)
        assert bool(jnp.isfinite(got).all()), row
        assert moved > 0.0, f"flag stream mixed nothing: {row}"
        assert err <= tol, row
    return rows


def fold_phase(dim: int) -> dict:
    import jax
    import jax.numpy as jnp

    from matcha_tpu.communicator import make_decen
    from matcha_tpu.parallel import shard_workers, worker_mesh

    sched = _chain_schedule(16)
    mesh = worker_mesh()
    weights = jnp.ones((sched.num_matchings,), jnp.float32)  # every edge
    x = jax.random.normal(jax.random.PRNGKey(0), (16, dim), jnp.float32)
    folded = make_decen(sched, mesh=mesh, backend="shard_map")
    dense = make_decen(sched, backend="dense")
    step = jax.jit(lambda v: folded.step(v, (), weights)[0])
    xs = shard_workers(x, mesh)
    hlo = step.lower(xs).compile().as_text()
    assert "collective-permute" in hlo, "folded step has no collective-permute"
    got = step(xs)
    want = jax.jit(lambda v: dense.step(v, (), weights)[0])(x)
    err = float(jnp.max(jnp.abs(jax.device_get(got) - jax.device_get(want)))
                / jnp.max(jnp.abs(want)))
    assert err <= TOL_F32, f"folded vs dense gossip step: rel err {err}"
    return {"devices": mesh.size, "collective_permutes":
            hlo.count("collective-permute-start") or
            hlo.count("collective-permute"), "rel_err": err, "tol": TOL_F32}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cpu-dry-run", action="store_true",
                        help="debugging only: tiny sizes on the CPU; "
                             "reports ok=false and never exits 0")
    args = parser.parse_args(argv)
    t_start = time.perf_counter()

    from matcha_tpu.native import native_available
    from matcha_tpu.obs.costs import chip_peaks
    from matcha_tpu.utils import compile_cache_dir, pin_platform

    pin_platform("cpu" if args.cpu_dry_run else None)
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.cpu_dry_run:
        if device["platform"] != "tpu":
            print(f"chip_smoke: no TPU: jax.devices()[0] is {device}; "
                  f"refusing to run (--cpu-dry-run is the debugging path)",
                  file=sys.stderr)
            return 1
        chip_peaks(device["kind"])  # UnknownChipError: not a chip we know
    print(f"# device {json.dumps(device)}", flush=True)

    cache_dir = compile_cache_dir()
    cache_before = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    cache_events = {"hits": 0, "misses": 0}

    def count_cache(event, **_):
        if event.endswith("/cache_hits"):
            cache_events["hits"] += 1
        elif event.endswith("/cache_misses"):
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(count_cache)

    dim = 1_000 if args.cpu_dry_run else FLAT_DIM
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        report = {"train": train_phase(args.cpu_dry_run, workdir)}
        print(f"# train {json.dumps(report['train'])}", flush=True)
        report["kernels"] = kernel_phase(dim)
        if len(devices) > 1:
            report["fold"] = fold_phase(dim)
            print(f"# fold {json.dumps(report['fold'])}", flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    report["compile_cache"] = {
        "dir": cache_dir, "from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "entries_before": cache_before,
        "entries_after": len(os.listdir(cache_dir)), **cache_events}
    report["native"] = "built" if native_available() else "python fallback"
    report["wall_seconds"] = round(time.perf_counter() - t_start, 1)
    print(f"# compile_cache {json.dumps(report['compile_cache'])}")
    print(f"# native {report['native']}")
    print(f"# wall_seconds {report['wall_seconds']}")
    if args.cpu_dry_run:
        print(json.dumps({"ok": False, "dry_run": "cpu: proves nothing "
                          "about the chip", "device": device}))
        return 2
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
