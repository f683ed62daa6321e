#!/usr/bin/env python
"""Per-step gossip rate: masked backend vs the cond-skipping backend.

The masked backends (`gather`/`dense`) execute every matching every
step and mask inactive ones to zero — the budget changes arithmetic, not
time.  The `skip` backend wraps each matching in ``lax.cond`` so inactive
matchings cost nothing at runtime.  This microbench measures that directly:
the same 16-worker, ResNet-20-sized gossip chain under a full D-PSGD
schedule (all matchings active) and a MATCHA budget-0.5 schedule (~half
active in expectation), on both backends.

This is the evidence behind the claims in README.md / docs/MULTIHOST.md —
including two honest ceilings.  (1) ``lax.cond``'s identity branch still
writes a full-state buffer, so on-chip the saving exists only while
per-matching *work* exceeds a state copy: at ResNet-18-ImageNet size the
chain is copy-bound and skip saves nothing (committed artifact, config 2).
(2) At ResNet-20 size the budget-0.5 schedule measures ~1.2× faster on
skip, but the masked control measured 1.06× and 1.16× on two runs of the
same chip — the run-to-run noise is comparable to the marginal gain, so
the committed numbers show the *direction*, not a precise on-chip speedup.
The regime the backend is actually for is the sharded one, where the
skipped cost is a cross-chip/DCN collective, not arithmetic
(``shard_map_gossip_fn(skip=True)``; semantics validated on the virtual
mesh, payoff measurable only on pod fabric).  Committed result:
``skip_microbench.json``.

Run: ``python benchmarks/skip_microbench.py [--workers N] [--steps T]``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# ResNet-20/CIFAR-10 flat parameter count (hardcoded here so the
# microbench never touches the model zoo)
RESNET20_DIM = 273_258


def time_chain(comm, x, flags, steps):
    import jax
    import jax.numpy as jnp

    # the readback serializes the whole chain (dispatch is asynchronous,
    # and a clock that stops early inflates rates 100x+)
    run = jax.jit(lambda x: jnp.sum(comm.run(x, flags)[0][:, :8]))
    float(run(x))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        float(run(x))
        best = min(best, time.perf_counter() - t0)
    return steps / best


def measure(workers: int, dim: int, steps: int) -> dict:
    import jax.numpy as jnp

    from matcha_tpu import topology as tp
    from matcha_tpu.communicator import make_decen
    from matcha_tpu.schedule import fixed_schedule, matcha_schedule

    # the paper's 16-node geometric zoo graph at the default size; a
    # same-family generated graph for any other --workers
    edges = (tp.select_graph(2) if workers == 16
             else tp.make_graph("geometric", workers, seed=1))
    scheds = {
        "dpsgd": fixed_schedule(edges, workers, iterations=steps),
        "matcha-0.5": matcha_schedule(edges, workers,
                                      iterations=steps, budget=0.5, seed=1),
    }
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(workers, dim)).astype(np.float32))

    result = {"workers": workers, "dim": dim, "steps": steps, "rates": {}}
    for sname, sched in scheds.items():
        flags = jnp.asarray(sched.flags, jnp.float32)
        result.setdefault("mean_active_matchings", {})[sname] = round(
            float(flags.sum(axis=1).mean()), 2)
        for backend in ("gather", "skip"):
            comm = make_decen(sched, backend=backend)
            rate = time_chain(comm, x, flags, steps)
            result["rates"][f"{sname}/{backend}"] = round(rate, 1)

    r = result["rates"]
    result["masked_speedup_at_half_budget"] = round(
        r["matcha-0.5/gather"] / r["dpsgd/gather"], 2)
    result["skip_speedup_at_half_budget"] = round(
        r["matcha-0.5/skip"] / r["dpsgd/skip"], 2)
    return result


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workers", type=int, default=16)
    # long chains amortize the fixed dispatch cost; short ones put the
    # run-to-run noise at ±10-15%, swamping the effect being measured
    p.add_argument("--steps", type=int, default=128)
    p.add_argument("--dim", type=int, default=RESNET20_DIM)
    # second size showing the cond identity-copy ceiling (ResNet-18/ImageNet
    # param count); 0 disables
    p.add_argument("--dim2", type=int, default=11_173_962)
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "skip_microbench.json"))
    args = p.parse_args()

    configs = [measure(args.workers, args.dim, args.steps)]
    if args.dim2:
        # the big-dim config runs ~36 ms/step; a short chain suffices (it is
        # bound by full-state traffic, not dispatch)
        configs.append(measure(args.workers, args.dim2, max(8, args.steps // 4)))
    result = {
        "experiment": "per-step gossip rate, masked vs cond-skipping backend",
        "configs": configs,
        "note": "skip pays only while per-matching work exceeds a full-state "
                "copy (the cond identity branch writes one); at the larger "
                "dim the chain is copy-bound and the budget buys nothing "
                "on-chip — the sharded skip path targets the regime where "
                "the avoided cost is a cross-chip collective instead",
    }
    print(json.dumps(result))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"# wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
