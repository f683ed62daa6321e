#!/usr/bin/env python
"""Consensus-only gossip chains: the chunk-composed fast path, demonstrated.

Training interleaves one gossip step per SGD step, but *pure averaging
phases* — initial model sync, periodic re-consensus, federated-style rounds,
or the throughput bench — run long uninterrupted chains of mixing steps.
There the chain composes: ``x_T = (W_T ⋯ W_1) x``, and
``compose_mixing_stack`` collapses runs of S steps into one matrix each
(exact by associativity), cutting apply cost ~S×.

This example runs 256 MATCHA steps on 64 virtual workers three ways —
per-step dense (the MXU oracle), the fused Pallas kernel, and fused +
chunk 64 — shows they agree, and reports the disagreement contraction and
wall-clock for each.  Works on CPU (Pallas interpreter; sized to finish in
~a minute) or a TPU chip.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# runs on the CPU unless JAX_PLATFORMS asks for something else (=tpu)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

import jax.numpy as jnp

from matcha_tpu import topology as tp
from matcha_tpu.communicator import make_decen
from matcha_tpu.parallel import worker_disagreement
from matcha_tpu.schedule import matcha_schedule


def main():
    n, d, steps = 64, 2048, 256
    edges = tp.make_graph("geometric", n, seed=1)
    sched = matcha_schedule(tp.decompose(edges, n, seed=1), n,
                            iterations=steps, budget=0.5, seed=0)
    x0 = jnp.asarray(np.random.default_rng(0).normal(size=(n, d)).astype(np.float32))
    d0 = float(worker_disagreement(x0))
    print(f"{n} workers, D={d}, {steps} MATCHA steps @ budget 0.5; "
          f"initial disagreement {d0:.3f}")

    results = {}
    for label, kwargs in [
        ("dense (per-step oracle)", dict(backend="dense")),
        ("fused (Pallas per-step)", dict(backend="fused")),
        ("fused + chunk 64", dict(backend="fused", chunk=64)),
    ]:
        comm = make_decen(sched, **kwargs)
        run = jax.jit(lambda x, c=comm: c.run(x, sched.flags)[0])
        run(x0).block_until_ready()  # compile
        t0 = time.perf_counter()
        xT = run(x0)
        dT = float(worker_disagreement(xT))  # forces completion via readback
        dt = time.perf_counter() - t0
        results[label] = np.asarray(xT)
        print(f"  {label:28s} {steps/dt:10.1f} steps/s   "
              f"disagreement {d0:.3f} -> {dT:.2e}")

    base = results["dense (per-step oracle)"]
    for label, out in results.items():
        err = np.abs(out - base).max()
        assert err < 1e-3, (label, err)
    print("all backends agree; the composed chain is the same map, just faster")


if __name__ == "__main__":
    main()
