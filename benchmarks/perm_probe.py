#!/usr/bin/env python
"""A/B harness: the permutation-form kernel vs the dense fused kernel.

Since ISSUE 13 the perm form is a **production backend**
(``matcha_tpu.parallel.perm_gossip_run`` — ``gossip_backend="perm"``), and
this probe re-exports it instead of carrying its own copy: there is exactly
one perm kernel in the repo, and the A/B below times the same program text
training runs.  The dense side is likewise the production fused W-stack
kernel (``fused_gossip_run``).  What remains probe-shaped is the protocol:

* Both forms run bf16 in/out with f32 accumulate — the production fused
  kernel's dtypes (bench.py default) — so the dense baseline streams
  exactly the bytes it streams in production.
* Correctness is checked on device against the dense form in f32 and GATES
  the ratio: outputs that diverge beyond rounding drift mark the record
  inconclusive and withhold the ratio (a silently mis-lowered gather must
  not trigger integration).  The f32 gate avoids bf16's percent-scale
  chain drift, which would blind it; a mis-lowered gather is
  dtype-independent and O(1) off.
* Writes one JSON record to --out; exits 0 even when inconclusive.  Run it
  on the chip; ``--smoke`` pins the CPU for an interpret-mode correctness
  check.

The hardware question it measures — can M VPU row-shuffles beat one MXU
matmul once the W stream is gone? — feeds the
``plan.cost.choose_gossip_backend`` gate together with the roofline's
measured-vs-ceiling ratio (``obs_tpu.py roofline --backend both``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

N, D, T, BD, W, M = 256, 273258, 2000, 4096, 8, 10
ALPHA = 0.37  # representative mixing weight; any fixed value works


def random_involutions(rng, m: int, n: int) -> np.ndarray:
    """M random involutions with fixed points (matching structure)."""
    perms = np.empty((m, n), np.int64)
    for j in range(m):
        pi = np.arange(n)
        pairs = rng.permutation(n)[: 2 * (n // 3)].reshape(-1, 2)
        pi[pairs[:, 0]], pi[pairs[:, 1]] = pairs[:, 1], pairs[:, 0]
        perms[j] = pi
    return perms


def laplacians_from_involutions(perms: np.ndarray,
                                partnered: np.ndarray) -> np.ndarray:
    """``L_j = D_j − A_j`` for each involution — what build_mixing_stack
    composes into the dense W stack (the same W the perm form applies)."""
    m, n = perms.shape
    L = np.zeros((m, n, n), np.float32)
    rows = np.arange(n)
    for j in range(m):
        L[j, rows, rows] = partnered[j]
        on = partnered[j] > 0
        L[j, rows[on], perms[j][on]] -= 1.0
    return L


def main() -> int:
    global N, D, T, BD, W, M
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=None)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes for a CPU correctness check")
    args = p.parse_args()
    if args.reps < 1:
        p.error("--reps must be >= 1")
    if args.smoke:
        N, D, T, BD, W, M = 16, 1024, 32, 512, 4, 4

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from matcha_tpu.utils import pin_platform

    # --smoke is the CPU correctness check (interpret mode)
    pin_platform("cpu" if args.smoke else None)
    import jax
    import jax.numpy as jnp

    from matcha_tpu.parallel import (
        build_mixing_stack,
        fused_gossip_run,
        involution_tables,
        perm_gossip_run,
    )

    rng = np.random.default_rng(0)
    perms, partnered = involution_tables(random_involutions(rng, M, N))
    laplacians = laplacians_from_involutions(perms, partnered)
    # Bernoulli flag stream at the MATCHA-0.5-like activation rate
    flags = (rng.random((T, M)) < 0.5).astype(np.float32)

    @jax.jit
    def gen_x():
        # bf16 state: the production kernels' wire dtype (bench.py
        # default) — the dense baseline must stream the same bytes it
        # really streams, or the perm/dense ratio is biased
        return jax.random.normal(jax.random.PRNGKey(0), (N, D), jnp.bfloat16)

    x = gen_x()
    jax.block_until_ready(x)
    weights_d = jnp.asarray(ALPHA * flags, jnp.float32)  # [T, M] stream

    interp = jax.devices()[0].platform == "cpu"  # CPU: interpret-mode only

    def run_dense(x, stk):
        return fused_gossip_run(x, stk, block_d=BD, w_window=W,
                                interpret=interp)

    def run_perm(x, weights):
        return perm_gossip_run(x, weights, perms, partnered, block_d=BD,
                               interpret=interp)

    def rate(fn, *a):
        g = jax.jit(lambda *a: jnp.sum(fn(*a)[:, :8].astype(jnp.float32)))
        float(g(*a))  # compile + warm, to a readback (dispatch is async)
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            float(g(*a))
            best = min(best, time.perf_counter() - t0)
        return T / best

    rec = {"probe": "perm-vs-dense-fused", "n": N, "d": D, "steps": T,
           "block_d": BD, "w_window": W, "matchings": M,
           "kernel": "matcha_tpu.parallel.perm_gossip_run",  # the ONE copy
           "device_kind": jax.devices()[0].device_kind}
    if args.smoke:
        # interpret-mode numbers are correctness evidence only — a smoke
        # record must never impersonate hardware in the session artifact
        rec["smoke_interpret_mode"] = True
    try:
        stack32 = build_mixing_stack(laplacians, ALPHA, flags, jnp.float32)
        jax.block_until_ready(stack32)
        # Correctness gate in f32 (same lowering path, no per-step rounding
        # divergence).  Dense composes W_t from the SAME involutions the
        # perm form gathers through, so agreement here is a proof about
        # the lowering, not the math.
        y_dense = run_dense(x.astype(jnp.float32), stack32)
        y_perm = run_perm(x.astype(jnp.float32), weights_d)
        err = float(jnp.max(jnp.abs(y_perm - y_dense))
                    / (jnp.max(jnp.abs(y_dense)) + 1e-30))
        rec["rel_err_vs_dense_f32"] = err
        rec["valid"] = err < 1e-3
        # Rates in the production dtypes: bf16 state/stack, f32 accumulate
        rec["dense_steps_per_sec"] = round(
            rate(run_dense, x, stack32.astype(jnp.bfloat16)), 1)
        rec["perm_steps_per_sec"] = round(rate(run_perm, x, weights_d), 1)
        if not rec["valid"]:
            rec["inconclusive"] = "f32 outputs diverge; ratio withheld"
        elif args.smoke:
            rec["inconclusive"] = ("interpret-mode timing is meaningless; "
                                   "ratio withheld (correctness gate only)")
        else:
            rec["ratio"] = round(rec["perm_steps_per_sec"]
                                 / rec["dense_steps_per_sec"], 4)
    except Exception as e:  # noqa: BLE001 — the artifact records the failure
        rec["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    line = json.dumps(rec)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
