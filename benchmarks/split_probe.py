#!/usr/bin/env python
"""One-question hardware probe: does column-splitting the per-step dot help?

The fused kernel's remaining ~9% to the v5e per-step ceiling is attributed
(benchmarks/ROOFLINE.md) to the per-step f32→wire cast serializing against
the MXU: within a w_window visit every step is ``cast(dot(W_t, state))`` and
the next step's dot consumes the cast's output, so Mosaic cannot overlap the
VPU cast with MXU work *of the same column range*.  Splitting the D-block's
columns in half makes the dependency per-half: the cast of half 0 can overlap
the dot of half 1 at every step.  Arithmetic is unchanged (columns of
``W @ X`` are independent; same dot shape over K, same f32 accumulation, same
per-step cast) — this is purely a scheduling question Mosaic has to answer,
so it is measured, not assumed.

Writes ``{base, split, ratio, device_kind}`` JSON to --out; exits 0 even when
inconclusive (the artifact records what happened).  Run it only on the
chip.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

N, D, T, BD, W = 256, 273258, 2000, 4096, 8


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=None)
    p.add_argument("--reps", type=int, default=5)
    args = p.parse_args()
    if args.reps < 1:
        p.error("--reps must be >= 1 (best-of-0 would emit Infinity, "
                "which is not valid JSON)")

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from matcha_tpu.utils import pin_platform

    pin_platform(None)  # compile cache
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @jax.jit
    def gen():
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(k1, (N, D), jnp.bfloat16)
        stk = (jax.random.normal(k2, (T, N, N), jnp.float32) * 0.01
               + jnp.eye(N)[None] * 0.9).astype(jnp.bfloat16)
        return x, stk

    x, stk = gen()
    jax.block_until_ready(x)

    def make_kernel(split):
        def _kernel(x_ref, w_ref, o_ref):
            t = pl.program_id(1)

            @pl.when(t == 0)
            def _():
                o_ref[...] = x_ref[...]

            half = BD // 2
            for k in range(W):
                if split:
                    xk = o_ref[...].astype(w_ref.dtype)
                    a0 = jnp.dot(w_ref[k], xk[:, :half],
                                 preferred_element_type=jnp.float32)
                    a1 = jnp.dot(w_ref[k], xk[:, half:],
                                 preferred_element_type=jnp.float32)
                    o_ref[:, :half] = a0.astype(o_ref.dtype)
                    o_ref[:, half:] = a1.astype(o_ref.dtype)
                else:
                    o_ref[...] = jnp.dot(
                        w_ref[k], o_ref[...].astype(w_ref.dtype),
                        preferred_element_type=jnp.float32,
                    ).astype(o_ref.dtype)
        return _kernel

    @functools.partial(jax.jit, static_argnames=("split",))
    def run(x, stk, split=False):
        return pl.pallas_call(
            make_kernel(split), grid=(pl.cdiv(D, BD), T // W),
            in_specs=[pl.BlockSpec((N, BD), lambda i, t: (0, i)),
                      pl.BlockSpec((W, N, N), lambda i, t: (t, 0, 0))],
            out_specs=pl.BlockSpec((N, BD), lambda i, t: (0, i)),
            out_shape=jax.ShapeDtypeStruct((N, D), x.dtype))(x, stk)

    def rate(split):
        g = jax.jit(lambda x: jnp.sum(run(x, stk, split=split)[:, :8]
                                      .astype(jnp.float32)))
        float(g(x))
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            float(g(x))
            best = min(best, time.perf_counter() - t0)
        return T / best

    rec = {"probe": "split-cast-overlap", "n": N, "d": D, "steps": T,
           "block_d": BD, "w_window": W,
           "device_kind": jax.devices()[0].device_kind}
    try:
        # whole-array equality on device (ADVICE r4: the earlier 8-column
        # f32-sum check could miss a divergence in the other 273k columns)
        y0 = run(x, stk)
        y1 = run(x, stk, split=True)
        rec["outputs_equal"] = bool(jnp.array_equal(y0, y1))
        rec["slice_sums_equal"] = rec["outputs_equal"]  # back-compat key
        del y0, y1
        rec["base_steps_per_sec"] = round(rate(False), 1)
        rec["split_steps_per_sec"] = round(rate(True), 1)
        rec["ratio"] = round(rec["split_steps_per_sec"]
                             / rec["base_steps_per_sec"], 4)
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
