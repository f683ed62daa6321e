#!/usr/bin/env python
"""CHOCO encode cost on hardware: exact vs approximate top-k.

``time_to_acc.json`` showed CHOCO's top-k encode is a real ~26% share of its
epoch time — the one place compression itself is the bottleneck on-chip.
``top_k_approx`` (jax.lax.approx_max_k, the TPU PartialReduce lowering) was
added on the δ-contraction argument in ops/compress.py; this harness measures
what it actually buys at the BASELINE config-4 shape (64 workers × ResNet-20,
ratio 0.9 ⇒ k = 27,325 of 273,258 per worker).

One JSON line per compressor: encode wall-clock (best of --reps, forced
readback) and the ratio against exact ``top_k``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workers", type=int, default=64)
    p.add_argument("--dim", type=int, default=273258)
    p.add_argument("--ratio", type=float, default=0.9)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--out", default=None)
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"])
    args = p.parse_args()
    if args.reps < 1:
        p.error("--reps must be >= 1 (best-of-0 would emit Infinity, "
                "which is not valid JSON)")

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from matcha_tpu.utils import pin_platform

    pin_platform(args.platform)
    import jax
    import jax.numpy as jnp

    from matcha_tpu.ops import select_compressor

    x = jax.random.normal(jax.random.PRNGKey(0), (args.workers, args.dim),
                          jnp.float32)
    jax.block_until_ready(x)
    key = jax.random.PRNGKey(1)

    results = {}
    for name in ("top_k", "top_k_approx", "random_k", "top_k_q8"):
        comp = select_compressor(name)

        @jax.jit
        def enc(x, key, comp=comp):
            vals, idx = comp(x, args.ratio, key)
            # a readback that depends on the whole encode (dispatch is
            # asynchronous): sum of values + first index column
            return (jnp.sum(vals.astype(jnp.float32))
                    + jnp.sum(idx[:, :1].astype(jnp.float32)))

        try:
            float(enc(x, key))  # compile + warm
            best = float("inf")
            for _ in range(args.reps):
                t0 = time.perf_counter()
                float(enc(x, key))
                best = min(best, time.perf_counter() - t0)
            results[name] = round(best * 1e3, 3)  # ms per encode
        except Exception as e:  # noqa: BLE001 — record, keep measuring others
            results[name] = f"{type(e).__name__}: {str(e)[:200]}"

    rec = {
        "metric": f"CHOCO encode ms @ {args.workers} workers x D={args.dim}, "
                  f"ratio {args.ratio}",
        "encode_ms": results,
        "device_kind": jax.devices()[0].device_kind,
    }
    # approximate-path quality, measured where it is real (on CPU the op
    # falls back to exact top-k and recall is trivially 1.0 — the unit test
    # cannot check this, tests/test_ops.py documents that): recall vs exact
    # top-k and the realized energy-capture ratio, the δ in CHOCO's
    # contraction assumption
    try:
        from matcha_tpu.ops import batched_top_k, batched_top_k_approx

        @jax.jit
        def quality(x):
            ev, ei = batched_top_k(x, args.ratio)
            av, ai = batched_top_k_approx(x, args.ratio)
            k = ei.shape[-1]
            # membership via a dense [N, D] mask (a [N, k, k] pairwise
            # compare would be ~50 G elements at the config-4 shape)
            rows = jnp.arange(x.shape[0])[:, None]
            mask = jnp.zeros(x.shape, jnp.bool_).at[rows, ei].set(True)
            hits = jnp.sum(mask[rows, ai], axis=-1)
            return (jnp.mean(hits / k),
                    jnp.mean(jnp.sum(av**2, -1) / jnp.sum(ev**2, -1)))

        recall, energy = quality(x)
        rec["approx_recall_vs_exact"] = round(float(recall), 4)
        rec["approx_energy_capture_vs_exact"] = round(float(energy), 4)
    except Exception as e:  # noqa: BLE001
        rec["approx_quality_error"] = f"{type(e).__name__}: {str(e)[:200]}"
    exact, approx = results.get("top_k"), results.get("top_k_approx")
    if isinstance(exact, float) and isinstance(approx, float) and approx > 0:
        rec["approx_speedup_vs_exact"] = round(exact / approx, 2)
    line = json.dumps(rec)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
