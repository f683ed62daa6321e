#!/usr/bin/env python
"""Full-train-step throughput at the north-star configuration.

BASELINE.json's north star is worded as a *training run*: 256 virtual
workers, ResNet-20/CIFAR-10, MATCHA budget 0.5, one gossip step per SGD step
(/root/reference/train_mpi.py:113-145 — the loop this framework compiles
into a single program).  This harness
measures the quantity the wording implies — `make_train_step` steps/sec with
the gossip mix inside the compiled step — plus the **marginal cost of
gossip** obtained by differencing against an identical run with
`communicator="none"`, and the roofline argument that connects the two:

    per train step, fwd+bwd ≈ 3 × 2 × B_total × F_model FLOPs
    gossip adds 2·N²·D FLOPs (the dense W_t @ x mix)

At N=256, B=32/worker, ResNet-20 (F ≈ 41 MFLOP/image, D = 273k):
fwd+bwd ≈ 2.0 TFLOP vs gossip 35.8 GFLOP — gossip is ~1.8% of the step's
FLOPs, so a MATCHA budget's saving on-chip is bounded by that share (the
budget economy targets comm-bound fabrics; see README Performance).

Run: ``python benchmarks/train_step_bench.py [--workers N] [--batch B]
[--steps K] [--reps R] [--platform cpu|tpu] [--out PATH]``
(CPU note: one step at the full config is ~2 TFLOP — pass
``--workers 16 --batch 4`` for a CPU smoke.)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(args) -> dict:
    import jax
    import jax.numpy as jnp

    from matcha_tpu import topology as tp
    from matcha_tpu.communicator import select_communicator
    from matcha_tpu.models import select_model
    from matcha_tpu.schedule import matcha_schedule
    from matcha_tpu.train import make_lr_schedule
    from matcha_tpu.train.state import init_train_state, make_optimizer, make_train_step

    n, b = args.workers, args.batch
    hw = args.image_size
    # dataset name only routes the zoo's variant choice: any 224 image size
    # picks the ImageNet 4-stage variant for 'res*' names
    model = select_model(args.model, "imagenet" if hw >= 64 else "cifar10",
                         num_classes=args.classes, remat=args.remat)
    print(f"# [{time.strftime('%H:%M:%S')}] building {n}-worker schedule "
          f"(CVX solve ~60-90s at 256)...", file=sys.stderr, flush=True)
    edges = tp.make_graph("geometric", n, seed=1)
    dec = tp.decompose(edges, n, seed=1)
    # every chain_j(state) rep restarts from the same initial state (and
    # therefore step 0), so only rows [0, steps) of the flag stream are read
    sched = matcha_schedule(dec, n, iterations=args.steps + 1,
                            budget=0.5, seed=0)
    lr = make_lr_schedule(0.1, batches_per_epoch=100, warmup=False)
    optimizer = make_optimizer(lr)

    rng = np.random.default_rng(0)
    xb = jnp.asarray(rng.normal(size=(n, b, hw, hw, 3)).astype(np.float32))
    yb = jnp.asarray(rng.integers(0, args.classes, size=(n, b)).astype(np.int32))
    key = jax.random.PRNGKey(0)
    # flat parameter count, from shapes only (no init program to compile)
    var_shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, hw, hw, 3)), train=False),
        jax.random.PRNGKey(0))
    d = sum(int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(var_shapes["params"]))

    def log(msg):
        # stage-by-stage wall-clock breadcrumbs on stderr: a timed-out
        # run must show WHERE the budget went (transfer? init
        # compile? chain compile?) instead of dying silently
        print(f"# [{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
              flush=True)

    def steps_per_sec(comm_name: str) -> float:
        comm = select_communicator(comm_name, sched)
        log(f"{comm_name}: init_train_state...")
        state, flattener = init_train_state(
            model, (hw, hw, 3), n, optimizer, comm, seed=0)
        jax.block_until_ready(state.params)
        log(f"{comm_name}: init done; compiling {args.steps}-step chain...")
        step = make_train_step(model, optimizer, comm, flattener, sched.flags,
                               lr_schedule=lr,
                               grad_chunk=args.grad_chunk or None)

        def chain(state):
            for _ in range(args.steps):  # unrolled; step count is small
                state, m = step(state, xb, yb, key)
            return state, m

        chain_j = jax.jit(chain)
        # time to a scalar readback (dispatch is asynchronous)
        out_state, m = chain_j(state)
        float(m["loss"])
        log(f"{comm_name}: chain compiled + warm; timing {args.reps} reps...")
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            _, m = chain_j(state)
            float(m["loss"])
            best = min(best, time.perf_counter() - t0)
        log(f"{comm_name}: {args.steps / best:.2f} steps/s")
        return args.steps / best

    log(f"data on device: x {xb.shape} {xb.nbytes >> 20} MiB...")
    jax.block_until_ready(xb)
    log("data transferred; schedule built")
    rate_full = steps_per_sec("decen")
    rate_none = steps_per_sec("none")

    # per-image forward FLOPs at the canonical sizes; off-canonical image
    # sizes scale ~quadratically with the spatial area.  Models without a
    # table entry get NO fwd/bwd roofline numbers (omitting beats emitting
    # a confidently-wrong gossip_flop_share of 1.0).
    canon = {"resnet20": (32, 41.0e6), "resnet50": (224, 4.1e9)}
    base = canon.get(args.model.lower())
    f_img = base[1] * (hw / base[0]) ** 2 if base else None
    flops_fwd_bwd = 3 * 2 * n * b * f_img if f_img else None  # fwd + ~2x bwd
    flops_gossip = 2.0 * n * n * d
    record = {
        "metric": f"train-steps/sec @ {n} workers x batch {b}, "
                  f"{args.model}@{hw}px, "
                  f"MATCHA budget 0.5 (gossip inside the step)",
        "value": round(rate_full, 3),
        "unit": "train_steps_per_sec",
        "train_steps_per_sec_no_comm": round(rate_none, 3),
        "gossip_marginal_frac": round(
            max(0.0, 1.0 - rate_full / max(rate_none, 1e-9)), 4),
        "roofline": {
            **({"flops_fwd_bwd_per_step": flops_fwd_bwd,
                "gossip_flop_share": round(
                    flops_gossip / (flops_gossip + flops_fwd_bwd), 4)}
               if flops_fwd_bwd else
               {"note_fwd_bwd": f"no canonical FLOP table entry for "
                                f"{args.model}; fwd/bwd share omitted"}),
            "flops_gossip_per_step": flops_gossip,
            "note": "gossip-steps/sec in a training run == train-steps/sec; "
                    "the isolated gossip kernel rate bounds "
                    "the comm term, and the FLOP share bounds what any "
                    "budget<1 can save on-chip",
        },
        "workers": n, "batch": b, "model": args.model,
        "image_size": hw, "flat_dim": d,
        "steps": args.steps, "reps": args.reps,
        "remat": args.remat, "grad_chunk": args.grad_chunk or None,
        "device_kind": jax.devices()[0].device_kind,
    }
    return record


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workers", type=int, default=256)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--model", default="resnet20",
                   help="zoo name (resnet20|resnet50|vgg16|wrn|mlp); "
                        "resnet50 + --image-size 224 is the BASELINE "
                        "config-5 scale probe")
    p.add_argument("--image-size", type=int, default=32, dest="image_size")
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--steps", type=int, default=4,
                   help="train steps per timed chain (min 1)")
    p.add_argument("--reps", type=int, default=2)
    p.add_argument("--remat", action="store_true",
                   help="block-level rematerialization — required to fit the "
                        "full 256x32 config in one v5e's HBM")
    p.add_argument("--grad-chunk", type=int, default=0, dest="grad_chunk",
                   help="workers per fwd/bwd slab (0 = all at once)")
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"])
    p.add_argument("--out", default=None)
    args = p.parse_args()
    args.steps = max(1, args.steps)
    from matcha_tpu.utils import pin_platform

    pin_platform(args.platform)
    record = measure(args)
    print(json.dumps(record))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)


if __name__ == "__main__":
    main()
