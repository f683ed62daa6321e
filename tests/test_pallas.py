"""The Pallas gossip kernels (the streamed exchange, flat and on the
leaves) and the grouped products as the TPU's compiler sees them.

On CPU the kernels run under the Pallas interpreter (same program, no
Mosaic); the cases below lower them for TPU and, in a child process,
compile them for a described v5e.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matcha_tpu import topology as tp
from matcha_tpu.communicator import make_decen
from matcha_tpu.parallel import (
    STREAM_MAX_WORKERS,
    leaf_mix,
    stream_mix,
    tree_mix,
    worker_deviation_rows,
    worker_disagreement,
)
from matcha_tpu.schedule import fixed_schedule, matcha_schedule


def _schedule(n=8, iterations=12, budget=0.6):
    edges = tp.ring_graph(n)
    dec = tp.decompose(edges, n, seed=0)
    return matcha_schedule(dec, n, iterations=iterations, budget=budget, seed=0)


def test_empty_flag_stream_is_identity():
    sched = _schedule(iterations=3)
    n = sched.perms.shape[1]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(n, 10)), jnp.float32)
    empty = np.zeros((0, sched.flags.shape[1]), np.float32)
    for backend in ("dense", "gather"):
        out, _ = make_decen(sched, backend=backend).run(x, empty)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


# ------------------------------------------------- the TPU compiler's view
# The kernels only ever ran under the interpreter in tier-1, which accepts
# programs Mosaic refuses (a vector row gather, an (1, 8) block).  These
# lower — and, where libtpu offers a compile-only v5e topology, compile —
# the real kernels at the train shapes, from the CPU host.

#: elements of the 2.34 GB float32 state of PR 28's gossip-only chains
#: (16 x 36,547,072; PERF.md section 6)
CHAIN_STATE_ELEMENTS = 16 * 36_547_072


def _kernel_program(kernel, n, wire, dim):
    """``(fn, abstract args)`` of one kernel program at ``[n, dim]``, f32
    state, compiled (``interpret=False``): one in-place exchange of the
    flat state (``stream``) or of one leaf ``[n, r, c]`` with its sums
    (``leaf``; ``dim`` is ``(r, c)``)."""
    f32 = jnp.float32
    if kernel == "leaf":
        return (lambda x, w: leaf_mix(x, w, wire_dtype=wire)), \
            (jax.ShapeDtypeStruct((n,) + dim, f32),
             jax.ShapeDtypeStruct((n, n), f32))
    return (lambda x, w: stream_mix(x, w, wire_dtype=wire)), \
        (jax.ShapeDtypeStruct((n, dim), f32),
         jax.ShapeDtypeStruct((n, n), f32))


KERNEL_CASES = \
    [  # the cells' own shapes (cell 1's D is no multiple of 128), an odd N
       ("stream", 16, "f32", 36_546_980), ("stream", 16, "bf16", 36_546_980),
       ("stream", 2, "f32", 267_211_008), ("stream", 3, "f32", 100_000)] \
    + [  # both sides of every chunk width up to the crossover: 32 is the
         # last N that ships streamed (1,024 columns a pass); 24 is no power
         # of two, and neither is its pass of 1,280 columns
       ("stream", n, w, CHAIN_STATE_ELEMENTS // n)
       for n in (8, 24, STREAM_MAX_WORKERS) for w in ("f32", "bf16")] \
    + [("stream", n, "bf16", CHAIN_STATE_ELEMENTS // n) for n in (2, 3)] \
    + [  # the leaf form (PR 34) at the cells' leaves as ``leaf_view`` reads
         # them: Mellum's experts collapsed and its head, the
         # sparse-attention cell's head lanes-first and its experts, a
         # router lanes-first, cell 1's 640-wide stage and its 320-wide one
         # in 384 lanes (a chunk and a tail at N = 16, one tail at N = 8);
         # then an odd N and the loop over the terms j above 4 workers
       ("leaf", 2, "f32", (18432, 896)), ("leaf", 2, "bf16", (2304, 12288)),
       ("leaf", 2, "f32", (18992, 2048)), ("leaf", 2, "f32", (6144, 2048)),
       ("leaf", 2, "f32", (64, 2304)),
       ("leaf", 16, "f32", (5760, 640)), ("leaf", 16, "bf16", (5760, 640)),
       ("leaf", 16, "f32", (2880, 320)), ("leaf", 8, "f32", (2880, 320)),
       ("leaf", 3, "f32", (1000, 384)),
       ("leaf", STREAM_MAX_WORKERS, "f32", (4096, 1024)),
       ("leaf", STREAM_MAX_WORKERS, "bf16", (4096, 1024))]


@pytest.mark.parametrize("kernel,n,wire,dim", KERNEL_CASES)
def test_pallas_kernels_cross_lower_for_tpu(kernel, n, wire, dim):
    """The Pallas TPU lowering accepts the streamed exchange at the cells'
    shapes and at every N up to its crossover, f32 and bf16 wire — no chip
    needed, and the next construct it refuses fails here."""
    fn, args = _kernel_program(kernel, n, wire, dim)
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


#: one pack of cell 2 (PERF.md section 6, PR 30): 8 workers x batch 32 of
#: ResNet-20, one whole train step, update and exchange included.  The
#: cell's slab of 64 is 8 such packs, one after another.
PACK_WORKERS, PACK_BATCH = 8, 32


def _step_readings(packed: bool, n: int, sharding) -> dict:
    """Compile one train step of ``n`` workers of ResNet-20 for the
    described device and read XLA's own counts and the layouts it chose
    for the activations."""
    import re

    from matcha_tpu.models import ResNet
    from matcha_tpu.ops import WorkerFlattener
    from matcha_tpu.train import make_lr_schedule
    from matcha_tpu.train.state import (
        init_train_state,
        make_optimizer,
        make_train_step,
    )

    class PerWorkerResNet(ResNet):
        pack_width = None  # the packed form hidden: the parent's program

    model = (ResNet if packed else PerWorkerResNet)(depth=20, num_classes=10)
    sched = fixed_schedule(tp.decompose(tp.ring_graph(n), n, seed=0), n,
                           iterations=4)
    comm = make_decen(sched, backend="dense")
    lr = make_lr_schedule(0.002, 12, warmup=False)
    optimizer = make_optimizer(lr)
    state = jax.eval_shape(lambda: init_train_state(
        model, (32, 32, 3), n, optimizer, comm, seed=0)[0])
    step = make_train_step(model, optimizer, comm,
                           WorkerFlattener(state.params), sched.flags,
                           lr_schedule=lr)
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=sharding)
    compiled = step.lower(
        jax.tree.map(on_chip, state),
        on_chip(jax.ShapeDtypeStruct((n, PACK_BATCH, 32, 32, 3), jnp.float32)),
        on_chip(jax.ShapeDtypeStruct((n, PACK_BATCH), jnp.int32))).compile()
    # float32 arrays of rank 4 (a pack's) or 5 (``vmap`` over workers) of
    # half a million elements and more with no dimension of 1 or 3 (a
    # kernel's window, the images' channels) are activations: the size of
    # the dimension the layout puts in the lanes
    lanes = {}
    for dims, layout in set(re.findall(
            r"f32\[((?:\d+,){3,4}\d+)\]\{(\d),", compiled.as_text())):
        shape = [int(d) for d in dims.split(",")]
        if np.prod(shape) >= 500_000 and not {1, 3} & set(shape):
            lanes[dims] = shape[int(layout)]
    return {"packed": packed, "workers": n,
            "bytes_accessed": compiled.cost_analysis()["bytes accessed"],
            "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
            "activation_lanes": lanes}


#: one layer's share of the Mellum cell's tree at N = 2, with the embedding
#: and the head (PERF.md section 4): 2 x 100.6 M elements, 0.8 GB
TOKEN_TREE = {
    "embed": (12288, 2304), "head": (2304, 12288),
    "up": (8, 2304, 896), "gate": (8, 2304, 896), "down": (8, 896, 2304),
    "q": (2304, 512), "o": (512, 2304), "k": (2304, 128), "v": (2304, 128),
    "router": (2304, 64), "norm_a": (2304,), "norm_b": (2304,),
}


def _exchange_readings(on: str, sharding) -> dict:
    """Compile the update, the exchange and the disagreement's sums over
    ``TOKEN_TREE`` at N = 2 for the described device, on the leaves or
    through the flat state as ``train/state.py:step`` has each, and read
    XLA's own counts."""
    import optax

    from matcha_tpu.ops import WorkerFlattener
    from matcha_tpu.train.state import make_optimizer

    n = 2
    spec = lambda shape: jax.ShapeDtypeStruct((n,) + shape, jnp.float32,
                                              sharding=sharding)
    params = {name: spec(shape) for name, shape in TOKEN_TREE.items()}
    optimizer = make_optimizer(lambda step: 0.001)
    opt_state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        jax.eval_shape(optimizer.init, params))
    flattener = WorkerFlattener(params)

    def step(params, opt_state, grads, w):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if on == "leaves":
            leaves, sq = tree_mix(flattener.treedef.flatten_up_to(params), w)
            return (flattener.treedef.unflatten(leaves), opt_state,
                    jnp.sqrt(jnp.sum(sq) / (n * flattener.dim)),
                    jnp.sqrt(sq / flattener.dim))
        flat = stream_mix(flattener.flatten(params), w)
        return (flattener.unflatten(flat), opt_state,
                worker_disagreement(flat), worker_deviation_rows(flat))

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, params,
        jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=sharding)).compile()
    return {"on": on, "state_bytes": 4 * n * flattener.dim,
            "kernels": compiled.as_text().count(
                'custom_call_target="tpu_custom_call"'),
            "bytes_accessed": compiled.cost_analysis()["bytes accessed"],
            "temp_bytes": compiled.memory_analysis().temp_size_in_bytes}


def _grouped_readings(sharding) -> list:
    """Compile the expert layer's three grouped products at the Mellum
    cell's gate/up shape (32,768 rows x 2,304 x 896, eight experts) with the
    tiles ``choose_tiles`` picks, for the described device."""
    from matcha_tpu.ops import grouped

    rows, k, n, experts = 32768, 2304, 896, 8
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                               sharding=sharding)
    sizes = jax.ShapeDtypeStruct((experts,), jnp.int32, sharding=sharding)
    cases = {"gmm": (grouped.moe_gmm, spec(rows, k), spec(experts, k, n)),
             "gmm_transposed": (
                 functools.partial(grouped.moe_gmm, transposed=True),
                 spec(rows, n), spec(experts, k, n)),
             "tgmm": (grouped.moe_tgmm, spec(rows, k), spec(rows, n))}
    readings = []
    for form, (kernel, a, b) in cases.items():
        compiled = jax.jit(kernel).lower(a, b, sizes).compile()
        readings.append({
            "form": form,
            "kernels": compiled.as_text().count(
                'custom_call_target="tpu_custom_call"'),
            "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
            "out_bytes": compiled.memory_analysis().output_size_in_bytes})
    return readings


def _compile_all_for_v5e() -> int:
    """Child-process body of the tests below: compile every kernel case,
    and one pack of cell 2 packed and per worker, for one device of a
    compile-only v5e topology (libtpu, no hardware)."""
    import json
    import os

    # no metadata server here: describe the host to libtpu by hand
    for key, value in (("TPU_SKIP_MDS_QUERY", "1"),
                       ("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                       ("TPU_WORKER_HOSTNAMES", "localhost")):
        os.environ.setdefault(key, value)
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 — any libtpu refusal means "n/a"
        print(f"NO-TOPOLOGY {type(e).__name__}: {e}")
        return 0
    sharding = jax.sharding.SingleDeviceSharding(topo.devices[0])
    for case in KERNEL_CASES:
        fn, args = _kernel_program(*case)
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
                for a in args]
        compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
        # in place: no product, no second state-sized buffer
        assert "convolution(" not in compiled.as_text(), case
        state = 4 * int(np.prod(args[0].shape))
        assert compiled.memory_analysis().temp_size_in_bytes < state // 8
        print("COMPILED", *case)
    for packed in (True, False):
        print("STEP", json.dumps(_step_readings(packed, PACK_WORKERS, sharding)))
    for on in ("leaves", "flat"):
        print("EXCHANGE", json.dumps(_exchange_readings(on, sharding)))
    for reading in _grouped_readings(sharding):
        print("GROUPED", json.dumps(reading))
    return 0


@pytest.fixture(scope="module")
def v5e_child():
    """One child process for every test that needs the v5e's compiler:
    loading libtpu here would hang a TPU plane on every later profiler
    trace of this one."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], cwd=repo,
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": repo})
    if "NO-TOPOLOGY" in proc.stdout:
        pytest.skip(f"no compile-only TPU topology here: {proc.stdout[-300:]}")
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_pallas_kernels_compile_for_v5e(v5e_child):
    """Mosaic itself (layout inference, VMEM allocation) compiles the
    kernels for the v5e at the train shapes, ahead of time."""
    assert v5e_child.count("COMPILED") == len(KERNEL_CASES), v5e_child


def test_packed_step_compiles_for_v5e_with_full_lanes(v5e_child):
    """One pack of cell 2's workers, as the v5e's compiler lays its step
    out (counts and layouts; nothing runs, so no time).  Per worker,
    activations sit with the 32-image batch or 32 or 64 channels in the 128
    lanes; packed, every activation has 128 or more, XLA counts under half
    the bytes, and the temporaries are an eighth of the 4 GB a slab of 8
    packs may take (the packs run one after another, so a slab's are one
    pack's)."""
    import json

    pack, per_worker = (json.loads(line.split(" ", 1)[1])
                        for line in v5e_child.splitlines()
                        if line.startswith("STEP "))
    assert pack["packed"] and not per_worker["packed"]
    assert min(per_worker["activation_lanes"].values()) < 128
    assert len(pack["activation_lanes"]) >= 3
    assert min(pack["activation_lanes"].values()) >= 128, pack
    assert pack["temp_bytes"] < 4e9 / 8
    assert pack["bytes_accessed"] < 0.5 * per_worker["bytes_accessed"]


def test_exchange_on_the_leaves_compiles_for_v5e_with_half_the_bytes(v5e_child):
    """Update, exchange and the disagreement's sums over a token-cell-shaped
    tree at N = 2, as the v5e's compiler counts them (nothing runs, so no
    time): on the leaves, the five leaves whose shapes hold 1/32 of the tree
    in place (four shapes: ``up`` and ``gate`` share a site) and the other
    seven as one small buffer, under half the bytes of the flat path and no
    state-sized temporary; the flat path holds the flat copy."""
    import json

    leaves, flat = (json.loads(line.split(" ", 1)[1])
                    for line in v5e_child.splitlines()
                    if line.startswith("EXCHANGE "))
    assert (leaves["on"], flat["on"]) == ("leaves", "flat")
    assert (leaves["kernels"], flat["kernels"]) == (6, 1)
    assert leaves["bytes_accessed"] < 0.5 * flat["bytes_accessed"], (leaves,
                                                                     flat)
    assert leaves["temp_bytes"] < leaves["state_bytes"] // 8
    assert flat["temp_bytes"] >= flat["state_bytes"]


def test_grouped_products_compile_for_v5e_at_whole_width_tiles(v5e_child):
    """The expert layer's three grouped products at the Mellum cell's
    gate/up shape, tiles of 512 rows by the whole of both widths under the
    48 MiB the kernels ask of VMEM (the compiler's own scope is 16 MiB and
    would refuse them): each compiles to one kernel, and the program holds
    no copy of an operand beside it (no transposed weights, no transposed
    activations)."""
    import json

    readings = {r["form"]: r for r in (
        json.loads(line.split(" ", 1)[1])
        for line in v5e_child.splitlines() if line.startswith("GROUPED "))}
    assert set(readings) == {"gmm", "gmm_transposed", "tgmm"}
    rows, k, n, experts = 32768, 2304, 896, 8
    assert readings["gmm"]["out_bytes"] == rows * n * 4
    assert readings["gmm_transposed"]["out_bytes"] == rows * k * 4
    assert readings["tgmm"]["out_bytes"] == experts * k * n * 4
    for form, reading in readings.items():
        assert reading["kernels"] == 1, reading
        # the grid's few hundred int32 and nothing operand-sized (the
        # weights are 33 MB, a bfloat16 operand 59 MB or more)
        assert reading["temp_bytes"] < 1 << 20, reading


if __name__ == "__main__":
    import sys

    sys.exit(_compile_all_for_v5e())
