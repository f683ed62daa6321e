"""The one-chip exchange on the leaves where they lie (PERF.md section 6, PR 34;
the kernel and most of these cases were PR 33's, refused for its set-up).

``pallas_gossip.leaf_mix`` mixes one ``[N, r, c]`` leaf in place and sums, in
the same pass, the squares the disagreement needs; ``leaf_view`` decides from
a leaf's shape whether it may go there, ``leaf_views`` from the tree's
whether its shape is worth a kernel site, or it rides the small flat buffer;
``tree_mix`` runs a whole parameter tree.  Here, on the CPU with the kernels
under the Pallas interpreter (``tests/test_leaf_cells.py`` has the set-up
guard and the cells' own trees; ``tests/test_leaf_step.py`` has the train
step, ``exchange_plan`` and the journal: two files, so that ``loadfile`` runs
them beside each other):

* the tree form reads what ``flatten -> stream_mix -> unflatten`` reads on
  every leaf, and its sums are ``worker_disagreement`` and
  ``worker_deviation_rows`` of the flat state;
* a NaN in one worker's leaf reaches the rows it reaches on the flat path;
* the rule takes a leaf or sends it to the remainder, and says why.

(That the kernel compiles for a described v5e, in place, at the cells' leaf
shapes is a case of ``tests/test_pallas.py``, in its child process.)
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matcha_tpu import topology as tp
from matcha_tpu.communicator import make_decen
from matcha_tpu.ops import WorkerFlattener
from matcha_tpu.parallel import (STREAM_MAX_WORKERS, leaf_mix, leaf_view,
                                 stream_mix, worker_deviation_rows,
                                 worker_disagreement, worker_square_rows)
from matcha_tpu.schedule import matcha_schedule

STEPS = 6


@functools.cache  # a schedule is solved once a worker count (128: seconds)
def _schedule(n, steps=STEPS):
    topology = "chain" if n < 4 else "ring"
    decomposed = tp.decompose(tp.make_graph(topology, n, seed=0), n, seed=0)
    return matcha_schedule(decomposed, n, iterations=steps, budget=0.7, seed=5)


#: leaves of rank 1 to 5 (the workers' axis counted), by what the rule says
IN_PLACE = {"conv": (3, 3, 16, 128), "wide": (24, 256), "router": (128, 64),
            "ragged_lanes": (16, 200), "experts": (2, 3, 8, 128)}
REMAINDER = {"scalar": (), "norm": (130,), "odd_rows": (3, 3, 3, 16),
             "odd_lanes": (24, 100), "one_row": (1, 256)}
TREES = {"every": IN_PLACE, "none": REMAINDER,
         "some": {**IN_PLACE, **REMAINDER}}
#: at N >= 16 a kernel unrolls 256 multiply-adds and the interpreter's
#: program compiles for seconds: one leaf in place a tree there, not five
FEW = {"every": "conv", "some": "router", "none": None}


def _shapes(kind, n):
    return {name: shape for name, shape in TREES[kind].items()
            if n < 16 or name in REMAINDER or name == FEW[kind]}


def _tree(n, shapes, seed=0):
    rng = np.random.default_rng(seed)
    return {name: jnp.asarray(rng.normal(size=(n,) + shape), jnp.float32)
            for name, shape in shapes.items()}


def _flat_reference(comm, flattener, tree, flags_t):
    flat, _ = comm.step(flattener.flatten(tree), (), flags_t)
    return flat, flattener.unflatten(flat)


@functools.lru_cache(maxsize=None)
def _compiled(n, wire):
    """One jitted ``leaves_step`` for every flag row and tree of a worker
    count and wire: a leaf's kernel compiles once."""
    sched = _schedule(n)
    comm = make_decen(sched, backend="dense", wire_dtype=wire)
    return sched, comm, jax.jit(comm.leaves_step)


@pytest.mark.parametrize("kind", sorted(TREES))
@pytest.mark.parametrize("flags", ["firing", "empty"])
@pytest.mark.parametrize("wire", [None, "bf16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("n", [2, 3, 16, STREAM_MAX_WORKERS])
def test_tree_form_reads_what_the_flat_exchange_reads(n, wire, flags, kind,
                                                      small_leaves):
    sched, comm, leaves_step = _compiled(n, wire)
    tree = _tree(n, _shapes(kind, n), seed=n)
    flattener = WorkerFlattener(tree)
    in_place = [name for name, leaf in tree.items()
                if not isinstance(leaf_view(leaf.shape), str)]
    assert sorted(in_place) == sorted(set(tree) & set(IN_PLACE))
    row = (np.zeros_like(sched.flags[0]) if flags == "empty"
           else np.ones_like(sched.flags[0]))
    flags_t = jnp.asarray(row, jnp.float32)

    leaves = flattener.treedef.flatten_up_to(tree)
    mixed, carry, sq = leaves_step(leaves, (), flags_t)
    flat, want = _flat_reference(comm, flattener, tree, flags_t)
    assert carry == ()
    for name, got, ref in zip(sorted(tree), mixed,
                              flattener.treedef.flatten_up_to(want)):
        assert got.shape == ref.shape and got.dtype == ref.dtype, name
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-7, err_msg=name)
    if flags == "empty" and wire is None:
        # W = I runs through the kernel and returns the state bitwise
        for got, before in zip(mixed, leaves):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(before))
    # (two workers that the one matching has just averaged stand 0 apart on
    # one path and a rounding of their unit-sized values on the other)
    np.testing.assert_allclose(
        np.sqrt(np.asarray(sq) / flattener.dim),
        np.asarray(worker_deviation_rows(flat)), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(
        np.sqrt(float(jnp.sum(sq)) / (n * flattener.dim)),
        float(worker_disagreement(flat)), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(worker_square_rows(mixed)),
                               np.asarray(sq), rtol=1e-6,
                               atol=1e-14 * flattener.dim)


@pytest.mark.parametrize("wire", [None, "bf16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("n,r,c,block_rows", [
    (2, 40, 256, 16),     # the last block holds 8 of its 16 rows
    (16, 24, 640, 16),    # 8 of 16, two registers a worker a pass
    (3, 40, 200, 32),     # 8 of 32, and the lanes ragged too
    (2, 16, 2048 + 304, 8),   # a whole chunk and a tail that ends mid-lane
    (32, 8, 128, None),   # one row group, one register a worker
])
def test_leaf_blocks_ragged_or_not_read_what_the_streamed_pass_reads(
        n, r, c, block_rows, wire):
    rng = np.random.default_rng(r + c)
    x = jnp.asarray(rng.normal(size=(n, r, c)), jnp.float32)
    w = rng.random((n, n)).astype(np.float32)
    w = jnp.asarray(w / w.sum(axis=1, keepdims=True))
    want = stream_mix(x.reshape(n, -1), w, wire_dtype=wire, interpret=True)
    got, blocks = jax.jit(lambda x, w: leaf_mix(
        x, w, wire_dtype=wire, interpret=True, block_rows=block_rows))(x, w)
    # the same float32 multiply-adds in the same order over j
    np.testing.assert_array_equal(np.asarray(got).reshape(n, -1),
                                  np.asarray(want))
    assert blocks.shape == (n, -(-r // (block_rows or r)))
    np.testing.assert_allclose(np.asarray(blocks.sum(axis=1)),
                               np.asarray(worker_square_rows([want])),
                               rtol=1e-6)


def test_leaf_mix_refuses_rows_that_are_not_whole_sublanes():
    x = jnp.zeros((2, 12, 128), jnp.float32)
    with pytest.raises(ValueError, match="whole sublanes"):
        leaf_mix(x, jnp.eye(2), interpret=True)
    with pytest.raises(ValueError, match="mixing matrix"):
        leaf_mix(jnp.zeros((2, 16, 128)), jnp.eye(3), interpret=True)


@pytest.mark.parametrize("worker,leaf", [(0, "conv"), (2, "norm"),
                                         (3, "router")])
def test_a_nan_reaches_the_rows_it_reaches_on_the_flat_path(worker, leaf,
                                                            small_leaves):
    """A non-finite value in one worker's leaf: after the exchange it sits
    in the rows of that leaf it sits in on the flat path (every row, since
    0 x NaN is NaN in both), at the same elements and nowhere else."""
    n = 4
    sched = _schedule(n)
    comm = make_decen(sched, backend="dense")
    tree = _tree(n, TREES["some"])
    tree[leaf] = tree[leaf].at[(worker,) + (0,) * (tree[leaf].ndim - 1)].set(
        jnp.nan)
    flattener = WorkerFlattener(tree)
    flags_t = jnp.ones((sched.flags.shape[1],), jnp.float32)
    mixed, _, sq = jax.jit(comm.leaves_step)(
        flattener.treedef.flatten_up_to(tree), (), flags_t)
    flat, want = _flat_reference(comm, flattener, tree, flags_t)
    hit = 0
    for got, ref in zip(mixed, flattener.treedef.flatten_up_to(want)):
        np.testing.assert_array_equal(np.isnan(np.asarray(got)),
                                      np.isnan(np.asarray(ref)))
        hit += int(np.isnan(np.asarray(got)).sum())
    assert hit == n  # one element of one leaf, in every worker's row
    np.testing.assert_array_equal(
        np.isnan(np.asarray(sq)),
        np.isnan(np.asarray(worker_deviation_rows(flat))))


# ------------------------------------------------------------------ the rule

@pytest.mark.parametrize("shape,view", [
    # the cells' leaves: every width the issue names
    ((2, 2304, 12288), (2304, 12288, False)),      # the Mellum head
    ((2, 8, 2304, 896), (18432, 896, False)),      # experts, collapsed
    ((2, 8, 768, 2048), (6144, 2048, False)),
    ((16, 3, 3, 640, 640), (5760, 640, False)),    # cell 1's widest stage
    ((16, 3, 3, 320, 320), (2880, 320, False)),    # 384 lanes: 20% padding
    ((2, 2048, 18992), (18992, 2048, True)),       # lies {1,2,0} on the v5e
    ((2, 18992, 2048), (18992, 2048, False)),
    ((2, 2304, 64), (64, 2304, True)),             # a router, lanes-first
    ((2, 2000, 1000), (2000, 1000, False)),        # a tie stays as written
])
def test_rule_takes_the_leaf_and_names_its_view(shape, view):
    assert leaf_view(shape) == view


@pytest.mark.parametrize("shape,dtype,reason", [
    ((16, 3, 3, 160, 160), jnp.float32, "60% padding"),
    ((16, 3, 3, 200, 200), jnp.float32, "28% padding"),
    ((16, 3, 3, 16, 160), jnp.float32, "60% padding"),
    ((16, 640, 100), jnp.float32, "not whole sublanes"),
    ((16, 3, 3, 3, 16), jnp.float32, "under"),
    ((16, 3, 3, 3, 1 << 16), jnp.float32, "not whole sublanes"),
    ((2, 2304), jnp.float32, "no dimension between"),
    ((2,), jnp.float32, "no dimension between"),
    ((2, 2304, 512), jnp.bfloat16, "bfloat16"),
    ((2, 64, 128), jnp.float32, "under 262144 elements"),
    ((32, 1024, 4096), jnp.float32, "do not fit the resident blocks"),
])
def test_rule_sends_the_leaf_to_the_remainder_and_says_why(shape, dtype,
                                                           reason):
    assert reason in leaf_view(shape, dtype)
