"""The one-chip exchange on the leaves where they lie (PERF.md section 6, PR 34;
the kernel and most of these cases were PR 33's, refused for its set-up).

``pallas_gossip.leaf_mix`` mixes one ``[N, r, c]`` leaf in place and sums, in
the same pass, the squares the disagreement needs; ``leaf_view`` decides from
a leaf's shape whether it may go there, ``leaf_views`` from the tree's
whether its shape is worth a kernel site, or it rides the small flat buffer;
``tree_mix`` runs a whole parameter tree; ``exchange_plan`` decides whether a
train step may.  Here, on the CPU with the kernels under the Pallas
interpreter (``tests/test_leaf_cells.py`` has the set-up guard and the cells'
own trees):

* the tree form reads what ``flatten -> stream_mix -> unflatten`` reads on
  every leaf, and its sums are ``worker_disagreement`` and
  ``worker_deviation_rows`` of the flat state;
* a NaN in one worker's leaf reaches the rows it reaches on the flat path;
* through ``make_train_step``, six steps on the leaves land where six steps
  on the flat state land (parameters, momentum and statistics bitwise, the
  disagreement and the telemetry to 1e-6), on the CIFAR ResNet at N = 16 and
  the toy token model at N = 2; a thinned step under ``local_steps`` too;
* every refusal of the plan (N = 33 and 128, a mesh, overlap, the ring, a
  fault plan, membership, CHOCO, the centralized communicator, ``gather``)
  keeps the flat step, to the byte, and ``train()`` journals which ran and
  why in its ``backend`` event.

(That the kernel compiles for a described v5e, in place, at the cells' leaf
shapes is a case of ``tests/test_pallas.py``, in its child process.)
"""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matcha_tpu import topology as tp
from matcha_tpu.communicator import (make_centralized, make_choco,
                                     make_decen)
from matcha_tpu.models import MLP, ResNet, select_model
from matcha_tpu.obs.journal import validate_event
from matcha_tpu.obs.telemetry import Telemetry, make_telemetry_spec
from matcha_tpu.ops import WorkerFlattener
from matcha_tpu.parallel import (STREAM_MAX_WORKERS, leaf_mix, leaf_view,
                                 pallas_gossip, stream_mix, tree_mix,
                                 worker_deviation_rows, worker_disagreement,
                                 worker_square_rows)
from matcha_tpu.resilience import FaultPlan
from matcha_tpu.schedule import matcha_schedule
from matcha_tpu.train import TrainConfig, make_lr_schedule, train
from matcha_tpu.train.state import (exchange_plan, init_train_state,
                                    make_optimizer, make_train_step)

STEPS = 6


def _no_leaves(monkeypatch):
    monkeypatch.setattr(pallas_gossip, "_LEAF_MIN_ELEMENTS", 1 << 62)


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


# ------------------------------------------------------------------ the step

IMAGE = (16, 16, 3)
TOKEN_SIZES = {
    "hidden": 32, "head_dim": 8, "q_heads_held": 4, "kv_heads_held": 1,
    "layer_types": ["sliding", "full"], "sliding_window": 8,
    "rope_theta": 500000,
    "yarn": {"factor": 16, "original_max_position_embeddings": 8192,
             "beta_fast": 32, "beta_slow": 1,
             "attention_factor": 1.2772588722239782},
    "num_experts": 8, "experts_per_token": 2, "experts_held": [0, 1],
    "expert_width": 24, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "vocab_held": 48, "seq_len": 32, "attn_block": 16, "loss_chunk": 16,
}


def _conv_job(n=16):
    model = ResNet(depth=8, num_classes=10)
    rng = np.random.default_rng(1)
    batches = [(jnp.asarray(rng.normal(size=(n, 2) + IMAGE), jnp.float32),
                jnp.asarray(rng.integers(0, 10, (n, 2)), jnp.int32))
               for _ in range(STEPS)]
    return model, IMAGE, batches, {}


def _token_job(n=2):
    from chipbench.tasks import next_token

    model = select_model("mellum2", sizes=TOKEN_SIZES, remat=True)
    data = next_token.make(11, n * 2 * STEPS, 2, {"sizes": TOKEN_SIZES})
    shape = (STEPS, n, 2, TOKEN_SIZES["seq_len"] + 1)
    xs = data["x_train"].reshape(shape)
    ys = data["y_train"].reshape(shape)
    batches = [(jnp.asarray(x), jnp.asarray(y)) for x, y in zip(xs, ys)]
    return model, (TOKEN_SIZES["seq_len"] + 1,), batches, {"grad_chunk": 1}


def _build_step(model, input_shape, n, comm=None, sched=None, **kwargs):
    sched = sched or _schedule(n)
    comm = comm or make_decen(sched, backend="dense")
    lr = make_lr_schedule(0.05, 2, warmup=False)
    optimizer = make_optimizer(lr)
    state, flattener = jax.jit(
        lambda: init_train_state(model, input_shape, n, optimizer, comm,
                                 seed=0, sync_init=False,
                                 overlap=kwargs.get("overlap", "off"),
                                 staleness=kwargs.get("staleness", 1))[0]
    )(), None
    flattener = WorkerFlattener(state.params)
    spec = make_telemetry_spec(sched.decomposed, flattener.dim,
                               overlap=kwargs.get("overlap", "off"),
                               staleness=kwargs.get("staleness", 1))
    state = state.replace(telemetry=Telemetry.zeros(
        n, kwargs.get("staleness", 1)))
    step = make_train_step(model, optimizer, comm, flattener, sched.flags,
                           lr_schedule=lr, telemetry=spec, **kwargs)
    return step, state, comm, flattener


def _six_steps(job, n, monkeypatch, on):
    model, input_shape, batches, kwargs = job
    if on == "flat":
        _no_leaves(monkeypatch)
    step, state, comm, flattener = _build_step(model, input_shape, n,
                                               **kwargs)
    assert exchange_plan(comm, flattener)["layout"] == on
    metrics = []
    for x, y in batches:
        state, m = step(state, x, y)
        metrics.append(jax.tree.map(np.asarray, m))
    return state, metrics


@pytest.mark.parametrize("job,n", [(_conv_job, 16), (_token_job, 2)],
                         ids=["cifar_resnet_n16", "toy_tokens_n2"])
def test_six_steps_on_the_leaves_land_where_six_flat_steps_land(
        job, n, small_leaves, monkeypatch):
    job = job(n)
    if n == 16:
        # the three widest convolutions in place, the rest in the remainder
        monkeypatch.setattr(pallas_gossip, "_LEAF_MIN_ELEMENTS",
                            16 * 3 * 3 * 32 * 32)
    got, got_m = _six_steps(job, n, monkeypatch, "leaves")
    want, want_m = _six_steps(job, n, monkeypatch, "flat")
    for name in ("params", "opt_state", "batch_stats", "telemetry"):
        a, b = getattr(got, name), getattr(want, name)
        assert jax.tree.structure(a) == jax.tree.structure(b), name
        for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                                jax.tree.leaves(b)):
            where = name + jax.tree_util.keystr(path)
            if name == "telemetry":
                # the disagreement's sums, a block and a leaf at a time
                np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                           rtol=2e-6, atol=1e-7,
                                           err_msg=where)
            else:
                # the same float32 products in the same order: bitwise
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                              err_msg=where)
    assert int(got.step) == int(want.step) == STEPS
    for a, b in zip(got_m, want_m):
        assert sorted(a) == sorted(b)
        for key in a:
            if key == "disagreement":
                np.testing.assert_allclose(a[key], b[key], rtol=1e-6,
                                           atol=1e-7)
            else:
                np.testing.assert_array_equal(a[key], b[key], err_msg=key)
    # the exchange fired at least once and left the workers apart
    assert float(got_m[-1]["disagreement"]) > 0
    assert any(float(m["active_matchings"]) > 0 for m in got_m)


def test_a_thinned_step_mixes_nothing_and_still_measures(small_leaves,
                                                         monkeypatch):
    """``local_steps`` 2 puts the exchange under a ``cond``: the leaves
    route runs inside that same ``cond`` (it does not fall back to the flat
    state), and the step that skips it reports the disagreement all the
    same, from the same sums taken a leaf at a time."""
    model, input_shape, batches, _ = _conv_job(4)
    batches = [(x[:4], y[:4]) for x, y in batches[:4]]
    out = {}
    for on in ("leaves", "flat"):
        if on == "flat":
            _no_leaves(monkeypatch)
        step, state, comm, flattener = _build_step(model, input_shape, 4,
                                                   local_steps=2)
        assert exchange_plan(comm, flattener)["layout"] == on
        rows = []
        for x, y in batches:
            state, m = step(state, x, y)
            rows.append(float(m["disagreement"]))
        out[on] = (state, rows)
    np.testing.assert_allclose(out["leaves"][1], out["flat"][1], rtol=1e-6,
                               atol=1e-7)
    for a, b in zip(jax.tree.leaves(out["leaves"][0].params),
                    jax.tree.leaves(out["flat"][0].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------------ the plan

class _Mesh:
    size = 4


def _mlp_flattener(n):
    model = MLP(num_classes=10, hidden=128)
    params = jax.eval_shape(lambda: jax.vmap(
        lambda k: model.init(k, jnp.zeros((1,) + IMAGE))["params"])(
            jax.random.split(jax.random.PRNGKey(0), n)))
    return model, WorkerFlattener(params)


def _refusals():
    n = 4
    sched = _schedule(n)
    dense = lambda **kw: make_decen(sched, backend="dense", **kw)
    above = lambda n: (n, lambda: make_decen(_schedule(n), backend="dense"),
                       {}, f"N = {n} > {STREAM_MAX_WORKERS}")
    return {
        "n33_above_the_crossover": above(STREAM_MAX_WORKERS + 1),
        "n128_above_the_crossover": above(128),
        "a_mesh": (n, lambda: dense(mesh=_Mesh()), {}, "a mesh of 4 devices"),
        "overlap": (n, dense, {"overlap": "1step"}, "overlap parks"),
        "staleness": (n, dense, {"overlap": "1step", "staleness": 3},
                      "staleness ring"),
        "fault_plan": (n, dense, {"faults": True}, "fault plan"),
        "elastic": (n, dense, {"elastic": True}, "elastic membership"),
        "choco": (n, lambda: make_choco(sched, ratio=0.5), {},
                  "carries flat state"),
        "centralized": (n, make_centralized, {}, "carries flat state"),
        "gather": (n, lambda: make_decen(sched, backend="gather"), {},
                   "'gather' is not the dense exchange"),
    }


@pytest.mark.parametrize("case", sorted(_refusals()))
def test_plan_keeps_the_flat_step_and_says_why(case, small_leaves):
    n, make_comm, kwargs, reason = _refusals()[case]
    _, flattener = _mlp_flattener(n)
    plan = exchange_plan(make_comm(), flattener, **kwargs)
    assert plan["layout"] == "flat" and reason in plan["reason"], plan
    assert plan["leaves_in_place"] == plan["small_buffer_elements"] == 0
    # a flat step holds the streamed pass's one kernel where the exchange
    # is that pass, and none otherwise
    assert plan["kernel_sites"] == int(
        case in ("overlap", "staleness", "fault_plan", "elastic"))
    # and the communicator itself says whether it has a leaves form at all
    assert (make_comm().leaves_step is None) == (plan["kernel_sites"] == 0)


def test_plan_counts_the_leaves_it_takes_and_refuses_where_none_passes(
        monkeypatch):
    n = 4
    _, flattener = _mlp_flattener(n)
    comm = make_decen(_schedule(n), backend="dense")
    # as shipped: fc1 [4, 768, 128] passes; fc2 (65,536 elements), fc3 (10
    # lanes) and the biases ride the remainder
    plan = exchange_plan(comm, flattener)
    assert plan == {"layout": "leaves", "kernel_sites": 2,
                    "leaves_in_place": 1,
                    "small_buffer_elements": n * (flattener.dim - 768 * 128)}
    _no_leaves(monkeypatch)
    plan = exchange_plan(comm, flattener)
    assert plan["layout"] == "flat" and "no leaf passes" in plan["reason"]
    assert plan["kernel_sites"] == 1


def _step_sha(n, make_comm, kwargs, sched=None):
    model, _ = _mlp_flattener(n)
    step_kwargs = dict(kwargs)
    sched = sched or _schedule(n)
    if step_kwargs.pop("faults", False):
        step_kwargs["faults"] = FaultPlan(events=()).compile(
            sched.iterations, n, sched.num_matchings)
    comm = make_comm()
    lr = make_lr_schedule(0.05, 2, warmup=False)
    optimizer = make_optimizer(lr)
    state = jax.eval_shape(lambda: init_train_state(
        model, IMAGE, n, optimizer, comm, seed=0,
        overlap=step_kwargs.get("overlap", "off"),
        staleness=step_kwargs.get("staleness", 1))[0])
    if step_kwargs.get("elastic"):
        from matcha_tpu.elastic.runtime import membership_arrays

        state = state.replace(membership=jax.eval_shape(
            lambda: membership_arrays(np.ones(n, np.float32), 1.0)))
    step = make_train_step(model, optimizer, comm,
                           WorkerFlattener(state.params), sched.flags,
                           lr_schedule=lr, **step_kwargs)
    x = jax.ShapeDtypeStruct((n, 2) + IMAGE, jnp.float32)
    y = jax.ShapeDtypeStruct((n, 2), jnp.int32)
    text = step.lower(state, x, y).as_text()
    return hashlib.sha256(text.encode()).hexdigest(), text


@pytest.mark.parametrize("case", sorted(_refusals()))
def test_refused_step_lowers_to_the_flat_program(case, small_leaves,
                                                 monkeypatch):
    """Where the plan says ``flat``, the step is the program it is when no
    leaf of the tree passes the rule at all (the parent's: ``PERF.md``
    section 6 has its SHA-256 against the parent commit): the same lowered
    text, and no leaf kernel in it."""
    n, make_comm, kwargs, _ = _refusals()[case]
    sched = _schedule(n)
    ours, text = _step_sha(n, make_comm, kwargs, sched)
    assert "leaf_mix" not in text and text.count("pallas_call") <= 1
    _no_leaves(monkeypatch)
    flat, _ = _step_sha(n, make_comm, kwargs, sched)
    assert ours == flat, case


def test_leaves_step_is_another_program(small_leaves, monkeypatch):
    n = 4
    dense = lambda: make_decen(_schedule(n), backend="dense")
    ours, text = _step_sha(n, dense, {})
    _no_leaves(monkeypatch)
    flat, _ = _step_sha(n, dense, {})
    assert ours != flat


# --------------------------------------------------------------- the journal

@pytest.mark.faults
@pytest.mark.parametrize("extra,on,reason", [
    ({}, "leaves", None),
    ({"overlap": "1step"}, "flat", "overlap parks"),
    ({"communicator": "choco"}, "flat", "carries flat state"),
    ({"fault_plan": FaultPlan(events=())}, "flat", "fault plan"),
])
def test_train_journals_where_the_exchange_runs(extra, on, reason,
                                                small_leaves):
    """The run's one ``backend`` event says it, in its ``exchange`` record
    beside the form; a communicator with no gossip backend journals none."""
    config = TrainConfig(
        name="leaves", model="mlp", model_kwargs={"hidden": 128},
        dataset="synthetic_image",
        dataset_kwargs={"num_train": 16, "num_test": 8}, num_workers=4,
        topology="ring", graphid=None, batch_size=2, epochs=2, lr=0.05,
        warmup=False, matcha=True, budget=0.7, seed=1, save=False,
        eval_every=0, measure_comm_split=False, devices=1, **extra)
    result = train(config)
    assert np.isfinite(result.history[-1]["loss"])
    assert result.history[-1]["disagreement"] >= 0
    assert not [e for e in result.recorder.events if e["kind"] == "exchange"]
    events = [e for e in result.recorder.events if e["kind"] == "backend"]
    if extra.get("communicator") == "choco":
        assert events == []
        return
    (event,) = events
    assert validate_event(event) == []
    record = event["exchange"]
    assert record["form"] == "streamed" and record["layout"] == on
    total = 4 * sum(int(np.prod(a.shape[1:])) for a in
                    jax.tree.leaves(result.state.params))
    if on == "leaves":
        assert "reason" not in record
        # fc1 and fc2 are two shapes, and the small buffer's one kernel
        assert record["leaves_in_place"] == 2 and record["kernel_sites"] == 3
        assert 0 < record["small_buffer_elements"] < total // 8
        assert not [e for e in result.recorder.events
                    if e["kind"] == "retrace"]
    else:
        assert reason in record["reason"]
        assert record["kernel_sites"] == 1
        assert record["leaves_in_place"] == record["small_buffer_elements"] == 0


def test_plan_is_a_function_of_shapes_not_of_a_config_field():
    """No ``TrainConfig`` field, flag or environment variable chooses the
    path: nothing in the config names it."""
    names = {f.name for f in dataclasses.fields(TrainConfig)}
    assert not {n for n in names if "leaf" in n or "leaves" in n
                or "exchange" in n}
