"""The train step on the leaves where they lie (PERF.md section 6, PR 34; the
kernel, the tree form and the shape rule are ``tests/test_leaf_exchange.py``).

``exchange_plan`` decides whether a train step may run the exchange on the
leaves.  Here, on the CPU with the kernels under the Pallas interpreter:

* through ``make_train_step``, six steps on the leaves land where six steps
  on the flat state land (parameters, momentum and statistics bitwise, the
  disagreement and the telemetry to 1e-6), on the CIFAR ResNet at N = 16 and
  the toy token model at N = 2; a thinned step under ``local_steps`` too;
* every refusal of the plan (N = 33 and 128, a mesh, overlap, the ring, a
  fault plan, membership, CHOCO, the centralized communicator, ``gather``)
  keeps the flat step, to the byte, and ``train()`` journals which ran and
  why in its ``backend`` event.
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matcha_tpu.communicator import (make_centralized, make_choco,
                                     make_decen)
from matcha_tpu.models import MLP, ResNet, select_model
from matcha_tpu.obs.journal import validate_event
from matcha_tpu.obs.telemetry import Telemetry, make_telemetry_spec
from matcha_tpu.ops import WorkerFlattener
from matcha_tpu.parallel import STREAM_MAX_WORKERS, pallas_gossip
from matcha_tpu.resilience import FaultPlan
from matcha_tpu.train import TrainConfig, make_lr_schedule, train
from matcha_tpu.train.state import (exchange_plan, init_train_state,
                                    make_optimizer, make_train_step)
from test_leaf_exchange import STEPS, _schedule  # beside this file


def _no_leaves(monkeypatch):
    monkeypatch.setattr(pallas_gossip, "_LEAF_MIN_ELEMENTS", 1 << 62)


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
