"""The leaves route of the one-chip exchange on the benchmark's own
configurations (PERF.md section 6, PR 34).

* The set-up guard PR 33 did not have: for each configuration's parameter
  tree at published widths (shapes alone: nothing compiled or run), the
  exchange with its reductions as the step calls it, lowered for TPU, holds
  at most a dozen Pallas kernel sites and at most three times the text of
  the flat route's.  A site is paid at every start, cache or no cache.
* On the three cells' rehearsal models and on a tree whose shapes repeat,
  at N = 2, 3, 16 and 32 and both wires, the leaves route reads what the
  flat route reads: parameters bitwise, the disagreement and the per-worker
  deviations to 1e-6; and one whole ``make_train_step`` a model.
"""

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import catalog, harness
from matcha_tpu import topology as tp
from matcha_tpu.communicator import make_decen
from matcha_tpu.models import mellum2, select_model
from matcha_tpu.ops import WorkerFlattener, grouped
from matcha_tpu.parallel import (STREAM_MAX_WORKERS, leaf_views,
                                 pallas_gossip, worker_deviation_rows,
                                 worker_disagreement)
from matcha_tpu.schedule import matcha_schedule
from matcha_tpu.train import make_lr_schedule
from matcha_tpu.train.state import (exchange_plan, init_train_state,
                                    make_optimizer, make_train_step)

CELLS = [w["name"] for w in catalog.benchmark()["workloads"]]
IMAGE = (32, 32, 3)


def _sizes(cell):
    """The ``sizes`` a cell's job gives its model, or ``{}``."""
    kwargs = catalog.load_cell(cell)[1]["train_config"].get("model_kwargs")
    return (kwargs or {}).get("sizes", {})


#: the cells whose model has ``sizes`` and an expert layer, whatever their
#: place in the list
TOKEN_CELLS = [c for c in CELLS if "experts_held" in _sizes(c)]


@functools.lru_cache(maxsize=None)
def _schedule(n):
    topology = "chain" if n < 4 else "ring"
    decomposed = tp.decompose(tp.make_graph(topology, n, seed=0), n, seed=0)
    return matcha_schedule(decomposed, n, iterations=4, budget=0.7, seed=5)


def _model(cell, rehearsal):
    """The cell's model (or its rehearsal's), and the input its init takes."""
    _, job, config_file = catalog.load_cell(cell)
    job, config_file = copy.deepcopy(job), copy.deepcopy(config_file)
    if rehearsal:
        harness.apply_rehearsal(job, config_file)
    tc = job["train_config"]
    model = select_model(tc["model"], tc["dataset"],
                         remat=tc.get("remat", False),
                         **(tc.get("model_kwargs") or {}))
    dummy = (model.dummy_input(()) if hasattr(model, "dummy_input")
             else jnp.zeros((1,) + IMAGE, jnp.float32))
    return tc, model, dummy


def _tree_shapes(cell):
    tc, model, dummy = _model(cell, rehearsal=False)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), dummy, train=False))["params"]
    n = tc["num_workers"]
    return n, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype), shapes)


# ------------------------------------------------------------ the set-up guard

SITE_BUDGET = 12
TEXT_BUDGET = 3.0


def _lowered_exchange(comm, flattener, tree, layout):
    """The exchange with its reductions as ``make_train_step`` calls it on
    either layout, lowered for TPU (on the CPU host: nothing compiles)."""
    n = flattener.num_workers

    def on_leaves(tree, flags_t):
        leaves, _, sq = comm.leaves_step(
            flattener.treedef.flatten_up_to(tree), (), flags_t)
        return (flattener.treedef.unflatten(leaves),
                jnp.sqrt(jnp.sum(sq) / (n * flattener.dim)),
                jnp.sqrt(sq / flattener.dim))

    def on_flat(tree, flags_t):
        flat, _ = comm.step(flattener.flatten(tree), (), flags_t)
        return (flattener.unflatten(flat), worker_disagreement(flat),
                worker_deviation_rows(flat))

    flags = jax.ShapeDtypeStruct((_schedule(n).flags.shape[1],), jnp.float32)
    return jax.jit(on_leaves if layout == "leaves" else on_flat).trace(
        tree, flags).lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("cell", CELLS)
def test_exchange_of_every_configuration_lowers_inside_the_setup_budget(
        cell, monkeypatch):
    # the kernels as the chip compiles them, not the interpreter's program
    monkeypatch.setattr(pallas_gossip, "pallas_interpret", lambda: False)
    n, tree = _tree_shapes(cell)
    flattener = WorkerFlattener(tree)
    comm = make_decen(_schedule(n), backend="dense")
    plan = exchange_plan(comm, flattener)
    flat = _lowered_exchange(comm, flattener, tree, "flat")
    if n > STREAM_MAX_WORKERS:
        # cell 2: the MXU product over the flat state, no kernel at all
        assert plan["layout"] == "flat" and f"N = {n}" in plan["reason"]
        assert plan["kernel_sites"] == flat.count("tpu_custom_call") == 0
        return
    assert plan["layout"] == "leaves", plan
    assert flat.count("tpu_custom_call") == 1
    leaves = _lowered_exchange(comm, flattener, tree, "leaves")
    sites = leaves.count("tpu_custom_call")
    # the journal's count is the program's
    assert sites == plan["kernel_sites"] <= SITE_BUDGET, (sites, plan)
    assert len(leaves) <= TEXT_BUDGET * len(flat), (len(leaves), len(flat))
    # and the leaves in place are nearly all of the state
    assert plan["small_buffer_elements"] < 0.07 * n * flattener.dim, plan


#: gate/up and down x forward, data gradient, weight gradient (PR 38)
GROUPED_SITE_BUDGET = 6


@pytest.mark.parametrize("cell", TOKEN_CELLS)
def test_expert_layer_of_every_token_configuration_holds_six_kernel_sites(
        cell, monkeypatch):
    """The grouped products of the expert layer at published widths and the
    cell's rows, on the TPU branch: two ``remat`` layers' gradient, lowered
    for TPU (shapes alone), holds one kernel site a form and shape, the
    recomputed forward's calls among them, as the journal's record counts;
    every product of a step runs on one, at tiles that hold the whole
    widths inside the VMEM budget the chooser was given."""
    monkeypatch.setattr(mellum2, "_one_bf16_pass", lambda: True)
    monkeypatch.setattr(grouped, "_interpret", lambda: False)
    tc, model, _ = _model(cell, rehearsal=False)
    z = model.sizes
    rows_a_worker, tokens = tc["batch_size"], tc["batch_size"] * z["seq_len"]
    record = model.expert_products(tokens, tc["num_workers"])
    assert record["products_per_step"] == record["on_kernel"] == 96
    assert record["kernel_sites"] == GROUPED_SITE_BUDGET
    rows = mellum2.moe_capacity(tokens, z)
    for product in record["products"]:
        assert product["rows"] == rows and "reason" not in product
        assert product["tiles"] == [512, product["k"], product["n"]]
        assert grouped.vmem_bytes(
            product["form"], product["tiles"], jnp.bfloat16, jnp.bfloat16,
            product["k"]) <= grouped.VMEM_BUDGET
    hid, width, held = z["hidden"], z["expert_width"], len(z["experts_held"])
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    layer = {"router": spec(hid, z["num_experts"]),
             "gate": spec(held, hid, width), "up": spec(held, hid, width),
             "down": spec(held, width, hid)}

    def two_layers(layers, x):
        for p in layers:
            x = x + jax.checkpoint(lambda p, x: mellum2._moe(p, x, z)[0])(p, x)
        return jnp.sum(x)

    text = jax.jit(jax.grad(two_layers, argnums=(0, 1))).trace(
        [layer, layer], spec(rows_a_worker, z["seq_len"], hid)).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == record["kernel_sites"]
    assert "ragged_dot" not in text


def test_a_tree_of_many_shapes_keeps_to_the_budget_of_sites(monkeypatch):
    """More distinct shapes than ``_LEAF_MAX_SHAPES``: the ones that hold
    the fewest elements ride the small buffer, whatever their order."""
    monkeypatch.setattr(pallas_gossip, "_LEAF_MIN_ELEMENTS", 1)
    n = 2
    shapes = [(8 * k, 128) for k in range(1, 16)] + [(8, 128)] * 40
    # as shipped, a shape has to hold 1/32 of the tree: 41 leaves of (8, 128)
    # do, and (8 k, 128) from k = 5 up: twelve, one past the most
    views = leaf_views(n, shapes, [jnp.float32] * len(shapes))
    assert {v[:2] for v in views if not isinstance(v, str)} == (
        {(8, 128)} | {(8 * k, 128) for k in range(6, 16)})
    assert [("under 1/32 of the tree" in v, "past the 11" in v)
            for v in views if isinstance(v, str)] == [(True, False)] * 3 + [
                (False, True)]
    monkeypatch.setattr(pallas_gossip, "_LEAF_SHAPE_SHARE", 0.0)
    views = leaf_views(n, shapes, [jnp.float32] * len(shapes))
    taken = {v[:2] for v in views if not isinstance(v, str)}
    assert len(taken) == pallas_gossip._LEAF_MAX_SHAPES
    # 41 leaves of (8, 128) hold more than one leaf of (8 k, 128), k < 5
    assert (8, 128) in taken and (16, 128) not in taken
    assert sum("past the" in v for v in views if isinstance(v, str)) == 4
    tree = [jax.ShapeDtypeStruct((n,) + s, jnp.float32) for s in shapes]
    comm = make_decen(_schedule(n), backend="dense")
    plan = exchange_plan(comm, WorkerFlattener(tree))
    assert plan["kernel_sites"] == SITE_BUDGET
    assert plan["leaves_in_place"] == 51
    assert plan["small_buffer_elements"] == n * 128 * 8 * (2 + 3 + 4 + 5)


# ------------------------------------- the routes on the cells' rehearsal models

def _repeated_shapes(n, seed=3):
    rng = np.random.default_rng(seed)
    shapes = {f"block{k}/kernel": (3, 3, 16, 128) for k in range(4)}
    shapes.update({f"block{k}/scale": (128,) for k in range(4)})
    shapes.update({"head/kernel": (128, 24), "up": (2, 24, 128),
                   "gate": (2, 24, 128), "down": (2, 128, 24)})
    return {name: jnp.asarray(rng.normal(size=(n,) + shape), jnp.float32)
            for name, shape in shapes.items()}


def _rehearsal_tree(cell, n, seed=1):
    """The rehearsal model's parameter tree, its values drawn here (what the
    exchange reads of a model is its tree's shapes)."""
    _, model, dummy = _model(cell, rehearsal=True)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), dummy, train=False))["params"]
    rng = np.random.default_rng(seed)
    return jax.tree.map(lambda a: jnp.asarray(
        rng.normal(size=(n,) + a.shape), a.dtype), shapes)


TREES = {"wrn": "wrn28-10-c100.w16-matcha",
         "mellum": "mellum2-12b-a2.5b.ep8-s4k.w2-matcha",
         "keye": "keye-vl2-30b-a3b.ep16-s8k.w2-matcha",
         "repeated_shapes": None}
assert set(filter(None, TREES.values())) <= set(CELLS)
#: every tree at its cell's own N and one more, the repeated shapes at all
#: four (a token tree's ten shapes at N = 32 compile for half a minute
#: under the interpreter)
CASES = ([("wrn", n) for n in (3, 16, STREAM_MAX_WORKERS)]
         + [("mellum", n) for n in (2, 16)] + [("keye", n) for n in (2, 3)]
         + [("repeated_shapes", n) for n in (2, 3, 16, STREAM_MAX_WORKERS)])


@pytest.mark.parametrize("wire", [None, "bf16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("name,n", CASES)
def test_leaves_route_reads_what_the_flat_route_reads(name, n, wire,
                                                      small_leaves):
    tree = (_repeated_shapes(n) if TREES[name] is None
            else _rehearsal_tree(TREES[name], n))
    flattener = WorkerFlattener(tree)
    sched = _schedule(n)
    comm = make_decen(sched, backend="dense", wire_dtype=wire)
    plan = exchange_plan(comm, flattener)
    # (a shape still has to hold 1/32 of the tree to get its site)
    assert plan["layout"] == "leaves" and plan["leaves_in_place"] >= 4
    assert 3 <= plan["kernel_sites"] <= SITE_BUDGET
    assert 0 < plan["small_buffer_elements"] < n * flattener.dim // 2
    flags_t = jnp.ones((sched.flags.shape[1],), jnp.float32)
    leaves, _, sq = jax.jit(comm.leaves_step)(
        flattener.treedef.flatten_up_to(tree), (), flags_t)
    flat, _ = jax.jit(comm.step)(flattener.flatten(tree), (), flags_t)
    for got, want in zip(leaves, flattener.treedef.flatten_up_to(
            flattener.unflatten(flat))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # (the absolute term: two workers that their one matching has just
    # averaged stand 0 apart on one route and a rounding of their unit-sized
    # values apart on the other)
    np.testing.assert_allclose(
        np.sqrt(float(jnp.sum(sq)) / (n * flattener.dim)),
        float(worker_disagreement(flat)), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.sqrt(np.asarray(sq) / flattener.dim),
                               np.asarray(worker_deviation_rows(flat)),
                               rtol=1e-6, atol=1e-7)
