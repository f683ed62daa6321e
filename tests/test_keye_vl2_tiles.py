"""The index scores of ``models/keye_vl2.py`` by key tiles (PR 44) against
the form that scores every tile, PR 43's query block kept here as the oracle:
the loss, every counter that was there and every gradient on packed rows,
documents numbered in any order; ``NaN`` for the keys of the tiles not scored;
the two tile counters against a NumPy count of the document numbers, equal
where a row is one document; and, in the whole step of a two-worker, two-row
job, the tile's product inside the loop that runs as often as tiles are live.
Rows, weights and sizes are ``test_keye_vl2.py``'s."""

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from matcha_tpu.models import keye_vl2, select_model
from test_keye_vl2 import (SEQ, close, layer_weights, rows,  # beside this file
                           sizes_of, weights)


def every_tile_query_block(q, qi, w, q_docs, k, v, ki, k_docs, *, start,
                           sizes):
    """The query block that scores every key (PR 43's, the oracle), with the
    tile counts of a block that passes over none."""
    b, block = q.shape[:2]
    stop, topk = start + block, sizes["index_topk"]
    sees = keye_vl2._visible(jnp.arange(start, stop), jnp.arange(stop),
                             q_docs, k_docs, None)
    index = jnp.where(sees, keye_vl2._index_scores(qi, ki, w), -jnp.inf)
    keep = keye_vl2._select(index, sees, topk) if stop > topk else sees
    scores = jnp.einsum("bikgd,bjkd->bkgij", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(keep[:, None, None], scores.astype(jnp.float32),
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgij,bjkd->bikgd", probs, v)
    target = lax.stop_gradient(jnp.mean(probs, axis=(1, 2)))
    guess = jax.nn.log_softmax(jnp.where(keep, index, -jnp.inf), axis=-1)
    kl = jnp.sum(jnp.where(target > 0, target * (jnp.log(target) - guess),
                           0.0))
    visible = jnp.sum(sees, axis=-1)
    tiles = stop // sizes["key_tile"]
    return (out.reshape(b, block, -1), kl, jnp.sum(visible),
            jnp.sum(visible > topk), jnp.sum(jnp.minimum(visible, topk)),
            tiles, tiles)


@contextlib.contextmanager
def query_block_is(form):
    real = keye_vl2._query_block
    keye_vl2._query_block = form
    try:
        yield
    finally:
        keye_vl2._query_block = real


@functools.lru_cache(maxsize=None)
def by_tiles(tile, every_tile=False):
    """Jitted ``(params, ids, docs) -> (loss, grads, counters)`` of the
    program at key tiles of ``tile``, or of the oracle in its place."""
    model = select_model("keye_vl2", "tokens",
                         sizes=sizes_of(8, key_tile=tile), remat=True)
    form = every_tile_query_block if every_tile else keye_vl2._query_block

    def program(params, ids, docs):
        with query_block_is(form):  # looked up while this is traced
            (total, aux), grads = jax.value_and_grad(
                lambda p: model.apply({"params": p}, ids, docs,
                                      method="batch_loss"),
                has_aux=True)(params)
        return total, grads, aux["counters"]

    return jax.jit(program)


def tiles_with_a_visible_pair(docs, tile, block=16):
    """``live[row, query block, key tile]`` from the document numbers
    ``[n, SEQ]`` alone (false too where the tile lies past the block)."""
    d = np.asarray(docs)
    t = np.arange(SEQ)
    sees = (t[None, :, None] >= t[None, None, :]) \
        & (d[:, :, None] == d[:, None, :])
    return sees.reshape(len(d), SEQ // block, block, SEQ // tile, tile) \
        .any(axis=(2, 4))


@pytest.mark.parametrize("tile", [4, 8])
@pytest.mark.parametrize("documents", ["packed", "many", "unsorted"])
def test_scores_by_tiles_equal_the_scores_of_every_tile(documents, tile):
    """The loss and every counter that was there exactly, every gradient to
    1e-6 of its largest entry (the key gradient sums over tiles in another
    order)."""
    _, params = weights(sizes_of(8))
    ids, docs = rows(documents)
    with jax.default_matmul_precision("highest"):
        total, grads, counters = by_tiles(tile)(params, ids, docs)
        want_total, want_grads, want = by_tiles(tile, True)(params, ids, docs)
    assert float(total) == float(want_total)
    for name in set(want) - {"dsa_key_tiles_scored"}:
        np.testing.assert_array_equal(counters[name], want[name], name)
    if documents != "packed":  # some tile hides from some block
        assert counters["dsa_key_tiles_scored"] < counters["dsa_key_tiles"]
    for name, g in want_grads.items():
        assert float(jnp.max(jnp.abs(g))) > 0, name
        close(grads[name], g, tol=1e-6, name=name)


@pytest.mark.parametrize("tile", [4, 8, 16])
@pytest.mark.parametrize("documents", ["one", "packed", "many", "unsorted"])
def test_tile_counters_equal_a_numpy_count_of_the_documents(documents, tile):
    layers = 2
    _, params = weights(sizes_of(8))
    ids, docs = rows(documents)
    counters = by_tiles(tile)(params, ids, docs)[2]
    live = tiles_with_a_visible_pair(docs[:, :-1], tile)
    in_reach = sum((start + 16) // tile for start in range(0, SEQ, 16))
    assert counters["dsa_key_tiles"] == layers * len(docs) * in_reach
    assert counters["dsa_key_tiles_scored"] == layers * live.sum()
    if documents == "one":  # nothing hides: the mechanism is bypassed
        assert live.sum() == len(docs) * in_reach
    elif documents != "packed" and tile < 16:
        assert live.sum() < len(docs) * in_reach


@pytest.mark.parametrize("documents", ["many", "unsorted"])
def test_keys_of_a_tile_not_scored_reach_nothing(documents):
    """``NaN`` for the indexer's keys in every tile that hides from the
    row's last query block: the block's outputs and every gradient are what
    they were, the poisoned keys' own exactly zero (where every tile is
    multiplied the backward's ``0 x NaN`` spoils them), through the block's
    checkpoint as the program runs it."""
    tile, start = 4, SEQ - 16
    sizes = sizes_of(8, key_tile=tile)
    p = layer_weights(4, 2, 8)
    h = jax.random.normal(jax.random.PRNGKey(9), (1, SEQ, 16))
    docs = rows(documents, 1, seed=2)[1][:, :-1]
    weigh = jax.random.normal(jax.random.PRNGKey(4), (1, 16, 4 * 8))
    hidden = ~tiles_with_a_visible_pair(docs, tile)[0, -1]
    assert 0 < hidden.sum() < len(hidden)
    block = jax.checkpoint(
        functools.partial(keye_vl2._query_block, start=start, sizes=sizes),
        policy=jax.checkpoint_policies.save_only_these_names(keye_vl2.KEPT))

    def of(q, k, v, qi, ki, w):
        at = slice(start, None)
        out, kl, *counts = block(q[:, at], qi[:, at], w[:, at], docs[:, at],
                                 k, v, ki, docs)
        return jnp.sum(out * weigh) + kl, counts

    run = jax.jit(jax.value_and_grad(of, argnums=tuple(range(6)),
                                     has_aux=True))
    with jax.default_matmul_precision("highest"):
        q, k, v, qi, ki, w = keye_vl2._project(p, h, sizes)
        (want, counts), want_grads = run(q, k, v, qi, ki, w)
        poisoned = jnp.where(np.repeat(hidden, tile)[None, :, None], jnp.nan,
                             ki)
        (got, _), grads = run(q, k, v, qi, poisoned, w)
    assert counts[-2] - counts[-1] == hidden.sum()
    assert np.isfinite(float(got)) and float(got) == float(want)
    for g, want_g in zip(grads, want_grads):
        assert float(jnp.max(jnp.abs(want_g))) > 0
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want_g))
    assert not np.any(np.asarray(grads[4])[0, np.repeat(hidden, tile)])


def _equations(jaxpr, inside=()):
    """Every equation of ``jaxpr`` and of the programs its equations hold,
    with the primitives it lies inside."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, inside + (eqn.primitive.name,))


def test_a_tiles_product_runs_as_often_as_tiles_are_live_in_the_whole_step():
    """The step of a two-worker, two-row job: every product of the
    indexer's ``[q, heads, tile]`` block (forward, the block's
    recomputation, and the backward's three: the product again and its two
    transposes) lies inside a ``while`` inside the maps over workers and
    rows, one row at a time.  A ``fori_loop`` is a ``while`` only where its
    bound is traced (to a static bound it is a ``scan``): the loop's length
    is the count of live tiles, which the ``lax.map``s keep one number.
    Under a ``vmap`` the products would carry the rows in their shapes, and
    every row would run to the longest count."""
    from matcha_tpu import topology as tp
    from matcha_tpu.communicator import make_decen
    from matcha_tpu.ops import WorkerFlattener
    from matcha_tpu.schedule import matcha_schedule
    from matcha_tpu.train import make_lr_schedule
    from matcha_tpu.train.state import (init_train_state, make_optimizer,
                                        make_train_step)

    n, tile = 2, 8
    sizes = sizes_of(8, key_tile=tile, num_layers=1)
    model = select_model("keye_vl2", "tokens", sizes=sizes, remat=True)
    decomposed = tp.decompose(tp.make_graph("chain", n, seed=0), n, seed=0)
    sched = matcha_schedule(decomposed, n, iterations=4, budget=0.5, seed=5)
    comm = make_decen(sched, backend="dense")
    lr = make_lr_schedule(0.05, 2, warmup=False)
    optimizer = make_optimizer(lr)
    state = jax.eval_shape(lambda: init_train_state(
        model, (SEQ + 1,), n, optimizer, comm, seed=0, sync_init=False)[0])
    step = make_train_step(model, optimizer, comm,
                           WorkerFlattener(state.params), sched.flags,
                           lr_schedule=lr, grad_chunk=1)
    batch = jax.ShapeDtypeStruct((n, 2, SEQ + 1), jnp.int32)
    traced = step.trace(state, batch, batch)
    dots = (1, 16, sizes["indexer_heads"], tile)
    products = [inside for eqn, inside in _equations(traced.jaxpr.jaxpr)
                if eqn.primitive.name == "dot_general" and dots in [
                    v.aval.shape for v in eqn.invars + eqn.outvars]]
    # two query blocks a row: forward, recomputed, three in the backward
    assert len(products) == 2 * 5
    for inside in products:
        assert inside[:2] == ("scan", "scan") and "cond" not in inside
        assert inside[-1] == "while", inside
    assert "stablehlo.while" in traced.lower().as_text()
