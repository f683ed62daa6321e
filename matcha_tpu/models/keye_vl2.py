"""One chip's share of the language tower of a Keye-VL-2.0-style sparse
decoder whose attention reads a learned choice of keys, for next-token
training through the same ``train()`` as ``mellum2``.

Pre-norm blocks ``h = x + Attn(RMSNorm(x))``, ``x' = h + MoE(RMSNorm(h))``,
a final RMSNorm, an untied head, no bias.  The expert layer, the norm, RoPE,
the visibility mask, the chunked head-and-loss and what stands around the
blocks (``TokenDecoder``) are ``models/mellum2.py``'s own, imported: one copy
serves both token models.  New here, per layer and row, with ``x`` the normed
input, ``t`` a query position and ``s`` a key position that ``t`` may see
(``s <= t``, same document):

* **the indexer** (a DeepSeek-Sparse-Attention lightning indexer):
  ``qI[t, j] = x_t WqI_j`` for ``indexer_heads`` heads of
  ``indexer_head_dim``, one shared key ``kI[s] = x_s WkI``, per-head weights
  ``w[t] = x_t WwI / sqrt(heads x head_dim)``, RoPE on ``qI`` and ``kI``,
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``.  All of it is float32
  at ``highest``: it decides a discrete choice, as the router's scores do.
  ``x`` enters it under ``stop_gradient``.
* **the selection** ``S_t``: the ``index_topk`` visible ``s`` of largest
  ``I[t, s]`` (all of them where fewer are visible; ties to the lower ``s``),
  exact, without a sort: the ``index_topk``-th largest score of a query is
  found bit by bit on the scores' order-preserving integer form (32 counting
  passes), and a key is kept if it lies above it, or on it and early enough.
  The selection masks the main scores, which are computed for every key up
  to the block's end; the indexer's own products follow the key tiles that
  hold a visible pair (below).
* **the attention**: grouped-query, softmax of ``q . k / sqrt(head_dim)``
  over ``S_t`` only, times ``v``, through ``wo``.
* **the indexer's loss**: ``p_t`` is the main attention's probabilities
  summed over the heads held and normalised over ``S_t``, under
  ``stop_gradient``; ``L_I`` is the mean over layers and query positions of
  ``KL(p_t || softmax(I[t, S_t]))``.  :meth:`KeyeVL2.batch_loss` returns
  ``L_LM + L_I``: the cross-entropy reaches no indexer weight (the selection
  is discrete), and ``L_I`` reaches nothing else.

**Query blocks.**  A layer's attention runs one row after another
(``lax.map``), and a row ``attn_block`` (1,024) query positions at a time
against the keys up to the block's end, each block under ``jax.checkpoint``:
the backward pass holds one block's scores (16 indexer heads of them), not
one layer's, which is what lets 8,192 positions run without a fused kernel
(compile-only, v5e: one worker's forward/backward of the cell takes 4.85 GB
of temporaries).  A block keeps the selection's thresholds (``[block]``
numbers, by name), so its recomputation does not search for them again.
``remat`` recomputes a layer's projections and its expert layer besides, the
expert layer over the dispatch it kept (``mellum2.checkpointed``).

**Key tiles.**  A block's index scores are computed ``KEY_TILE`` (512) keys at
a time (:func:`_index_by_tiles`).  Rows are packed documents and a query sees
its own document only, so many a tile holds no visible (query, key) pair of
the block: ``sees``, which ``_visible`` returned, reduced over the block's
queries and the tile's keys, says which (exactly, whatever the order of the
document numbers).  Such a tile is ``-inf`` as it stands: the tiles run
through one loop body as often as tiles are live, in the forward pass, the
block's recomputation and the backward alike, and the ``[block, heads,
tile]`` products of a hidden tile exist in none of them.  The loop's length
is data, one number because one row runs at a time; the backward keeps
nothing but the block's arguments and multiplies a live tile again (what
autodiff would keep under a ``scan`` of ``cond``s, a hidden tile's zeros
among it, cost more than the product: PERF.md section 6, PR 44).  A visible
pair's score is the all-tiles block's: bit for bit on the CPU; on the chip the
compiler schedules a tile's product otherwise than a whole block's in some
blocks, and a score may differ in its last bit there (7e-7 at most, PERF.md
section 6), inside the stated float32 at ``highest``.  The indexer's
gradients differ besides by the order of their sums over tiles.

Counters, returned with the loss and summed over layers: ``dsa_queries``
(layer-queries), ``dsa_queries_selecting`` (those that see more than
``index_topk`` keys), ``dsa_keys_visible``, ``dsa_keys_kept``, ``dsa_kl_sum``
(``L_I`` is ``dsa_kl_sum / dsa_queries``), ``dsa_key_tiles`` (key tiles the
query blocks range over, a layer-row), ``dsa_key_tiles_scored`` (those that
hold a visible pair and were multiplied; the two are equal where a row is one
document), and the expert layer's and the loss's as in ``mellum2``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..utils.profiling import device_span
from .mellum2 import (MOE_COUNTERS, TokenDecoder, _head_loss, _moe,
                      _next_ids, _rms_norm, _rope, _visible,
                      attention_weights, checkpointed, expert_weights,
                      rope_tables)

__all__ = ["KeyeVL2"]

HIGHEST = lax.Precision.HIGHEST
#: what a checkpointed query block keeps beside its inputs
KEPT = "dsa_threshold"
#: what a layer's attention counts (the module docstring says of what)
DSA_COUNTERS = ("dsa_queries", "dsa_queries_selecting", "dsa_keys_visible",
                "dsa_keys_kept", "dsa_kl_sum", "dsa_key_tiles",
                "dsa_key_tiles_scored")
#: keys a tile of a query block's index scores (chosen on the chip among
#: 1,024, 512, 256 and 128 at the cell's shape: PERF.md section 5)
KEY_TILE = 512


def _project(p, h, sizes):
    """The normed input's projections, RoPE applied: main ``q[B, S, kv,
    group, d]``, ``k``/``v[B, S, kv, d]``; the indexer's ``qi[B, S, J, dI]``,
    ``ki[B, S, dI]``, ``w[B, S, J]`` from the same input under
    ``stop_gradient``, float32 at ``highest``."""
    b, s, _ = h.shape
    d, hq, hkv = sizes["head_dim"], sizes["q_heads_held"], \
        sizes["kv_heads_held"]
    heads, di = sizes["indexer_heads"], sizes["indexer_head_dim"]
    x = _rms_norm(h, p["attn_norm"], sizes["rms_norm_eps"])
    cos, sin = rope_tables(s, d, sizes["rope_theta"])
    q = _rope(jnp.dot(x, p["wq"]).reshape(b, s, hq, d), cos, sin)
    k = _rope(jnp.dot(x, p["wk"]).reshape(b, s, hkv, d), cos, sin)
    v = jnp.dot(x, p["wv"]).reshape(b, s, hkv, d)
    xi = lax.stop_gradient(x)
    cos, sin = rope_tables(s, di, sizes["rope_theta"])
    exact = functools.partial(jnp.dot, precision=HIGHEST)
    qi = _rope(exact(xi, p["idx_wq"]).reshape(b, s, heads, di), cos, sin)
    ki = _rope(exact(xi, p["idx_wk"]).reshape(b, s, 1, di), cos, sin)[:, :, 0]
    w = exact(xi, p["idx_ww"]) / math.sqrt(heads * di)
    return q.reshape(b, s, hkv, hq // hkv, d), k, v, qi, ki, w


def _index_scores(qi, ki, w):
    """``I[B, q, s] = sum_j w[B, q, j] relu(qi[B, q, j] . ki[B, s])``."""
    dots = jnp.einsum("bqjd,bsd->bqjs", qi, ki, precision=HIGHEST)
    return jnp.sum(w[..., None] * jax.nn.relu(dots), axis=2)


def _tile(a, t, tile, axis):
    return lax.dynamic_slice_in_dim(a, t * tile, tile, axis)


def _tile_scores(qi, keys, w, seen):
    """One tile's :func:`_index_scores`, ``-inf`` where ``seen`` is false."""
    return jnp.where(seen, _index_scores(qi, keys, w), -jnp.inf)


def _live_tiles(sees, tile):
    """(the key tiles of ``tile`` keys, those in which ``sees[B, q, s]``
    holds a visible pair first; how many those are)."""
    b, block, stop = sees.shape
    live = jnp.any(sees.reshape(b, block, stop // tile, tile), axis=(0, 1, 3))
    return jnp.argsort(~live, stable=True), jnp.sum(live)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _index_by_tiles(qi, ki, w, sees, tile):
    """``I[B, q, s]`` of :func:`_index_scores` with ``-inf`` where ``sees``
    is false, a tile of ``tile`` keys at a time through one body that runs
    as many times as tiles hold a visible pair: a tile that holds none is
    ``-inf`` as it stands and is multiplied in no pass.  The trip count is
    the chip's to read where one row runs at a time (``_sparse_attention``).
    Nothing is kept for the backward pass but the arguments: it runs over the
    same tiles and multiplies each again."""
    order, count = _live_tiles(sees, tile)

    def one(i, index):
        t = order[i]
        scores = _tile_scores(qi, _tile(ki, t, tile, 1), w,
                              _tile(sees, t, tile, 2))
        return lax.dynamic_update_slice_in_dim(index, scores, t * tile, 2)

    return lax.fori_loop(0, count, one,
                         jnp.full(sees.shape, -jnp.inf, w.dtype))


def _index_by_tiles_fwd(qi, ki, w, sees, tile):
    return _index_by_tiles(qi, ki, w, sees, tile), (qi, ki, w, sees)


def _index_by_tiles_bwd(tile, kept, g):
    qi, ki, w, sees = kept
    order, count = _live_tiles(sees, tile)

    def one(i, sums):
        d_qi, d_ki, d_w = sums
        t = order[i]
        _, back = jax.vjp(
            functools.partial(_tile_scores, seen=_tile(sees, t, tile, 2)),
            qi, _tile(ki, t, tile, 1), w)
        of_qi, of_keys, of_w = back(_tile(g, t, tile, 2))
        return (d_qi + of_qi, lax.dynamic_update_slice_in_dim(
            d_ki, of_keys, t * tile, 1), d_w + of_w)

    return (*lax.fori_loop(0, count, one,
                           jax.tree.map(jnp.zeros_like, (qi, ki, w))), None)


_index_by_tiles.defvjp(_index_by_tiles_fwd, _index_by_tiles_bwd)


def _ordered(scores):
    """int32 of float32 in the same order (-0.0 with 0.0)."""
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(scores == 0, 0,
                     jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits))


def _kth_largest(keys, k):
    """The ``k``-th largest of each row of int32 ``keys[..., S]``
    (``S >= k``), built from its highest bit down: a bit stays where at
    least ``k`` keys lie at or above the number that has it."""
    flip = jnp.int32(-2 ** 31)  # unsigned order of the bits, signed compares

    def with_bit(i, found):
        trial = found | lax.shift_left(jnp.int32(1), 31 - i)
        enough = jnp.sum(keys >= (trial ^ flip)[..., None], axis=-1) >= k
        return jnp.where(enough, trial, found)

    return lax.fori_loop(0, 32, with_bit,
                         jnp.zeros(keys.shape[:-1], jnp.int32)) ^ flip


def _select(scores, sees, k):
    """``keep[B, q, s]``: the ``k`` visible keys of largest score (all where
    fewer are visible; ties to the lower ``s``).  ``scores`` are ``-inf``
    where ``sees`` is false."""
    keys = _ordered(lax.stop_gradient(scores))
    kth = checkpoint_name(_kth_largest(keys, k), KEPT)[..., None]
    above = keys > kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    on = keys == kth
    return (above | (on & (jnp.cumsum(on, axis=-1) <= room))) & sees


def _query_block(q, qi, w, q_docs, k, v, ki, k_docs, *, start, sizes):
    """Query positions ``[start, start + Q)`` against the keys ``[0, start +
    Q)``: (the heads' outputs ``[B, Q, heads x d]``, the sum of the queries'
    KL, how many keys the queries see, how many of the queries see more than
    ``index_topk``, how many keys are kept, how many key tiles the block
    ranges over, how many of them were scored)."""
    b, block = q.shape[:2]
    stop, topk = start + block, sizes["index_topk"]
    tile = sizes.get("key_tile", KEY_TILE)  # a test seam: tiles at S = 32
    if block % tile:
        tile = block
    sees = _visible(jnp.arange(start, stop), jnp.arange(stop), q_docs, k_docs,
                    None)
    with device_span("matcha/dsa_index"):
        index = _index_by_tiles(qi, ki, w, sees, tile)
    with device_span("matcha/dsa_select"):
        # up to ``topk`` keys in reach: every visible one is kept
        keep = _select(index, sees, topk) if stop > topk else sees
    with device_span("matcha/attn_sparse"):
        scores = jnp.einsum("bikgd,bjkd->bkgij", q, k) / math.sqrt(
            q.shape[-1])
        scores = jnp.where(keep[:, None, None], scores.astype(jnp.float32),
                           -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgij,bjkd->bikgd", probs, v)
    with device_span("matcha/dsa_kl"):
        # every head's probabilities sum to 1 over the selection
        target = lax.stop_gradient(jnp.mean(probs, axis=(1, 2)))
        guess = jax.nn.log_softmax(jnp.where(keep, index, -jnp.inf), axis=-1)
        kl = jnp.sum(jnp.where(target > 0,
                               target * (jnp.log(target) - guess), 0.0))
    visible = jnp.sum(sees, axis=-1)
    return (out.reshape(b, block, -1), kl, jnp.sum(visible),
            jnp.sum(visible > topk), jnp.sum(jnp.minimum(visible, topk)),
            stop // tile, _live_tiles(sees, tile)[1])


def _row_attention(q, k, v, qi, ki, w, docs, sizes):
    """Rows ``[B, S, ...]`` a checkpointed block of query positions at a
    time: (the heads' outputs ``[B, S, heads x d]``, the sum of the queries'
    KL, and the five counts of :func:`_query_block`)."""
    s = docs.shape[1]
    block = sizes.get("attn_block", 1024)  # a test seam: blocks at S = 32
    if s % block:
        block = s
    outs, sums = [], []
    for start in range(0, s, block):
        at, stop = slice(start, start + block), start + block
        out, *counted = jax.checkpoint(
            functools.partial(_query_block, start=start, sizes=sizes),
            policy=jax.checkpoint_policies.save_only_these_names(KEPT))(
                q[:, at], qi[:, at], w[:, at], docs[:, at], k[:, :stop],
                v[:, :stop], ki[:, :stop], docs[:, :stop])
        outs.append(out)
        sums.append(counted)
    return (jnp.concatenate(outs, axis=1),
            *(sum(c) for c in zip(*sums)))


def _sparse_attention(q, k, v, qi, ki, w, docs, sizes):
    """(the heads' outputs ``[B, S, heads x d]``, the layer's counters), one
    row after another."""
    b, s = docs.shape
    out, *sums = lax.map(
        lambda row: _row_attention(*(a[None] for a in row), sizes),
        (q, k, v, qi, ki, w, docs))
    kl, visible, selecting, kept, tiles, scored = (
        jnp.sum(c).astype(jnp.float32) for c in sums)
    return out.reshape(b, s, -1), {
        "dsa_queries": jnp.float32(b * s), "dsa_queries_selecting": selecting,
        "dsa_keys_visible": visible, "dsa_keys_kept": kept, "dsa_kl_sum": kl,
        "dsa_key_tiles": tiles, "dsa_key_tiles_scored": scored}


def _experts_of(p, h, sizes):
    return _moe(p, _rms_norm(h, p["moe_norm"], sizes["rms_norm_eps"]), sizes)


def _block(p, h, docs, sizes, remat):
    again = checkpointed(remat)
    projected = again(functools.partial(_project, sizes=sizes))(p, h)
    out, counters = _sparse_attention(*projected, docs, sizes)
    h = h + jnp.dot(out, p["wo"])
    y, moe = again(functools.partial(_experts_of, sizes=sizes))(p, h)
    return h + y, {**counters, **moe}


class KeyeVL2(TokenDecoder):
    """``sizes`` as in ``chipbench/configs/keye-vl2-30b-a3b.ep16-s8k.json``
    (README "Training a language model" lists the keys)."""

    @property
    def expert_layers(self):
        return self.sizes["num_layers"]

    def setup(self):
        z = self.sizes
        hid, heads, di = z["hidden"], z["indexer_heads"], \
            z["indexer_head_dim"]
        self.declare([{**attention_weights(z), **expert_weights(z),
                       "idx_wq": (hid, heads * di), "idx_wk": (hid, di),
                       "idx_ww": (hid, heads)}] * z["num_layers"])

    def hidden(self, ids, docs):
        """(the final norm's output ``[B, S, H]``, the layers' counters
        summed, ``moe_load[layer, expert held]``)."""
        with device_span("matcha/lm_embed"):
            h = self.embed[ids]
        counters = []
        for p in self.layers:
            h, c = _block(p, h, docs, self.sizes, self.remat)
            counters.append(c)
        return self.normed(h, counters, MOE_COUNTERS + DSA_COUNTERS)

    def batch_loss(self, x_raw, y_raw):
        """``x_raw``/``y_raw`` as ``Mellum2.batch_loss`` takes them.  Returns
        (the next id's cross-entropy over judged positions plus the
        indexer's KL over layers and query positions, ``{"accuracy",
        "counters"}``)."""
        ids, docs, targets = _next_ids(x_raw, y_raw)
        h, counters = self.hidden(ids, docs)
        loss, accuracy, counters["loss_positions"] = _head_loss(
            h, self.head, targets, self.sizes)
        indexer_kl = counters["dsa_kl_sum"] / counters["dsa_queries"]
        return loss + indexer_kl, {"accuracy": accuracy, "counters": counters}
