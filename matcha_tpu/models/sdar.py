"""One chip's share of an SDAR-style sparse decoder trained by block
diffusion, through the same ``train()`` as the next-token models.

The layer is the Keye layer less its indexer, plus a per-head RMS norm on
``q`` and ``k`` before RoPE: pre-norm blocks ``a = h + Wo Attn(RoPE(norm_q(Wq
n1(h))), RoPE(norm_k(Wk n1(h))), Wv n1(h); M)``, ``out = a + MoE(n2(a))``, a
final RMSNorm, an untied head, no bias.  The expert layer, the norm, RoPE's
rotation, the chunked head-and-loss and what stands around the blocks
(``TokenDecoder``) are ``models/mellum2.py``'s own, imported.  New here is
the training step.

**The doubled row.**  A row of ``S`` tokens passes through every layer as ``2
S`` positions: its *noisy* copy (token ``i`` replaced by the ``[MASK]`` id,
``sizes["mask_id"]``, where it is masked) and then its *clean* copy.  Both
copies carry the positions ``0..S-1`` (RoPE).  With ``B`` =
``sizes["block_length"]`` and ``blk(i) = i // B``, query sees key iff both lie
in one document and

* noisy -> noisy: ``blk`` equal (both directions: a block denoises as one);
* noisy -> clean: ``blk(key) < blk(query)`` (the blocks already written);
* clean -> clean: ``blk(key) <= blk(query)`` (block-causal);
* clean -> noisy: never.

**The loss** is over the noisy copy alone, at masked positions, of the
position's *own* token (no shift): ``sum_i masked_i (1 / t_blk(i))
CE(logits_noisy[i], x_i) / (rows x S)``, ``t`` the noise level the block was
masked at; accuracy is over the masked positions.  The head runs over the
noisy half only.

**Nothing is drawn here.**  The masks and ``t`` come with the row
(``chipbench/tasks/block_diffusion.py:make`` draws them on the host from the
seed), so the epoch program stays a function of its inputs and the reference
sees the same masks.  A raw row is two int32 arrays ``[2 S]``
(``data.load_tokens``): ``x`` the noisy ids then the clean ids (a position is
masked iff its noisy id is the ``[MASK]`` id, which no clean token is); ``y``
the ``S`` document numbers, then a position's ``t`` in 65,536ths.

**Query blocks.**  A dense masked product over ``[2 S, 2 S]`` would score
four times the pairs the mask lets see.  A layer's attention runs one row
after another (``lax.map``) and a row ``attn_block`` (1,024) queries at a
time, each block under ``jax.checkpoint`` as ``models/keye_vl2.py`` runs its
own; a block of clean queries scores the clean keys up to its own end, a
block of noisy queries those and its own noisy keys, and nothing else: at
``S`` 4,096, 24 blocks of 1,024 x 1,024 pairs a head and row, 0.375 of the
square.  Plain ``jax.numpy``: what the mask rules out *inside* a scored block
is computed and masked.

Counters, returned with the loss and summed over rows (and layers, where a
layer counts): ``bd_tokens`` (``rows x S``), ``bd_positions_masked``,
``bd_pairs_visible`` (pairs the mask lets see), ``bd_pairs_scored`` (pairs
the query blocks scored, a head), ``bd_slots_held_masked`` (of the expert
layer's ``moe_slots_held``, those a masked position sent), and the expert
layer's and the loss's as in ``mellum2``.
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils.profiling import device_span
from .mellum2 import (MOE_COUNTERS, TokenDecoder, _head_loss, _moe, _rms_norm,
                      _rope, attention_weights, checkpointed, expert_weights,
                      rope_tables)

__all__ = ["Sdar"]

#: what a layer counts beside the expert layer's own
BD_COUNTERS = ("bd_pairs_visible", "bd_pairs_scored", "moe_slots_marked")
#: ``t`` comes with the row as an integer: this many parts make 1
T_UNIT = 65536


def _bd_visible(q_at, q_noisy, k_at, k_noisy, q_docs, k_docs, block_length):
    """``[B, q, k]``: the block-diffusion mask.  ``q_at``/``k_at`` are token
    numbers ``[q]``/``[k]`` (the same in both copies), ``q_noisy``/``k_noisy``
    say which copy a position lies in, ``q_docs[B, q]``/``k_docs[B, k]`` are
    the documents' numbers."""
    q_blk, k_blk = q_at[:, None] // block_length, k_at[None, :] // block_length
    qn, kn = q_noisy[:, None], k_noisy[None, :]
    sees = (qn & kn & (k_blk == q_blk)) | (qn & ~kn & (k_blk < q_blk)) \
        | (~qn & ~kn & (k_blk <= q_blk))
    return sees[None] & (q_docs[:, :, None] == k_docs[:, None, :])


def _project(p, h, sizes):
    """The normed input's projections over all ``2 S`` positions, ``q`` and
    ``k`` normed a head and rotated by the position their copy gives them:
    ``q[B, 2S, kv, group, d]``, ``k``/``v[B, 2S, kv, d]``."""
    b, s2, _ = h.shape
    d, hq, hkv = sizes["head_dim"], sizes["q_heads_held"], \
        sizes["kv_heads_held"]
    eps = sizes["rms_norm_eps"]
    x = _rms_norm(h, p["attn_norm"], eps)
    at = jnp.arange(s2 // 2, dtype=jnp.float32)
    cos, sin = rope_tables(jnp.concatenate([at, at]), d, sizes["rope_theta"])
    q = _rope(_rms_norm(jnp.dot(x, p["wq"]).reshape(b, s2, hq, d),
                        p["q_norm"], eps), cos, sin)
    k = _rope(_rms_norm(jnp.dot(x, p["wk"]).reshape(b, s2, hkv, d),
                        p["k_norm"], eps), cos, sin)
    v = jnp.dot(x, p["wv"]).reshape(b, s2, hkv, d)
    return q.reshape(b, s2, hkv, hq // hkv, d), k, v


def _block_keys(start, stop, noisy):
    """What the query block of tokens ``[start, stop)`` of one copy reads:
    runs ``(first token, last token + 1, of the noisy copy?)`` of the doubled
    row.  Every block reads the clean keys up to its own end; a noisy block
    its own noisy keys after them."""
    return ((0, stop, False),) + (((start, stop, True),) if noisy else ())


def _query_block(q, q_docs, k, v, k_docs, *, start, noisy, runs,
                 block_length):
    """Query tokens ``[start, start + Q)`` of the noisy or the clean copy
    against the keys handed to it, which are ``runs`` end to end: (the heads'
    outputs ``[B, Q, heads x d]``, how many pairs the mask lets see)."""
    b, block = q.shape[:2]
    k_at = jnp.concatenate([jnp.arange(lo, hi) for lo, hi, _ in runs])
    k_noisy = jnp.concatenate([jnp.full((hi - lo,), flag)
                               for lo, hi, flag in runs])
    sees = _bd_visible(jnp.arange(start, start + block),
                       jnp.full((block,), noisy), k_at, k_noisy, q_docs,
                       k_docs, block_length)
    scores = jnp.einsum("bikgd,bjkd->bkgij", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(sees[:, None, None], scores.astype(jnp.float32),
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgij,bjkd->bikgd", probs, v)
    return out.reshape(b, block, -1), jnp.sum(sees)


def _query_blocks(s: int, sizes):
    """``[(first token, last token + 1, of the noisy copy?)]``: a row's query
    blocks, the noisy copy's then the clean copy's."""
    block = sizes.get("attn_block", 1024)  # a test seam: blocks at S = 32
    if s % block:
        block = s
    return [(start, start + block, noisy) for noisy in (True, False)
            for start in range(0, s, block)]


def pairs_scored(s: int, sizes) -> int:
    """Pairs a doubled row's query blocks score, a head."""
    return sum((stop - start) * (hi - lo)
               for start, stop, noisy in _query_blocks(s, sizes)
               for lo, hi, _ in _block_keys(start, stop, noisy))


def _row_attention(q, k, v, docs, sizes):
    """Rows ``[B, 2S, ...]`` a checkpointed block of queries at a time: (the
    heads' outputs ``[B, 2S, heads x d]``, the pairs the mask lets see)."""
    s = docs.shape[1]
    docs2 = jnp.concatenate([docs, docs], axis=1)
    # where a run of tokens of a copy lies in the doubled row
    run = lambda a, lo, hi, noisy: a[:, lo + (0 if noisy else s):
                                     hi + (0 if noisy else s)]
    outs, visible = [], 0
    for start, stop, noisy in _query_blocks(s, sizes):
        runs = _block_keys(start, stop, noisy)
        keys = lambda a: jnp.concatenate([run(a, *r) for r in runs], axis=1)
        out, seen = jax.checkpoint(functools.partial(
            _query_block, start=start, noisy=noisy, runs=runs,
            block_length=sizes["block_length"]))(
                run(q, start, stop, noisy), run(docs2, start, stop, noisy),
                keys(k), keys(v), keys(docs2))
        outs.append(out)
        visible = visible + seen
    return jnp.concatenate(outs, axis=1), visible


def _bd_attention(q, k, v, docs, sizes):
    """(the heads' outputs ``[B, 2S, heads x d]``, the layer's counters), one
    row after another."""
    b, s = docs.shape
    with device_span("matcha/bd_attn"):
        out, visible = lax.map(
            lambda row: _row_attention(*(a[None] for a in row), sizes),
            (q, k, v, docs))
    return out.reshape(b, 2 * s, -1), {
        "bd_pairs_visible": jnp.sum(visible).astype(jnp.float32),
        "bd_pairs_scored": jnp.float32(b * pairs_scored(s, sizes))}


def _experts_of(p, h, masked, sizes):
    return _moe(p, _rms_norm(h, p["moe_norm"], sizes["rms_norm_eps"]), sizes,
                marked=masked)


def _block(p, h, docs, masked, sizes, remat):
    again = checkpointed(remat)
    projected = again(functools.partial(_project, sizes=sizes))(p, h)
    out, counters = _bd_attention(*projected, docs, sizes)
    h = h + jnp.dot(out, p["wo"])
    y, moe = again(functools.partial(_experts_of, sizes=sizes))(p, h, masked)
    return h + y, {**counters, **moe}


class Sdar(TokenDecoder):
    """``sizes`` as in ``chipbench/configs/sdar-30b-a3b.ep16-s4k.json``
    (README "Training a language model" lists the keys)."""

    @property
    def expert_layers(self):
        return self.sizes["num_layers"]

    def setup(self):
        z = self.sizes
        ones, d = nn.initializers.ones, z["head_dim"]
        self.declare([{**attention_weights(z), "q_norm": (ones, (d,)),
                       "k_norm": (ones, (d,)), **expert_weights(z)}]
                     * z["num_layers"])

    def dummy_input(self, input_shape):
        """What ``init`` traces: one row of two blocks."""
        return jnp.zeros((1, 2 * self.sizes["block_length"]), jnp.int32)

    def row_tokens(self, width: int) -> int:
        """A raw row ``[2 S]`` holds ``S`` tokens, each predicted where it is
        masked."""
        return width // 2

    def row_positions(self, width: int) -> int:
        """All ``2 S`` positions, noisy and clean, pass through every layer."""
        return width

    def judged_positions(self, x_raw, y_raw) -> int:
        """The masked positions of raw rows ``x_raw[n, 2 S]`` (numpy)."""
        noisy = np.asarray(x_raw)[:, :x_raw.shape[1] // 2]
        return int(np.sum(noisy == self.sizes["mask_id"]))

    def hidden(self, ids, docs):
        """Of doubled rows ``ids[B, 2S]`` (noisy then clean) and their
        document numbers ``docs[B, S]``: (the final norm's output ``[B, 2S,
        H]``, the layers' counters summed, ``moe_load[layer, expert held]``)."""
        s = docs.shape[1]
        if ids.shape[1] != 2 * s or s % self.sizes["block_length"]:
            raise ValueError(
                f"sdar: ids {ids.shape} must be [rows, 2 S] beside docs "
                f"{docs.shape} [rows, S], S a multiple of block_length "
                f"{self.sizes['block_length']}")
        masked = jnp.pad(ids[:, :s] == self.sizes["mask_id"],
                         ((0, 0), (0, s)))
        with device_span("matcha/lm_embed"):
            h = self.embed[ids]
        counters = []
        for p in self.layers:
            h, c = _block(p, h, docs, masked, self.sizes, self.remat)
            counters.append(c)
        return self.normed(h, counters, MOE_COUNTERS + BD_COUNTERS)

    def logits(self, ids, docs):
        """Float32 ``[B, S, V]`` of the noisy copy of rows ``ids[B, S]`` in
        which nothing is masked (each block sees itself and the blocks
        before it)."""
        h, _ = self.hidden(jnp.concatenate([ids, ids], axis=1), docs)
        return jnp.dot(h[:, :ids.shape[1]], self.head).astype(jnp.float32)

    def batch_loss(self, x_raw, y_raw):
        """``x_raw``/``y_raw``: int32 ``[B, 2S]`` (the module docstring has
        the layout).  Returns (the weighted cross-entropy of the masked
        positions' own ids over ``B x S``, ``{"accuracy", "counters"}``)."""
        if x_raw.shape[1] % 2:
            raise ValueError(
                f"sdar: a raw row is [2 S] (noisy ids then clean ids); got "
                f"{x_raw.shape}: is this a next-token data set?")
        b, s = x_raw.shape[0], x_raw.shape[1] // 2
        docs, t = y_raw[:, :s], y_raw[:, s:]
        masked = x_raw[:, :s] == self.sizes["mask_id"]
        h, counters = self.hidden(x_raw, docs)
        loss, accuracy, judged = _head_loss(
            h[:, :s], self.head, jnp.where(masked, x_raw[:, s:], -1),
            self.sizes, weights=T_UNIT / t.astype(jnp.float32),
            normaliser=float(b * s))
        counters["bd_slots_held_masked"] = counters.pop("moe_slots_marked")
        counters["bd_tokens"] = jnp.float32(b * s)
        counters["bd_positions_masked"] = counters["loss_positions"] = judged
        return loss, {"accuracy": accuracy, "counters": counters}
