"""One chip's share of an SDAR-style decoder under its block-diffusion
training pass: pre-norm blocks of grouped-query attention (per-head RMS norm
on ``q`` and ``k``, then RoPE) and a sparse expert layer, RMSNorm, an untied
head; no bias anywhere.  Written from the configuration's equations
(``chipbench/configs/sdar-30b-a3b.ep16-s4k.json``), not from the program.

``x`` is ``block_diffusion.prepare``'s ``{"ids": [rows, 2 S], "docs": [rows,
S]}``: a row's noisy copy (``[MASK]`` where masked) and then its clean copy,
``2 S`` positions that all pass through every layer; position ``p`` of the
doubled row is token ``p mod S``, which is also its RoPE position, and lies
in the noisy copy iff ``p < S``.  The output is the logits of the noisy copy,
float32 ``[rows, S, vocab_held]``, which ``tasks/block_diffusion.loss``
weighs.  With ``B = block_length`` and ``blk(i) = i // B``, query ``p`` sees
key ``r`` iff their tokens lie in one document and

* both noisy: ``blk`` equal;
* ``p`` noisy, ``r`` clean: ``blk(r) < blk(p)``;
* both clean: ``blk(r) <= blk(p)``;
* ``p`` clean, ``r`` noisy: never.

Kept plain: the mask is one dense ``[2 S, 2 S]`` boolean a row, built from
those four lines and the document numbers; every query scores every key of
the doubled row (no block is skipped); every expert held is applied to every
position under the router's weight or zero.  The concessions are to memory:
a layer runs a row at a time (``lax.map``) and is recomputed in the backward
pass, and a row's queries go ``REFERENCE_BLOCK`` at a time in a Python loop
against the whole doubled row, each block recomputed too, which changes no
number.  Parameter names are the program's (``layer<n>_wq`` ...
``layer<n>_q_norm``, ``layer<n>_k_norm`` ... ``layer<n>_down``, ``embed``,
``final_norm``, ``head``).  Projections, scores, values, expert products and
the head are at ``ops.precision``; the router's product is float32 at
``highest`` whatever ``ops`` says; softmaxes, norms and RoPE are float32.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

REFERENCE_BLOCK = 1024
HIGHEST = lax.Precision.HIGHEST


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return w * x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1,
                                     keepdims=True) + eps)


def rope(x, at, theta):
    """``x[P, heads, d]`` rotated by the positions ``at[P]``; pairs ``(i, i +
    d/2)``."""
    d = x.shape[-1]
    inv_freq = float(theta) ** (-2.0 * jnp.arange(d // 2,
                                                  dtype=jnp.float32) / d)
    angle = at.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def visible(docs, block_length):
    """The block-diffusion mask of one row, ``[2 S, 2 S]`` (query, key),
    from its tokens' document numbers ``docs[S]``."""
    s = docs.shape[0]
    p = jnp.arange(2 * s)
    blk, noisy, doc = (p % s) // block_length, p < s, docs[p % s]
    q_blk, k_blk = blk[:, None], blk[None, :]
    q_noisy, k_noisy = noisy[:, None], noisy[None, :]
    sees = jnp.where(
        q_noisy,
        jnp.where(k_noisy, k_blk == q_blk, k_blk < q_blk),
        jnp.where(k_noisy, False, k_blk <= q_blk))
    return sees & (doc[:, None] == doc[None, :])


def attention(p, name, x, docs, sizes, ops):
    """One doubled row ``x[2 S, H]`` (normed), ``docs[S]``: the layer's
    attention output ``[2 S, H]``."""
    s2 = x.shape[0]
    d, hq, hkv = sizes["head_dim"], sizes["q_heads_held"], \
        sizes["kv_heads_held"]
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    at = jnp.arange(s2) % (s2 // 2)
    mm = lambda a, m: jnp.einsum("sh,hk->sk", a, m, precision=ops.precision)
    q = rope(rms_norm(mm(x, p[name + "_wq"]).reshape(s2, hq, d),
                      p[name + "_q_norm"], eps), at, theta)
    k = rope(rms_norm(mm(x, p[name + "_wk"]).reshape(s2, hkv, d),
                      p[name + "_k_norm"], eps), at, theta)
    v = mm(x, p[name + "_wv"]).reshape(s2, hkv, d)
    # query head g uses KV head g // (hq / hkv)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    sees = visible(docs, sizes["block_length"])

    def queries(q, sees):
        """A block of queries against the whole doubled row."""
        scores = jnp.einsum("ihd,jhd->hij", q, k,
                            precision=ops.precision) / math.sqrt(d)
        scores = jnp.where(sees[None], scores.astype(jnp.float32), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hij,jhd->ihd", probs, v, precision=ops.precision)

    block = REFERENCE_BLOCK if s2 % REFERENCE_BLOCK == 0 else s2
    # recomputed in the backward pass, so that one block's scores are held
    # and not the row's
    out = jnp.concatenate([
        jax.checkpoint(queries)(q[start:start + block],
                                sees[start:start + block])
        for start in range(0, s2, block)], axis=0)
    return mm(out.reshape(s2, hq * d), p[name + "_wo"])


def experts(p, name, h, sizes, ops):
    r = jnp.einsum("sh,he->se", h, p[name + "_router"], precision=HIGHEST)
    prob = jax.nn.softmax(r.astype(jnp.float32), axis=-1)
    top, sel = lax.top_k(prob, sizes["experts_per_token"])
    w = top / jnp.sum(top, axis=-1, keepdims=True) \
        if sizes["norm_topk_prob"] else top
    y = jnp.zeros_like(h)
    for slot, e in enumerate(sizes["experts_held"]):
        # the weight of expert e at each position: 0 where it was not chosen
        w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        mm = lambda a, m: jnp.einsum("sh,hf->sf", a, m,
                                     precision=ops.precision)
        inner = jax.nn.silu(mm(h, p[name + "_gate"][slot])) \
            * mm(h, p[name + "_up"][slot])
        y = y + w_e[:, None] * mm(inner, p[name + "_down"][slot])
    return y


def forward(p, stats, x, sizes, ops):
    eps = sizes["rms_norm_eps"]
    s = x["docs"].shape[1]
    h = p["embed"][x["ids"]]
    for n in range(sizes["num_layers"]):
        blk = f"layer{n}"

        def layer(row, blk=blk):
            h, docs = row
            h = h + attention(p, blk, rms_norm(h, p[blk + "_attn_norm"], eps),
                              docs, sizes, ops)
            return h + experts(p, blk, rms_norm(h, p[blk + "_moe_norm"], eps),
                               sizes, ops)

        # a row at a time, and recomputed in the backward pass: the same
        # numbers as the whole batch at once, in a fraction of the memory
        h = lax.map(jax.checkpoint(layer), (h, x["docs"]))
    # the head reads the noisy copy alone
    h = rms_norm(h[:, :s], p["final_norm"], eps)
    logits = jnp.einsum("bsh,hv->bsv", h, p["head"], precision=ops.precision)
    return logits.astype(jnp.float32), {}


def visible_pairs(s: int, block_length: int) -> int:
    """Pairs the mask's four rules let see in a doubled row of ``S`` tokens,
    the document term apart (it lets see fewer): with ``n = S / B`` blocks,
    noisy -> noisy ``n B^2``, noisy -> clean ``B^2 n (n - 1) / 2``, clean ->
    clean ``B^2 n (n + 1) / 2``."""
    n = s // block_length
    return block_length ** 2 * (n + n * (n - 1) // 2 + n * (n + 1) // 2)


def forward_macs(sizes) -> int:
    """Multiply-accumulates of one row's forward pass, from the shapes alone.
    All ``2 S`` positions pass through the layers: the four projections;
    scores and values over the pairs the mask's four rules let see
    (:func:`visible_pairs`; **the document term is not counted off**, a
    packed row's queries see fewer, ``bd_pairs_visible`` has the count; nor
    is what the program scores and masks counted on, ``bd_pairs_scored``);
    the router; the experts held at their expected load (``experts_per_token
    x held / num_experts`` slots a position: a half, at 8 x 8 / 128).  The
    head runs over the ``S`` positions of the noisy copy.  Lookups, norms
    and RoPE are no matrix product and count nothing."""
    s, h, d = sizes["seq_len"], sizes["hidden"], sizes["head_dim"]
    hq, hkv = sizes["q_heads_held"], sizes["kv_heads_held"]
    slots = sizes["experts_per_token"] * len(sizes["experts_held"]) \
        / sizes["num_experts"]
    layer = 2 * s * h * d * (2 * hq + 2 * hkv)  # wq, wo; wk, wv
    layer += 2 * hq * d * visible_pairs(s, sizes["block_length"])
    layer += 2 * s * h * sizes["num_experts"]
    layer += int(2 * s * slots * 3 * h * sizes["expert_width"])
    return sizes["num_layers"] * layer + s * h * sizes["vocab_held"]
