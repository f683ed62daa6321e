"""One chip's share of the language tower of a Keye-VL-2.0-style decoder:
pre-norm blocks of grouped-query attention over a learned choice of keys (a
DeepSeek-Sparse-Attention indexer) and a sparse expert layer, RMSNorm, RoPE,
an untied head; no bias anywhere.  Written from the configuration's
equations (``chipbench/configs/keye-vl2-30b-a3b.ep16-s8k.json``), not from
the program.

``x`` is ``next_token.prepare``'s ``{"ids", "docs"}`` of ``[B, S]``; the
output is ``{"logits": f32[B, S, vocab_held], "indexer_kl": f32 scalar}``,
which ``tasks/next_token_indexed.loss`` adds up.  Per layer and row, with
``x`` the normed input, ``t`` a query and ``s`` a key that ``t`` may see
(``s <= t``, same document):

* indexer: ``qI[t, j] = x_t WqI_j`` (``indexer_heads`` heads of
  ``indexer_head_dim``), ``kI[s] = x_s WkI`` (one key head), ``w[t] = x_t WwI
  / sqrt(heads x head_dim)``, RoPE on ``qI`` and ``kI``, ``I[t, s] = sum_j
  w[t, j] relu(qI[t, j] . kI[s])``;
* selection ``S_t``: the ``index_topk`` visible ``s`` of largest ``I[t, s]``
  (``lax.top_k``: ties to the lower ``s``), all of them where fewer are
  visible; as indices, scattered into a mask;
* attention: softmax of ``q . k / sqrt(head_dim)`` over ``S_t`` only, times
  ``v``, through ``wo``;
* ``indexer_kl``: the mean over layers and query positions of ``KL(p_t ||
  softmax(I[t, S_t]))``, ``p_t`` the attention's probabilities summed over
  the heads held and normalised to sum 1, with ``p_t`` and the indexer's
  ``x`` under ``stop_gradient``.

The chip holds ``q_heads_held`` query heads that share its ``kv_heads_held``
KV heads, the experts ``experts_held`` of ``num_experts``, a slice of the
vocabulary, and the whole indexer.  What absent experts and heads would add
is left out.

Kept plain: every expert held applied to every token under a 0/1 mask,
whole-row keys for every query.  The concessions are to memory: a layer runs
a row at a time (``lax.map``) and is recomputed in the backward pass, and a
row's queries go ``REFERENCE_BLOCK`` at a time in a Python loop, each block
recomputed too, which changes no number.  Parameter names are the program's
(``layer<n>_wq`` ... ``layer<n>_idx_ww`` ... ``layer<n>_down``, ``embed``,
``final_norm``, ``head``).  Projections, expert products and the head are at
``ops.precision``; the router's product and the whole indexer are float32 at
``highest`` whatever ``ops`` says; softmaxes, norms, RoPE and the KL are
float32.
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


def rope(x, theta):
    """``x[S, heads, d]`` rotated by its position; pairs ``(i, i + d/2)``."""
    s, _, d = x.shape
    inv_freq = float(theta) ** (-2.0 * jnp.arange(d // 2,
                                                  dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, name, x, docs, sizes, ops):
    """One row ``x[S, H]`` (normed), ``docs[S]``: (the layer's attention
    output ``[S, H]``, the sum over its queries of the indexer's KL)."""
    s = x.shape[0]
    d, hq, hkv = sizes["head_dim"], sizes["q_heads_held"], \
        sizes["kv_heads_held"]
    heads, di, topk = sizes["indexer_heads"], sizes["indexer_head_dim"], \
        sizes["index_topk"]
    theta = sizes["rope_theta"]
    mm = lambda a, m: jnp.einsum("sh,hk->sk", a, m, precision=ops.precision)
    q = rope(mm(x, p[name + "_wq"]).reshape(s, hq, d), theta)
    k = rope(mm(x, p[name + "_wk"]).reshape(s, hkv, d), theta)
    v = mm(x, p[name + "_wv"]).reshape(s, hkv, d)
    # query head g uses KV head g // (hq / hkv)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)

    xi = lax.stop_gradient(x)
    exact = lambda a, m: jnp.einsum("sh,hk->sk", a, m, precision=HIGHEST)
    qi = rope(exact(xi, p[name + "_idx_wq"]).reshape(s, heads, di), theta)
    ki = rope(exact(xi, p[name + "_idx_wk"]).reshape(s, 1, di), theta)[:, 0]
    w = exact(xi, p[name + "_idx_ww"]) / math.sqrt(heads * di)

    block = REFERENCE_BLOCK if s % REFERENCE_BLOCK == 0 else s
    j = jnp.arange(s)[None, :]

    def queries(q, qi, w, q_docs, i):
        """The queries at positions ``i[block, 1]`` against the whole row."""
        sees = (j <= i) & (q_docs[:, None] == docs[None, :])
        per_head = jax.nn.relu(jnp.einsum("ihd,jd->ihj", qi, ki,
                                          precision=HIGHEST))
        index = jnp.where(sees, jnp.einsum("ih,ihj->ij", w, per_head,
                                           precision=HIGHEST), -jnp.inf)
        chosen = lax.top_k(index, min(topk, s))[1]
        keep = jnp.zeros(sees.shape, bool).at[
            jnp.arange(block)[:, None], chosen].set(True) & sees
        scores = jnp.einsum("ihd,jhd->hij", q, k,
                            precision=ops.precision) / math.sqrt(d)
        scores = jnp.where(keep[None], scores.astype(jnp.float32), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("hij,jhd->ihd", probs, v, precision=ops.precision)
        p_t = lax.stop_gradient(jnp.sum(probs, axis=0))
        p_t = p_t / jnp.sum(p_t, axis=-1, keepdims=True)
        guess = jax.nn.log_softmax(jnp.where(keep, index, -jnp.inf), axis=-1)
        # 0 log 0 is 0; a key that is not kept has p_t 0
        return out, jnp.sum(jnp.where(p_t > 0,
                                      p_t * (jnp.log(p_t) - guess), 0.0))

    outs, kl = [], 0.0
    for start in range(0, s, block):
        at = slice(start, start + block)
        # recomputed in the backward pass, so that one block's scores are
        # held and not the row's
        out, kl_block = jax.checkpoint(queries)(
            q[at], qi[at], w[at], docs[at],
            jnp.arange(start, start + block)[:, None])
        outs.append(out)
        kl = kl + kl_block
    out = jnp.concatenate(outs, axis=0).reshape(s, hq * d)
    return mm(out, p[name + "_wo"]), kl


def experts(p, name, h, sizes, ops):
    r = jnp.einsum("sh,he->se", h, p[name + "_router"], precision=HIGHEST)
    prob = jax.nn.softmax(r.astype(jnp.float32), axis=-1)
    top, sel = lax.top_k(prob, sizes["experts_per_token"])
    w = top / jnp.sum(top, axis=-1, keepdims=True) \
        if sizes["norm_topk_prob"] else top
    y = jnp.zeros_like(h)
    for slot, e in enumerate(sizes["experts_held"]):
        # the weight of expert e at each token: 0 where it was not chosen
        w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        mm = lambda a, m: jnp.einsum("sh,hf->sf", a, m,
                                     precision=ops.precision)
        inner = jax.nn.silu(mm(h, p[name + "_gate"][slot])) \
            * mm(h, p[name + "_up"][slot])
        y = y + w_e[:, None] * mm(inner, p[name + "_down"][slot])
    return y


def forward(p, stats, x, sizes, ops):
    eps = sizes["rms_norm_eps"]
    h = p["embed"][x["ids"]]
    kl = 0.0
    for n in range(sizes["num_layers"]):
        blk = f"layer{n}"

        def layer(row, blk=blk):
            h, docs = row
            a, kl = attention(p, blk, rms_norm(h, p[blk + "_attn_norm"], eps),
                              docs, sizes, ops)
            h = h + a
            return h + experts(p, blk, rms_norm(h, p[blk + "_moe_norm"], eps),
                               sizes, ops), kl

        # a row at a time, and recomputed in the backward pass: the same
        # numbers as the whole batch at once, in a fraction of the memory
        h, kl_rows = lax.map(jax.checkpoint(layer), (h, x["docs"]))
        kl = kl + jnp.sum(kl_rows)
    h = rms_norm(h, p["final_norm"], eps)
    logits = jnp.einsum("bsh,hv->bsv", h, p["head"], precision=ops.precision)
    queries = sizes["num_layers"] * x["ids"].shape[0] * x["ids"].shape[1]
    return {"logits": logits.astype(jnp.float32),
            "indexer_kl": kl / queries}, {}


def forward_macs(sizes) -> int:
    """Multiply-accumulates of one row's forward pass, from the shapes alone:
    the main and the indexer's projections; the indexer's scores over the
    causal half of the row (every head against every earlier key); the main
    scores and values over the ``min(t + 1, index_topk)`` keys a query at
    position ``t`` keeps; the router; the experts held at their expected load
    (``experts_per_token x held / num_experts`` slots a token: a half, at 8 x
    8 / 128); the head.  **The document mask is not counted**: a packed row's
    queries see, and keep, fewer keys than this (``dsa_keys_kept`` has the
    count).  The program computes every main score and masks, which is not
    counted either.  Lookups, norms, RoPE and the selection are no matrix
    product and count nothing."""
    s, h, d = sizes["seq_len"], sizes["hidden"], sizes["head_dim"]
    hq, hkv = sizes["q_heads_held"], sizes["kv_heads_held"]
    heads, di = sizes["indexer_heads"], sizes["indexer_head_dim"]
    topk = min(sizes["index_topk"], s)
    slots = sizes["experts_per_token"] * len(sizes["experts_held"]) \
        / sizes["num_experts"]
    kept = topk * (topk + 1) // 2 + (s - topk) * topk
    layer = s * h * d * (2 * hq + 2 * hkv)  # wq, wo; wk, wv
    layer += s * h * (heads * di + di + heads)  # the indexer's three
    layer += heads * di * (s * (s + 1) // 2)
    layer += 2 * hq * d * kept
    layer += s * h * sizes["num_experts"]
    layer += int(s * slots * 3 * h * sizes["expert_width"])
    return sizes["num_layers"] * layer + s * h * sizes["vocab_held"]
