"""One chip's share of a Mellum2-style decoder: pre-norm blocks of grouped-
query attention (sliding-window or full, by ``layer_types``) and a sparse
expert layer, RMSNorm, RoPE (plain on sliding layers, YaRN on full ones), an
untied head; no bias anywhere.  Written from the configuration's equations
(``chipbench/configs/mellum2-12b-a2.5b.ep8-s4k.json``), not from the program.

``x`` is ``next_token.prepare``'s ``{"ids", "docs"}`` of ``[B, S]``; the
output is ``logits[B, S, vocab_held]`` in float32.  The chip holds
``q_heads_held`` query heads that share its ``kv_heads_held`` KV heads, the
experts ``experts_held`` of ``num_experts`` and a slice of the vocabulary.
The router scores all ``num_experts`` and keeps ``experts_per_token``; what
the absent experts would add is left out and the partial sum goes on.

Kept plain on purpose: whole ``S x S`` masks, and every expert held applied
to every token under a 0/1 mask of who chose it, so nothing here can share a
dispatch fault with the program.  The one concession is to memory: a layer
runs a row of the batch at a time and is recomputed in the backward pass,
which changes no number.  Parameter names are the program's
(``layer<n>_wq`` ... ``layer<n>_down``, ``embed``, ``final_norm``, ``head``).  Projections, expert products and the head
are at ``ops.precision``; the router's product, the softmaxes, the norms and
RoPE are float32 at ``highest`` whatever ``ops`` says.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax


def rope_inv_freq(kind, sizes):
    """(``inv_freq[head_dim / 2]``, the factor on cos and sin) of a layer."""
    d, theta = sizes["head_dim"], float(sizes["rope_theta"])
    i = jnp.arange(d // 2, dtype=jnp.float32)
    extrap = theta ** (-2.0 * i / d)
    if kind == "sliding":
        return extrap, 1.0
    y = sizes["yarn"]
    interp = extrap / y["factor"]

    def c(rotations):  # the dimension that turns ``rotations`` times
        return d * math.log(y["original_max_position_embeddings"]
                            / (2 * math.pi * rotations)) / (
                                2 * math.log(theta))

    low = max(math.floor(c(y["beta_fast"])), 0)
    high = min(math.ceil(c(y["beta_slow"])), d - 1)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return interp * ramp + extrap * (1.0 - ramp), y["attention_factor"]


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return w * x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1,
                                     keepdims=True) + eps)


def rope(x, cos, sin):
    """``x[B, S, heads, d]``; pairs ``(i, i + d/2)``."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, name, h, docs, kind, sizes, ops):
    b, s, _ = h.shape
    d, hq, hkv = sizes["head_dim"], sizes["q_heads_held"], \
        sizes["kv_heads_held"]
    mm = lambda a, w: jnp.einsum("bsh,hk->bsk", a, w, precision=ops.precision)
    q = mm(h, p[name + "_wq"]).reshape(b, s, hq, d)
    k = mm(h, p[name + "_wk"]).reshape(b, s, hkv, d)
    v = mm(h, p[name + "_wv"]).reshape(b, s, hkv, d)
    inv_freq, factor = rope_inv_freq(kind, sizes)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    q, k = rope(q, cos, sin), rope(k, cos, sin)
    # query head g uses KV head g // (hq / hkv)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    scores = jnp.einsum("bihd,bjhd->bhij", q, k,
                        precision=ops.precision) / math.sqrt(d)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    sees = (j <= i)[None] & (docs[:, :, None] == docs[:, None, :])
    if kind == "sliding":
        sees = sees & ((i - j) < sizes["sliding_window"])[None]
    scores = jnp.where(sees[:, None], scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhij,bjhd->bihd", probs, v, precision=ops.precision)
    return mm(out.reshape(b, s, hq * d), p[name + "_wo"])


def experts(p, name, h, sizes, ops):
    r = jnp.einsum("bsh,he->bse", h, p[name + "_router"],
                   precision=lax.Precision.HIGHEST)
    prob = jax.nn.softmax(r.astype(jnp.float32), axis=-1)
    top, sel = lax.top_k(prob, sizes["experts_per_token"])
    w = top / jnp.sum(top, axis=-1, keepdims=True) \
        if sizes["norm_topk_prob"] else top
    y = jnp.zeros_like(h)
    for slot, e in enumerate(sizes["experts_held"]):
        # the weight of expert e at each token: 0 where it was not chosen
        w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        mm = lambda a, m: jnp.einsum("bsh,hf->bsf", a, m,
                                     precision=ops.precision)
        inner = jax.nn.silu(mm(h, p[name + "_gate"][slot])) \
            * mm(h, p[name + "_up"][slot])
        y = y + w_e[..., None] * mm(inner, p[name + "_down"][slot])
    return y


def forward(p, stats, x, sizes, ops):
    eps = sizes["rms_norm_eps"]
    h = p["embed"][x["ids"]]
    for n, kind in enumerate(sizes["layer_types"]):
        blk = f"layer{n}"

        def layer(row, blk=blk, kind=kind):
            h, docs = row[0][None], row[1][None]  # one row as a batch of one
            h = h + attention(p, blk, rms_norm(
                h, p[blk + "_attn_norm"], eps), docs, kind, sizes, ops)
            h = h + experts(p, blk, rms_norm(
                h, p[blk + "_moe_norm"], eps), sizes, ops)
            return h[0]

        # a row at a time, and recomputed in the backward pass: the same
        # numbers as the whole batch at once, in a tenth of the memory
        h = lax.map(jax.checkpoint(layer), (h, x["docs"]))
    h = rms_norm(h, p["final_norm"], eps)
    logits = jnp.einsum("bsh,hv->bsv", h, p["head"],
                        precision=ops.precision)
    return logits.astype(jnp.float32), {}


def forward_macs(sizes) -> int:
    """Multiply-accumulates of one row's forward pass, from the shapes alone:
    the four projections; scores and values over the pairs each layer's mask
    shape lets through (causal, or causal within the window; a document
    mask lets through fewer, which is not counted off); the router; the
    experts held at their expected load (``experts_per_token x held /
    num_experts`` slots a token: one, at 8 x 8 / 64); the head.  Embedding
    lookups, norms and RoPE are no matrix product and count nothing."""
    s, h, d = sizes["seq_len"], sizes["hidden"], sizes["head_dim"]
    hq, hkv = sizes["q_heads_held"], sizes["kv_heads_held"]
    slots = sizes["experts_per_token"] * len(sizes["experts_held"]) \
        / sizes["num_experts"]
    total = 0
    for kind in sizes["layer_types"]:
        total += s * h * d * (2 * hq + 2 * hkv)  # wq, wo; wk, wv
        w = min(sizes["sliding_window"], s) if kind == "sliding" else s
        pairs = w * (w + 1) // 2 + (s - w) * w
        total += 2 * hq * d * pairs
        total += s * h * sizes["num_experts"]
        total += int(s * slots * 3 * h * sizes["expert_width"])
    return total + s * h * sizes["vocab_held"]
