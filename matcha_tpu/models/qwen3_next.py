"""One chip's share of a Qwen3-Next-style hybrid decoder: linear attention
(Gated DeltaNet) and gated softmax attention 3:1, a sparse expert layer with
a shared expert, for next-token training through the same ``train()`` as
``mellum2`` and ``keye_vl2``.

``norm0(x, w) = x / sqrt(mean(x^2) + eps) * (1 + w)`` with ``w`` from 0.
Pre-norm blocks ``h = x + Mixer(norm0(x))``, ``x' = h + MoE(norm0(h))``;
layer ``i`` is full attention where ``(i + 1) % full_attention_interval ==
0`` and Gated DeltaNet elsewhere, so the layers are of two kinds with
different parameter sets (``TokenDecoder.declare`` takes one set a layer); a
final ``norm0``, an untied head, no bias.  The expert layer, RoPE's rotation,
the visibility mask, the chunked head-and-loss and what stands around the
blocks are ``models/mellum2.py``'s own, imported.

**Gated DeltaNet**, per row, ``Hk`` key heads of ``dk`` and ``Hv = R Hk``
value heads of ``dv`` (value head ``j`` uses key head ``j // R``):
``q, k, v, z = split(x W_qkvz)``, ``b, a = split(x W_ba)``; a causal
depthwise convolution of ``conv_kernel`` taps and a SiLU over ``concat(q, k,
v)``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; ``q
<- l2norm(q) / sqrt(dk)``, ``k <- l2norm(k)``; a state ``S[dk, dv]`` a value
head, 0 at a document's first token, and for each token ``S <- exp(g_t) S``;
``u = beta_t (v_t - S^T k_t)``; ``S <- S + k_t u^T``; ``o_t = S^T q_t``; the
output is ``o / rms(o) * w_norm * silu(z)`` a head, through ``W_out``.

**The recurrence runs chunked** (``gdn_chunk`` tokens, 64).  With ``gamma_i``
the running sum of ``g`` inside a chunk and ``D_ij = exp(gamma_i - gamma_j)``
where ``j <= i`` lie in one document (0 elsewhere), the chunk's ``u`` solve
``(I + A) u = beta v - (beta e^gamma c k) S0`` with ``A_ij = beta_i D_ij k_i
. k_j`` strictly lower and ``c_i`` 1 where token ``i`` still lies in the
document of the token before the chunk (whose state ``S0`` is).  So, for all
chunks of a row at once (:func:`_chunk_prep`): ``T = (I + A)^-1``, ``U = T
(beta v)``, ``W = T (beta e^gamma c k)``, ``P_ij = D_ij q_i . k_j``; and a
``lax.scan`` over the chunks (:func:`_chunk_scan`) carries ``S`` and does
``u = U - W S``, ``o = e^gamma c (q S) + P u``, ``S <- e^gamma_C c_C S +
(D_C. k)^T u``.  A document's first token cuts all three: the pairs inside
the chunk (``D``), the carried state (``c``) and the convolution's taps.

``T`` is formed by halves (:func:`_unit_lower_inverse`): the inverse of a
unit lower-triangular ``[[M11, 0], [M21, M22]]`` is ``[[T11, 0], [-T22 M21
T11, T22]]``, from blocks of 1 up to the chunk in ``log2(chunk)`` levels of
batched products.  It is exact like row-by-row substitution, without its
``chunk`` sequential passes; the product form over ``A``'s powers of two
cancels where keys repeat (all-ones ``A``: ``A^32`` holds 1e17 against an
inverse of 0s and 1s) and was not taken (PERF.md section 4).  The backward
pass keeps ``T`` and nothing of the levels that built it: ``dT = -T dA T``,
so a cotangent ``G`` of ``T`` is ``-T^T G T^T`` of ``A``, two products of the
``T`` the forward pass has (``_unit_lower_inverse``'s ``custom_vjp``).  Under
``remat`` a layer is computed twice (the block's checkpoint keeps nothing of
the mixer; the expert layer's keeps its dispatch, ``mellum2.MOE_KEPT``)
and its chunk systems, convolution and gated norm lie under checkpoints of
their own inside it; the chunk systems' keeps ``T`` by name (``KEPT``), so
its third pass inverts nothing and is left the decays ``D`` and the two Gram
products ``K K^T`` and ``Q K^T``, which cost less computed again than kept
(PERF.md section 6, PR 36).

**Gated full attention**: ``wq`` gives ``2 head_dim`` a head, query and gate;
``q = norm0(query)``, ``k = norm0(x W_k)`` a head; RoPE on the first
``rotary_dim`` of each head's dimensions (rotate-half pairs ``(i, i +
rotary_dim / 2)``), the rest pass; softmax of ``q . k / sqrt(head_dim)`` over
``s <= t`` of the same document; ``(P v) sigmoid(gate)`` through ``wo``.  A
row at a time and a checkpointed block of ``attn_block`` queries at a time,
as ``keye_vl2`` runs its own.

**Expert layer**: ``mellum2._moe`` (softmax router over all experts, the
``experts_per_token`` largest renormalised, the experts held on the slots
that chose them) plus ``sigmoid(x w_sg) SwiGLU_shared(x)``, which every chip
of a layer computes alike.

Precision: projections, expert products, attention scores and values and
the head at the MXU's default; the router at ``highest``; and, because they
compound over a row's chunks, the gates' projection ``W_ba``, ``beta``, ``g``,
the L2 norms, ``gamma``, ``A``, ``T``, ``U``, ``W``, ``P`` and the carried
state float32 with their products at ``highest``.

Counters, returned with the loss and summed over the DeltaNet layers:
``gdn_chunks`` (layer-row chunks scanned), ``gdn_chunks_reset`` (those in
which a token follows one of another document, the chunk's first after the
chunk before it included), ``gdn_gates`` (token x value-head x layer gates),
``gdn_decay_sum`` (the sum of ``exp(g_t)`` over them); and the expert
layer's and the loss's as in ``mellum2``.
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..utils.profiling import device_span
from .mellum2 import (TokenDecoder, _head_loss, _moe, _next_ids, _rope,
                      _swiglu, _visible, checkpointed, expert_weights,
                      rope_tables)

__all__ = ["Qwen3Next"]

HIGHEST = lax.Precision.HIGHEST
#: what a Gated DeltaNet layer counts (the module docstring says of what)
GDN_COUNTERS = ("gdn_chunks", "gdn_chunks_reset", "gdn_gates",
                "gdn_decay_sum")
L2_EPS = 1e-6
#: what the checkpoint around a layer's chunk systems keeps by name for its
#: backward pass: a chunk's inverse (the module docstring says why no more)
KEPT = "gdn_inverse"
#: the name scope of the inverse's levels (a test counts the products in it)
LEVELS = "gdn_inverse_levels"


def _norm0(x, w, eps):
    x = x.astype(jnp.float32)
    return (1.0 + w) * x * lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + L2_EPS)


def _exact(spec, *operands):
    return jnp.einsum(spec, *operands, precision=HIGHEST)


def layer_kinds(sizes):
    every = sizes["full_attention_interval"]
    return ["full" if (i + 1) % every == 0 else "linear"
            for i in range(sizes["num_layers"])]


# ---------------------------------------------------------------- DeltaNet

def _causal_conv(x, taps, docs):
    """``y[t, c] = sum_i taps[i, c] x[t - (K - 1) + i, c]`` over ``x[B, S,
    C]``, a tap that would reach before the token's own document reading 0.
    (``taps[K, C]``, the channels last: a leaf whose last dimension is 4
    pads to 128 lanes wherever the flat state is cut into leaves, and the
    v5e's compiler then asks 33 GB for the initial sync: PERF.md section 6.)"""
    s, kernel = x.shape[1], taps.shape[0]
    y = x * taps[kernel - 1]
    for back in range(1, kernel):
        earlier = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :s]
        theirs = jnp.pad(docs, ((0, 0), (back, 0)), constant_values=-1)[:, :s]
        y = y + jnp.where((theirs == docs)[..., None], earlier, 0.0) \
            * taps[kernel - 1 - back]
    return y


@jax.named_scope(LEVELS)
def _by_halves(a):
    """``(I + a)^-1`` of strictly lower-triangular ``a[..., C, C]``, ``C`` a
    power of two, by halves: the diagonal blocks of size ``m`` are inverted
    from those of size ``m / 2`` and the quarter between them."""
    lead, c = a.shape[:-2], a.shape[-1]
    t = jnp.ones(lead + (c, 1, 1), a.dtype)  # the blocks of size 1
    half = 1
    while half < c:
        pairs = c // (2 * half)
        # the lower-left quarter of every diagonal block of size 2 half
        quarter = a.reshape(lead + (pairs, 2, half, pairs, 2, half))[
            ..., :, 1, :, :, 0, :]
        own = jnp.eye(pairs, dtype=bool)[:, None, :, None]
        m21 = jnp.sum(jnp.where(own, quarter, 0.0), axis=-2)
        t = t.reshape(lead + (pairs, 2, half, half))
        t11, t22 = t[..., 0, :, :], t[..., 1, :, :]
        low = -_exact("...ij,...jk,...kl->...il", t22, m21, t11)
        t = jnp.concatenate([
            jnp.concatenate([t11, jnp.zeros_like(t11)], axis=-1),
            jnp.concatenate([low, t22], axis=-1)], axis=-2)
        half *= 2
    return checkpoint_name(t.reshape(lead + (c, c)), KEPT)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``T = (I + a)^-1`` as :func:`_by_halves` builds it, differentiated
    through ``T`` itself and not through the levels: ``dT = -T da T``, so a
    cotangent ``G`` of ``T`` is ``-T^T G T^T`` of ``a``."""
    return _by_halves(a)


def _inverse_fwd(a):
    t = _by_halves(a)
    return t, t


def _inverse_bwd(t, g):
    return (-_exact("...ji,...jk,...lk->...il", t, g, t),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _chunk_prep(q, k, v, beta, g, docs, chunk):
    """What of a row's chunks does not depend on the carried state, for all
    chunks at once.  ``q``, ``k[B, S, Hk, dk]`` (normed), ``v[B, S, Hk, R,
    dv]``, ``beta``, ``g[B, S, Hk, R]``, ``docs[B, S]``.  Returns, each with
    the chunks ``N`` leading: ``U[N, B, Hk, R, C, dv]``, ``W[N, B, Hk, R, C,
    dk]``, ``P[N, B, Hk, R, C, C]``, ``q``, ``k[N, B, Hk, C, dk]``, the decay
    from before the chunk to each token ``into[N, B, Hk, R, C]`` (0 past a
    document start) and from each token to the chunk's end ``out[N, B, Hk,
    R, C]``; and how many chunks hold a document start."""
    b, s = docs.shape
    n = s // chunk
    chunks = lambda a: a.reshape((b, n, chunk) + a.shape[2:])
    q, k, v, beta, g, d = (chunks(a) for a in (q, k, v, beta, g, docs))
    heads_first = lambda a: jnp.moveaxis(a, 2, -1)  # [B, N, Hk, R, C]
    beta, gamma = heads_first(beta), heads_first(jnp.cumsum(g, axis=2))

    before = jnp.concatenate(  # the document of the token before each chunk
        [jnp.full((b, 1), -1, d.dtype), d[:, :-1, -1]], axis=1)
    carried = (d == before[:, :, None])[:, :, None, None, :]
    into = jnp.where(carried, jnp.exp(gamma), 0.0)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    same = ((d[:, :, :, None] == d[:, :, None, :]) & lower)[:, :, None, None]
    decay = jnp.exp(jnp.where(
        same, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))

    kk = _exact("bnihd,bnjhd->bnhij", k, k)[:, :, :, None]
    a = jnp.where(jnp.tril(lower, -1), beta[..., None] * decay * kk, 0.0)
    t = _unit_lower_inverse(a)
    u = _exact("bnhrij,bnhrj,bnjhre->bnhrie", t, beta, v)
    w = _exact("bnhrij,bnhrj,bnjhd->bnhrid", t, beta * into, k)
    p = decay * _exact("bnihd,bnjhd->bnhij", q, k)[:, :, :, None]

    # a token that follows one of another document (a row's first follows
    # none and drops nothing)
    starts = chunks(docs != jnp.concatenate([docs[:, :1], docs[:, :-1]], 1))
    first = lambda x: jnp.moveaxis(x, 1, 0)
    per_head = lambda x: first(jnp.moveaxis(x, 2, 3))  # [N, B, Hk, C, dk]
    return (first(u), first(w), first(p), per_head(q), per_head(k),
            first(into), first(decay[..., -1, :]),
            jnp.sum(jnp.any(starts, axis=2)))


def _chunk_scan(u, w, p, q, k, into, out):
    """The carried part: ``o[N, B, Hk, R, C, dv]`` of :func:`_chunk_prep`'s
    arrays, the state ``S[B, Hk, R, dk, dv]`` from 0."""
    def chunk(state, xs):
        u, w, p, q, k, into, out = xs
        u = u - _exact("bhrid,bhrde->bhrie", w, state)
        o = into[..., None] * _exact("bhid,bhrde->bhrie", q, state) \
            + _exact("bhrij,bhrje->bhrie", p, u)
        state = into[..., -1, None, None] * state \
            + _exact("bhrj,bhjd,bhrje->bhrde", out, k, u)
        return state, o

    state = jnp.zeros(u.shape[1:4] + (q.shape[-1], u.shape[-1]), u.dtype)
    return lax.scan(chunk, state, (u, w, p, q, k, into, out))[1]


def _gated_delta_rule(q, k, v, beta, g, docs, chunk, again=lambda f: f):
    """``o[B, S, Hk, R, dv]`` of the recurrence in the module docstring,
    chunked; and how many chunks hold a document start."""
    b, s = docs.shape
    if s % chunk:
        raise ValueError(f"a row of {s} positions is not whole chunks of "
                         f"{chunk} (sizes['gdn_chunk'])")
    with device_span("matcha/gdn_chunk_prep"):
        *prepared, resets = again(functools.partial(_chunk_prep, chunk=chunk))(
            q, k, v, beta, g, docs)
    with device_span("matcha/gdn_scan"):
        o = _chunk_scan(*prepared)  # [N, B, Hk, R, C, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 4, 2)  # [B, N, C, Hk, R, dv]
    return o.reshape((b, s) + o.shape[3:]), resets


def _conv_qkv(mixed, taps, docs, hk, dk):
    """``q``, ``k[B, S, Hk, dk]`` (normed, ``q`` scaled) and ``v[B, S, Hv
    dv]`` of the projection's ``concat(q, k, v)``."""
    b, s, _ = mixed.shape
    mixed = jax.nn.silu(_causal_conv(mixed, taps, docs))
    q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)
    return (_l2norm(q.reshape(b, s, hk, dk)) / math.sqrt(dk),
            _l2norm(k.reshape(b, s, hk, dk)), v)


def _gate_norm(o, z, w, eps):
    """``o[B, S, Hk, R, dv]`` normed a head and gated by ``z[B, S, Hv dv]``."""
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                      + eps) * w
    return o.reshape(z.shape) * jax.nn.silu(z)


def _gated_delta_net(p, h, docs, sizes, again=lambda f: f):
    """(the layer's output ``[B, S, H]`` of the residual stream ``h``, its
    counters).  ``again`` (``jax.checkpoint`` under ``remat``, keeping what
    ``KEPT`` names) wraps what holds many row-sized arrays and is cheap to
    compute a third time: the convolution's shifted copies, the gated norm,
    and of the chunk systems all but the inverse."""
    b, s, _ = h.shape
    hk, hv = sizes["linear_key_heads_held"], sizes["linear_value_heads_held"]
    dk, dv = sizes["linear_key_dim"], sizes["linear_value_dim"]
    r, eps = hv // hk, sizes["rms_norm_eps"]
    x = _norm0(h, p["attn_norm"], eps)
    with device_span("matcha/gdn_proj"):
        mixed, z = jnp.split(jnp.dot(x, p["in_proj_qkvz"]),
                             [2 * hk * dk + hv * dv], axis=-1)
        gates = jnp.dot(x, p["in_proj_ba"], precision=HIGHEST)
        beta = jax.nn.sigmoid(gates[..., :hv]).reshape(b, s, hk, r)
        g = (-jnp.exp(p["A_log"]) * jax.nn.softplus(
            gates[..., hv:] + p["dt_bias"])).reshape(b, s, hk, r)
    with device_span("matcha/gdn_conv"):
        q, k, v = again(functools.partial(_conv_qkv, hk=hk, dk=dk))(
            mixed, p["conv"], docs)
    o, resets = _gated_delta_rule(q, k, v.reshape(b, s, hk, r, dv), beta, g,
                                  docs, sizes["gdn_chunk"], again)
    with device_span("matcha/gdn_gate_norm"):
        o = again(functools.partial(_gate_norm, eps=eps))(o, z, p["gdn_norm"])
    return jnp.dot(o, p["out_proj"]), {
        "gdn_chunks": jnp.float32(b * (s // sizes["gdn_chunk"])),
        "gdn_chunks_reset": resets.astype(jnp.float32),
        "gdn_gates": jnp.float32(b * s * hv),
        "gdn_decay_sum": jnp.sum(jnp.exp(g))}


# ---------------------------------------------------- gated full attention

def _partial_rope(x, cos, sin):
    """RoPE on the first ``2 cos.shape[-1]`` of ``x[B, S, heads, d]``'s last
    dimension; the rest pass."""
    rotary = 2 * cos.shape[-1]
    return jnp.concatenate([_rope(x[..., :rotary], cos, sin),
                            x[..., rotary:]], axis=-1)


def _attn_project(p, h, sizes):
    """``q[B, S, kv, group, d]``, ``k``, ``v[B, S, kv, d]`` (normed a head,
    RoPE applied) and the output gate ``[B, S, heads x d]``."""
    b, s, _ = h.shape
    d, hq, hkv = sizes["head_dim"], sizes["q_heads_held"], \
        sizes["kv_heads_held"]
    eps = sizes["rms_norm_eps"]
    x = _norm0(h, p["attn_norm"], eps)
    cos, sin = rope_tables(s, sizes["rotary_dim"], sizes["rope_theta"])
    query, gate = jnp.split(jnp.dot(x, p["wq"]).reshape(b, s, hq, 2 * d), 2,
                            axis=-1)
    q = _partial_rope(_norm0(query, p["q_norm"], eps), cos, sin)
    k = _partial_rope(_norm0(jnp.dot(x, p["wk"]).reshape(b, s, hkv, d),
                             p["k_norm"], eps), cos, sin)
    v = jnp.dot(x, p["wv"]).reshape(b, s, hkv, d)
    return (q.reshape(b, s, hkv, hq // hkv, d), k, v,
            gate.reshape(b, s, hq * d))


def _query_block(q, q_docs, k, v, k_docs, *, start):
    """Query positions ``[start, start + Q)`` against the keys ``[0, start +
    Q)``: the heads' outputs ``[B, Q, heads x d]``."""
    b, block = q.shape[:2]
    stop = start + block
    sees = _visible(jnp.arange(start, stop), jnp.arange(stop), q_docs, k_docs,
                    None)
    scores = jnp.einsum("bikgd,bjkd->bkgij", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(sees[:, None, None], scores.astype(jnp.float32),
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgij,bjkd->bikgd", probs, v).reshape(b, block, -1)


def _row_attention(q, k, v, docs, sizes):
    s = docs.shape[1]
    block = sizes.get("attn_block", 1024)  # a test seam: blocks at S = 32
    if s % block:
        block = s
    outs = []
    for start in range(0, s, block):
        at, stop = slice(start, start + block), start + block
        outs.append(jax.checkpoint(
            functools.partial(_query_block, start=start))(
                q[:, at], docs[:, at], k[:, :stop], v[:, :stop],
                docs[:, :stop]))
    return jnp.concatenate(outs, axis=1)


def _gated_attention(p, h, docs, sizes, again):
    """The full layer's output ``[B, S, H]`` of the residual stream ``h``,
    one row after another."""
    b, s, _ = h.shape
    with device_span("matcha/attn_full_gated"):
        q, k, v, gate = again(functools.partial(_attn_project, sizes=sizes))(
            p, h)
        out = lax.map(lambda row: _row_attention(
            *(a[None] for a in row), sizes)[0], (q, k, v, docs))
        # graftlint: disable=GL001 — a sigmoid weight in (0, 1) on finite
        # values, the model's output gate: no mask
        return jnp.dot(out * jax.nn.sigmoid(gate), p["wo"])


# ------------------------------------------------------------ expert layer

def _shared_expert(p, x):
    """``sigmoid(x w_sg) SwiGLU_shared(x)``: whole on every chip."""
    shared = _swiglu(x, {"gate": p["shared_gate"], "up": p["shared_up"],
                         "down": p["shared_down"]}, jnp.dot)
    return jax.nn.sigmoid(jnp.dot(x, p["shared_sigmoid"]))[..., None] * shared


def _experts_of(p, h, sizes):
    """(the routed experts held's part plus the shared expert, the
    counters)."""
    x = _norm0(h, p["moe_norm"], sizes["rms_norm_eps"])
    y, counters = _moe(p, x, sizes)
    with device_span("matcha/moe_shared"):
        y = y + _shared_expert(p, x)
    return y, counters


#: ``jax.checkpoint`` as the checkpoints inside a Gated DeltaNet layer run it
#: (the chunk systems' is the one that holds the name)
_again_keeping = functools.partial(
    jax.checkpoint,
    policy=jax.checkpoint_policies.save_only_these_names(KEPT))


def _block(p, h, docs, kind, sizes, remat):
    again = checkpointed(remat)
    if kind == "linear":
        # the layer's own checkpoint keeps nothing of it; those inside it
        # keep what is named ``KEPT``
        out, counters = again(functools.partial(
            _gated_delta_net, sizes=sizes,
            again=_again_keeping if remat else again))(p, h, docs)
    else:
        out, counters = _gated_attention(p, h, docs, sizes, again), {}
    h = h + out
    y, moe = again(functools.partial(_experts_of, sizes=sizes))(p, h)
    return h + y, {**counters, **moe}


def _a_log(key, shape, dtype=jnp.float32):
    """``log(A)``, ``A`` uniform over (0, 16]."""
    return jnp.log(16.0 * (1.0 - jax.random.uniform(key, shape, dtype)))


def mixer_weights(kind, z) -> dict:
    """``{name: shape | (init, shape)}`` of a layer's sequence mixer, in the
    order they are declared."""
    hid, zeros, ones = z["hidden"], nn.initializers.zeros, nn.initializers.ones
    if kind == "full":
        d, hq, hkv = z["head_dim"], z["q_heads_held"], z["kv_heads_held"]
        return {"attn_norm": (zeros, (hid,)), "wq": (hid, 2 * hq * d),
                "wk": (hid, hkv * d), "wv": (hid, hkv * d),
                "q_norm": (zeros, (d,)), "k_norm": (zeros, (d,)),
                "wo": (hq * d, hid)}
    hk, hv = z["linear_key_heads_held"], z["linear_value_heads_held"]
    key, value = hk * z["linear_key_dim"], hv * z["linear_value_dim"]
    half = 1.0 / math.sqrt(z["conv_kernel"])
    return {"attn_norm": (zeros, (hid,)),
            "in_proj_qkvz": (hid, 2 * key + 2 * value),
            "in_proj_ba": (hid, 2 * hv),
            "conv": (lambda rng, shape, dtype=jnp.float32: jax.random.uniform(
                rng, shape, dtype, -half, half),
                     (z["conv_kernel"], 2 * key + value)),
            "dt_bias": (ones, (hv,)), "A_log": (_a_log, (hv,)),
            "gdn_norm": (ones, (z["linear_value_dim"],)),
            "out_proj": (value, hid)}


class Qwen3Next(TokenDecoder):
    """``sizes`` as in ``chipbench/configs/qwen3-next-80b-a3b.ep64-s8k.json``
    (README "Training a language model" lists the keys)."""

    norm = staticmethod(_norm0)
    norm_init = staticmethod(nn.initializers.zeros)

    @property
    def expert_layers(self):
        return len(layer_kinds(self.sizes))

    def setup(self):
        z = self.sizes
        hid, width = z["hidden"], z["shared_expert_width"]
        experts = {**expert_weights(z, nn.initializers.zeros),
                   "shared_gate": (hid, width), "shared_up": (hid, width),
                   "shared_down": (width, hid), "shared_sigmoid": (hid,)}
        self.declare([{**mixer_weights(kind, z), **experts}
                      for kind in layer_kinds(z)])

    @property
    def remat_keeps(self):
        """What the expert layers' checkpoints keep by name, and the chunk
        systems' beside it: the journal's ``fwd_bwd`` event carries it
        (``train/state.py:fwd_bwd_plan``)."""
        linear = "linear" in layer_kinds(self.sizes)
        return super().remat_keeps + (
            (KEPT,) if self.remat and linear else ())

    def dummy_input(self, input_shape):
        """What ``init`` traces: one row of one whole chunk."""
        return jnp.zeros((1, self.sizes["gdn_chunk"]), jnp.int32)

    def hidden(self, ids, docs):
        """(the final norm's output ``[B, S, H]``, the layers' counters
        summed, ``moe_load[layer, expert held]``)."""
        with device_span("matcha/lm_embed"):
            h = self.embed[ids]
        counters = []
        for p, kind in zip(self.layers, layer_kinds(self.sizes)):
            h, c = _block(p, h, docs, kind, self.sizes, self.remat)
            counters.append(c)
        h, total = self.normed(h, counters)
        linear = [c for c in counters if "gdn_chunks" in c]
        total.update({k: sum(c[k] for c in linear) for k in GDN_COUNTERS})
        return h, total

    def batch_loss(self, x_raw, y_raw):
        """``x_raw``/``y_raw`` as ``Mellum2.batch_loss`` takes them.  Returns
        (the mean over judged positions of the next id's cross-entropy,
        ``{"accuracy", "counters"}``)."""
        ids, docs, targets = _next_ids(x_raw, y_raw)
        h, counters = self.hidden(ids, docs)
        loss, accuracy, counters["loss_positions"] = _head_loss(
            h, self.head, targets, self.sizes)
        return loss, {"accuracy": accuracy, "counters": counters}
