"""One chip's share of a Qwen3-Next-style hybrid decoder: Gated DeltaNet
(linear attention) and gated softmax attention 3:1, a sparse expert layer
with a shared expert, zero-centred RMSNorm, an untied head; no bias
anywhere.  Written from the configuration's equations
(``chipbench/configs/qwen3-next-80b-a3b.ep64-s8k.json``), not from the
program.

``x`` is ``next_token.prepare``'s ``{"ids", "docs"}`` of ``[B, S]``; the
output is float32 logits ``[B, S, vocab_held]``.

``norm0(x, w) = x / sqrt(mean(x^2) + eps) * (1 + w)``.  Block: ``h = x +
Mixer(norm0(x))``, ``x' = h + MoE(norm0(h))``; layer ``i`` is full attention
where ``(i + 1) % full_attention_interval == 0``, else Gated DeltaNet.

* Gated DeltaNet, a row, ``Hk`` key heads of ``dk``, ``Hv`` value heads of
  ``dv`` (value head ``j`` uses key head ``j // (Hv / Hk)``): ``q, k, v, z =
  split(x W_qkvz)``; ``b, a = split(x W_ba)``; ``(q, k, v) <-
  silu(conv(concat(q, k, v)))``, a causal depthwise convolution of
  ``conv_kernel`` taps, four shifted adds, a tap that would reach before the
  token's own document reading 0; ``beta_t = sigmoid(b_t)``, ``g_t =
  -exp(A_log) softplus(a_t + dt_bias)``; ``q <- l2norm(q) / sqrt(dk)``, ``k
  <- l2norm(k)`` (eps 1e-6 under the root); then **token by token** (a
  ``lax.scan`` over the row's positions, no chunks), with ``S[dk, dv]`` 0 at
  each document's first token: ``S <- exp(g_t) S``; ``r = S^T k_t``; ``u =
  beta_t (v_t - r)``; ``S <- S + k_t u^T``; ``o_t = S^T q_t``; the output is
  ``concat_j(o_j / sqrt(mean(o_j^2) + eps) * w_norm * silu(z_j)) W_out``.
* Gated full attention: ``wq`` gives ``2 head_dim`` a head, query then gate;
  ``q = norm0(query)``, ``k = norm0(x W_k)`` a head; RoPE (rotate-half pairs
  ``(i, i + rotary_dim / 2)``) on the first ``rotary_dim`` of a head's
  dimensions; softmax of ``q . k / sqrt(head_dim)`` over ``s <= t`` of the
  same document; ``(P v) sigmoid(gate)`` through ``wo``.
* Expert layer: float32 softmax over all router outputs, the
  ``experts_per_token`` largest, renormalised; SwiGLU experts; plus
  ``sigmoid(x w_sg) SwiGLU_shared(x)``.

The chip holds ``linear_key_heads_held`` key heads with the value heads that
use them, ``q_heads_held`` query heads that share its ``kv_heads_held`` KV
heads, the experts ``experts_held`` of ``num_experts``, a slice of the
vocabulary, and the router, the shared expert and the norms whole.  What
absent experts and heads would add is left out.

Kept plain: every expert held on every token under a 0/1 mask, whole-row
keys for every query, the recurrence a token at a time.  The concessions are
to memory and change no number: a layer runs a row at a time (``lax.map``)
and is recomputed in the backward pass; a row's queries go
``REFERENCE_BLOCK`` at a time in a Python loop, each block recomputed; the
recurrence's scan is cut into ``REFERENCE_SEGMENT`` positions, each segment
recomputed, so that the backward pass holds a segment's states and not the
row's.  Parameter names are the program's.  Projections, expert products,
attention scores and values and the head are at ``ops.precision``; the
router's product, the gates' projection ``W_ba`` and the whole recurrence
are float32 at ``highest`` whatever ``ops`` says; softmaxes, norms, RoPE and
the gates are float32.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

REFERENCE_BLOCK = 1024
REFERENCE_SEGMENT = 64
HIGHEST = lax.Precision.HIGHEST


def norm0(x, w, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * (1.0 + w)


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + 1e-6)


def is_full(layer, sizes):
    return (layer + 1) % sizes["full_attention_interval"] == 0


def conv_silu(x, taps, docs):
    """``x[S, C]`` through the causal depthwise convolution ``taps[K, C]``
    (``taps[K - 1]`` meets the token itself) and a SiLU."""
    s, kernel = x.shape[0], taps.shape[0]
    t = jnp.arange(s)
    y = jnp.zeros_like(x)
    for back in range(kernel):
        source = jnp.maximum(t - back, 0)
        reaches = (t - back >= 0) & (docs[source] == docs)
        y = y + jnp.where(reaches[:, None], x[source], 0.0) \
            * taps[kernel - 1 - back]
    return jax.nn.silu(y)


def delta_rule(q, k, v, beta, g, docs):
    """The recurrence over one row: ``q``, ``k[S, Hv, dk]``, ``v[S, Hv, dv]``,
    ``beta``, ``g[S, Hv]``, ``docs[S]``; returns ``o[S, Hv, dv]``."""
    s = docs.shape[0]
    starts = jnp.concatenate([jnp.ones((1,), bool), docs[1:] != docs[:-1]])

    def token(state, at):
        q_t, k_t, v_t, beta_t, g_t, start = at
        state = jnp.where(start, 0.0, state)
        state = jnp.exp(g_t)[:, None, None] * state
        r = jnp.einsum("hde,hd->he", state, k_t, precision=HIGHEST)
        u = beta_t[:, None] * (v_t - r)
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hde,hd->he", state, q_t, precision=HIGHEST)

    segment = REFERENCE_SEGMENT if s % REFERENCE_SEGMENT == 0 else s
    cut = lambda a: a.reshape((s // segment, segment) + a.shape[1:])
    state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    _, o = lax.scan(jax.checkpoint(lambda st, xs: lax.scan(token, st, xs)),
                    state, tuple(cut(a) for a in (q, k, v, beta, g, starts)))
    return o.reshape((s,) + o.shape[2:])


def gated_delta_net(p, name, x, docs, sizes, ops):
    """One row ``x[S, H]`` (normed), ``docs[S]``: the layer's output."""
    s = x.shape[0]
    hk, hv = sizes["linear_key_heads_held"], sizes["linear_value_heads_held"]
    dk, dv = sizes["linear_key_dim"], sizes["linear_value_dim"]
    mm = lambda a, m: jnp.einsum("sh,hk->sk", a, m, precision=ops.precision)
    qkvz = mm(x, p[name + "_in_proj_qkvz"])
    ba = jnp.einsum("sh,hk->sk", x, p[name + "_in_proj_ba"],
                    precision=HIGHEST)
    key, value = hk * dk, hv * dv
    mixed = conv_silu(qkvz[:, :2 * key + value], p[name + "_conv"], docs)
    z = qkvz[:, 2 * key + value:].reshape(s, hv, dv)
    q = l2norm(mixed[:, :key].reshape(s, hk, dk)) / math.sqrt(dk)
    k = l2norm(mixed[:, key:2 * key].reshape(s, hk, dk))
    v = mixed[:, 2 * key:].reshape(s, hv, dv)
    # value head j uses key head j // (hv / hk)
    q = jnp.repeat(q, hv // hk, axis=1)
    k = jnp.repeat(k, hv // hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p[name + "_A_log"]) * jax.nn.softplus(
        ba[:, hv:] + p[name + "_dt_bias"])
    o = delta_rule(q, k, v, beta, g, docs)
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                     + sizes["rms_norm_eps"]) * p[name + "_gdn_norm"]
    return mm((o * jax.nn.silu(z)).reshape(s, value), p[name + "_out_proj"])


def partial_rope(x, theta, rotary):
    """``x[S, heads, d]``: the first ``rotary`` dimensions rotated by the
    position, pairs ``(i, i + rotary / 2)``; the rest pass."""
    s = x.shape[0]
    inv_freq = float(theta) ** (-2.0 * jnp.arange(
        rotary // 2, dtype=jnp.float32) / rotary)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary:]], axis=-1)


def gated_attention(p, name, x, docs, sizes, ops):
    """One row ``x[S, H]`` (normed), ``docs[S]``: the layer's output."""
    s = x.shape[0]
    d, hq, hkv = sizes["head_dim"], sizes["q_heads_held"], \
        sizes["kv_heads_held"]
    eps, theta, rotary = sizes["rms_norm_eps"], sizes["rope_theta"], \
        sizes["rotary_dim"]
    mm = lambda a, m: jnp.einsum("sh,hk->sk", a, m, precision=ops.precision)
    both = mm(x, p[name + "_wq"]).reshape(s, hq, 2 * d)
    q = partial_rope(norm0(both[..., :d], p[name + "_q_norm"], eps), theta,
                     rotary)
    gate = both[..., d:]
    k = partial_rope(norm0(mm(x, p[name + "_wk"]).reshape(s, hkv, d),
                           p[name + "_k_norm"], eps), theta, rotary)
    v = mm(x, p[name + "_wv"]).reshape(s, hkv, d)
    # query head g uses KV head g // (hq / hkv)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    block = REFERENCE_BLOCK if s % REFERENCE_BLOCK == 0 else s
    j = jnp.arange(s)[None, :]

    def queries(q, q_docs, i):
        """The queries at positions ``i[block, 1]`` against the whole row."""
        sees = (j <= i) & (q_docs[:, None] == docs[None, :])
        scores = jnp.einsum("ihd,jhd->hij", q, k,
                            precision=ops.precision) / math.sqrt(d)
        scores = jnp.where(sees[None], scores.astype(jnp.float32), -jnp.inf)
        return jnp.einsum("hij,jhd->ihd", jax.nn.softmax(scores, axis=-1), v,
                          precision=ops.precision)

    outs = []
    for start in range(0, s, block):
        at = slice(start, start + block)
        # recomputed in the backward pass: one block's scores are held
        outs.append(jax.checkpoint(queries)(
            q[at], docs[at], jnp.arange(start, start + block)[:, None]))
    out = jnp.concatenate(outs, axis=0) * jax.nn.sigmoid(gate)
    return mm(out.reshape(s, hq * d), p[name + "_wo"])


def experts(p, name, h, sizes, ops):
    mm = lambda a, m: jnp.einsum("sh,hf->sf", a, m, precision=ops.precision)
    swiglu = lambda gate, up, down: mm(
        jax.nn.silu(mm(h, gate)) * mm(h, up), down)
    r = jnp.einsum("sh,he->se", h, p[name + "_router"], precision=HIGHEST)
    prob = jax.nn.softmax(r.astype(jnp.float32), axis=-1)
    top, sel = lax.top_k(prob, sizes["experts_per_token"])
    w = top / jnp.sum(top, axis=-1, keepdims=True) \
        if sizes["norm_topk_prob"] else top
    y = jax.nn.sigmoid(mm(h, p[name + "_shared_sigmoid"][:, None])) * swiglu(
        p[name + "_shared_gate"], p[name + "_shared_up"],
        p[name + "_shared_down"])
    for slot, e in enumerate(sizes["experts_held"]):
        # the weight of expert e at each token: 0 where it was not chosen
        w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * swiglu(p[name + "_gate"][slot],
                                      p[name + "_up"][slot],
                                      p[name + "_down"][slot])
    return y


def forward(p, stats, x, sizes, ops):
    eps = sizes["rms_norm_eps"]
    h = p["embed"][x["ids"]]
    for n in range(sizes["num_layers"]):
        blk = f"layer{n}"
        mixer = gated_attention if is_full(n, sizes) else gated_delta_net

        def layer(row, blk=blk, mixer=mixer):
            h, docs = row
            h = h + mixer(p, blk, norm0(h, p[blk + "_attn_norm"], eps), docs,
                          sizes, ops)
            return h + experts(p, blk, norm0(h, p[blk + "_moe_norm"], eps),
                               sizes, ops)

        # a row at a time, and recomputed in the backward pass: the same
        # numbers as the whole batch at once, in a fraction of the memory
        h = lax.map(jax.checkpoint(layer), (h, x["docs"]))
    h = norm0(h, p["final_norm"], eps)
    logits = jnp.einsum("bsh,hv->bsv", h, p["head"], precision=ops.precision)
    return logits.astype(jnp.float32), {}


def forward_macs(sizes) -> int:
    """Multiply-accumulates of one row's forward pass, from the shapes alone.
    A Gated DeltaNet layer: its projections held (``W_qkvz``, ``W_ba``,
    ``W_out``), the convolution's taps a channel, and the recurrence **as
    the recurrent form needs it**, ``3 dk dv`` a token and value head (read
    ``S^T k``, write ``k u^T``, query ``S^T q``), whatever a chunked program
    spends on its in-chunk systems.  The full layer: its projections (``wq``
    doubled for the gate) and the causal pairs of a row at ``2 heads x
    head_dim`` a pair (**the document mask is not counted off**, as in
    ``reference/mellum2.py``).  Every layer: the router over all experts,
    the shared expert whole with its gate, and the routed experts held at
    their expected load (``experts_per_token x held / num_experts`` slots a
    token: 0.156 at 10 x 8 / 512).  The head.  Lookups, norms, gates, RoPE
    and the decay are no matrix product and count nothing."""
    s, h = sizes["seq_len"], sizes["hidden"]
    hk, hv = sizes["linear_key_heads_held"], sizes["linear_value_heads_held"]
    dk, dv = sizes["linear_key_dim"], sizes["linear_value_dim"]
    d, hq, hkv = sizes["head_dim"], sizes["q_heads_held"], \
        sizes["kv_heads_held"]
    conv = 2 * hk * dk + hv * dv
    linear = s * h * (conv + hv * dv + 2 * hv)  # W_qkvz, W_ba
    linear += s * hv * dv * h  # W_out
    linear += s * conv * sizes["conv_kernel"]
    linear += s * hv * 3 * dk * dv
    full = s * h * d * (2 * hq + 2 * hkv) + s * hq * d * h
    full += 2 * hq * d * (s * (s + 1) // 2)
    slots = sizes["experts_per_token"] * len(sizes["experts_held"]) \
        / sizes["num_experts"]
    moe = s * h * sizes["num_experts"]
    moe += s * (3 * h * sizes["shared_expert_width"] + h)
    moe += int(s * slots * 3 * h * sizes["expert_width"])
    kinds = [is_full(n, sizes) for n in range(sizes["num_layers"])]
    return sum(full if k else linear for k in kinds) + len(kinds) * moe \
        + s * h * sizes["vocab_held"]
