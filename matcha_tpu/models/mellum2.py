"""One chip's share of a Mellum2-style sparse decoder, for next-token
training through the same ``train()`` as the image models.

Pre-norm blocks ``h = x + Attn(RMSNorm(x))``, ``x' = h + MoE(RMSNorm(h))``;
grouped-query attention that is sliding-window or full by ``layer_types``
(plain RoPE on sliding layers, YaRN on full ones), masked to the row's own
documents; a softmax router over all ``num_experts`` that keeps
``experts_per_token`` and, with ``norm_topk_prob``, renormalises their
weights; SwiGLU experts; a final RMSNorm and an untied head; no bias.

**The share.**  ``sizes`` says what this chip holds of each layer:
``q_heads_held`` query heads with the ``kv_heads_held`` KV heads they use,
the experts ``experts_held`` (ids among the ``num_experts`` the router
scores), ``vocab_held`` ids of the vocabulary.  The expert layer routes
over all experts and computes its own experts' part of the sum; what the
absent ones would add is left out and the partial sum goes on
(``tests/test_mellum2.py`` adds the shares of 8 chips back up).

**The expert layer drops nothing.**  The slots that landed on an expert
held are laid out sorted by expert in ``moe_capacity`` rows
(``ROWS_PER_EVEN_SLOT`` times what an even router would send here, or
``sizes["moe_rows_per_even_slot"]`` times where a configuration says so) and
three grouped products run over them (gate, up, down; with their two
gradients each, nine a layer): the cost follows the number of slots, not the
busiest expert, which matters because a row's tokens route alike (one expert
held took 3.7 times its even share of a step at the published widths).  On
the TPU the products are ``ops/grouped.py``'s kernels (``moe_gmm``, its
transposed-weights form, ``moe_tgmm``: tiles of 512 rows by the whole of
both widths at every benchmark shape, 32,768 x 2,304 x 896, 32,768 x 2,048 x
768 and 10,240 x 2,048 x 512; six kernel sites a program), on bfloat16
operands rounded here, float32 sums; where rows or a width do not tile (rows
not in 128s, a width not in whole lanes: the test sizes) and anywhere off
the TPU they are ``jax.lax.ragged_dot``, and the journal's ``backend`` event
says which and why (:func:`expert_products`).  Slots past the rows go through
every expert held under a 0/1 mask, in a branch that runs only in a step that
has such slots.
**A layer's rows are dispatched once.**  The tokens ``[T, H]`` are rounded to
bfloat16 first and their rows gathered after, at two bytes an element, and
gate and up read that one operand (:func:`_dispatched_bf16`): rounding
commutes with a gather, so the kernels read the values they would of float32
rows rounded after, and nothing passes over ``[rows, H]`` to round it.  The
rounding sits before the gather behind a rule of its own because the way back
must not be rounded: the two data gradients are summed and scatter-added into
``[T, H]`` in float32, where a token in several slots sums them.  The layout
is one scatter of whole numbers (the slot a row holds) and a gather of the
weights by it.  The gathered rows (151 MB a layer at 32,768 x 2,304) and the
layout's small arrays carry names (``MOE_KEPT``), and a model wraps its layers
in :func:`checkpointed`, which under ``remat`` keeps those and computes the
rest again: the second pass is gate, up, SwiGLU and down over kept rows (down
too: its output meets the cotangent in the router's gradient), no layout, no
gather, no rounding of the rows.
**Rows that hold no slot are in no group.**  The groups are the slots' own
counts and sum to the rows or less; the kernels' grids visit the row tiles a
group touches and stop at the last (a quarter to a half of the rows hold a
slot at the benchmark's sizes), and what a product leaves on a row in no
group (anything, on the kernels) leaves the layer by an index out of bounds.
Shapes are static either way, and the counters say what it cost:
``moe_rows_computed`` rows were laid out, gathered, passed through SwiGLU and
scatter-added for ``moe_slots_held`` slots that were real, and
``moe_rows_multiplied`` of them lay in a row tile the products visited (all
of them where ``lax.ragged_dot`` runs the products).

**The model supplies its loss** (:meth:`Mellum2.batch_loss`, which
``train/state.py`` calls in place of cross-entropy over one label a row):
the mean over judged positions of float32 softmax cross-entropy of the next
id, where a position is judged unless the next id starts a new document.
It is computed a chunk of positions at a time under ``jax.checkpoint``, so
no ``[B, S, V]`` array of all positions exists.

Matrix products run at the MXU's default precision, but the router's, which
decides a discrete choice, at ``highest``; norms, RoPE, softmaxes and the
loss are float32.

**What the token models share** lives here: :class:`TokenDecoder` (the
embedding, the final norm, the untied head, the counters' sums), the expert
layer, the head and its loss.  ``TokenDecoder.declare`` takes one parameter
set a layer (:func:`attention_weights`, :func:`expert_weights` are this
model's), so a model's layers may be of kinds with different ones
(``models/qwen3_next.py``: linear and softmax attention 3:1).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..data.datasets import judged_positions
from ..ops.grouped import (FORMS, grouped_dot, grouped_dot_transposed,
                           grouped_outer, product_plan)
from ..utils.profiling import device_span

__all__ = ["Mellum2", "TokenDecoder", "rope_inv_freq", "rope_tables",
           "moe_capacity", "attention_weights", "expert_weights",
           "checkpointed", "MOE_KEPT"]

INIT_STD = 0.02
#: rows of the grouped expert products over the slots an even router would
#: send to the experts held: a layer took up to 1.41 times its even total of
#: a step at the published widths (PERF.md section 6, PR 27)
ROWS_PER_EVEN_SLOT = 2
#: what the expert layer counts beside ``moe_load``, summed over the layers
MOE_COUNTERS = ("moe_slots_held", "moe_rows_computed",
                "moe_rows_multiplied")
#: what the expert layer names for the checkpoint around it to keep
#: (:func:`checkpointed`): the rows gathered for the products, as the
#: products read them, and the layout's small arrays (``fits``, ``slot``,
#: ``w_rows``, ``groups``), so that a recomputed layer dispatches nothing
MOE_ROWS, MOE_LAYOUT = MOE_KEPT = ("moe_rows", "moe_layout")


def checkpointed(remat: bool):
    """What a token model wraps a layer, or a part of one, in: under
    ``remat`` a ``jax.checkpoint`` that keeps, of all it computes, what the
    expert layer names (``MOE_KEPT``); otherwise nothing."""
    if not remat:
        return lambda f: f
    return functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(*MOE_KEPT))


def rope_inv_freq(kind: str, sizes):
    """(``inv_freq[head_dim / 2]``, the factor on cos and sin) of a layer
    kind: ``theta^(-2i/d)`` on sliding layers; on full layers YaRN's blend
    of that with itself over ``factor``, by a ramp between the dimensions
    that turn ``beta_fast`` and ``beta_slow`` times in the original
    context."""
    d, theta = sizes["head_dim"], float(sizes["rope_theta"])
    i = jnp.arange(d // 2, dtype=jnp.float32)
    extrap = theta ** (-2.0 * i / d)
    if kind == "sliding":
        return extrap, 1.0
    yarn = sizes["yarn"]

    def turns(rotations):
        return d * math.log(yarn["original_max_position_embeddings"]
                            / (2 * math.pi * rotations)) / (
                                2 * math.log(theta))

    low = max(math.floor(turns(yarn["beta_fast"])), 0)
    high = min(math.ceil(turns(yarn["beta_slow"])), d - 1)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (extrap / yarn["factor"] * ramp + extrap * (1.0 - ramp),
            yarn["attention_factor"])


def moe_capacity(tokens: int, sizes) -> int:
    """Rows the grouped expert products run over in a step:
    ``ROWS_PER_EVEN_SLOT`` times the slots an even router would send to the
    experts held (``sizes["moe_rows_per_even_slot"]`` times, where a
    configuration whose share is small enough to swing further gives it),
    rounded up to 8 rows and never more than every slot there can be."""
    held = len(sizes["experts_held"])
    even = tokens * sizes["experts_per_token"] * held / sizes["num_experts"]
    per_even = sizes.get("moe_rows_per_even_slot", ROWS_PER_EVEN_SLOT)
    rows = math.ceil(per_even * even / 8) * 8
    return min(max(rows, 8), tokens * min(held, sizes["experts_per_token"]))


def _rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return scale * x * lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _rope(x, cos, sin):
    """``x[B, S, heads, d]``, rotate-half pairs ``(i, i + d/2)``."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def rope_tables(positions, head_dim, theta):
    """(cos, sin), each ``[len(positions), head_dim / 2]``, of plain RoPE at
    the float32 ``positions`` given, or at ``0..s-1`` where a length ``s`` is:
    a row's own positions, or whatever a model lays out (``models/sdar.py``:
    both copies of a row carry ``0..S-1``)."""
    inv_freq, _ = rope_inv_freq("sliding", {"head_dim": head_dim,
                                            "rope_theta": theta})
    if isinstance(positions, int):
        positions = jnp.arange(positions, dtype=jnp.float32)
    angle = positions[:, None] * inv_freq[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def _visible(q_pos, k_pos, q_docs, k_docs, window):
    """``[B, q, k]``: position ``i`` sees ``j`` iff ``j <= i``, both lie in
    one document and, on a sliding layer, ``i - j < window``."""
    sees = (k_pos[None, :] <= q_pos[:, None])[None] \
        & (q_docs[:, :, None] == k_docs[:, None, :])
    if window is not None:
        sees = sees & ((q_pos[:, None] - k_pos[None, :]) < window)[None]
    return sees


def _attention(p, h, docs, kind, sizes):
    """Grouped-query attention of ``h[B, S, H]`` (already normed), a block
    of query positions at a time against the keys its mask can reach."""
    b, s, _ = h.shape
    d, hq, hkv = sizes["head_dim"], sizes["q_heads_held"], \
        sizes["kv_heads_held"]
    group = hq // hkv
    q = jnp.dot(h, p["wq"]).reshape(b, s, hkv, group, d)
    k = jnp.dot(h, p["wk"]).reshape(b, s, hkv, d)
    v = jnp.dot(h, p["wv"]).reshape(b, s, hkv, d)
    inv_freq, factor = rope_inv_freq(kind, sizes)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    q = _rope(q.reshape(b, s, hq, d), cos, sin).reshape(b, s, hkv, group, d)
    k = _rope(k, cos, sin)

    window = sizes["sliding_window"] if kind == "sliding" else None
    block = sizes.get("attn_block", 1024)  # a test seam: blocks at S = 32
    if s % block:
        block = s
    pos = jnp.arange(s)
    outs = []
    for start in range(0, s, block):
        stop = start + block
        # the first key a query of this block can see, on a 128 boundary
        first = 0 if window is None else max(start - window + 1, 0) // 128 * 128
        scores = jnp.einsum("bikgd,bjkd->bkgij", q[:, start:stop],
                            k[:, first:stop]) / math.sqrt(d)
        sees = _visible(pos[start:stop], pos[first:stop],
                        docs[:, start:stop], docs[:, first:stop], window)
        scores = jnp.where(sees[:, None, None], scores.astype(jnp.float32),
                           -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("bkgij,bjkd->bikgd", probs, v[:, first:stop]))
    out = jnp.concatenate(outs, axis=1).reshape(b, s, hq * d)
    return jnp.dot(out, p["wo"])


def _route(p, x, sizes):
    """Router over all experts: (weights ``w[T, k]`` of the experts chosen,
    their ids ``sel[T, k]``)."""
    r = jnp.dot(x, p["router"], precision=lax.Precision.HIGHEST)
    prob = jax.nn.softmax(r.astype(jnp.float32), axis=-1)
    top, sel = lax.top_k(prob, sizes["experts_per_token"])
    if sizes["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return top, sel


def _one_bf16_pass() -> bool:
    """Whether a float32 product at the default precision is one bfloat16
    pass with float32 accumulation here, as it is on the TPU's MXU."""
    return jax.default_backend() == "tpu" and \
        jax.config.jax_default_matmul_precision in (None, "default",
                                                    "bfloat16")


@jax.custom_vjp
def _grouped_bf16(lhs, weights, groups):
    """The grouped product of float32 operands as one bfloat16 pass with
    float32 accumulation, in the backward products too: what a ``dot`` at
    the default precision is on the MXU.  Operands and the cotangent are
    rounded by hand (left float32, the cotangent makes half of the backward
    products float32 ones); what multiplies them is ``ops/grouped.py``: its
    kernels where the shapes tile, ``lax.ragged_dot`` where they do not."""
    return _grouped_bf16_fwd(lhs, weights, groups)[0]


def _grouped_bf16_fwd(lhs, weights, groups):
    lhs, weights = lhs.astype(jnp.bfloat16), weights.astype(jnp.bfloat16)
    return grouped_dot(lhs, weights, groups), (lhs, weights, groups)


def _grouped_bf16_bwd(kept, g):
    lhs, weights, groups = kept
    g = g.astype(jnp.bfloat16)
    return (grouped_dot_transposed(g, weights, groups),
            grouped_outer(lhs, g, groups),
            np.zeros(groups.shape, jax.dtypes.float0))


_grouped_bf16.defvjp(_grouped_bf16_fwd, _grouped_bf16_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatched_bf16(tokens, flat, token, weights, groups):
    """The grouped products of the rows ``flat[token]`` with each of
    ``weights`` (gate and up: one left operand), each as
    :func:`_grouped_bf16` runs one.  ``flat[T, H]`` is rounded to bfloat16
    once, its rows are gathered at two bytes an element and named
    ``MOE_ROWS``, and the products read them as they are: rounding commutes
    with a gather, so these are the values of gathering float32 rows and
    rounding them after.  The way back stays float32: the products' data
    gradients are summed and scatter-added into ``[T, H]`` unrounded (an
    ``astype`` before the gather would have autodiff round that cotangent to
    bfloat16 before the sum over a token's slots: another result).
    ``token[rows]`` is the token a row holds, ``tokens`` where it holds
    none: such a row reads the last token, is in no group and adds nothing
    back (whatever its products left on it is dropped by index)."""
    return _dispatched_bf16_fwd(tokens, flat, token, weights, groups)[0]


def _dispatched_bf16_fwd(tokens, flat, token, weights, groups):
    rows = checkpoint_name(
        flat.astype(jnp.bfloat16)[jnp.minimum(token, tokens - 1)], MOE_ROWS)
    weights = tuple(w.astype(jnp.bfloat16) for w in weights)
    return (tuple(grouped_dot(rows, w, groups) for w in weights),
            (rows, token, weights, groups))


def _dispatched_bf16_bwd(tokens, kept, gs):
    rows, token, weights, groups = kept
    gs = [g.astype(jnp.bfloat16) for g in gs]
    backs = [grouped_dot_transposed(g, w, groups)
             for g, w in zip(gs, weights)]
    back = sum(backs[1:], backs[0])
    return (jnp.zeros((tokens, rows.shape[1]), back.dtype).at[token].add(
                back, mode="drop"),
            np.zeros(token.shape, jax.dtypes.float0),
            tuple(grouped_outer(rows, g, groups) for g in gs),
            np.zeros(groups.shape, jax.dtypes.float0))


_dispatched_bf16.defvjp(_dispatched_bf16_fwd, _dispatched_bf16_bwd)


def _gate_and_up(flat, token, p, groups):
    """(gate's, up's) grouped products of the rows ``flat[token]`` (as
    :func:`_dispatched_bf16` reads ``token``), at the precision ``jnp.dot``
    has by default: on the TPU one bfloat16 pass (float32 operands would
    cost the grouped kernel several); anywhere else ``lax.ragged_dot`` of
    the float32 rows.  The gathered rows carry the name ``MOE_ROWS`` either
    way."""
    tokens = flat.shape[0]
    weights = (p["gate"], p["up"])
    if _one_bf16_pass():
        return _dispatched_bf16(tokens, flat, token, weights, groups)
    rows = checkpoint_name(flat[jnp.minimum(token, tokens - 1)], MOE_ROWS)
    return tuple(lax.ragged_dot(rows, w, groups) for w in weights)


def _grouped_product(lhs, weights, groups):
    """The grouped product of float32 ``lhs`` at the precision ``jnp.dot``
    has by default: :func:`_grouped_bf16` on the TPU, anywhere else
    ``lax.ragged_dot`` as it is."""
    if _one_bf16_pass():
        return _grouped_bf16(lhs, weights, groups)
    return lax.ragged_dot(lhs, weights, groups)


def expert_products(sizes, tokens: int, layers: int, remat: bool,
                    workers: int) -> dict:
    """What runs the grouped products of a step, for the journal's
    ``backend`` event: a record a form and shape (``ops/grouped.py:
    product_plan``: the kernel and its tiles, or ``lax.ragged_dot`` and the
    reason), with how many products of a step are of it (a layer has gate,
    up and down, each once forward, again where ``remat`` recomputes the
    layer (down too, though the second pass keeps its dispatch: the router's
    gradient reads its output; read off the compiled epoch program, PR 42),
    and once a gradient), the kernel sites an epoch program holds
    (one a form and shape) and the products that run on them.
    ``empty_rows`` says where the rows that hold no slot count: in no group,
    so a kernel's grid stops at the last tile that holds a slot (what it ran
    over is the counter ``moe_rows_multiplied``)."""
    rows = moe_capacity(tokens, sizes)
    hid, width = sizes["hidden"], sizes["expert_width"]
    passes = {"gmm": 2 if remat else 1, "gmm_transposed": 1, "tgmm": 1}
    products = []
    for form in FORMS:
        # (k, n) as the kernel tiles them: gate and up, then down
        for count, (k, n) in ((2, (hid, width)), (1, (width, hid))):
            if form == "gmm_transposed":
                k, n = n, k
            plan = (product_plan(form, rows, k, n, jnp.bfloat16, jnp.bfloat16)
                    if _one_bf16_pass() else
                    {"form": form, "rows": rows, "k": k, "n": n,
                     "kernel": "lax.ragged_dot", "reason": "float32 products "
                     "off the TPU's one-bfloat16-pass path"})
            products.append({**plan, "per_step":
                             count * passes[form] * layers * workers})
    on_kernel = [p for p in products if "tiles" in p]
    return {"products_per_step": sum(p["per_step"] for p in products),
            "on_kernel": sum(p["per_step"] for p in on_kernel),
            "kernel_sites": len(on_kernel), "empty_rows": "in no group",
            "products": products}


def _swiglu(x, p, product):
    """``x`` through SwiGLU experts, ``product(lhs, weights)`` saying which
    rows meet which expert."""
    inner = jax.nn.silu(product(x, p["gate"])) * product(x, p["up"])
    return product(inner, p["down"])


def _experts(p, x, w_held, took, sizes):
    """The experts held on the tokens that chose them: ``x[B, S, H]``,
    ``w_held[T, E]`` each token's weight on each expert held (0 where it
    did not choose it), ``took[T, E]`` who chose whom.  Returns (their
    weighted sum ``[B, S, H]``, ``{"moe_rows_computed": rows laid out, which
    every gather, SwiGLU pass and scatter-add runs over, "moe_rows_multiplied":
    rows of the row tiles the grouped products visit}``, each with every
    expert held on every token more in a step whose slots spill).

    The slots are laid out sorted by expert in ``rows`` rows (expert ``e``'s
    queue starts where the queues before it end), and three grouped
    products (:func:`_gate_and_up`, :func:`_grouped_product`; one group an
    expert) run over them, so the cost follows the slots and not the busiest
    expert.  Rows past the last slot are in no group: the kernels pass over
    the row tiles that hold only such rows, and what a product leaves on
    such a row is anything (``ops/grouped.py``, "Rows in no group";
    ``lax.ragged_dot`` leaves zeros).  None of it is kept: such a row holds
    token ``tokens`` and slot ``tokens x held``, both out of bounds, so
    the output's scatter-add and the two cotangents' (into the tokens, into
    ``w_held``) drop it by index, and everything between is row by row.
    The gathered rows and the layout's arrays carry the names ``MOE_KEPT``,
    which a checkpoint around the layer keeps (:func:`checkpointed`)."""
    b, s, hidden = x.shape
    tokens, held = took.shape
    flat = x.reshape(tokens, hidden)
    rows = moe_capacity(tokens, sizes)
    count = jnp.sum(took, axis=0)
    start = jnp.cumsum(count) - count  # where each expert's queue starts
    place = start[None, :] + jnp.cumsum(took, axis=0) - 1
    layout = lambda a: checkpoint_name(a, MOE_LAYOUT)
    fits = layout(took & (place < rows))
    # the slot ``token x held + expert`` each row holds, by one scatter of
    # whole numbers (``rows`` is out of bounds: dropped); a row that holds
    # none reads ``tokens x held``: token ``tokens``, weight 0
    slot = layout(jnp.full((rows,), tokens * held, jnp.int32).at[
        jnp.where(fits, place, rows)].set(
            jnp.arange(tokens * held, dtype=jnp.int32).reshape(tokens, held),
            mode="drop"))
    token = slot // held
    w_rows = layout(w_held.reshape(-1).at[slot].get(mode="fill",
                                                    fill_value=0))
    ends = jnp.minimum(start + count, rows)
    groups = layout((ends - jnp.minimum(start, rows)).astype(jnp.int32))
    gate_rows, up_rows = _gate_and_up(flat, token, p, groups)
    y_rows = _grouped_product(jax.nn.silu(gate_rows) * up_rows, p["down"],
                              groups)
    y = jnp.zeros_like(flat).at[token].add(y_rows * w_rows[:, None],
                                          mode="drop")

    # slots past the rows: every expert held on every token under the
    # mask, a row of the batch at a time, and only in a step that has such
    # slots
    w_rest = jnp.where(took & ~fits, w_held, 0.0)

    @jax.checkpoint
    def every_expert(row):
        x_row, w_row = row
        each = _swiglu(jnp.broadcast_to(x_row, (held,) + x_row.shape), p,
                       lambda lhs, weights: jnp.einsum(
                           "erh,ehf->erf", lhs, weights))
        return jnp.einsum("eth,te->th", each, w_row)

    overflow = jnp.sum(count) > rows
    y = y.reshape(b, s, hidden) + lax.cond(
        overflow,
        lambda: lax.map(every_expert, (x, w_rest.reshape(b, s, held))),
        lambda: jnp.zeros_like(x))
    past = jnp.where(overflow, held * tokens, 0)
    # the slots fill the rows from the first, so the row tiles a group
    # touches are those up to the last slot's (one ``tm`` for the six
    # shapes: it follows the rows); ``lax.ragged_dot`` multiplies every row
    tiles = product_plan("gmm", rows, hidden, p["gate"].shape[2],
                         jnp.bfloat16, jnp.bfloat16).get("tiles") \
        if _one_bf16_pass() else None
    visited = -(-jnp.sum(groups) // tiles[0]) * tiles[0] if tiles else rows
    return y, {"moe_rows_computed": (rows + past).astype(jnp.float32),
               "moe_rows_multiplied": (visited + past).astype(jnp.float32)}


def _moe(p, x, sizes, marked=None):
    """(the experts held's part of the layer's output, its counters).  With
    ``marked[B, S]`` (positions a model wants told apart: ``models/sdar.py``
    marks the masked ones) the counters also hold ``moe_slots_marked``, the
    slots held that a marked position sent."""
    b, s, hidden = x.shape
    with device_span("matcha/moe_route"):
        w, sel = _route(p, x.reshape(b * s, hidden), sizes)
        chosen = sel[:, :, None] == jnp.asarray(
            sizes["experts_held"], sel.dtype)[None, None, :]
        w_held = jnp.sum(jnp.where(chosen, w[:, :, None], 0.0), axis=1)
        took = jnp.any(chosen, axis=1)
    with device_span("matcha/moe_experts"):
        y, rows_run = _experts(p, x, w_held, took, sizes)
    load = jnp.sum(took, axis=0).astype(jnp.float32)
    counters = {"moe_slots_held": jnp.sum(load), **rows_run,
                "moe_load": load}
    if marked is not None:
        counters["moe_slots_marked"] = jnp.sum(
            took & marked.reshape(b * s, 1)).astype(jnp.float32)
    return y, counters


def _block(p, h, docs, kind, sizes):
    eps = sizes["rms_norm_eps"]
    with device_span(f"matcha/attn_{'window' if kind == 'sliding' else 'full'}"):
        h = h + _attention(p, _rms_norm(h, p["attn_norm"], eps), docs, kind,
                           sizes)
    y, counters = _moe(p, _rms_norm(h, p["moe_norm"], eps), sizes)
    return h + y, counters


def _next_ids(x_raw, y_raw):
    """(ids, document numbers, the next id or -1 where it starts a new
    document and is not judged), each ``[B, S]``, of raw rows ``[B, S + 1]``."""
    ids, docs = x_raw[:, :-1], y_raw[:, :-1]
    return ids, docs, jnp.where(y_raw[:, 1:] == docs, x_raw[:, 1:], -1)


def _head_loss(h, head, targets, sizes, weights=None, normaliser=None):
    """The untied head and its loss over ``h[B, S, H]``, a chunk of positions
    at a time under ``jax.checkpoint``: (the mean over judged positions of
    float32 softmax cross-entropy, token accuracy over them, how many).
    ``targets`` is -1 where a position is not judged.  With ``weights[B, S]``
    a judged position's cross-entropy counts that many times, and with a
    ``normaliser`` the sum is divided by it and not by the number judged
    (``models/sdar.py``: ``1 / t`` a masked position, over every token of the
    batch)."""
    b, s, _ = h.shape
    chunk = sizes.get("loss_chunk", 1024)  # a test seam, as ``attn_block``
    if s % chunk:
        chunk = s

    @jax.checkpoint
    def of_chunk(part):
        h_c, t_c, *w_c = part  # [B, chunk, H], [B, chunk], weights alike
        logits = jnp.dot(h_c, head).astype(jnp.float32)
        judged = t_c >= 0
        picked = jnp.take_along_axis(
            logits, jnp.maximum(t_c, 0)[..., None], axis=-1)[..., 0]
        nll = jax.scipy.special.logsumexp(logits, axis=-1) - picked
        if w_c:
            nll = nll * w_c[0]
        hit = jnp.argmax(logits, axis=-1) == t_c
        return (jnp.sum(jnp.where(judged, nll, 0.0)),
                jnp.sum(judged & hit), jnp.sum(judged))

    with device_span("matcha/lm_head_loss"):
        split = lambda a: jnp.moveaxis(
            a.reshape((b, s // chunk, chunk) + a.shape[2:]), 1, 0)
        parts = (h, targets) if weights is None else (h, targets, weights)
        nll, hits, judged = lax.map(of_chunk, tuple(map(split, parts)))
        judged = jnp.sum(judged).astype(jnp.float32)
        positions = jnp.maximum(judged, 1.0)
        loss = jnp.sum(nll) / (positions if normaliser is None
                               else normaliser)
    return loss, jnp.sum(hits) / positions, judged


def attention_weights(z) -> dict:
    """``{name: shape | (init, shape)}`` of a softmax-attention layer's own
    parameters, in the order they are declared."""
    hid, d = z["hidden"], z["head_dim"]
    return {"attn_norm": (nn.initializers.ones, (hid,)),
            "wq": (hid, z["q_heads_held"] * d),
            "wk": (hid, z["kv_heads_held"] * d),
            "wv": (hid, z["kv_heads_held"] * d),
            "wo": (z["q_heads_held"] * d, hid)}


def expert_weights(z, norm_init=nn.initializers.ones) -> dict:
    """As :func:`attention_weights`, of the expert layer every token model
    shares (:func:`_moe`)."""
    hid, width, held = z["hidden"], z["expert_width"], len(z["experts_held"])
    return {"moe_norm": (norm_init, (hid,)),
            "router": (hid, z["num_experts"]),
            "gate": (held, hid, width),
            "up": (held, hid, width),
            "down": (held, width, hid)}


class TokenDecoder(nn.Module):
    """What the token models share around their blocks.  A subclass declares
    its parameters in ``setup`` (:meth:`declare`: a parameter set a layer,
    so layers may be of kinds with different ones) and gives ``hidden(ids,
    docs)`` and ``batch_loss(x_raw, y_raw)``."""

    sizes: Any
    remat: bool = False

    #: ``train/state.py`` calls :meth:`batch_loss` instead of a cross-entropy
    #: over ``__call__``'s logits, and runs the workers one after another
    #: (a ``vmap`` would turn the expert layer's ``cond`` into both branches)
    supplies_loss = True

    #: the final norm and its weight's initial value: ``x * w`` from 1 here;
    #: a model whose norms are ``x * (1 + w)`` from 0 gives its own
    norm = staticmethod(_rms_norm)
    norm_init = staticmethod(nn.initializers.ones)

    def declare(self, layers):
        """``embed``, then ``layer<n>_<name>`` for each name of
        ``layers[n]`` (``{name: shape}`` of normal weights, or ``{name:
        (init, shape)}``) in its order, the final norm and the untied head.
        The order is part of the model: a parameter's initial value follows
        its place among the calls."""
        z = self.sizes
        hid = z["hidden"]
        normal = nn.initializers.normal(INIT_STD)
        self.embed = self.param("embed", normal, (z["vocab_held"], hid))

        def declared(name, spec):
            init, shape = spec if callable(spec[0]) else (normal, spec)
            return self.param(name, init, shape)

        self.layers = [{k: declared(f"layer{n}_{k}", spec)
                        for k, spec in weights.items()}
                       for n, weights in enumerate(layers)]
        self.final_norm = self.param("final_norm", self.norm_init, (hid,))
        self.head = self.param("head", normal, (hid, z["vocab_held"]))

    def dummy_input(self, input_shape):
        """What ``init`` traces: parameters do not depend on the length."""
        return jnp.zeros((1, 8), jnp.int32)

    # What a raw row of the data set is, is the model's to say: the loop
    # asks here and reads nothing off a row's width.  These are next-token
    # training's: a row ``[S + 1]`` of ids is ``S`` inputs and the ``S``
    # next ids; a model whose task lays a row out otherwise gives its own
    # (``models/sdar.py``).

    def row_tokens(self, width: int) -> int:
        """Tokens a raw row of ``width`` numbers gives a step to predict."""
        return width - 1

    def row_positions(self, width: int) -> int:
        """Positions of such a row that pass through every layer."""
        return self.row_tokens(width)

    def judged_positions(self, x_raw, y_raw) -> int:
        """Positions of the raw rows (numpy, on the host) that carry a loss:
        what an evaluation batch's mean is a mean over."""
        return judged_positions(y_raw)

    @property
    def remat_keeps(self):
        """What the layers' checkpoints keep by name for the backward pass
        under ``remat`` (:func:`checkpointed`; a subclass adds its own):
        the journal's ``fwd_bwd`` event carries it
        (``train/state.py:fwd_bwd_plan``)."""
        return MOE_KEPT if self.remat else ()

    def expert_products(self, tokens: int, workers: int) -> dict:
        """:func:`expert_products` of a step of ``workers`` workers over
        ``tokens`` positions each (a subclass says how many of its layers
        hold the expert layer: ``expert_layers``)."""
        return expert_products(self.sizes, tokens, self.expert_layers,
                               self.remat, workers)

    def normed(self, h, counters, summed=MOE_COUNTERS):
        """(the final norm's output ``[B, S, H]``, the layers' counters
        ``summed`` and ``moe_load[layer, expert held]`` stacked)."""
        total = {k: sum(c[k] for c in counters) for k in summed}
        total["moe_load"] = jnp.stack([c["moe_load"] for c in counters])
        return self.norm(h, self.final_norm,
                         self.sizes["rms_norm_eps"]), total

    def logits(self, ids, docs):
        """Float32 ``[B, S, V]`` of ids and document numbers ``[B, S]``."""
        h, _ = self.hidden(ids, docs)
        return jnp.dot(h, self.head).astype(jnp.float32)

    def __call__(self, x, train: bool = True):
        """Logits of token ids ``x[B, S]``, each row one document."""
        ids = x.astype(jnp.int32)
        return self.logits(ids, jnp.zeros_like(ids))


class Mellum2(TokenDecoder):
    """``sizes`` as in ``chipbench/configs/mellum2-12b-a2.5b.ep8-s4k.json``
    (README "Training a language model" lists the keys)."""

    @property
    def expert_layers(self):
        return len(self.sizes["layer_types"])

    def setup(self):
        z = self.sizes
        self.declare([{**attention_weights(z), **expert_weights(z)}]
                     * self.expert_layers)

    def hidden(self, ids, docs):
        """(the final norm's output ``[B, S, H]``, the expert layers'
        counters, ``moe_load[layer, expert held]``)."""
        with device_span("matcha/lm_embed"):
            h = self.embed[ids]
        counters = []
        for p, kind in zip(self.layers, self.sizes["layer_types"]):
            block = lambda p, h, docs, kind=kind: _block(
                p, h, docs, kind, self.sizes)
            h, c = checkpointed(self.remat)(block)(p, h, docs)
            counters.append(c)
        return self.normed(h, counters)

    def batch_loss(self, x_raw, y_raw):
        """``x_raw``/``y_raw``: int32 ``[B, S + 1]`` ids and document
        numbers (``data.load_tokens``).  Returns (the mean over judged
        positions of the next id's cross-entropy, ``{"accuracy", "counters"}``)."""
        ids, docs, targets = _next_ids(x_raw, y_raw)
        h, counters = self.hidden(ids, docs)
        loss, accuracy, counters["loss_positions"] = _head_loss(
            h, self.head, targets, self.sizes)
        return loss, {"accuracy": accuracy, "counters": counters}
