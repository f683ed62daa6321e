"""The hybrid linear-attention decoder (``models/qwen3_next.py``) against the
benchmark's plain reference (``chipbench/reference/qwen3_next.py``): logits,
the loss and every gradient leaf on packed rows of several documents with
both layer kinds; the chunked scan against the token-by-token recurrence;
one row of two documents against the two documents alone; the shares of a
deployment added back up to the uncut layer; partial RoPE; the parameter
and operation counts of the published share; the counters against a NumPy
count.  Small sizes, seeded weights, float32 products at ``highest`` on both
sides.

**Tolerance** (``close``): 2e-4 of the largest entry of what is compared, as
``tests/test_keye_vl2.py`` holds its model to.  Both sides are float32 at
``highest``; what differs is the order of the sums (a chunk's ``(I + A)^-1``
and three products against one token at a time), which reads 1e-6 to 3e-5
here, and a gradient passes through both layers' (one Gated DeltaNet, one
full: ``full_attention_interval`` 2; the published 3:1 is in
``test_counts_of_the_published_share``).  A fault of the
mathematics (a reset left out, a gate, a norm) reads 1e-2 and more.
"""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from chipbench.reference import qwen3_next as reference
from chipbench.reference.layers import make_ops
from chipbench.tasks import next_token
from matcha_tpu.models import mellum2, qwen3_next, select_model

SEQ = 32
CONFIG = (Path(reference.__file__).parents[1] / "configs"
          / "qwen3-next-80b-a3b.ep64-s8k.json")


def sizes_of(**more):
    sizes = {
        "hidden": 16, "head_dim": 8, "rotary_dim": 4, "q_heads_held": 4,
        "kv_heads_held": 2, "num_layers": 2, "full_attention_interval": 2,
        "rope_theta": 10_000_000, "linear_key_heads_held": 2,
        "linear_value_heads_held": 4, "linear_key_dim": 8,
        "linear_value_dim": 4, "conv_kernel": 4, "gdn_chunk": 8,
        "num_experts": 16, "experts_per_token": 3, "experts_held": [0, 1, 2],
        "expert_width": 12, "shared_expert_width": 10,
        "norm_topk_prob": True, "rms_norm_eps": 1e-6, "vocab_held": 24,
        "seq_len": SEQ, "attn_block": 16, "loss_chunk": 16,
    }
    sizes.update(more)
    return sizes


def rows(n=3, seed=0):
    """(ids, document numbers) ``[n, SEQ + 1]``, documents packed so that
    every row holds boundaries, most of them inside a chunk."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 24, (n, SEQ + 1), dtype=np.int32)
    cuts = np.sort(rng.choice(np.arange(1, n * (SEQ + 1)), 2 * n, False))
    docs = np.searchsorted(cuts, np.arange(n * (SEQ + 1)), "right") \
        .astype(np.int32).reshape(n, SEQ + 1)
    return jnp.asarray(ids), jnp.asarray(docs)


def weights(sizes, seed=1):
    """Seeded weights far from zero: norm weights near their initial value,
    decays that let a state live through several chunks."""
    model = select_model("qwen3_next", "tokens", sizes=sizes, remat=True)
    params = model.init(jax.random.PRNGKey(seed), model.dummy_input(()),
                        train=False)["params"]
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(params))
    out = {}
    for key, (k, v) in zip(keys, sorted(params.items())):
        noise = 0.3 * jax.random.normal(key, v.shape)
        if k.endswith("A_log"):
            out[k] = noise - 2.0  # exp(g) about 0.85 to 0.95 a token
        elif k.endswith(("dt_bias", "gdn_norm")):
            out[k] = 1.0 + noise
        else:
            out[k] = noise
    return model, out


@functools.lru_cache(maxsize=None)
def compiled(chunk=8, block=16):
    """(program, reference): jitted ``(params, ids, docs) -> (logits, loss,
    grads, counters | None)``, compiled once a shape."""
    sizes = sizes_of(gdn_chunk=chunk, attn_block=block)
    model = select_model("qwen3_next", "tokens", sizes=sizes, remat=True)
    ops = make_ops(lax.Precision.HIGHEST)

    def program(params, ids, docs):
        logits = model.apply({"params": params}, ids[:, :-1], docs[:, :-1],
                             method="logits")
        (loss, aux), grads = jax.value_and_grad(
            lambda p: model.apply({"params": p}, ids, docs,
                                  method="batch_loss"), has_aux=True)(params)
        return logits, loss, grads, aux["counters"]

    def plain(params, ids, docs):
        x, targets = next_token.prepare(ids, docs, None)

        def loss_of(p):
            logits, _ = reference.forward(p, {}, x, sizes, ops)
            return next_token.loss(logits, targets), logits

        (loss, logits), grads = jax.value_and_grad(loss_of, has_aux=True)(
            params)
        return logits, loss, grads, None

    return jax.jit(program), jax.jit(plain)


def close(got, want, tol=2e-4, name=""):
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=tol,
                               err_msg=name)


def test_program_agrees_with_the_plain_reference():
    _, params = weights(sizes_of())
    ids, docs = rows()
    program, plain = compiled()
    with jax.default_matmul_precision("highest"):
        logits, loss, grads, _ = program(params, ids, docs)
        want_logits, want_loss, want_grads, _ = plain(params, ids, docs)
    close(logits, want_logits, name="logits")
    np.testing.assert_allclose(loss, want_loss, rtol=2e-4)
    assert set(grads) == set(want_grads)
    for name, want in want_grads.items():
        assert float(jnp.max(jnp.abs(want))) > 0, name
        close(grads[name], want, name=name)


def delta_rule_inputs(seed=4, hk=2, r=2, dk=8, dv=4, n=2):
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = qwen3_next._l2norm(normal(n, SEQ, hk, dk)) / np.sqrt(dk)
    k = qwen3_next._l2norm(normal(n, SEQ, hk, dk))
    v = normal(n, SEQ, hk, r, dv)
    beta = jax.nn.sigmoid(normal(n, SEQ, hk, r))
    g = -0.2 * jax.nn.softplus(normal(n, SEQ, hk, r))
    return (q, k, v, beta, g), rows(n, seed)[1][:, :-1]


def recurrent(q, k, v, beta, g, docs):
    """The reference's token-by-token recurrence over rows of the program's
    layout."""
    r = v.shape[3]
    heads = lambda a: a.reshape(a.shape[:1] + (-1,) + a.shape[3:])

    def row(args):
        q, k, v, beta, g, docs = args
        return reference.delta_rule(
            jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1), heads(v),
            heads(beta), heads(g), docs)

    return lax.map(row, (q, k, v, beta, g, docs)).reshape(v.shape)


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_scan_equals_the_token_by_token_recurrence(chunk):
    """Forward and gradient (of a fixed random projection of the output, to
    every input), document starts inside and between chunks."""
    inputs, docs = delta_rule_inputs()
    probe = jnp.asarray(np.random.default_rng(9).normal(
        size=inputs[2].shape), jnp.float32)
    chunked = lambda *a: qwen3_next._gated_delta_rule(*a, docs, chunk)[0]
    plain = lambda *a: recurrent(*a, docs)
    with jax.default_matmul_precision("highest"):
        (out, grads), (want_out, want_grads) = (
            jax.jit(lambda *a, f=f: (f(*a), jax.grad(
                lambda *a: jnp.sum(f(*a) * probe),
                argnums=tuple(range(5)))(*a)))(*inputs)
            for f in (chunked, plain))
    close(out, want_out, tol=1e-5)
    for name, got, want in zip("q k v beta g".split(), grads, want_grads):
        close(got, want, tol=1e-5, name=name)


def test_unit_lower_inverse_where_keys_repeat():
    """All-ones below the diagonal (one key repeated at ``beta`` 1 with no
    decay): the inverse is 1 on the diagonal and -1 beneath it, where the
    sum over powers of ``A`` would cancel 1e17 against it."""
    a = jnp.tril(jnp.ones((64, 64), jnp.float32), -1)
    want = np.eye(64) - np.eye(64, k=-1)
    inverse = jax.jit(qwen3_next._unit_lower_inverse)
    np.testing.assert_array_equal(inverse(a[None])[0], want)
    rng = np.random.default_rng(0)
    a = jnp.asarray(np.tril(rng.normal(size=(3, 16, 16)), -1), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = inverse(a)
    np.testing.assert_allclose(
        got, np.linalg.inv(np.eye(16) + np.asarray(a, np.float64)),
        rtol=1e-4, atol=1e-4)


def _lower(*shape, rng=np.random.default_rng(5)):
    return np.tril(rng.normal(size=shape), -1)


#: strictly lower-triangular ``a[..., C, C]`` a case: random at three sizes,
#: one key repeated (all ones), and leading dimensions
INVERSE_CASES = {"C4": _lower(3, 4, 4), "C16": _lower(3, 16, 16),
                 "C64": 0.3 * _lower(2, 64, 64),
                 "keys_repeat": np.tril(np.ones((1, 64, 64)), -1),
                 "leading_dimensions": _lower(2, 3, 2, 16, 16)}


@pytest.mark.parametrize("case", list(INVERSE_CASES))
def test_inverse_is_differentiated_through_itself_as_through_its_levels(case):
    """``-T^T G T^T`` (``_unit_lower_inverse``'s rule) against autodiff
    through the construction by halves, below the diagonal, where ``a`` has
    its entries and ``_chunk_prep``'s ``where`` lets the cotangent through;
    the values themselves bit for bit."""
    a = jnp.asarray(INVERSE_CASES[case], jnp.float32)
    probe = jnp.asarray(np.random.default_rng(6).normal(size=a.shape),
                        jnp.float32)
    below = np.tril(np.ones(a.shape[-2:], bool), -1)
    with jax.default_matmul_precision("highest"):
        got, want = (
            jax.jit(jax.grad(lambda a, f=f: jnp.sum(f(a) * probe)))(a)
            for f in (qwen3_next._unit_lower_inverse, qwen3_next._by_halves))
        np.testing.assert_array_equal(
            jax.jit(qwen3_next._unit_lower_inverse)(a),
            jax.jit(qwen3_next._by_halves)(a))
    assert not np.any(np.where(below, 0.0, want))  # the levels read no more
    close(jnp.where(below, got, 0.0), want, tol=2e-6)


AGAINS = {"checkpoint": jax.checkpoint, "keeping": qwen3_next._again_keeping}


@pytest.mark.parametrize("again", list(AGAINS))
def test_gradients_under_checkpoints_equal_those_without(again):
    """The chunk systems under a checkpoint of their own inside the layer's
    (plain, and keeping ``KEPT`` by name as ``_block`` runs them): the
    gradient by every input equals the unwrapped rule's and the
    token-by-token recurrence's, document starts inside chunks."""
    inputs, docs = delta_rule_inputs()
    probe = jnp.asarray(np.random.default_rng(9).normal(
        size=inputs[2].shape), jnp.float32)
    rule = lambda again: lambda *a: qwen3_next._gated_delta_rule(
        *a, docs, 8, again)[0]
    forms = (jax.checkpoint(rule(AGAINS[again])), rule(lambda f: f),
             lambda *a: recurrent(*a, docs))
    with jax.default_matmul_precision("highest"):
        under, plain, token = (
            jax.jit(jax.grad(lambda *a, f=f: jnp.sum(f(*a) * probe),
                             argnums=tuple(range(5))))(*inputs)
            for f in forms)
    for name, got, same, want in zip("q k v beta g".split(), under, plain,
                                     token):
        close(got, same, tol=1e-6, name=name)
        close(got, want, tol=1e-5, name=name)


def level_products(jaxpr):
    """How many ``dot_general`` equations the program holds under the
    inverse's name scope (the levels' own and whatever autodiff derives
    from them), sub-programs included."""
    def walk(jaxpr):
        found = 0
        for eqn in jaxpr.eqns:
            found += eqn.primitive.name == "dot_general" \
                and qwen3_next.LEVELS in str(eqn.source_info.name_stack)
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) \
                        else [value]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        found += walk(sub)
        return found

    return walk(jaxpr.jaxpr)


@pytest.mark.parametrize("remat, inversions", [(True, 2), (False, 1)])
def test_a_layers_gradient_inverts_twice_and_transposes_no_level(
        remat, inversions, monkeypatch):
    """The program of a linear layer's gradient: under ``remat`` the levels
    of a chunk's inverse are built in the forward pass and in the layer's
    recomputation and not a third time (the chunk systems' checkpoint keeps
    ``T`` by name), and no product is derived from a level (the backward
    pass is ``T``'s own rule: two products outside the scope).  Without the
    policy or without the rule the count says so."""
    sizes = sizes_of()
    p = layer_weights("linear", sizes)
    h = jax.random.normal(jax.random.PRNGKey(9), (2, SEQ, 16))
    docs = rows(2)[1][:, :-1]
    per_inverse = 2 * int(np.log2(sizes["gdn_chunk"]))

    def program(remat):
        return jax.make_jaxpr(jax.grad(lambda p, h: jnp.sum(jnp.square(
            qwen3_next._block(p, h, docs, "linear", sizes, remat)[0])),
            argnums=(0, 1)))(p, h)

    assert level_products(jax.make_jaxpr(qwen3_next._by_halves)(
        jnp.zeros((8, 8)))) == per_inverse
    assert level_products(program(remat)) == inversions * per_inverse
    if remat:  # what the count reads where an edit drops either half
        monkeypatch.setattr(qwen3_next, "_again_keeping", jax.checkpoint)
        assert level_products(program(True)) == 3 * per_inverse
        monkeypatch.undo()
        monkeypatch.setattr(qwen3_next, "_unit_lower_inverse",
                            qwen3_next._by_halves)
        assert level_products(program(True)) > 3 * per_inverse


def test_a_row_of_two_documents_is_the_two_documents_alone():
    """State, convolution and attention alike: logits of a packed row of 13
    + 19 positions equal, position for position, those of each document as a
    row of its own (its chunks one token long, so any length is whole
    chunks)."""
    model, params = weights(sizes_of())
    alone = select_model("qwen3_next", "tokens",
                         sizes=sizes_of(gdn_chunk=1), remat=True)
    ids = rows(1, seed=5)[0][:, :-1]
    docs = jnp.asarray([[4] * 13 + [7] * 19], jnp.int32)
    logits = lambda m, i, d: jax.jit(lambda i, d: m.apply(
        {"params": params}, i, d, method="logits"))(i, d)
    with jax.default_matmul_precision("highest"):
        packed = logits(model, ids, docs)
        first = logits(alone, ids[:, :13], docs[:, :13])
        second = logits(alone, ids[:, 13:], docs[:, 13:])
    close(packed[:, :13], first, tol=1e-5)
    close(packed[:, 13:], second, tol=1e-5)
    # and they are not what a row of one document gives
    whole = logits(model, ids, jnp.zeros_like(docs))
    assert float(jnp.max(jnp.abs(whole[:, 13:] - second))) > 1e-2


def layer_weights(kind, sizes, seed=3):
    shapes = {**qwen3_next.mixer_weights(kind, sizes),
              **mellum2.expert_weights(sizes)}
    width, hid = sizes["shared_expert_width"], sizes["hidden"]
    shapes.update(shared_gate=(hid, width), shared_up=(hid, width),
                  shared_down=(width, hid), shared_sigmoid=(hid,))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    p = {}
    for key, (k, spec) in zip(keys, shapes.items()):
        shape = spec[1] if callable(spec[0]) else spec
        p[k] = 0.5 * jax.random.normal(key, shape)
    return dict(p, A_log=p["A_log"] - 2.0) if kind == "linear" else p


def columns(groups, share, shares):
    """The slice of ``sum(groups)`` columns that holds share ``share`` of
    each group (groups laid side by side, each split evenly)."""
    at, out = 0, []
    for g in groups:
        out.append(np.arange(at + share * g // shares,
                             at + (share + 1) * g // shares))
        at += g
    return np.concatenate(out)


@pytest.mark.parametrize("kind", ["linear", "full"])
def test_the_shares_sum_to_the_uncut_layer(kind):
    """The deployment in small: a layer's 16 routed experts 4 ways and the
    heads of its mixer 2 ways; the router, the norms, the shared expert and
    its gate whole on every chip and counted once.  The program's shares add
    up to the reference's layer with every head and expert."""
    experts, hk, hv, dk, dv, hq, hkv, d = 16, 4, 8, 8, 4, 4, 2, 8
    sizes = sizes_of(linear_key_heads_held=hk, linear_value_heads_held=hv,
                     q_heads_held=hq, kv_heads_held=hkv,
                     experts_held=list(range(experts)), attn_block=SEQ)
    whole = layer_weights(kind, sizes)
    h = jax.random.normal(jax.random.PRNGKey(9), (2, SEQ, 16))
    docs = rows(2)[1][:, :-1]
    ops = make_ops(lax.Precision.HIGHEST)
    named = {"layer0_" + k: v for k, v in whole.items()}
    mixer = reference.gated_delta_net if kind == "linear" \
        else reference.gated_attention
    half = dict(sizes, linear_key_heads_held=hk // 2,
                linear_value_heads_held=hv // 2, q_heads_held=hq // 2,
                kv_heads_held=hkv // 2)
    def uncut(row):
        x, row_docs = row
        x = x + mixer(named, "layer0", reference.norm0(
            x, whole["attn_norm"], 1e-6), row_docs, sizes, ops)
        return x, x + reference.experts(named, "layer0", reference.norm0(
            x, whole["moe_norm"], 1e-6), sizes, ops)

    def mixer_share(share):
        part = dict(whole)
        if kind == "linear":
            qkvz = columns([hk * dk, hk * dk, hv * dv, hv * dv], share, 2)
            conv = columns([hk * dk, hk * dk, hv * dv], share, 2)
            heads = columns([hv], share, 2)
            part.update(
                in_proj_qkvz=whole["in_proj_qkvz"][:, qkvz],
                in_proj_ba=whole["in_proj_ba"][
                    :, columns([hv, hv], share, 2)],
                conv=whole["conv"][:, conv], dt_bias=whole["dt_bias"][heads],
                A_log=whole["A_log"][heads],
                out_proj=whole["out_proj"][columns([hv * dv], share, 2)])
            return qwen3_next._gated_delta_net(part, h, docs, half)[0]
        q = columns([2 * hq * d], share, 2)
        kv = columns([hkv * d], share, 2)
        part.update(wq=whole["wq"][:, q], wk=whole["wk"][:, kv],
                    wv=whole["wv"][:, kv],
                    wo=whole["wo"][columns([hq * d], share, 2)])
        return qwen3_next._gated_attention(part, h, docs, half, lambda f: f)

    def experts_share(mid, share):
        held = list(range(4 * share, 4 * share + 4))
        part = {k: v[held[0]:held[-1] + 1] if k in ("gate", "up", "down")
                else v for k, v in whole.items()}
        held = dict(sizes, experts_held=held)
        y, c = mellum2._moe(part, qwen3_next._norm0(
            mid, whole["moe_norm"], 1e-6), held)
        # with what every chip computes alike
        return y, qwen3_next._experts_of(part, mid, held)[0], \
            c["moe_slots_held"]

    @jax.jit
    def shares():
        mid = h + mixer_share(0) + mixer_share(1)
        parts = [experts_share(mid, share) for share in range(4)]
        # the shared expert and its gate once: with share 0
        total = mid + parts[0][1] + sum(y for y, _, _ in parts[1:])
        return mid, total, sum(c for _, _, c in parts), \
            jnp.max(jnp.abs(parts[0][1] - parts[0][0]))

    with jax.default_matmul_precision("highest"):
        want_mid, want = jax.jit(lambda: lax.map(uncut, (h, docs)))()
        mid, total, slots, shared = shares()
    close(mid, want_mid, tol=1e-5)
    assert float(shared) > 1e-2
    assert slots == 2 * SEQ * 3
    close(total, want, tol=1e-5)


def test_partial_rope_turns_64_of_256_dimensions_and_leaves_192():
    s, d, rotary, theta = 16, 256, 64, 10_000_000
    x = jax.random.normal(jax.random.PRNGKey(2), (1, s, 3, d))
    inv_freq, factor = mellum2.rope_inv_freq(
        "sliding", {"head_dim": rotary, "rope_theta": theta})
    assert factor == 1.0 and inv_freq.shape == (32,)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    got = qwen3_next._partial_rope(x, jnp.cos(angle), jnp.sin(angle))
    np.testing.assert_array_equal(got[..., rotary:], x[..., rotary:])
    turned = np.abs(np.asarray(got - x))[0, 1:, :, :rotary]
    assert np.all(turned.max(axis=(0, 1)) > 0)  # every one of the 64 moves
    np.testing.assert_array_equal(got[:, 0], x[:, 0])  # position 0: angle 0
    # pairs (i, i + 32) keep their length
    pair = lambda a, i: np.hypot(a[..., i], a[..., i + 32])
    np.testing.assert_allclose(pair(np.asarray(got), 5),
                               pair(np.asarray(x), 5), rtol=1e-5)
    close(got[0], reference.partial_rope(x[0], theta, rotary), tol=1e-6)


def test_counts_of_the_published_share():
    """Parameters off the tree and ``forward_macs`` against a count by hand
    from the published widths (ISSUE 35's table)."""
    conf = json.loads(CONFIG.read_text())
    sizes = conf["sizes"]
    model = select_model("qwen3_next", "tokens", sizes=sizes)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), model.dummy_input(()),
                           train=False))["params"]
    counts = {k: int(np.prod(v.shape)) for k, v in shapes.items()}
    of = lambda prefix, names: sum(counts[prefix + n] for n in names)
    every = ("moe_norm", "attn_norm", "router", "gate", "up", "down",
             "shared_gate", "shared_up", "shared_down", "shared_sigmoid")
    assert of("layer0_", every) == 2 * 2048 + 2048 * 512 + 3 * 2048 * 512 \
        + 2048 + 8 * 3 * 2048 * 512 == 29_366_272
    gdn = ("in_proj_qkvz", "in_proj_ba", "conv", "dt_bias", "A_log",
           "gdn_norm", "out_proj")
    assert of("layer0_", gdn) == 2048 * 6144 + 2048 * 32 + 4096 * 4 + 16 \
        + 16 + 128 + 2048 * 2048 == 16_859_296
    full = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
    assert of("layer3_", full) == 2048 * 4096 + 2 * 2048 * 256 \
        + 2048 * 2048 + 2 * 256 == 13_632_000
    assert counts["embed"] + counts["head"] + counts["final_norm"] \
        == 77_793_280
    assert sum(counts.values()) == conf["parameters_per_worker"] \
        == 3 * 16_859_296 + 13_632_000 + 4 * 29_366_272 + 77_793_280
    # multiply-accumulates a token forward, by hand
    s = sizes["seq_len"]
    linear = 2048 * (6144 + 32) + 2048 * 2048 + 4 * 4096 + 16 * 3 * 128 * 128
    full = 2048 * (4096 + 512) + 2048 * 2048 + 2 * 8 * 256 * (s + 1) / 2
    moe = 2048 * 512 + 3 * 2048 * 512 + 2048 + 10 * 8 / 512 * 3 * 2048 * 512
    head = 2048 * 18992
    assert reference.forward_macs(sizes) == pytest.approx(
        s * (3 * linear + full + 4 * moe + head), rel=1e-9)
    per_token = reference.forward_macs(sizes) / s
    assert per_token == pytest.approx(141.0e6, rel=2e-3)
    assert 3 * linear / per_token == pytest.approx(0.375, abs=0.005)
    # the grouped expert products' rows for a worker-step of 2 rows
    assert mellum2.moe_capacity(2 * s, sizes) == \
        sizes["moe_rows_per_even_slot"] * 2560


def test_counters_equal_a_numpy_count():
    _, params = weights(sizes_of())
    ids, docs = rows()
    counters = compiled()[0](params, ids, docs)[3]
    d = np.asarray(docs)[:, :-1]
    n, chunk, linear, hv = d.shape[0], 8, 1, 4
    assert counters["gdn_chunks"] == linear * n * SEQ // chunk
    follows_another = np.zeros(d.shape, bool)
    follows_another[:, 1:] = d[:, 1:] != d[:, :-1]
    assert follows_another.sum() > n  # several documents a row
    assert counters["gdn_chunks_reset"] == linear * follows_another.reshape(
        n, SEQ // chunk, chunk).any(-1).sum()
    assert counters["gdn_gates"] == linear * n * SEQ * hv
    # the decays themselves, from the equations: the one DeltaNet layer is
    # the first, so its input can be written down
    x = reference.norm0(params["embed"][ids[:, :-1]],
                        params["layer0_attn_norm"], 1e-6)
    a = jnp.einsum("bsh,hk->bsk", x, params["layer0_in_proj_ba"],
                   precision=lax.Precision.HIGHEST)[..., hv:]
    decay = np.exp(-np.exp(params["layer0_A_log"]) * np.logaddexp(
        0.0, a + params["layer0_dt_bias"]))
    assert 0.5 < decay.mean() < 1.0
    np.testing.assert_allclose(counters["gdn_decay_sum"], decay.sum(),
                               rtol=1e-5)
    assert counters["loss_positions"] == np.sum(
        np.asarray(docs)[:, 1:] == np.asarray(docs)[:, :-1])
    assert np.asarray(counters["moe_load"]).shape == (2, 3)
    assert counters["moe_slots_held"] == np.asarray(
        counters["moe_load"]).sum()


def test_trains_by_name_through_train(tmp_path):
    """``model="qwen3_next"`` on the normal path: the loss falls, nothing
    retraces, the ``gdn_*`` counters ride each period's record beside the
    expert layer's, the ``fwd_bwd`` event names what the chunk systems'
    checkpoint keeps, and evaluation gives the held-out loss."""
    from matcha_tpu.train import TrainConfig, train

    sizes = sizes_of(hidden=32, expert_width=24, vocab_held=48)
    data = next_token.make(11, 2 * 2 * 3, 4, {"sizes": sizes})
    np.savez(tmp_path / "data.npz", **data)
    config = TrainConfig(
        name="gdn", model="qwen3_next", dataset="tokens",
        datasetRoot=str(tmp_path / "data.npz"), model_kwargs={"sizes": sizes},
        num_workers=2, graphid=None, topology="chain", batch_size=2, epochs=3,
        lr=0.05, warmup=False, matcha=True, budget=0.5, seed=3, eval_every=1,
        remat=True, devices=1, save=True, savePath=str(tmp_path))
    result = train(config, boundary_hook=lambda seam: None)
    losses = [h["loss"] for h in result.history]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert "retrace" not in [e["kind"] for e in result.recorder.events]
    (plan,) = [e for e in result.recorder.events if e["kind"] == "fwd_bwd"]
    assert plan["remat_keeps"] == [*mellum2.MOE_KEPT, qwen3_next.KEPT]
    assert not plan["packed"]
    records = [e for e in result.recorder.events if e["kind"] == "spans"]
    assert len(records) == 3
    rows_an_epoch = 3 * 2 * 2  # steps x workers x rows
    for r, h in zip(records, result.history):
        c = r["counters"]
        assert set(c) == {
            "loss_positions", "moe_slots_held", "moe_rows_computed",
            "moe_rows_multiplied",
            "moe_load", "gdn_chunks", "gdn_chunks_reset", "gdn_gates",
            "gdn_decay_sum"}
        assert c["gdn_chunks"] == rows_an_epoch * SEQ // 8
        assert 0 <= c["gdn_chunks_reset"] < c["gdn_chunks"]
        assert c["gdn_gates"] == rows_an_epoch * SEQ * 4
        assert 0 < c["gdn_decay_sum"] < c["gdn_gates"]
        assert 0 < h["test_loss_mean"] < 2 * np.log(48)
