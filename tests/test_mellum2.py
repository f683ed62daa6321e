"""The sparse decoder (``models/mellum2.py``) against the benchmark's plain
reference (``chipbench/reference/mellum2.py``): logits, loss and every
gradient leaf; YaRN's frequencies against numbers worked by hand; the 8
chips' shares added back up to the uncut layers; four planted faults that
the comparison has to catch.  Small sizes, seeded weights, float32 products
at ``highest``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from chipbench.reference import mellum2 as reference
from chipbench.reference.layers import make_ops
from chipbench.tasks import next_token
from matcha_tpu.models import mellum2, select_model

SEQ = 32
YARN = {"factor": 16, "original_max_position_embeddings": 8192,
        "beta_fast": 32, "beta_slow": 1,
        "attention_factor": 1.2772588722239782}


def sizes_of(kind="sliding", window=8, **more):
    sizes = {
        "hidden": 16, "head_dim": 8, "q_heads_held": 4, "kv_heads_held": 2,
        "layer_types": [kind], "sliding_window": window,
        "rope_theta": 500000, "yarn": YARN,
        "num_experts": 8, "experts_per_token": 2, "experts_held": [0, 1],
        "expert_width": 12, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
        "vocab_held": 24, "seq_len": SEQ, "attn_block": 16, "loss_chunk": 16,
    }
    sizes.update(more)
    return sizes


def rows(documents, n=3, seed=0):
    """(ids, document numbers) ``[n, SEQ + 1]``: one document a row, or
    documents packed so that every row holds boundaries."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 24, (n, SEQ + 1), dtype=np.int32)
    if documents == "one":
        docs = np.repeat(np.arange(n, dtype=np.int32)[:, None], SEQ + 1, 1)
    else:
        cuts = np.sort(rng.choice(np.arange(1, n * (SEQ + 1)), 3 * n, False))
        docs = np.searchsorted(cuts, np.arange(n * (SEQ + 1)), "right") \
            .astype(np.int32).reshape(n, SEQ + 1)
    return jnp.asarray(ids), jnp.asarray(docs)


def weights(sizes, routing, seed=1):
    """Seeded weights far from zero; ``routing`` plants where tokens go:
    ``balanced`` leaves the router alone, ``hog`` sends every token to
    experts 0 (held) and 5, ``both`` to 0 and 1 (both held: twice the slots
    that the grouped products have rows for), ``none`` to 4 and 5 (neither
    held).  A constant first feature in the embedding, which RMSNorm keeps
    positive, lets the router's first row decide."""
    model = select_model("mellum2", "tokens", sizes=sizes)
    params = model.init(jax.random.PRNGKey(seed), model.dummy_input(()),
                        train=False)["params"]
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(params))
    params = {k: 0.3 * jax.random.normal(key, v.shape)
              + (1.0 if k.endswith("norm") else 0.0)
              for key, (k, v) in zip(keys, sorted(params.items()))}
    if routing != "balanced":
        favoured = {"hog": [0, 5], "both": [0, 1], "none": [4, 5]}[routing]
        params["embed"] = params["embed"].at[:, 0].set(6.0)
        row = jnp.where(jnp.isin(jnp.arange(sizes["num_experts"]),
                                 jnp.asarray(favoured)), 8.0, -8.0)
        for k in params:
            if k.endswith("router"):
                params[k] = params[k].at[0].set(row)
    return model, params


@functools.lru_cache(maxsize=None)
def compiled(kind, window, program_sizes=None):
    """(program, reference): jitted ``(params, ids, docs) -> (logits, loss,
    grads, counters)``, compiled once per shape of the comparison.
    ``program_sizes`` overrides sizes on the program's side only."""
    sizes = sizes_of(kind, window)
    model = select_model("mellum2", "tokens", sizes=dict(
        sizes, **dict(program_sizes or ())), remat=True)
    ops = make_ops(lax.Precision.HIGHEST)

    def program(params, ids, docs):
        logits = model.apply({"params": params}, ids[:, :-1], docs[:, :-1],
                             method="logits")
        (loss, aux), grads = jax.value_and_grad(
            lambda p: model.apply({"params": p}, ids, docs,
                                  method="batch_loss"), has_aux=True)(params)
        return logits, loss, grads, aux

    def plain(params, ids, docs):
        x, targets = next_token.prepare(ids, docs, None)

        def loss_of(p):
            logits, _ = reference.forward(p, {}, x, sizes, ops)
            return next_token.loss(logits, targets), logits

        (loss, logits), grads = jax.value_and_grad(loss_of, has_aux=True)(
            params)
        return logits, loss, grads

    return jax.jit(program), jax.jit(plain)


def compare(kind, window, documents, routing, program_sizes=None, tol=2e-4):
    model, params = weights(sizes_of(kind, window), routing)
    ids, docs = rows(documents)
    program, plain = compiled(kind, window, program_sizes)
    with jax.default_matmul_precision("highest"):
        logits, loss, grads, aux = program(params, ids, docs)
        want_logits, want_loss, want_grads = plain(params, ids, docs)
    np.testing.assert_allclose(logits, want_logits, rtol=tol, atol=tol)
    np.testing.assert_allclose(loss, want_loss, rtol=tol)
    assert set(grads) == set(want_grads)
    for name, want in want_grads.items():
        scale = float(jnp.max(jnp.abs(want))) + 1e-6
        np.testing.assert_allclose(grads[name] / scale, want / scale,
                                   atol=tol, err_msg=name)
    return aux["counters"]


@pytest.mark.parametrize("routing", ["balanced", "hog", "both", "none"])
@pytest.mark.parametrize("window", [8, SEQ], ids=["window<S", "window>=S"])
@pytest.mark.parametrize("documents", ["one", "packed"])
@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_program_agrees_with_the_plain_reference(kind, documents, window,
                                                 routing):
    counters = compare(kind, window, documents, routing)
    tokens = 3 * SEQ
    load = np.asarray(counters["moe_load"])
    assert load.shape == (1, 2) and counters["moe_slots_held"] == load.sum()
    room = mellum2.moe_capacity(tokens, sizes_of())
    assert room == tokens  # twice the even total of 2 x 2 / 8 slots a token
    if routing == "hog":  # expert 0 takes every token; they fit the rows
        assert load.tolist() == [[tokens, 0]]
    elif routing == "both":  # 2 slots a token held: half are past the rows
        assert load.tolist() == [[tokens, tokens]]
    elif routing == "none":
        assert load.sum() == 0
    # the grouped products' rows, and every expert held on every token more
    # in a step whose slots do not fit them
    assert counters["moe_rows_computed"] == room + (
        2 * tokens if routing == "both" else 0)
    judged = np.asarray(rows(documents)[1])
    assert counters["loss_positions"] == np.sum(judged[:, 1:] == judged[:, :-1])


@pytest.mark.parametrize("per_even, past_the_rows", [(2, True), (3, True),
                                                     (4, False)])
def test_rows_per_even_slot_of_the_sizes(per_even, past_the_rows):
    """``sizes["moe_rows_per_even_slot"]`` sets the grouped products' rows:
    with both experts held chosen by every token (4 times the even total)
    the slots fit 4 times the even rows and no fewer, and the result is the
    reference's either way."""
    counters = compare("full", SEQ, "packed", "both", program_sizes=(
        ("moe_rows_per_even_slot", per_even),))
    tokens = 3 * SEQ
    room = mellum2.moe_capacity(tokens, sizes_of(
        moe_rows_per_even_slot=per_even))
    assert room == per_even * tokens // 2
    assert counters["moe_slots_held"] == 2 * tokens
    assert counters["moe_rows_computed"] == room + (
        2 * tokens if past_the_rows else 0)


def drop_last_slot(real):
    def route(p, x, sizes):
        w, sel = real(p, x, sizes)
        return w.at[:, -1].set(0.0), sel
    return route


@pytest.mark.parametrize("fault", ["window_ignored", "documents_ignored",
                                   "weights_not_renormalised",
                                   "a_slot_dropped"])
def test_planted_fault_is_caught(fault, monkeypatch):
    """Each fault in the program alone; the same comparison must fail."""
    program_sizes = None
    if fault == "window_ignored":
        program_sizes = (("sliding_window", 10 ** 6),)
    elif fault == "weights_not_renormalised":
        program_sizes = (("norm_topk_prob", False),)
    elif fault == "documents_ignored":
        real = mellum2._visible
        monkeypatch.setattr(
            mellum2, "_visible", lambda q, k, qd, kd, window: real(
                q, k, jnp.zeros_like(qd), jnp.zeros_like(kd), window))
        program_sizes = (("planted", fault),)  # a compile of its own
    else:
        monkeypatch.setattr(mellum2, "_route",
                            drop_last_slot(mellum2._route))
        program_sizes = (("planted", fault),)
    with pytest.raises(AssertionError):
        compare("sliding", 8, "packed", "balanced", program_sizes)


def test_tpu_branch_of_the_grouped_product_is_one_bf16_pass(monkeypatch):
    """On the TPU the grouped products round operands and cotangents to
    bfloat16 by hand (``_grouped_bf16``).  Here, forced on the CPU: the
    forward is the product of the rounded operands, the gradients are the
    plain rule's within bfloat16's rounding, and the whole model still
    agrees with the reference to that precision."""
    rng = jax.random.split(jax.random.PRNGKey(5), 3)
    lhs = jax.random.normal(rng[0], (40, 16))
    w = jax.random.normal(rng[1], (3, 16, 12))
    g = jax.random.normal(rng[2], (40, 12))
    groups = jnp.asarray([7, 0, 33], jnp.int32)
    rounded = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(lambda a, b: mellum2._grouped_bf16(a, b, groups),
                           lhs, w)
        want, plain = jax.vjp(lambda a, b: lax.ragged_dot(a, b, groups),
                              rounded(lhs), rounded(w))
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
        for got, ref in zip(vjp(g), plain(rounded(g))):
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    monkeypatch.setattr(mellum2, "_one_bf16_pass", lambda: True)
    compare("sliding", 8, "packed", "balanced", (("planted", "bf16"),),
            tol=3e-2)
    with pytest.raises(AssertionError):  # and it is not the float32 path
        compare("sliding", 8, "packed", "balanced", (("planted", "bf16"),),
                tol=1e-5)


def test_yarn_frequencies_against_numbers_worked_by_hand():
    """head_dim 128, theta 500000, factor 16 over 8192, beta 32 and 1: the
    ramp runs from dimension 18 to 35 (floor 18.08, ceil 34.98); below it
    the plain frequency, above it a sixteenth, between them the blend."""
    sizes = {"head_dim": 128, "rope_theta": 500000, "yarn": YARN}
    by_hand = {0: 1.0, 1: 0.8146172338565447, 18: 0.024955408670558694,
               19: 0.019208015577607825, 26: 0.0027043825167258223,
               34: 0.00011040869063028003, 35: 4.7781061769823416e-05,
               63: 1.5344629944572555e-07}
    plain = {0: 1.0, 19: 0.020329105980970152, 63: 2.455140791131609e-06}
    for module in (mellum2, reference):
        full, factor = module.rope_inv_freq("full", sizes)
        sliding, one = module.rope_inv_freq("sliding", sizes)
        assert factor == 1.2772588722239782 and one == 1.0
        assert full.shape == sliding.shape == (64,)
        for i, want in by_hand.items():
            assert float(full[i]) == pytest.approx(want, rel=2e-5), i
        for i, want in plain.items():
            assert float(sliding[i]) == pytest.approx(want, rel=2e-5), i


def test_eight_expert_shares_sum_to_the_uncut_layer():
    """64 experts, 8 a token: the layer that holds all 64 equals the sum of
    8 layers that hold 8 each, every one routing over all 64."""
    sizes = sizes_of(num_experts=64, experts_per_token=8,
                     experts_held=list(range(64)))
    rng = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(rng[0], (2, SEQ, 16))
    whole = {"router": jax.random.normal(rng[1], (16, 64)),
             "gate": 0.3 * jax.random.normal(rng[2], (64, 16, 12)),
             "up": 0.3 * jax.random.normal(rng[3], (64, 16, 12)),
             "down": 0.3 * jax.random.normal(rng[4], (64, 12, 16))}
    with jax.default_matmul_precision("highest"):
        want, counted = mellum2._moe(whole, x, sizes)
        total = jnp.zeros_like(want)
        slots = 0
        for share in range(8):
            held = list(range(8 * share, 8 * share + 8))
            part = {k: v if k == "router" else v[held[0]:held[-1] + 1]
                    for k, v in whole.items()}
            y, c = mellum2._moe(part, x, dict(sizes, experts_held=held))
            total, slots = total + y, slots + c["moe_slots_held"]
    assert counted["moe_slots_held"] == slots == 2 * SEQ * 8
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_eight_attention_shares_sum_to_the_whole_attention(kind):
    """32 query heads over 4 KV heads: 8 shares of 4 query heads and the KV
    head they use (each KV head on two shares) add up through ``W_o``."""
    d, hid = 8, 16
    sizes = sizes_of(kind, q_heads_held=32, kv_heads_held=4)
    rng = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(rng[0], (2, SEQ, hid))
    docs = rows("packed", 2)[1][:, :-1]
    whole = {"wq": jax.random.normal(rng[1], (hid, 32 * d)),
             "wk": jax.random.normal(rng[2], (hid, 4 * d)),
             "wv": jax.random.normal(rng[3], (hid, 4 * d)),
             "wo": jax.random.normal(rng[4], (32 * d, hid))}
    with jax.default_matmul_precision("highest"):
        want = mellum2._attention(whole, x, docs, kind, sizes)
        total = jnp.zeros_like(want)
        for share in range(8):
            q = slice(4 * d * share, 4 * d * (share + 1))
            kv = slice(d * (share // 2), d * (share // 2 + 1))
            part = {"wq": whole["wq"][:, q], "wk": whole["wk"][:, kv],
                    "wv": whole["wv"][:, kv], "wo": whole["wo"][q]}
            total = total + mellum2._attention(
                part, x, docs, kind,
                dict(sizes, q_heads_held=4, kv_heads_held=1))
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


def test_forward_macs_counts_the_published_share():
    """141 MFLOP a token forward at the cell's sizes: 84 in the four layers,
    57 in the head (ISSUE 27's reckoning, from the shapes)."""
    import json
    from pathlib import Path

    conf = json.loads((Path(reference.__file__).parents[1] / "configs"
                       / "mellum2-12b-a2.5b.ep8-s4k.json").read_text())
    sizes = conf["sizes"]
    per_token = reference.forward_macs(sizes) / sizes["seq_len"]
    head = sizes["hidden"] * sizes["vocab_held"]
    assert head == 28_311_552
    assert 2 * (per_token - head) / 1e6 == pytest.approx(84, abs=0.5)
    assert 2 * per_token / 1e6 == pytest.approx(141, abs=1)
