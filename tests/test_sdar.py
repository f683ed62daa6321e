"""The block-diffusion decoder (``models/sdar.py``) against the benchmark's
plain reference (``chipbench/reference/sdar.py``): the loss, the noisy
copy's logits and every gradient leaf over packed documents; the mask
enumerated against its four rules; what may and may not leak between the two
copies; the counters against a NumPy count; the shares of 16 chips added back
up to the uncut layer; the parameters a worker off the tree; and the normal
path, ``train()``, by the model's name.  Small sizes, seeded weights, float32
(on the CPU a product at any ``precision`` is a float32 one).

**Tolerances.**  ``close`` compares after dividing by the largest reference
value of the array, at ``TOL`` 2e-5: program and reference are both float32
and differ by summation order alone (largest reading here 2e-6).  One
bfloat16 pass moves the same numbers by 1e-3 and more, a causal in-block mask
or a leaked clean block by 1e-2 and more;
``test_the_tolerance_tells_what_it_has_to`` plants each and requires the
comparison to fail."""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from chipbench.reference import sdar as reference
from chipbench.reference.layers import make_ops
from chipbench.tasks import block_diffusion
from matcha_tpu.models import mellum2, sdar, select_model

SEQ, BLOCK, VOCAB = 32, 4, 24
TOL = 2e-5


def sizes_of(**more):
    sizes = {
        "hidden": 16, "head_dim": 8, "q_heads_held": 4, "kv_heads_held": 2,
        "num_layers": 2, "rope_theta": 1_000_000,
        "num_experts": 8, "experts_per_token": 2, "experts_held": [0, 1],
        "expert_width": 12, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
        "vocab_held": VOCAB, "seq_len": SEQ, "block_length": BLOCK,
        "mask_id": VOCAB - 1, "noise_t_min": 0.05, "attn_block": 16,
        "loss_chunk": 16,
    }
    sizes.update(more)
    return sizes


def raw_rows(n=3, seed=0, seq=SEQ, documents="packed"):
    """Raw rows ``(x, y)[n, 2 seq]`` as ``block_diffusion.make`` lays them
    out, with document boundaries (on multiples of ``BLOCK``) in every row."""
    rng = np.random.default_rng(seed)
    clean = rng.integers(0, VOCAB - 1, (n, seq), dtype=np.int32)
    if documents == "one":
        docs = np.repeat(np.arange(n, dtype=np.int32)[:, None], seq, 1)
    else:
        cuts = np.sort(rng.choice(np.arange(1, n * seq // BLOCK), 2 * n,
                                  False)) * BLOCK
        docs = np.searchsorted(cuts, np.arange(n * seq), "right") \
            .astype(np.int32).reshape(n, seq)
    t = np.repeat(rng.integers(3277, 65537, (n, seq // BLOCK),
                               dtype=np.int32), BLOCK, axis=1)
    masked = rng.random((n, seq)) * 65536 < t
    noisy = np.where(masked, np.int32(VOCAB - 1), clean)
    return (jnp.asarray(np.concatenate([noisy, clean], 1)),
            jnp.asarray(np.concatenate([docs, t], 1)))


def weights(sizes, seed=1):
    """Seeded weights far from zero (norm scales near 1)."""
    model = select_model("sdar", "tokens", sizes=sizes, remat=True)
    params = model.init(jax.random.PRNGKey(seed), model.dummy_input(()),
                        train=False)["params"]
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(params))
    return model, {k: 0.3 * jax.random.normal(key, v.shape)
                   + (1.0 if k.endswith("norm") else 0.0)
                   for key, (k, v) in zip(keys, sorted(params.items()))}


@functools.lru_cache(maxsize=None)
def compiled(block=16):
    """(program, reference): jitted ``(params, x_raw, y_raw) -> (the noisy
    copy's logits, loss, grads, ...)``, compiled once a shape."""
    sizes = sizes_of(attn_block=block)
    model = select_model("sdar", "tokens", sizes=sizes, remat=True)
    ops = make_ops(lax.Precision.HIGHEST)
    config = {"sizes": sizes}

    def program(params, x_raw, y_raw):
        (total, aux), grads = jax.value_and_grad(
            lambda p: model.apply({"params": p}, x_raw, y_raw,
                                  method="batch_loss"), has_aux=True)(params)
        return total, grads, aux

    def plain(params, x_raw, y_raw):
        x, targets = block_diffusion.prepare(x_raw, y_raw, config)

        def loss_of(p):
            logits, _ = reference.forward(p, {}, x, sizes, ops)
            return block_diffusion.loss(logits, targets), logits

        (total, logits), grads = jax.value_and_grad(loss_of, has_aux=True)(
            params)
        return total, grads, logits

    return jax.jit(program), jax.jit(plain)


@functools.lru_cache(maxsize=None)
def hidden_of(block=16):
    """Jitted ``(params, ids[B, 2S], docs[B, S]) -> the final norm's output
    [B, 2S, H]`` of the program, and its head's logits of the noisy half."""
    model = select_model("sdar", "tokens", sizes=sizes_of(attn_block=block),
                         remat=True)

    def of(m, ids, docs):
        h, _ = m.hidden(ids, docs)
        return h, jnp.dot(h[:, :docs.shape[1]], m.head)

    return jax.jit(lambda params, ids, docs: model.apply(
        {"params": params}, ids, docs, method=of))


def close(got, want, tol=TOL, name=""):
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=tol,
                               err_msg=name)


@pytest.mark.parametrize("documents", ["one", "packed"])
def test_program_agrees_with_the_plain_reference(documents):
    _, params = weights(sizes_of())
    x_raw, y_raw = raw_rows(documents=documents)
    program, plain = compiled()
    loss, grads, aux = program(params, x_raw, y_raw)
    want_loss, want_grads, want_logits = plain(params, x_raw, y_raw)
    assert abs(float(loss) - float(want_loss)) <= TOL * float(want_loss)
    logits = hidden_of()(params, x_raw, y_raw[:, :SEQ])[1]
    close(logits, want_logits, name="logits")
    assert set(grads) == set(want_grads)
    for k in sorted(want_grads):
        assert float(jnp.max(jnp.abs(want_grads[k]))) > 0, k
        close(grads[k], want_grads[k], name=k)
    # accuracy is over the masked positions, of the position's own id
    masked = np.asarray(x_raw[:, :SEQ]) == VOCAB - 1
    hits = np.asarray(jnp.argmax(want_logits, -1)) == np.asarray(
        x_raw[:, SEQ:])
    assert float(aux["accuracy"]) == pytest.approx(
        (hits & masked).sum() / masked.sum())


def test_blocked_equals_unblocked():
    """Query blocks of 16 (two a copy) against one block a copy: the same
    activations in both copies and the same logits.  (The blocked program's
    gradients are held to the reference above.)"""
    _, params = weights(sizes_of())
    x_raw, y_raw = raw_rows()
    blocked = hidden_of(16)(params, x_raw, y_raw[:, :SEQ])
    whole = hidden_of(SEQ)(params, x_raw, y_raw[:, :SEQ])
    for got, want in zip(blocked, whole):
        close(got, want)


def four_rules(docs, block):
    """``M[2S, 2S]`` of one row by the four rules, pair by pair."""
    s = len(docs)
    sees = np.zeros((2 * s, 2 * s), bool)
    for q in range(2 * s):
        for k in range(2 * s):
            i, j = q % s, k % s
            if docs[i] != docs[j]:
                continue
            if q < s and k < s:
                sees[q, k] = i // block == j // block
            elif q < s:
                sees[q, k] = j // block < i // block
            elif k >= s:
                sees[q, k] = j // block <= i // block
    return sees


def test_mask_is_the_four_rules_enumerated():
    """``S`` 16, ``B`` 4, two documents (the second starts at token 8): the
    program's mask function over the whole doubled row, the reference's dense
    mask, and the enumeration agree; and what the enumeration says of a few
    pairs by hand."""
    s, block = 16, 4
    docs = np.repeat([0, 1], 8).astype(np.int32)
    want = four_rules(docs, block)
    p = jnp.arange(2 * s)
    docs2 = jnp.asarray(np.concatenate([docs, docs]))[None]
    got = sdar._bd_visible(p % s, p < s, p % s, p < s, docs2, docs2, block)[0]
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(reference.visible(jnp.asarray(docs), block)), want)
    noisy, clean = (lambda i: i), (lambda i: s + i)
    assert want[noisy(5), noisy(7)] and want[noisy(7), noisy(4)]  # own block
    assert not want[noisy(5), noisy(3)] and not want[noisy(5), noisy(8)]
    assert want[noisy(5), clean(3)] and not want[noisy(5), clean(4)]
    assert not want[noisy(9), clean(7)]  # another document
    assert not want[noisy(9), clean(8)] and want[noisy(13), clean(9)]
    assert want[clean(5), clean(7)] and not want[clean(5), clean(8)]
    assert not want[clean(9), clean(7)]  # another document
    assert not want[clean(5):, :s].any()  # clean never sees noisy
    # every query sees itself, so no softmax runs over nothing
    assert want[np.arange(2 * s), np.arange(2 * s)].all()
    # a document of two blocks: noisy -> noisy 8 x 4, noisy -> clean 4 x 4,
    # clean -> clean 4 x 4 + 4 x 8
    assert want.sum() == 2 * (8 * 4 + 4 * 4 + 4 * 4 + 4 * 8)


def test_counters_equal_a_numpy_count():
    sizes = sizes_of()
    _, params = weights(sizes)
    x_raw, y_raw = raw_rows()
    _, _, aux = compiled()[0](params, x_raw, y_raw)
    c = {k: np.asarray(v) for k, v in aux["counters"].items()}
    n, layers = x_raw.shape[0], sizes["num_layers"]
    docs = np.asarray(y_raw[:, :SEQ])
    masked = np.asarray(x_raw[:, :SEQ]) == VOCAB - 1
    assert c["bd_tokens"] == n * SEQ
    assert c["bd_positions_masked"] == c["loss_positions"] == masked.sum()
    assert c["bd_pairs_visible"] == layers * sum(
        four_rules(row, BLOCK).sum() for row in docs)
    # blocks of 16 queries at S = 32: noisy (16 + 16) + (32 + 16), clean 16
    # + 32 keys
    assert sdar.pairs_scored(SEQ, sizes) == 16 * (32 + 48 + 16 + 32)
    assert c["bd_pairs_scored"] == layers * n * sdar.pairs_scored(SEQ, sizes)
    assert 0 < c["bd_slots_held_masked"] < c["moe_slots_held"]
    assert c["moe_load"].shape == (layers, 2)


def test_nothing_leaks_that_may_not():
    """One document a row.  Changing clean token ``i`` leaves the noisy
    logits of ``blk(i)`` (and before) as they were and moves those of
    ``blk(i) + 1``; changing a noisy token moves its own block's noisy logits
    and no clean-copy activation."""
    _, params = weights(sizes_of())
    x_raw, y_raw = raw_rows(n=1, documents="one")
    docs = y_raw[:, :SEQ]
    run = hidden_of()
    h, logits = run(params, x_raw, docs)
    i = 13  # block 3: tokens 12..15
    other = (x_raw[0, SEQ + i] + 1) % (VOCAB - 1)
    h_c, logits_c = run(params, x_raw.at[0, SEQ + i].set(other), docs)
    upto = (i // BLOCK + 1) * BLOCK
    np.testing.assert_array_equal(np.asarray(logits_c[:, :upto]),
                                  np.asarray(logits[:, :upto]))
    moved = np.abs(np.asarray(logits_c - logits)).max(-1)[0]
    assert (moved[upto:upto + BLOCK] > 1e-4).all()
    # the clean copy is block-causal: its own block moves, none before it
    clean_moved = np.abs(np.asarray(h_c - h))[0, SEQ:].max(-1)
    assert (clean_moved[:upto - BLOCK] == 0).all()
    assert (clean_moved[upto - BLOCK:upto] > 1e-4).all()
    h_n, logits_n = run(params, x_raw.at[0, i].set(
        (x_raw[0, i] + 1) % VOCAB), docs)
    np.testing.assert_array_equal(np.asarray(h_n[:, SEQ:]),
                                  np.asarray(h[:, SEQ:]))
    moved = np.abs(np.asarray(logits_n - logits)).max(-1)[0]
    assert (moved[upto - BLOCK:upto] > 1e-4).all()  # both directions
    assert (np.delete(moved, np.s_[upto - BLOCK:upto]) == 0).all()


@pytest.mark.parametrize("fault", ["bf16_weights", "causal_in_block",
                                   "clean_block_leaked"])
def test_the_tolerance_tells_what_it_has_to(fault, monkeypatch):
    """Each planted in the program alone; the logits' comparison at ``TOL``
    has to fail."""
    _, params = weights(sizes_of())
    x_raw, y_raw = raw_rows()
    want = compiled()[1](params, x_raw, y_raw)[2]
    real = sdar._bd_visible

    def causal(q_at, q_noisy, k_at, k_noisy, *rest):
        both = q_noisy[:, None] & k_noisy[None, :]
        return real(q_at, q_noisy, k_at, k_noisy, *rest) \
            & ~(both & (k_at[None, :] > q_at[:, None]))[None]

    def leaked(q_at, q_noisy, k_at, k_noisy, q_docs, k_docs, block):
        own = (q_noisy[:, None] & ~k_noisy[None, :]
               & (k_at[None, :] // block == q_at[:, None] // block))
        return real(q_at, q_noisy, k_at, k_noisy, q_docs, k_docs, block) \
            | (own[None] & (q_docs[:, :, None] == k_docs[:, None, :]))

    if fault == "bf16_weights":
        params = {k: v.astype(jnp.bfloat16).astype(jnp.float32)
                  for k, v in params.items()}
    else:
        monkeypatch.setattr(sdar, "_bd_visible",
                            causal if fault == "causal_in_block" else leaked)
    hidden_of.cache_clear()
    try:
        got = hidden_of()(params, x_raw, y_raw[:, :SEQ])[1]
    finally:
        hidden_of.cache_clear()
    with pytest.raises(AssertionError):
        close(got, want)


def layer_weights(hq, hkv, experts, hidden=16, d=8, width=12, seed=4):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    normal = lambda *shape: 0.3 * jax.random.normal(next(keys), shape)
    return {"attn_norm": 1 + normal(hidden), "moe_norm": 1 + normal(hidden),
            "q_norm": 1 + normal(d), "k_norm": 1 + normal(d),
            "wq": normal(hidden, hq * d), "wk": normal(hidden, hkv * d),
            "wv": normal(hidden, hkv * d), "wo": normal(hq * d, hidden),
            "router": normal(hidden, experts),
            "gate": normal(experts, hidden, width),
            "up": normal(experts, hidden, width),
            "down": normal(experts, width, hidden)}


def test_sixteen_chips_shares_sum_to_the_uncut_layer():
    """The deployment in small: 16 chips share a layer, its 32 experts 16
    ways and its 16 query heads with their 4 KV heads 8 ways (each head share
    on two chips, counted once).  The program's shares add up to the
    reference's layer with every head and expert, over the doubled row."""
    hq, hkv, experts, d = 16, 4, 32, 8
    whole = layer_weights(hq, hkv, experts)
    sizes = sizes_of(num_experts=experts, experts_per_token=4,
                     q_heads_held=hq, kv_heads_held=hkv,
                     experts_held=list(range(experts)), attn_block=SEQ)
    h = jax.random.normal(jax.random.PRNGKey(9), (2, 2 * SEQ, 16))
    x_raw, y_raw = raw_rows(2)
    docs = y_raw[:, :SEQ]
    masked = jnp.pad(x_raw[:, :SEQ] == VOCAB - 1, ((0, 0), (0, SEQ)))
    ops = make_ops(lax.Precision.HIGHEST)
    named = {"layer0_" + k: v for k, v in whole.items()}

    @jax.jit
    def uncut(h, docs):
        def row(args):
            x, row_docs = args
            x = x + reference.attention(named, "layer0", reference.rms_norm(
                x, whole["attn_norm"], 1e-6), row_docs, sizes, ops)
            return x, x + reference.experts(
                named, "layer0", reference.rms_norm(x, whole["moe_norm"],
                                                    1e-6), sizes, ops)
        return lax.map(row, (h, docs))

    # a share's shapes are every share's: one compiled function a kind
    two_heads = dict(sizes, q_heads_held=2, kv_heads_held=1)
    two_experts = dict(sizes, experts_held=[0, 1])

    @jax.jit
    def heads_share(part, h, docs):
        out, _ = sdar._bd_attention(*sdar._project(part, h, two_heads), docs,
                                    two_heads)
        return jnp.dot(out, part["wo"])

    @jax.jit
    def experts_share(part, mid, masked):
        return sdar._experts_of(part, mid, masked, two_experts)

    want_mid, want = uncut(h, docs)
    mid = h
    for share in range(8):
        q = slice(2 * d * share, 2 * d * (share + 1))
        kv = slice(d * (share // 2), d * (share // 2 + 1))
        mid = mid + heads_share(
            dict(whole, wq=whole["wq"][:, q], wk=whole["wk"][:, kv],
                 wv=whole["wv"][:, kv], wo=whole["wo"][q]), h, docs)
    close(mid, want_mid, tol=1e-5)
    total, slots, marked = mid, 0, 0
    for share in range(16):
        # the share's two experts first, so that it holds "0 and 1" of a
        # router whose columns are turned with them
        turn = np.roll(np.arange(experts), -2 * share)
        part = {k: v[turn[:2]] if k in ("gate", "up", "down") else v
                for k, v in whole.items()}
        part["router"] = whole["router"][:, turn]
        y, c = experts_share(part, mid, masked)
        total, slots = total + y, slots + c["moe_slots_held"]
        marked = marked + c["moe_slots_marked"]
    assert slots == 2 * 2 * SEQ * 4
    assert marked == 4 * int(masked.sum())
    close(total, want, tol=1e-5)


def test_the_published_share_counted():
    """The cell's sizes: parameters a worker off the tree, ``forward_macs``
    by its parts, the expert rows of a worker-step and the pairs the query
    blocks score."""
    conf = json.loads((Path(reference.__file__).parents[1] / "configs"
                       / "sdar-30b-a3b.ep16-s4k.json").read_text())
    sizes = conf["sizes"]
    s = sizes["seq_len"]
    model = select_model("sdar", "tokens", sizes=sizes)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), model.dummy_input(()),
                           train=False))["params"]
    counts = {k: int(np.prod(v.shape)) for k, v in shapes.items()}
    assert len(counts) == 4 * 12 + 3
    assert sum(v for k, v in counts.items()
               if k.startswith("layer0_")) == 40_636_672
    assert sum(counts.values()) == conf["parameters_per_worker"] \
        == 240_339_968
    per_token = reference.forward_macs(sizes) / s
    head = sizes["hidden"] * sizes["vocab_held"]
    assert head == 38_895_616
    # a position of a layer: projections 2.62 M, scores and values over the
    # 2,050 keys a position sees in the mean 2.10, router 0.26, experts at
    # half a slot 2.36
    assert reference.visible_pairs(s, 4) / (2 * s) == pytest.approx(2050, 1e-3)
    position = (per_token - head) / (2 * sizes["num_layers"])
    assert position == pytest.approx(7.34e6, rel=2e-3)
    assert per_token == pytest.approx(97.6e6, rel=2e-3)
    # a worker-step is 2 rows of 8,192 positions: 4 times the 8,192 slots an
    # even router sends to 8 of 128 experts
    assert model.row_tokens(2 * s) == s and model.row_positions(2 * s) == 2 * s
    assert mellum2.moe_capacity(2 * model.row_positions(2 * s), sizes) \
        == sizes["moe_rows_per_even_slot"] * 8192
    # 24 blocks of 1,024 x 1,024: 0.375 of the doubled row's square
    assert sdar.pairs_scored(s, sizes) == 24 * 1024 ** 2 \
        == 0.375 * (2 * s) ** 2


def test_the_task_lays_rows_out_as_its_docstring_says():
    """``block_diffusion.make`` at a few hundred blocks: two int32 arrays a
    row; no clean id is ``[MASK]`` and a position is masked iff its noisy id
    is; a document's length is a multiple of the block, so no block straddles
    two; ``t`` is one number a block in ``[noise_t_min, 1]`` and the masked
    share follows it; the seed decides everything."""
    sizes = sizes_of(seq_len=64, vocab_held=48, mask_id=47)
    data = block_diffusion.make(3, 40, 4, {"sizes": sizes})
    again = block_diffusion.make(3, 40, 4, {"sizes": sizes})
    other = block_diffusion.make(4, 40, 4, {"sizes": sizes})
    assert all(np.array_equal(data[k], again[k]) for k in data)
    assert not np.array_equal(data["x_train"], other["x_train"])
    x, y = data["x_train"], data["y_train"]
    assert x.dtype == y.dtype == np.int32 and x.shape == y.shape == (40, 128)
    noisy, clean, docs, t = x[:, :64], x[:, 64:], y[:, :64], y[:, 64:]
    assert clean.min() >= 0 and clean.max() == 46  # every id but [MASK]
    masked = noisy == 47
    assert np.array_equal(noisy[~masked], clean[~masked])
    blocks = lambda a: a.reshape(40, 16, 4)
    assert (blocks(docs) == blocks(docs)[..., :1]).all()
    assert (blocks(t) == blocks(t)[..., :1]).all()
    assert np.ceil(0.05 * 65536) <= t.min() and t.max() <= 65536
    assert (np.diff(docs.reshape(-1)) >= 0).all() and docs.max() > 0
    # t ~ U[0.05, 1]: its mean 0.525 is the masked share; 640 blocks here
    assert abs(t.mean() / 65536 - 0.525) < 0.04
    assert abs(masked.mean() - t.mean() / 65536) < 0.03
    inputs, targets = block_diffusion.prepare(jnp.asarray(x), jnp.asarray(y),
                                              {"sizes": sizes})
    assert inputs["ids"].shape == (40, 128) and inputs["docs"].shape == (40, 64)
    assert float(targets["weight"].max()) <= 20.0 + 1e-3
    with pytest.raises(ValueError, match="multiple of block_length"):
        block_diffusion.make(3, 4, 1, {"sizes": dict(sizes, seq_len=66)})


def test_a_next_token_row_is_refused():
    model, params = weights(sizes_of())
    odd = jnp.zeros((2, SEQ + 1), jnp.int32)
    with pytest.raises(ValueError, match="noisy ids then clean ids"):
        model.apply({"params": params}, odd, odd, method="batch_loss")


def test_trains_by_name_through_train(tmp_path):
    """``model="sdar"`` on the normal path, on what the task's ``make``
    wrote: the loss falls, nothing retraces, the ``bd_*`` counters ride each
    period's record beside the expert layer's, ``dispatch`` counts ``S``
    tokens a row, and evaluation weighs its batches by their masked
    positions."""
    from matcha_tpu.data import load_tokens
    from matcha_tpu.train import TrainConfig, train

    sizes = sizes_of(hidden=32, expert_width=24, vocab_held=48, mask_id=47)
    data = block_diffusion.make(11, 2 * 2 * 3, 4, {"sizes": sizes})
    np.savez(tmp_path / "data.npz", **data)
    assert load_tokens(str(tmp_path / "data.npz")).x_train.shape == (
        12, 2 * SEQ)
    config = TrainConfig(
        name="bd", model="sdar", dataset="tokens",
        datasetRoot=str(tmp_path / "data.npz"), model_kwargs={"sizes": sizes},
        num_workers=2, graphid=None, topology="chain", batch_size=2, epochs=3,
        lr=0.05, warmup=False, matcha=True, budget=0.5, seed=3, eval_every=1,
        remat=True, devices=1, save=True, savePath=str(tmp_path))
    result = train(config, boundary_hook=lambda seam: None)
    losses = [h["loss"] for h in result.history]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    events = result.recorder.events
    assert "retrace" not in [e["kind"] for e in events]
    records = [e for e in events if e["kind"] == "spans"]
    assert len(records) == 3
    masked = int(np.sum(data["x_train"][:, :SEQ] == 47))
    for r, h in zip(records, result.history):
        c = r["counters"]
        assert set(c) == {
            "loss_positions", "moe_slots_held", "moe_rows_computed",
            "moe_rows_multiplied",
            "moe_load", "bd_tokens", "bd_positions_masked",
            "bd_pairs_visible", "bd_pairs_scored", "bd_slots_held_masked"}
        assert c["bd_tokens"] == 12 * SEQ
        assert c["bd_positions_masked"] == masked
        assert 0 < c["bd_pairs_visible"] < c["bd_pairs_scored"]
        dispatch = [s for s in r["spans"] if s["name"] == "dispatch"]
        assert sum(s["tokens"] for s in dispatch) == 12 * SEQ
        assert 0 < h["test_loss_mean"] < 20 * np.log(48)
    backend = next(e for e in events if e["kind"] == "backend")
    # 2 rows of 2 S positions a worker-step through the expert layer
    assert backend["expert_products"]["products"][0]["rows"] \
        == mellum2.moe_capacity(2 * 2 * SEQ, sizes)
