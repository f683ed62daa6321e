"""The decoder with learned sparse attention (``models/keye_vl2.py``) against
the benchmark's plain reference (``chipbench/reference/keye_vl2.py``): the
loss, both of its terms and every gradient leaf, with rows shorter and longer
than ``index_topk`` and packed documents; blocked against unblocked; with
every key kept, dense grouped-query attention and ``mellum2``'s ``full`` path
on the same weights; the shares of 16 chips added back up to the uncut layer;
each loss term's gradient zero where the other's parameters are; the
counters against a NumPy count of the mask.  Small sizes, seeded weights,
float32 products at ``highest``.  (The index scores by key tiles are held to
the form that scores every tile in ``test_keye_vl2_tiles.py``, beside this
file, which takes its rows and weights from here.)"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from chipbench.reference import keye_vl2 as reference
from chipbench.reference.layers import make_ops
from chipbench.tasks import next_token_indexed
from matcha_tpu.models import keye_vl2, mellum2, select_model

SEQ = 32
INDEXER = ("idx_wq", "idx_wk", "idx_ww")


def sizes_of(topk=8, **more):
    sizes = {
        "hidden": 16, "head_dim": 8, "q_heads_held": 4, "kv_heads_held": 2,
        "num_layers": 2, "rope_theta": 10_000_000,
        "indexer_heads": 4, "indexer_head_dim": 4, "index_topk": topk,
        "num_experts": 8, "experts_per_token": 2, "experts_held": [0, 1],
        "expert_width": 12, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
        "vocab_held": 24, "seq_len": SEQ, "attn_block": 16, "loss_chunk": 16,
    }
    sizes.update(more)
    return sizes


def rows(documents, n=3, seed=0):
    """(ids, document numbers) ``[n, SEQ + 1]``: one document a row, or
    documents packed so that every row holds boundaries (``many``: a
    boundary every six positions or so, so that whole key tiles hide from
    whole query blocks; ``unsorted``: those, their numbers shuffled, two of
    them alike, so that numbers fall as well as rise and one comes back)."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 24, (n, SEQ + 1), dtype=np.int32)
    if documents == "one":
        docs = np.repeat(np.arange(n, dtype=np.int32)[:, None], SEQ + 1, 1)
    else:
        boundaries = n if documents == "packed" else n * (SEQ + 1) // 6
        cuts = np.sort(rng.choice(np.arange(1, n * (SEQ + 1)), boundaries,
                                  False))
        docs = np.searchsorted(cuts, np.arange(n * (SEQ + 1)), "right") \
            .astype(np.int32).reshape(n, SEQ + 1)
        if documents == "unsorted":
            numbers = rng.permutation(boundaries + 1).astype(np.int32)
            docs = np.minimum(numbers, boundaries - 1)[docs]
    return jnp.asarray(ids), jnp.asarray(docs)


def weights(sizes, seed=1):
    """Seeded weights far from zero (norm scales near 1)."""
    model = select_model("keye_vl2", "tokens", sizes=sizes, remat=True)
    params = model.init(jax.random.PRNGKey(seed), model.dummy_input(()),
                        train=False)["params"]
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(params))
    return model, {k: 0.3 * jax.random.normal(key, v.shape)
                   + (1.0 if k.endswith("norm") else 0.0)
                   for key, (k, v) in zip(keys, sorted(params.items()))}


def one_term(model, term):
    """``(params, ids, docs) -> L_LM`` (term 0) or ``L_I`` (term 1) of the
    program, each computed on its own."""
    def of(m, x_raw, y_raw):
        ids, docs, targets = mellum2._next_ids(x_raw, y_raw)
        h, c = m.hidden(ids, docs)
        if term:
            return c["dsa_kl_sum"] / c["dsa_queries"]
        return mellum2._head_loss(h, m.head, targets, m.sizes)[0]
    return lambda params, ids, docs: model.apply({"params": params}, ids,
                                                 docs, method=of)


@functools.lru_cache(maxsize=None)
def compiled(topk, block=16):
    """(program, reference): jitted ``(params, ids, docs) -> (logits, (L_LM,
    L_I), grads of their sum, ...)``, compiled once a shape."""
    sizes = sizes_of(topk, attn_block=block)
    model = select_model("keye_vl2", "tokens", sizes=sizes, remat=True)
    ops = make_ops(lax.Precision.HIGHEST)

    def program(params, ids, docs):
        logits = model.apply({"params": params}, ids[:, :-1], docs[:, :-1],
                             method="logits")
        (total, aux), grads = jax.value_and_grad(
            lambda p: model.apply({"params": p}, ids, docs,
                                  method="batch_loss"), has_aux=True)(params)
        return logits, total, grads, aux["counters"]

    def plain(params, ids, docs):
        x, targets = next_token_indexed.prepare(ids, docs, None)

        def loss_of(p):
            out, _ = reference.forward(p, {}, x, sizes, ops)
            return next_token_indexed.loss(out, targets), out

        (total, out), grads = jax.value_and_grad(loss_of, has_aux=True)(
            params)
        return out["logits"], total, grads, out["indexer_kl"]

    return jax.jit(program), jax.jit(plain)


def close(got, want, tol=2e-4, name=""):
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=tol,
                               err_msg=name)


@pytest.mark.parametrize("documents", ["one", "packed"])
@pytest.mark.parametrize("topk", [8, SEQ + 8], ids=["rows>topk", "rows<topk"])
def test_program_agrees_with_the_plain_reference(topk, documents):
    _, params = weights(sizes_of(topk))
    ids, docs = rows(documents)
    program, plain = compiled(topk)
    with jax.default_matmul_precision("highest"):
        logits, total, grads, counters = program(params, ids, docs)
        want_logits, want_total, want_grads, want_kl = plain(params, ids, docs)
    close(logits, want_logits, name="logits")
    kl = counters["dsa_kl_sum"] / counters["dsa_queries"]
    assert float(want_kl) > 1e-3  # the second term is there to be compared
    np.testing.assert_allclose(kl, want_kl, rtol=2e-4)
    np.testing.assert_allclose(total, want_total, rtol=2e-4)
    np.testing.assert_allclose(total - kl, want_total - want_kl, rtol=2e-4)
    assert set(grads) == set(want_grads)
    for name, want in want_grads.items():
        assert float(jnp.max(jnp.abs(want))) > 0, name
        close(grads[name], want_grads[name], name=name)


@pytest.mark.parametrize("documents", ["one", "packed"])
def test_blocked_equals_unblocked(documents):
    _, params = weights(sizes_of(8))
    ids, docs = rows(documents)
    with jax.default_matmul_precision("highest"):
        blocked = compiled(8, 16)[0](params, ids, docs)
        whole = compiled(8, SEQ)[0](params, ids, docs)
    for counters in (blocked[3], whole[3]):  # they count the blocking itself
        counters.pop("dsa_key_tiles"), counters.pop("dsa_key_tiles_scored")
    for got, want in zip(jax.tree_util.tree_leaves(blocked),
                         jax.tree_util.tree_leaves(whole)):
        close(got, want, tol=1e-5)


@pytest.mark.parametrize("documents", ["one", "packed"])
def test_counters_equal_a_numpy_count_of_the_mask(documents):
    topk, layers = 8, 2
    _, params = weights(sizes_of(topk))
    ids, docs = rows(documents)
    counters = compiled(topk)[0](params, ids, docs)[3]
    d = np.asarray(docs)[:, :-1]
    t = np.arange(SEQ)
    sees = (t[None, :, None] >= t[None, None, :]) \
        & (d[:, :, None] == d[:, None, :])
    visible = sees.sum(-1)
    assert counters["dsa_queries"] == layers * visible.size
    assert counters["dsa_queries_selecting"] == layers * (visible > topk).sum()
    assert counters["dsa_keys_visible"] == layers * visible.sum()
    assert counters["dsa_keys_kept"] == layers * np.minimum(
        visible, topk).sum()
    assert counters["loss_positions"] == np.sum(
        np.asarray(docs)[:, 1:] == np.asarray(docs)[:, :-1])
    assert np.asarray(counters["moe_load"]).shape == (layers, 2)
    assert counters["moe_slots_held"] == np.asarray(
        counters["moe_load"]).sum()


@pytest.mark.parametrize("term, reaches", [(0, "the rest"), (1, "indexer")],
                         ids=["cross_entropy", "indexer_kl"])
@pytest.mark.parametrize("topk", [8, SEQ + 8], ids=["rows>topk", "rows<topk"])
def test_each_loss_term_reaches_its_own_parameters_alone(topk, term, reaches):
    """The cross-entropy's gradient is exactly zero on every indexer weight
    (the selection is discrete), the indexer's KL's on everything else (its
    input and its target are detached)."""
    model, params = weights(sizes_of(topk))
    ids, docs = rows("packed")
    with jax.default_matmul_precision("highest"):
        grads = jax.jit(jax.grad(one_term(model, term)))(params, ids, docs)
    for name, g in grads.items():
        indexer = name.endswith(INDEXER)
        if indexer == (reaches == "indexer"):
            assert float(jnp.max(jnp.abs(g))) > 0, name
        else:
            assert float(jnp.max(jnp.abs(g))) == 0.0, name


def layer_weights(hq, hkv, experts, seed=3, hid=16, d=8, heads=4, di=4,
                  width=12):
    shapes = {"wq": (hid, hq * d), "wk": (hid, hkv * d), "wv": (hid, hkv * d),
              "wo": (hq * d, hid), "idx_wq": (hid, heads * di),
              "idx_wk": (hid, di), "idx_ww": (hid, heads),
              "router": (hid, experts), "gate": (experts, hid, width),
              "up": (experts, hid, width), "down": (experts, width, hid)}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    p = {k: 0.5 * jax.random.normal(key, shape)
         for key, (k, shape) in zip(keys, shapes.items())}
    return dict(p, attn_norm=jnp.ones(hid), moe_norm=jnp.ones(hid))


@pytest.mark.parametrize("documents", ["one", "packed"])
def test_every_key_kept_is_dense_attention_and_mellum2s_full_path(documents):
    """``index_topk`` at least the row length: the layer's attention is
    dense grouped-query attention, which ``mellum2``'s ``full`` layer
    computes from the same weights (YaRN at factor 1 is plain RoPE)."""
    sizes = sizes_of(SEQ)
    p = layer_weights(4, 2, 8)
    h = jax.random.normal(jax.random.PRNGKey(9), (2, SEQ, 16))
    docs = rows(documents, 2)[1][:, :-1]
    plain_rope = {"factor": 1.0, "original_max_position_embeddings": 8192,
                  "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.0}
    with jax.default_matmul_precision("highest"):
        out, counters = keye_vl2._sparse_attention(
            *keye_vl2._project(p, h, sizes), docs, sizes)
        got = jnp.dot(out, p["wo"])
        want = mellum2._attention(
            p, mellum2._rms_norm(h, p["attn_norm"], 1e-6), docs, "full",
            dict(sizes, yarn=plain_rope))
    assert counters["dsa_queries_selecting"] == 0
    assert counters["dsa_keys_kept"] == counters["dsa_keys_visible"]
    close(got, want, tol=1e-5)


def test_sixteen_chips_shares_sum_to_the_uncut_layer():
    """The deployment in small: 16 chips share a layer, its 32 experts 16
    ways and its 16 query heads with their 4 KV heads 8 ways (each head share
    on two chips, counted once); every chip computes the whole indexer, so
    its choice is the same everywhere and enters once.  The program's shares
    add up to the reference's layer with every head and expert."""
    hq, hkv, experts, topk, d = 16, 4, 32, 8, 8
    whole = layer_weights(hq, hkv, experts)
    sizes = sizes_of(topk, num_experts=experts, experts_per_token=4,
                     q_heads_held=hq, kv_heads_held=hkv,
                     experts_held=list(range(experts)), attn_block=SEQ)
    h = jax.random.normal(jax.random.PRNGKey(9), (2, SEQ, 16))
    docs = rows("packed", 2)[1][:, :-1]
    ops = make_ops(lax.Precision.HIGHEST)
    named = {"layer0_" + k: v for k, v in whole.items()}
    with jax.default_matmul_precision("highest"):
        def uncut(row):
            x, row_docs = row
            a, _ = reference.attention(named, "layer0", reference.rms_norm(
                x, whole["attn_norm"], 1e-6), row_docs, sizes, ops)
            x = x + a
            return x, x + reference.experts(named, "layer0", reference.rms_norm(
                x, whole["moe_norm"], 1e-6), sizes, ops)

        want_mid, want = lax.map(uncut, (h, docs))
        mid = h
        for share in range(8):
            q = slice(2 * d * share, 2 * d * (share + 1))
            kv = slice(d * (share // 2), d * (share // 2 + 1))
            part = dict(whole, wq=whole["wq"][:, q], wk=whole["wk"][:, kv],
                        wv=whole["wv"][:, kv], wo=whole["wo"][q])
            held = dict(sizes, q_heads_held=2, kv_heads_held=1)
            out, _ = keye_vl2._sparse_attention(
                *keye_vl2._project(part, h, held), docs, held)
            mid = mid + jnp.dot(out, part["wo"])
        close(mid, want_mid, tol=1e-5)
        total, slots = mid, 0
        for share in range(16):
            held = [2 * share, 2 * share + 1]
            part = {k: v[held[0]:held[1] + 1] if k in ("gate", "up", "down")
                    else v for k, v in whole.items()}
            y, c = keye_vl2._experts_of(part, mid,
                                        dict(sizes, experts_held=held))
            total, slots = total + y, slots + c["moe_slots_held"]
    assert slots == 2 * SEQ * 4
    close(total, want, tol=1e-5)


def test_selection_is_exact_with_ties_to_the_lower_key():
    """Scores with many exact ties (zeros of both signs among them) and rows
    that see fewer keys than ``k``: the kept set is ``lax.top_k``'s."""
    rng = np.random.default_rng(0)
    k, s = 5, 24
    scores = rng.choice([-1.5, -0.0, 0.0, 0.25, 0.25, 2.0, 1e-30, -1e-30],
                        (2, 7, s)).astype(np.float32)
    sees = rng.random((2, 7, s)) < 0.6
    sees[0, 0] = False
    sees[0, 0, :3] = True  # fewer visible than k
    masked = jnp.where(sees, scores, -jnp.inf)
    keep = keye_vl2._select(masked, jnp.asarray(sees), k)
    chosen = lax.top_k(masked, k)[1]
    want = np.zeros(sees.shape, bool)
    np.put_along_axis(want, np.asarray(chosen), True, axis=-1)
    np.testing.assert_array_equal(np.asarray(keep), want & sees)


def test_forward_macs_counts_the_published_share():
    """The cell's sizes: 38.9 MMAC a token in the head and about 13 in each
    of the four layers, the indexer's scores over the causal half of a row
    the largest part of a layer (``forward_macs``' docstring)."""
    import json
    from pathlib import Path

    conf = json.loads((Path(reference.__file__).parents[1] / "configs"
                       / "keye-vl2-30b-a3b.ep16-s8k.json").read_text())
    sizes = conf["sizes"]
    per_token = reference.forward_macs(sizes) / sizes["seq_len"]
    head = sizes["hidden"] * sizes["vocab_held"]
    assert head == 38_895_616
    index_scores = 16 * 64 * (sizes["seq_len"] + 1) / 2
    assert index_scores == pytest.approx(4.195e6, rel=1e-3)
    layer = (per_token - head) / sizes["num_layers"]
    assert layer == pytest.approx(13.52e6, rel=5e-3)
    # parameters a worker, off the tree
    model = select_model("keye_vl2", "tokens", sizes=sizes)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), model.dummy_input(()),
                           train=False))["params"]
    counts = {k: int(np.prod(v.shape)) for k, v in shapes.items()}
    assert sum(v for k, v in counts.items()
               if k.startswith("layer0_")) == 42_897_408
    assert sum(counts.values()) == conf["parameters_per_worker"] \
        == 249_382_912
    # the grouped expert products' rows for a worker-step of 2 rows: 4 times
    # the 8,192 slots an even router sends to 8 of 128 experts
    assert mellum2.moe_capacity(2 * sizes["seq_len"], sizes) == 32_768


def test_trains_by_name_through_train(tmp_path):
    """``model="keye_vl2"`` on the normal path: the two-term loss falls,
    nothing retraces, the ``dsa_*`` counters ride each period's record
    beside the expert layer's, and evaluation gives the held-out loss."""
    from chipbench.tasks import next_token
    from matcha_tpu.train import TrainConfig, train

    sizes = sizes_of(8, hidden=32, expert_width=24, vocab_held=48)
    data = next_token.make(11, 2 * 2 * 3, 4, {"sizes": sizes})
    np.savez(tmp_path / "data.npz", **data)
    config = TrainConfig(
        name="dsa", model="keye_vl2", dataset="tokens",
        datasetRoot=str(tmp_path / "data.npz"), model_kwargs={"sizes": sizes},
        num_workers=2, graphid=None, topology="chain", batch_size=2, epochs=3,
        lr=0.05, warmup=False, matcha=True, budget=0.5, seed=3, eval_every=1,
        remat=True, devices=1, save=True, savePath=str(tmp_path))
    result = train(config, boundary_hook=lambda seam: None)
    losses = [h["loss"] for h in result.history]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert "retrace" not in [e["kind"] for e in result.recorder.events]
    records = [e for e in result.recorder.events if e["kind"] == "spans"]
    assert len(records) == 3
    queries = 2 * 3 * 2 * 2 * SEQ  # layers x steps x workers x rows x S
    for r, h in zip(records, result.history):
        c = r["counters"]
        assert set(c) == {
            "loss_positions", "moe_slots_held", "moe_rows_computed",
            "moe_rows_multiplied",
            "moe_load", "dsa_queries", "dsa_queries_selecting",
            "dsa_keys_visible", "dsa_keys_kept", "dsa_kl_sum",
            "dsa_key_tiles", "dsa_key_tiles_scored"}
        assert c["dsa_queries"] == queries
        # blocks of 16 at S = 32, a tile a block: 1 + 2 tiles a layer-row
        assert c["dsa_key_tiles"] == queries // SEQ * 3
        assert queries // SEQ * 2 <= c["dsa_key_tiles_scored"] \
            <= c["dsa_key_tiles"]
        assert 0 < c["dsa_queries_selecting"] < queries
        assert 0 < c["dsa_keys_kept"] < c["dsa_keys_visible"]
        assert c["dsa_kl_sum"] > 0
        assert 0 < h["test_loss_mean"] < 2 * np.log(48)
