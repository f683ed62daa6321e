import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matcha_tpu.ops import (
    WorkerFlattener,
    batched_random_k,
    batched_top_k,
    dense_from_sparse,
    make_flattener,
    scatter_rows,
    select_compressor,
    top_k_ratio_size,
)


def make_tree(n=4, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "dense": {"w": rng.normal(size=(n, 3, 5)).astype(np.float32),
                  "b": rng.normal(size=(n, 5)).astype(np.float32)},
        "scale": rng.normal(size=(n,)).astype(np.float32).reshape(n),
    }


def test_flattener_roundtrip():
    tree = make_tree()
    fl = make_flattener(tree)
    assert fl.dim == 3 * 5 + 5 + 1
    flat = fl.flatten(tree)
    assert flat.shape == (4, 21) and flat.dtype == jnp.float32
    back = fl.unflatten(flat)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        tree, back)


def test_flattener_scalar_leaf_and_dtype_restore():
    n = 3
    tree = {"a": np.ones((n, 2), np.float32), "c": np.arange(n, dtype=np.float32)}
    fl = WorkerFlattener(tree)
    back = fl.unflatten(fl.flatten(tree))
    assert back["c"].shape == (n,)


def test_flattener_rejects_mismatched_leading_axis():
    with pytest.raises(ValueError):
        WorkerFlattener({"a": np.ones((3, 2)), "b": np.ones((4, 2))})
    fl = WorkerFlattener({"a": np.ones((3, 2), np.float32)})
    with pytest.raises(ValueError):
        fl.unflatten(jnp.ones((3, 5)))


def test_top_k_ratio_semantics():
    # reference parity: ratio=0.9 keeps the top 1-ratio fraction, computed as
    # int(n*(1-ratio)) — float repr makes that 9 (not 10) for n=100, exactly
    # like torch's int() truncation in compressors.py:10
    assert top_k_ratio_size(100, 0.9) == int(100 * (1 - 0.9)) == 9
    assert top_k_ratio_size(100, 0.5) == 50
    assert top_k_ratio_size(10, 0.99) == 1  # max(1, ...)


def test_batched_top_k_picks_largest_magnitude():
    x = jnp.asarray([[1.0, -5.0, 0.1, 3.0], [0.0, 0.2, -0.1, 0.05]])
    vals, idx = batched_top_k(x, ratio=0.5)  # keep 2
    assert vals.shape == (2, 2) and idx.dtype == jnp.int32
    got0 = set(np.asarray(idx)[0].tolist())
    assert got0 == {1, 3}
    # values keep sign
    dense = np.asarray(dense_from_sparse(idx, vals, 4))
    np.testing.assert_allclose(dense[0], [0, -5.0, 0, 3.0])


def test_batched_random_k_statistics():
    key = jax.random.PRNGKey(0)
    x = jnp.ones((2, 50))
    k = top_k_ratio_size(50, 0.8)
    vals, idx = batched_random_k(x, ratio=0.8, key=key)
    assert vals.shape == (2, k)
    for row in np.asarray(idx):
        assert len(set(row.tolist())) == k  # no replacement


def test_scatter_rows_per_worker_scale():
    base = jnp.zeros((2, 5))
    idx = jnp.asarray([[0, 2], [1, 1]], jnp.int32)
    vals = jnp.asarray([[1.0, 2.0], [3.0, 4.0]])
    out = np.asarray(scatter_rows(base, idx, vals, jnp.asarray([2.0, 0.5])))
    np.testing.assert_allclose(out[0], [2.0, 0, 4.0, 0, 0])
    # duplicate index accumulates (scatter-add semantics)
    np.testing.assert_allclose(out[1], [0, 3.5, 0, 0, 0])


def test_select_compressor():
    assert select_compressor("top_k") is batched_top_k
    with pytest.raises(KeyError):
        select_compressor("zip")


def test_profiler_trace_writes_events(tmp_path):
    import jax
    import jax.numpy as jnp

    from matcha_tpu.utils import SpanRecorder, trace

    with trace(str(tmp_path)):
        with SpanRecorder().span("tiny-matmul"):
            out = jax.jit(lambda a: a @ a)(jnp.ones((8, 8)))
            jax.block_until_ready(out)
    # the profiler lays out <dir>/plugins/profile/<run>/*.xplane.pb
    produced = list(tmp_path.rglob("*.xplane.pb"))
    assert produced, f"no trace files under {tmp_path}"


def test_quantize_stochastic_unbiased_and_bounded():
    from matcha_tpu.ops import quantize_stochastic

    x = jnp.asarray(np.random.default_rng(3).normal(size=(4, 257)), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), 400)
    qs = jax.vmap(lambda k: quantize_stochastic(x, 4, k))(keys)
    # unbiased: the average over draws recovers x
    np.testing.assert_allclose(np.asarray(qs.mean(0)), np.asarray(x),
                               atol=3e-2, rtol=0)
    # each draw stays on the quantization grid within one level of x
    scale = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
    assert float(jnp.abs(qs - x).max()) <= (scale / 15).max() + 1e-6
    # zero rows stay exactly zero
    z = quantize_stochastic(jnp.zeros((2, 8)), 8, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(z), 0.0)


def test_top_k_q8_registry_and_selection():
    from matcha_tpu.ops import batched_top_k_q8, select_compressor

    assert select_compressor("top_k_q8") is batched_top_k_q8
    x = jnp.asarray(np.random.default_rng(4).normal(size=(3, 40)), jnp.float32)
    vals, idx = batched_top_k_q8(x, ratio=0.8, key=jax.random.PRNGKey(2))
    ref_vals, ref_idx = batched_top_k(x, ratio=0.8)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
    # quantized payload stays within one 8-bit level of the selected values
    scale = np.abs(np.asarray(ref_vals)).max(axis=-1, keepdims=True)
    assert np.abs(np.asarray(vals) - np.asarray(ref_vals)).max() <= (scale / 255).max() + 1e-6


def test_top_k_approx_registry_and_contraction():
    """``top_k_approx`` (jax.lax.approx_max_k — the TPU-native PartialReduce
    lowering): same (x, ratio, key) registry signature, k entries selected by
    magnitude, and at least the δ-contraction CHOCO's theory needs — checked
    against the exact top-k's energy capture at a 5% recall slack."""
    from matcha_tpu.ops import batched_top_k_approx, select_compressor

    assert select_compressor("top_k_approx") is batched_top_k_approx
    x = jnp.asarray(np.random.default_rng(7).normal(size=(4, 257)), jnp.float32)
    vals, idx = batched_top_k_approx(x, ratio=0.8, key=None)
    k = max(1, int(257 * 0.2))
    assert vals.shape == (4, k) and idx.shape == (4, k)
    assert idx.dtype == jnp.int32
    # selected values are the original entries at the selected coordinates
    np.testing.assert_array_equal(
        np.asarray(vals), np.take_along_axis(np.asarray(x), np.asarray(idx), -1))
    # indices are distinct per row (a valid sparsification support)
    for row in np.asarray(idx):
        assert len(set(row.tolist())) == k
    # energy-capture floor at the 5% recall slack.  NOTE: on CPU (this
    # suite) approx_max_k falls back to exact top-k, so this bound is loose
    # here by construction — the real approximation quality is measured
    # on-device by benchmarks/encode_bench.py (approx_recall_vs_exact /
    # approx_energy_capture_vs_exact fields), not by this unit test.
    exact_vals, _ = batched_top_k(x, ratio=0.8)
    k95 = int(np.ceil(0.95 * k))
    exact95 = np.sort(np.abs(np.asarray(exact_vals)), axis=-1)[:, -k95:]
    assert (np.sum(np.asarray(vals) ** 2, -1)
            >= np.sum(exact95 ** 2, -1) - 1e-5).all()
