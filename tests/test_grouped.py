"""The expert layer's grouped products (``matcha_tpu/ops/grouped.py``, PR 38)
under the Pallas interpreter against ``lax.ragged_dot`` /
``lax.ragged_dot_general``: each form over uneven groups, an empty group, a
group boundary inside a row tile, the padded last group and tiles that split
the contraction or the width; the tile chooser; the fallback and its reason;
``mellum2._grouped_bf16``'s gradients through the kernels against the stock
products it ran until PR 38."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from matcha_tpu.models import mellum2
from matcha_tpu.ops import grouped

ROWS, K, N, EXPERTS = 512, 256, 384, 4
BF = jnp.bfloat16
#: group sizes over 512 rows in tiles of 128 (they sum to the rows: the
#: expert layer counts the rows past its last slot with the last expert)
GROUPS = {
    "uneven": [100, 37, 300, 75],
    "on_tile_boundaries": [128, 128, 0, 256],
    "an_empty_group_between_and_last": [200, 0, 312, 0],
    "boundaries_inside_one_tile": [130, 3, 2, 377],
    "padded_last_group": [40, 30, 20, 422],
    "one_group_holds_every_row": [0, 0, 512, 0],
}
#: (tm, tk, tn): whole widths; the contraction split; the width split; the
#: rows in one tile (every group shares it)
TILES = [(128, K, N), (128, 128, N), (256, K, 128), (512, 128, 128)]
#: float32 sums of up to 512 bfloat16 products of unit normals, in another
#: order: 512 x 2^-24 x the largest partial sum (about 60) is 2e-3 at worst
#: and the typical gap 3e-5; a wrong row or group moves a sum by 1 or more
TOL = dict(rtol=1e-5, atol=2e-4)


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(38)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), BF)
    return {"lhs": draw(ROWS, K), "rows_n": draw(ROWS, N),
            "weights": draw(EXPERTS, K, N)}


def _stock(form, a, b, sizes):
    if form == "gmm":
        return lax.ragged_dot(a, b, sizes, preferred_element_type=jnp.float32)
    if form == "gmm_transposed":
        return lax.ragged_dot(a, jnp.swapaxes(b, 1, 2), sizes,
                              preferred_element_type=jnp.float32)
    return lax.ragged_dot_general(a, b, sizes, grouped._BY_ROWS,
                                  preferred_element_type=jnp.float32)


def _kernel(form, a, b, sizes, tiles):
    if form == "tgmm":
        return grouped.moe_tgmm(a, b, sizes, tiles=tiles, interpret=True)
    if form == "gmm_transposed" and tiles:
        tiles = (tiles[0], tiles[2], tiles[1])  # it contracts the width N
    return grouped.moe_gmm(a, b, sizes, transposed=form == "gmm_transposed",
                           tiles=tiles, interpret=True)


def _args(form, operands):
    return {"gmm": (operands["lhs"], operands["weights"]),
            "gmm_transposed": (operands["rows_n"], operands["weights"]),
            "tgmm": (operands["lhs"], operands["rows_n"])}[form]


@pytest.mark.parametrize("tiles", TILES, ids=lambda t: "x".join(map(str, t)))
@pytest.mark.parametrize("case", list(GROUPS))
def test_forward_product_is_the_ragged_dot(case, tiles, operands):
    sizes = jnp.asarray(GROUPS[case], jnp.int32)
    a, b = _args("gmm", operands)
    got = _kernel("gmm", a, b, sizes, tiles)
    assert got.shape == (ROWS, N) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, _stock("gmm", a, b, sizes), **TOL)


@pytest.mark.parametrize("tiles", TILES, ids=lambda t: "x".join(map(str, t)))
@pytest.mark.parametrize("case", list(GROUPS))
def test_transposed_weights_product_is_the_ragged_dot_of_the_swapped(
        case, tiles, operands):
    sizes = jnp.asarray(GROUPS[case], jnp.int32)
    a, b = _args("gmm_transposed", operands)
    got = _kernel("gmm_transposed", a, b, sizes, tiles)
    assert got.shape == (ROWS, K) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, _stock("gmm_transposed", a, b, sizes),
                               **TOL)


@pytest.mark.parametrize("tiles", TILES, ids=lambda t: "x".join(map(str, t)))
@pytest.mark.parametrize("case", list(GROUPS))
def test_weight_gradient_product_is_the_ragged_dot_general(case, tiles,
                                                           operands):
    sizes = jnp.asarray(GROUPS[case], jnp.int32)
    a, b = _args("tgmm", operands)
    got = _kernel("tgmm", a, b, sizes, tiles)
    assert got.shape == (EXPERTS, K, N) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, _stock("tgmm", a, b, sizes), **TOL)
    for e, size in enumerate(GROUPS[case]):
        if size == 0:  # an expert that took no slot: exactly zero
            assert not np.any(np.asarray(got[e]))


@pytest.mark.parametrize("form", grouped.FORMS)
def test_rows_of_other_groups_never_reach_a_product(form, operands):
    """A tile that groups share is masked, not weighted: a NaN in another
    group's rows stays out of this group's sums."""
    sizes = jnp.asarray([130, 126, 250, 6], jnp.int32)
    a, b = _args(form, operands)
    poisoned = a.at[130:256].set(jnp.nan)  # all of group 1, and only it
    got = np.asarray(_kernel(form, poisoned, b, sizes, (128, 128, 128)))
    clean = np.asarray(_kernel(form, a, b, sizes, (128, 128, 128)))
    if form == "tgmm":
        assert np.all(np.isnan(got[1]))
        others = [0, 2, 3]
    else:
        assert np.all(np.isnan(got[130:256]))
        others = np.r_[0:130, 256:ROWS]
    np.testing.assert_array_equal(got[others], clean[others])


# ------------------------------------------------------------ the tile chooser

#: (rows, hidden, expert width) of the three token cells
CELL_SHAPES = {"mellum": (32768, 2304, 896), "keye": (32768, 2048, 768),
               "qwen3_next": (10240, 2048, 512)}


@pytest.mark.parametrize("form", grouped.FORMS)
@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_tiles_of_the_cells_shapes_are_whole_widths_inside_the_budget(
        cell, form):
    rows, hidden, width = CELL_SHAPES[cell]
    for k, n in ((hidden, width), (width, hidden)):
        tm, tk, tn = grouped.choose_tiles(form, rows, k, n, BF, BF)
        assert (tm, tk, tn) == (512, k, n)
        assert grouped.vmem_bytes(form, (tm, tk, tn), BF, BF, k) \
            <= grouped.VMEM_BUDGET


def test_a_smaller_budget_splits_the_contraction_then_the_width():
    """The whole width where one lane-wide tile of the contraction fits
    beside it, then the largest divisor of the contraction in whole lanes;
    under less, a divisor of the width."""
    budgets = (48, 12, 8, 4, 2)
    picks = [grouped.choose_tiles("gmm", 32768, 2304, 896, BF, BF,
                                  budget=mib * 2 ** 20) for mib in budgets]
    assert picks == [(512, 2304, 896), (512, 768, 896), (512, 128, 896),
                     (512, 1152, 128), (512, 384, 128)]
    for mib, tiles in zip(budgets, picks):
        assert grouped.vmem_bytes("gmm", tiles, BF, BF, 2304) <= mib * 2 ** 20
    assert grouped.choose_tiles(
        "gmm", 32768, 2304, 896, BF, BF, budget=2 ** 20) == (
            "no tile of 512 rows fits 1048576 bytes of VMEM")
    # the weight gradient holds a [tk, tn] float32 block an expert
    assert grouped.choose_tiles("tgmm", 32768, 2304, 896, BF, BF,
                                budget=12 * 2 ** 20) == (512, 768, 896)
    # rows that 512 does not divide take 256 or 128
    assert grouped.choose_tiles("gmm", 768, 128, 128, BF, BF)[0] == 256
    assert grouped.choose_tiles("gmm", 640, 128, 128, BF, BF)[0] == 128


@pytest.mark.parametrize("form,stock", [
    ("gmm", "lax.ragged_dot"), ("gmm_transposed", "lax.ragged_dot"),
    ("tgmm", "lax.ragged_dot_general")])
def test_shapes_that_do_not_tile_fall_back_and_say_why(form, stock, operands):
    plan = grouped.product_plan(form, 40, 128, 128, BF, BF)
    assert plan["kernel"] == stock and "tiles" not in plan
    assert plan["reason"] == "40 rows are not whole tiles of 128"
    plan = grouped.product_plan(form, 512, 128, 24, BF, BF)
    assert plan["kernel"] == stock
    assert plan["reason"] == "a width of 24 is not whole lanes"
    tiled = grouped.product_plan(form, ROWS, K, N, BF, BF)
    assert tiled["kernel"] == ("moe_tgmm" if form == "tgmm" else "moe_gmm")
    assert tiled["tiles"] == [512, K, N] and "reason" not in tiled
    # and the entry points a model calls give the stock product there
    sizes = jnp.asarray([7, 0, 30, 3], jnp.int32)
    a, b = _args(form, operands)
    a = a[:40]
    if form == "tgmm":
        b = b[:40]
    call = {"gmm": grouped.grouped_dot,
            "gmm_transposed": grouped.grouped_dot_transposed,
            "tgmm": grouped.grouped_outer}[form]
    np.testing.assert_array_equal(call(a, b, sizes),
                                  _stock(form, a, b, sizes))
    with pytest.raises(ValueError, match="not whole tiles"):
        _kernel(form, a, b, sizes, None)


# ------------------------------------------- the expert layer's custom gradient

def _stock_grouped_bf16(lhs, weights, groups):
    """``mellum2._grouped_bf16`` as it stood until PR 38."""

    @jax.custom_vjp
    def product(lhs, weights):
        return fwd(lhs, weights)[0]

    def fwd(lhs, weights):
        lhs, weights = lhs.astype(BF), weights.astype(BF)
        return _stock("gmm", lhs, weights, groups), (lhs, weights)

    def bwd(kept, g):
        lhs, weights = kept
        g = g.astype(BF)
        return (_stock("gmm_transposed", g, weights, groups),
                _stock("tgmm", lhs, g, groups))

    product.defvjp(fwd, bwd)
    return product(lhs, weights)


@pytest.mark.parametrize("case", ["uneven", "an_empty_group_between_and_last",
                                  "padded_last_group"])
def test_gradients_of_the_expert_product_through_the_kernels(case):
    """``jax.vjp`` of ``_grouped_bf16`` at shapes that tile runs all three
    kernels (here under the interpreter) and gives the stock products'
    values: the same rounded operands, float32 sums in another order."""
    rng = np.random.default_rng(5)
    lhs = jnp.asarray(rng.normal(size=(ROWS, K)), jnp.float32)
    weights = jnp.asarray(rng.normal(size=(EXPERTS, K, N)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(ROWS, N)), jnp.float32)
    groups = jnp.asarray(GROUPS[case], jnp.int32)
    for form, (k, n) in (("gmm", (K, N)), ("gmm_transposed", (N, K)),
                         ("tgmm", (K, N))):
        assert "tiles" in grouped.product_plan(form, ROWS, k, n, BF, BF)
    out, vjp = jax.vjp(
        lambda a, b: mellum2._grouped_bf16(a, b, groups), lhs, weights)
    want, stock_vjp = jax.vjp(
        lambda a, b: _stock_grouped_bf16(a, b, groups), lhs, weights)
    text = str(jax.make_jaxpr(lambda a, b, g: jax.vjp(
        lambda a, b: mellum2._grouped_bf16(a, b, groups), a, b)[1](g))(
            lhs, weights, g))
    assert text.count("moe_gmm") >= 2 and "moe_tgmm" in text
    assert "ragged_dot" not in text
    np.testing.assert_allclose(out, want, **TOL)
    for got, ref in zip(vjp(g), stock_vjp(g)):
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(got, ref, **TOL)


def test_expert_products_of_a_step_are_counted_by_form_and_shape(monkeypatch):
    """The journal's record: on the TPU branch at shapes that tile, six
    sites and every product of a step on a kernel; off it, none."""
    sizes = {"hidden": 256, "expert_width": 128, "num_experts": 8,
             "experts_per_token": 2, "experts_held": [0, 1, 2, 3]}
    cpu = mellum2.expert_products(sizes, 512, layers=4, remat=True, workers=2)
    assert cpu["products_per_step"] == 96 and cpu["on_kernel"] == 0
    assert cpu["kernel_sites"] == 0
    assert {p["kernel"] for p in cpu["products"]} == {"lax.ragged_dot"}
    assert all("off the TPU" in p["reason"] for p in cpu["products"])
    monkeypatch.setattr(mellum2, "_one_bf16_pass", lambda: True)
    tpu = mellum2.expert_products(sizes, 512, layers=4, remat=True, workers=2)
    assert mellum2.moe_capacity(512, sizes) == 1024
    assert tpu["products_per_step"] == tpu["on_kernel"] == 96
    assert tpu["kernel_sites"] == 6
    assert [(p["form"], p["k"], p["n"], p["per_step"], p["tiles"])
            for p in tpu["products"]] == [
        ("gmm", 256, 128, 32, [512, 256, 128]),
        ("gmm", 128, 256, 16, [512, 128, 256]),
        ("gmm_transposed", 128, 256, 16, [512, 128, 256]),
        ("gmm_transposed", 256, 128, 8, [512, 256, 128]),
        ("tgmm", 256, 128, 16, [512, 256, 128]),
        ("tgmm", 128, 256, 8, [512, 128, 256])]
    plain = mellum2.expert_products(sizes, 512, layers=4, remat=False,
                                    workers=2)
    assert plain["products_per_step"] == 72
    # rows that do not tile: the stock product, and the reason
    odd = mellum2.expert_products(sizes, 20, layers=1, remat=False, workers=1)
    assert odd["on_kernel"] == 0 and all(
        p["reason"] == "40 rows are not whole tiles of 128"
        for p in odd["products"])
