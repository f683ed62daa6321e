"""The expert layer's grouped products (``matcha_tpu/ops/grouped.py``, PR 38)
under the Pallas interpreter against ``lax.ragged_dot`` /
``lax.ragged_dot_general``: each form over uneven groups, an empty group, a
group boundary inside a row tile, the padded last group, groups that sum to
fewer than the rows (rows in no group, PR 43) and tiles that split the
contraction or the width; the grid's steps; the tile chooser; the fallback
and its reason; ``mellum2._grouped_bf16``'s gradients through the kernels
against the stock products it ran until PR 38."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from matcha_tpu.models import mellum2
from matcha_tpu.ops import grouped

ROWS, K, N, EXPERTS = 512, 256, 384, 4
BF = jnp.bfloat16
#: group sizes over 512 rows in tiles of 128: the first six sum to the rows
#: (until PR 43 the expert layer counted the rows past its last slot with the
#: last expert), the rest to fewer: rows past the sum are in no group
GROUPS = {
    "uneven": [100, 37, 300, 75],
    "on_tile_boundaries": [128, 128, 0, 256],
    "an_empty_group_between_and_last": [200, 0, 312, 0],
    "boundaries_inside_one_tile": [130, 3, 2, 377],
    "padded_last_group": [40, 30, 20, 422],
    "one_group_holds_every_row": [0, 0, 512, 0],
    "short_last_group_ends_inside_a_tile": [100, 37, 120, 30],
    "short_empty_groups_first_and_between": [0, 90, 0, 60],
    "short_empty_groups_last": [200, 61, 0, 0],
    "short_ends_on_a_tile_boundary": [128, 100, 28, 0],
    "short_one_row": [0, 0, 1, 0],
    "every_group_empty": [0, 0, 0, 0],
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


def _assert_is_the_stock_product(form, case, tiles, operands):
    """The kernel of ``form`` over ``GROUPS[case]`` against the stock
    product: on every row in a group for the forward forms (what a row in no
    group holds is anything: the interpreter leaves NaN, the stock product
    zeros), on every expert for the weight gradient.  Where the sizes sum to
    fewer than the rows the kernel also gives, to the bit, what it gives of
    the layout until PR 43 (the rest of the rows with the last group) over
    operands whose rows in no group are zero: the same tiles multiplied in
    the same order, and sums that differ by exact zeros."""
    sizes = jnp.asarray(GROUPS[case], jnp.int32)
    a, b = _args(form, operands)
    held = sum(GROUPS[case])
    got = _kernel(form, a, b, sizes, tiles)
    kept = slice(None) if form == "tgmm" else slice(held)
    np.testing.assert_allclose(got[kept], _stock(form, a, b, sizes)[kept],
                               **TOL)
    if held < ROWS:
        in_a_group = (jnp.arange(ROWS) < held)[:, None]
        a = jnp.where(in_a_group, a, 0)
        if form == "tgmm":
            b = jnp.where(in_a_group, b, 0)
        padded = _kernel(form, a, b, sizes.at[-1].add(ROWS - held), tiles)
        np.testing.assert_array_equal(got[kept], padded[kept])
    return got


@pytest.mark.parametrize("tiles", TILES, ids=lambda t: "x".join(map(str, t)))
@pytest.mark.parametrize("case", list(GROUPS))
def test_forward_product_is_the_ragged_dot(case, tiles, operands):
    got = _assert_is_the_stock_product("gmm", case, tiles, operands)
    assert got.shape == (ROWS, N) and got.dtype == jnp.float32


@pytest.mark.parametrize("tiles", TILES, ids=lambda t: "x".join(map(str, t)))
@pytest.mark.parametrize("case", list(GROUPS))
def test_transposed_weights_product_is_the_ragged_dot_of_the_swapped(
        case, tiles, operands):
    got = _assert_is_the_stock_product("gmm_transposed", case, tiles,
                                       operands)
    assert got.shape == (ROWS, K) and got.dtype == jnp.float32


@pytest.mark.parametrize("tiles", TILES, ids=lambda t: "x".join(map(str, t)))
@pytest.mark.parametrize("case", list(GROUPS))
def test_weight_gradient_product_is_the_ragged_dot_general(case, tiles,
                                                           operands):
    got = _assert_is_the_stock_product("tgmm", case, tiles, operands)
    assert got.shape == (EXPERTS, K, N) and got.dtype == jnp.float32
    for e, size in enumerate(GROUPS[case]):
        if size == 0:  # an expert that took no slot: exactly zero
            assert not np.any(np.asarray(got[e]))


@pytest.mark.parametrize("poisoned", ["another_group", "no_group"])
@pytest.mark.parametrize("form", grouped.FORMS)
def test_rows_of_other_groups_never_reach_a_product(form, poisoned, operands):
    """A tile that groups share is masked, not weighted: a NaN in another
    group's rows stays out of this group's sums, and so does one in the rows
    of no group (the last group ends inside a tile and shares it with
    them; the tile after holds only such rows)."""
    sizes = jnp.asarray({"another_group": [130, 126, 250, 6],
                         "no_group": [130, 100, 26, 2]}[poisoned], jnp.int32)
    a, b = _args(form, operands)
    clean = np.asarray(_kernel(form, a, b, sizes, (128, 128, 128)))
    if poisoned == "no_group":
        held = 258  # two rows into the third tile of four
        got = np.asarray(_kernel(form, a.at[held:].set(jnp.nan), b, sizes,
                                 (128, 128, 128)))
        others = slice(None) if form == "tgmm" else slice(held)
        assert not np.any(np.isnan(got[others]))
        np.testing.assert_array_equal(got[others], clean[others])
        return
    # all of group 1, and only it
    got = np.asarray(_kernel(form, a.at[130:256].set(jnp.nan), b, sizes,
                             (128, 128, 128)))
    if form == "tgmm":
        assert np.all(np.isnan(got[1]))
        others = [0, 2, 3]
    else:
        assert np.all(np.isnan(got[130:256]))
        others = np.r_[0:130, 256:ROWS]
    np.testing.assert_array_equal(got[others], clean[others])


# ------------------------------------------------------------ the grid's steps

def _steps_until_pr_43(group_sizes, rows, tm, every_group):
    """``grouped._steps`` as it stood until PR 43: the steps past the pairs
    repeat the last group and walk on over the tiles to the last."""
    groups = group_sizes.shape[0]
    tiles = rows // tm
    steps = tiles + groups - 1
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts // tm
    visits = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first,
                       1 if every_group else 0)
    step0 = jnp.cumsum(visits) - visits
    group_of = jnp.repeat(jnp.arange(groups, dtype=jnp.int32), visits,
                          total_repeat_length=steps)
    tile_of = first[group_of] + jnp.arange(steps, dtype=jnp.int32) \
        - step0[group_of]
    return (offsets.astype(jnp.int32), group_of,
            jnp.clip(tile_of, 0, tiles - 1).astype(jnp.int32),
            jnp.sum(visits).astype(jnp.int32)[None])


#: (sizes, rows, tm, every_group) -> (live, the pairs (group, tile) in order)
STEPS = {
    "rows_in_no_group": (
        [700, 0, 900, 300, 0, 0, 500, 0], 8192, 512, False,
        [(0, 0), (0, 1), (2, 1), (2, 2), (2, 3), (3, 3), (6, 3), (6, 4)]),
    # a group of no rows visits the tile its start lies on, once
    "rows_in_no_group_every_group": (
        [700, 0, 900, 300, 0, 0, 500, 0], 8192, 512, True,
        [(0, 0), (0, 1), (1, 1), (2, 1), (2, 2), (2, 3), (3, 3), (4, 3),
         (5, 3), (6, 3), (6, 4), (7, 4)]),
    "last_group_ends_inside_a_tile": (
        [100, 37, 120, 30], 512, 128, False,
        [(0, 0), (1, 0), (1, 1), (2, 1), (2, 2), (3, 2)]),
    "one_row": ([0, 0, 1, 0], 512, 128, False, [(2, 0)]),
    "every_group_empty": ([0, 0, 0, 0], 512, 128, False, []),
    "every_group_empty_every_group": (
        [0, 0, 0, 0], 512, 128, True, [(0, 0), (1, 0), (2, 0), (3, 0)]),
    "the_rows_filled": (
        [100, 37, 300, 75], 512, 128, False,
        [(0, 0), (1, 0), (1, 1), (2, 1), (2, 2), (2, 3), (3, 3)]),
}


@pytest.mark.parametrize("case", list(STEPS))
def test_steps_past_the_last_pair_stay_on_its_tile_and_group(case):
    """The grid's length is static; a step past ``live`` has the block
    indices of the last pair (lhs and output by tile, weights by group), so
    nothing is fetched for it and nothing written back after it."""
    sizes, rows, tm, every_group, pairs = STEPS[case]
    offsets, group_of, tile_of, live = grouped._steps(
        jnp.asarray(sizes, jnp.int32), rows, tm, every_group)
    steps = rows // tm + len(sizes) - 1
    assert group_of.shape == tile_of.shape == (steps,) and live.shape == (1,)
    assert offsets.tolist() == np.cumsum([0] + sizes).tolist()
    assert int(live[0]) == len(pairs)
    got = list(zip(group_of.tolist(), tile_of.tolist()))
    assert got[:len(pairs)] == pairs
    assert set(got[len(pairs):]) <= {pairs[-1] if pairs else got[0]}
    assert 0 <= min(tile_of.tolist()) and max(tile_of.tolist()) < rows // tm


@pytest.mark.parametrize("every_group", [False, True],
                         ids=["gmm", "every_group"])
@pytest.mark.parametrize("case", [c for c in GROUPS
                                  if sum(GROUPS[c]) == ROWS])
def test_sizes_that_fill_the_rows_step_as_they_did(case, every_group):
    """Sizes that sum to the rows: the pairs are the parent's, and so is
    every step past them where the last group holds a row (the expert layer
    until PR 43: the rows past the last slot were its).  Where the last group
    is empty and takes no step the parent's dead steps named it, and with it
    another block of weights to fetch; they stay on the last pair's now."""
    sizes = jnp.asarray(GROUPS[case], jnp.int32)
    got = grouped._steps(sizes, ROWS, 128, every_group)
    want = _steps_until_pr_43(sizes, ROWS, 128, every_group)
    live = int(want[3][0])
    upto = None if GROUPS[case][-1] or every_group else live
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a[:upto], b[:upto])
    assert int(got[3][0]) == live


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
