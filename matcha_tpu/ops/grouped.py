"""Grouped matrix products of the expert layer, tiled to the experts' own
widths: rows sorted by expert against ``[E, K, N]`` weights, one group of
rows an expert.

Three forms, jitted entry points as ``pallas_gossip.leaf_mix`` is, so that
the calls of one form and shape in a program share one traced and lowered
kernel site (under ``remat`` too: :func:`_one_site`):

* :func:`moe_gmm` ``rows x [E, K, N] -> rows x N`` (the forward product),
* the same with ``transposed=True``: ``rows x N`` against the weights read
  as ``[E, N, K]`` where they lie (the data gradient: no ``swapaxes`` copy
  of the weights is made),
* :func:`moe_tgmm` ``rows x K, rows x N -> [E, K, N]`` (the weight
  gradient: both operands are read by rows, the transpose is of a tile in
  VMEM; an expert that holds no row reads zeros).

Operands come in the dtype the caller rounded them to (bfloat16 in the
expert layer); products accumulate in float32 and leave in float32.

**Why not ``lax.ragged_dot``**: on the TPU it lowers to the compiler's own
kernel at fixed tiles of 512 x 256 x 128 (4,473 grid steps a product of
32,768 x 2,304 x 896, the left operand read again for every 128 output
columns: PERF.md section 6, PR 38).  Here the tiles follow the shapes
(:func:`choose_tiles`): a whole expert width where it fits a VMEM budget,
so a row tile is read once and an expert's weights once a group.

**The grid** is after ``jax.experimental.pallas.ops.tpu.megablox``: one
step a (row tile, group) pair that share rows, groups in order, so a tile
that two groups share is visited by both, one after the other, and each
writes its own rows.  Its length is static (row tiles + groups - 1, the most
there can be); steps past the pairs there are stay on the last pair's tile
and group, so that they fetch nothing, write nothing back and do nothing.

**Rows in no group.**  The groups may sum to fewer than the rows (the expert
layer's rows past its last slot): those rows are in no pair, a row tile
that holds only such rows is never visited, and a product's time follows
the tiles its groups touch.  What the forward forms' output holds on a row
in no group is whatever the buffer held (anything: NaN too), so a caller
lets no such row reach a result it keeps (``models/mellum2.py:_experts``
drops them by index); the weight gradient reads only rows in a group.

:func:`grouped_dot`, :func:`grouped_dot_transposed` and
:func:`grouped_outer` are what a model calls: the kernel where
:func:`product_plan` finds tiles for the shapes, ``lax.ragged_dot`` /
``lax.ragged_dot_general`` where it does not (and says why).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend import core as jex_core
from jax.interpreters import mlir

__all__ = ["FORMS", "VMEM_BUDGET", "choose_tiles", "grouped_dot",
           "grouped_dot_transposed", "grouped_outer", "moe_gmm", "moe_tgmm",
           "product_plan", "vmem_bytes"]

_LANES = 128
#: rows of a tile: the stock kernel's, and what megablox tunes to on the
#: v5e; 256 or 128 where the rows do not divide by it
_ROW_TILES = (512, 256, 128)
#: what a kernel's buffers may take of the v5e's 128 MiB of VMEM (the
#: compiler's default scope is 16 MiB; the limit each kernel asks for is
#: this budget)
VMEM_BUDGET = 48 * 1024 * 1024
#: forward, data gradient (weights read transposed), weight gradient
FORMS = ("gmm", "gmm_transposed", "tgmm")


def vmem_bytes(form: str, tiles, lhs_dtype, rhs_dtype, k: int) -> int:
    """What a kernel of ``form`` holds in VMEM at ``tiles`` = (tm, tk, tn):
    both operands' blocks and the float32 output block double-buffered, the
    product's float32 value before it is stored or added, and the
    accumulator where the contraction ``k`` takes more than one tile."""
    tm, tk, tn = tiles
    lb, rb = jnp.dtype(lhs_dtype).itemsize, jnp.dtype(rhs_dtype).itemsize
    if form == "tgmm":
        return 2 * (tm * tk * lb + tm * tn * rb + tk * tn * 4) + tk * tn * 4
    held = 2 * (tm * tk * lb + tk * tn * rb + tm * tn * 4) + tm * tn * 4
    return held + (tm * tn * 4 if tk < k else 0)


def _lane_divisors(x: int):
    """Divisors of ``x`` that are whole lanes, largest first."""
    return [d for d in range(x, 0, -_LANES) if x % d == 0] \
        if x % _LANES == 0 else []


def choose_tiles(form: str, rows: int, k: int, n: int, lhs_dtype, rhs_dtype,
                 budget: int = VMEM_BUDGET):
    """(tm, tk, tn) of a product of ``form`` over ``rows`` rows, from the
    shapes alone, or the reason (a ``str``) there is none.

    ``k`` and ``n`` are the two widths of an expert's matrix as the kernel
    tiles them: ``gmm`` contracts ``k`` and writes ``n`` columns,
    ``gmm_transposed`` the same of the transposed weights, ``tgmm``
    contracts the rows and writes ``[k, n]`` an expert.  tm is the largest
    of 512, 256, 128 that divides the rows; tn the whole of ``n`` where
    one lane-wide tile of ``k`` beside it fits the budget
    (:func:`vmem_bytes`), else its largest divisor in whole lanes that
    does; tk then the largest such divisor of ``k`` that fits."""
    tm = next((t for t in _ROW_TILES if rows % t == 0), None)
    if tm is None:
        return f"{rows} rows are not whole tiles of {_ROW_TILES[-1]}"
    for width in (k, n):
        if width % _LANES:
            return f"a width of {width} is not whole lanes"
    fits = lambda tk, tn: vmem_bytes(
        form, (tm, tk, tn), lhs_dtype, rhs_dtype, k) <= budget
    tn = next((d for d in _lane_divisors(n) if fits(_LANES, d)), None)
    if tn is None:
        return f"no tile of {tm} rows fits {budget} bytes of VMEM"
    tk = next(d for d in _lane_divisors(k) if fits(d, tn))
    return tm, tk, tn


def _steps(group_sizes, rows: int, tm: int, every_group: bool):
    """The grid's (row tile, group) pairs, groups in order: (``offsets[E +
    1]`` the row each group starts at, ``group_of[steps]``,
    ``tile_of[steps]``, ``[live]`` how many of the steps are pairs).  A
    group takes the tiles its rows touch; one that holds no row takes none,
    or with ``every_group`` one (the weight gradient writes its zeros
    there).  The sizes sum to ``rows`` or less: rows past the sum are in no
    pair.  ``steps`` is the most there can be; the steps past ``live``
    repeat the last pair's tile and group, so that no operand's or output's
    block index changes after the last pair: nothing is fetched for them and
    nothing but the last pair's block is written back."""
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
    step = jnp.arange(steps, dtype=jnp.int32)
    tile_of = first[group_of] + step - step0[group_of]
    live = jnp.sum(visits).astype(jnp.int32)
    pair = jnp.minimum(step, jnp.maximum(live - 1, 0))
    return (offsets.astype(jnp.int32), group_of[pair],
            jnp.clip(tile_of, 0, tiles - 1).astype(jnp.int32)[pair],
            live[None])


def _rows_of_group(offsets, group_of, tile_of, s, tm):
    """(the group's first row, one past its last, the tile's first row)."""
    g = group_of[s]
    return offsets[g], offsets[g + 1], tile_of[s] * tm


def _own_rows(start, end, row0, shape):
    rows = row0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= start) & (rows < end)


def _gmm_kernel(tm, tiles_k, transposed):
    contract = (((1,), (1 if transposed else 0,)), ((), ()))

    def kernel(offsets, group_of, tile_of, live, lhs, rhs, out, *acc):
        s, k_i = pl.program_id(1), pl.program_id(2)
        start, end, row0 = _rows_of_group(offsets, group_of, tile_of, s, tm)
        running = s < live[0]
        whole = (start <= row0) & (row0 + tm <= end)

        def product():
            return lax.dot_general(lhs[...], rhs[...], contract,
                                   preferred_element_type=jnp.float32)

        def store(value):
            # a tile inside one group is stored as it is; one that groups
            # share keeps the other groups' rows
            @pl.when(whole)
            def _():
                out[...] = value()

            @pl.when(~whole)
            def _():
                out[...] = jnp.where(_own_rows(start, end, row0, out.shape),
                                     value(), out[...])

        if tiles_k == 1:
            pl.when(running)(lambda: store(product))
            return
        acc, = acc

        @pl.when(running & (k_i == 0))
        def _():
            acc[...] = product()

        @pl.when(running & (k_i > 0))
        def _():
            acc[...] += product()

        pl.when(running & (k_i == tiles_k - 1))(
            lambda: store(lambda: acc[...]))

    return kernel


def _tgmm_kernel(tm):
    by_rows = (((0,), (0,)), ((), ()))

    def kernel(offsets, group_of, tile_of, live, lhs, rhs, out):
        s = pl.program_id(2)
        start, end, row0 = _rows_of_group(offsets, group_of, tile_of, s, tm)
        running = (s < live[0]) & (end > start)
        whole = (start <= row0) & (row0 + tm <= end)

        @pl.when((s == 0) | (group_of[s] != group_of[jnp.maximum(s - 1, 0)]))
        def _():
            out[...] = jnp.zeros_like(out)

        def add(own):
            out[...] += lax.dot_general(own(lhs), own(rhs), by_rows,
                                        preferred_element_type=jnp.float32)

        def masked(ref):
            # (the select in float32: the v5e's vector unit has no bfloat16)
            return jnp.where(_own_rows(start, end, row0, ref.shape),
                             ref[...].astype(jnp.float32), 0.0).astype(
                                 ref.dtype)

        pl.when(running & whole)(lambda: add(lambda ref: ref[...]))
        pl.when(running & ~whole)(lambda: add(masked))

    return kernel


def _tiles_or_raise(form, tiles, rows, k, n, lhs, rhs):
    if tiles is None:
        tiles = choose_tiles(form, rows, k, n, lhs.dtype, rhs.dtype)
        if isinstance(tiles, str):
            raise ValueError(f"{form} {rows} x {k} x {n}: {tiles}")
    tm, tk, tn = tiles
    if rows % tm or k % tk or n % tn:
        raise ValueError(f"{form} {rows} x {k} x {n}: tiles {tiles} do not "
                         f"divide it")
    return tm, tk, tn


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=VMEM_BUDGET)


@functools.partial(jax.jit,
                   static_argnames=("transposed", "tiles", "interpret"))
def moe_gmm(lhs: jax.Array, weights: jax.Array, group_sizes: jax.Array, *,
            transposed: bool = False, tiles=None, interpret: bool = False):
    """``out[r] = lhs[r] @ weights[group of r]`` in float32, ``lhs[rows,
    K]``, ``weights[E, K, N]``; with ``transposed`` ``weights[E, N, K]``,
    read where they lie.  ``group_sizes[E]`` int32 sum to ``rows`` or less:
    the first ``group_sizes[0]`` rows meet expert 0, and so on; a row past
    the sum meets none and its ``out`` is not written (it holds what the
    buffer held: see the module's "Rows in no group").  ``tiles`` (tm, tk,
    tn) as :func:`choose_tiles` picks them unless given (a test seam)."""
    rows, k = lhs.shape
    n = weights.shape[1 if transposed else 2]
    if weights.shape[2 if transposed else 1] != k or \
            group_sizes.shape != weights.shape[:1]:
        raise ValueError(f"rows {lhs.shape} against weights {weights.shape}"
                         f"{' transposed' if transposed else ''} in groups "
                         f"{group_sizes.shape}")
    form = "gmm_transposed" if transposed else "gmm"
    tm, tk, tn = _tiles_or_raise(form, tiles, rows, k, n, lhs, weights)
    tiles_k = k // tk
    steps = _steps(group_sizes.astype(jnp.int32), rows, tm, False)
    of_weights = ((lambda n_i, s, k_i, offsets, group_of, *_:
                   (group_of[s], n_i, k_i)) if transposed else
                  (lambda n_i, s, k_i, offsets, group_of, *_:
                   (group_of[s], k_i, n_i)))
    return pl.pallas_call(
        _gmm_kernel(tm, tiles_k, transposed),
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, steps[1].shape[0], tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, s, k_i, offsets, group_of,
                             tile_of, live: (tile_of[s], k_i)),
                pl.BlockSpec((None, tn, tk) if transposed
                             else (None, tk, tn), of_weights)],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n_i, s, k_i, offsets, group_of, tile_of,
                live: (tile_of[s], n_i)),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if tiles_k > 1 else [])),
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=lhs.nbytes * (n // tn) + weights.nbytes
            + rows * n * 4),
        interpret=interpret, name="moe_gmm",
    )(*steps, lhs, weights)


@functools.partial(jax.jit,
                   static_argnames=("tiles", "interpret"))
def moe_tgmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
             tiles=None, interpret: bool = False):
    """``out[e] = lhs[rows of e]^T @ rhs[rows of e]`` in float32,
    ``lhs[rows, K]``, ``rhs[rows, N]``, ``out[E, K, N]``; zeros for a group
    of no rows.  ``group_sizes`` and ``tiles`` as :func:`moe_gmm`: rows past
    the groups' sum are read by no expert's sum."""
    rows, k = lhs.shape
    n = rhs.shape[1]
    if rhs.shape[0] != rows or group_sizes.ndim != 1:
        raise ValueError(f"rows {lhs.shape} against rows {rhs.shape} in "
                         f"groups {group_sizes.shape}")
    tm, tk, tn = _tiles_or_raise("tgmm", tiles, rows, k, n, lhs, rhs)
    steps = _steps(group_sizes.astype(jnp.int32), rows, tm, True)
    groups = group_sizes.shape[0]
    return pl.pallas_call(
        _tgmm_kernel(tm),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, k // tk, steps[1].shape[0]),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, k_i, s, offsets, group_of,
                             tile_of, live: (tile_of[s], k_i)),
                pl.BlockSpec((tm, tn), lambda n_i, k_i, s, offsets, group_of,
                             tile_of, live: (tile_of[s], n_i))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda n_i, k_i, s, offsets, group_of,
                tile_of, live: (group_of[s], k_i, n_i))),
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=lhs.nbytes * (n // tn) + rhs.nbytes * (k // tk)
            + groups * k * n * 4),
        interpret=interpret, name="moe_tgmm",
    )(*steps, lhs, rhs)


# ------------------------------------------------ what the expert layer calls

_KERNEL = {"gmm": "moe_gmm", "gmm_transposed": "moe_gmm", "tgmm": "moe_tgmm"}
_STOCK = {"gmm": "lax.ragged_dot", "gmm_transposed": "lax.ragged_dot",
          "tgmm": "lax.ragged_dot_general"}


def product_plan(form: str, rows: int, k: int, n: int, lhs_dtype,
                 rhs_dtype) -> dict:
    """What runs a grouped product of ``form`` at these shapes, as the
    journal's ``backend`` event records it: ``{"kernel": "moe_gmm" |
    "moe_tgmm", "tiles": [tm, tk, tn]}``, or ``{"kernel": "lax.ragged_dot"
    | "lax.ragged_dot_general", "reason"}`` where the shapes do not tile."""
    tiles = choose_tiles(form, rows, k, n, lhs_dtype, rhs_dtype)
    shape = {"form": form, "rows": rows, "k": k, "n": n}
    if isinstance(tiles, str):
        return {**shape, "kernel": _STOCK[form], "reason": tiles}
    return {**shape, "kernel": _KERNEL[form], "tiles": list(tiles)}


def _interpret() -> bool:
    # the rule of ``pallas_gossip.pallas_interpret``
    return jax.default_backend() == "cpu"


def _one_site(kernel):
    """``kernel`` (a jitted entry point) behind a primitive of its own, so
    that every call of one form and shape in a program lowers to a call of
    one function.  ``jax.jit`` alone does not give that under ``remat``, whose
    dead-code pass copies a ``jit`` equation's program (equal, but another
    object, and the lowering shares by object), so the recomputed forward
    products would be sites of their own.  A primitive is opaque to that
    pass; its lowering traces the ``jit`` call afresh, which finds the one
    program the trace cache holds."""
    def shape_of(*avals, **static):
        out = jax.eval_shape(functools.partial(kernel, **static), *avals)
        return jax.core.ShapedArray(out.shape, out.dtype)

    site = jex_core.Primitive(kernel.__name__)
    site.def_impl(kernel)
    site.def_abstract_eval(shape_of)
    mlir.register_lowering(site, mlir.lower_fun(kernel,
                                                multiple_results=False))
    return site


_gmm_site, _tgmm_site = _one_site(moe_gmm), _one_site(moe_tgmm)


def _tiled(form, rows, k, n, lhs, rhs):
    return not isinstance(
        choose_tiles(form, rows, k, n, lhs.dtype, rhs.dtype), str)


def grouped_dot(lhs, weights, group_sizes):
    """``lax.ragged_dot(lhs, weights, group_sizes)`` in float32."""
    (rows, k), n = lhs.shape, weights.shape[2]
    if _tiled("gmm", rows, k, n, lhs, weights):
        return _gmm_site.bind(lhs, weights, group_sizes, transposed=False,
                              tiles=None, interpret=_interpret())
    return lax.ragged_dot(lhs, weights, group_sizes,
                          preferred_element_type=jnp.float32)


def grouped_dot_transposed(lhs, weights, group_sizes):
    """``lax.ragged_dot(lhs, swapaxes(weights, 1, 2), group_sizes)`` in
    float32: ``lhs[rows, N]`` against ``weights[E, K, N]`` gives ``[rows,
    K]``."""
    (rows, n), k = lhs.shape, weights.shape[1]
    if _tiled("gmm_transposed", rows, n, k, lhs, weights):
        return _gmm_site.bind(lhs, weights, group_sizes, transposed=True,
                              tiles=None, interpret=_interpret())
    return lax.ragged_dot(lhs, jnp.swapaxes(weights, 1, 2), group_sizes,
                          preferred_element_type=jnp.float32)


_BY_ROWS = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def grouped_outer(lhs, rhs, group_sizes):
    """``out[e] = lhs[rows of e]^T @ rhs[rows of e]`` in float32
    (``lax.ragged_dot_general`` with the ragged dimension contracted)."""
    (rows, k), n = lhs.shape, rhs.shape[1]
    if _tiled("tgmm", rows, k, n, lhs, rhs):
        return _tgmm_site.bind(lhs, rhs, group_sizes, tiles=None,
                               interpret=_interpret())
    return lax.ragged_dot_general(lhs, rhs, group_sizes, _BY_ROWS,
                                  preferred_element_type=jnp.float32)
