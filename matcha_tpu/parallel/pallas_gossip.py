"""Pallas TPU kernels: the one-step streamed exchange at small N, over the
flat state (``stream_mix``) and over the parameter leaves where they lie
(``leaf_mix``, ``leaf_view``, ``leaf_views``, ``tree_mix``, at the end).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops import WorkerFlattener
from .collectives import worker_square_rows
from .gossip import resolve_wire_dtype

__all__ = [
    "leaf_mix",
    "leaf_view",
    "leaf_views",
    "pallas_interpret",
    "stream_mix",
    "tree_mix",
]


#: Mosaic's default scoped-VMEM limit per kernel on the v5e (the compiler's
#: own message: "limit 16.00M"); every resident block of a kernel here,
#: pipeline double-buffers included, must fit under it.
SCOPED_VMEM_BYTES = 16 * 2 ** 20


def pallas_interpret() -> bool:
    """THE rule for ``interpret=``: the Pallas interpreter runs on the
    ``cpu`` platform and nowhere else — on any accelerator the kernels
    compile for the device or raise, never quietly interpret."""
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# Streamed small-N exchange (one gossip step, vector unit, in place)
# ---------------------------------------------------------------------------

#: bytes of one resident ``[N, block_d]`` float32 block of ``stream_mix``:
#: in and out blocks, each double-buffered, keep 8 MiB of the 16 MiB scoped
#: VMEM (4 MiB blocks are refused by Mosaic's allocator).  On the v5e a
#: 16 x 36.5 M chain read 7.49 ms a step at 1 MiB and 7.22 at 2 MiB
#: (PERF.md section 6, PR 28).
_STREAM_BLOCK_BYTES = 2 * 2 ** 20

#: float32 elements of the ``[N, chunk]`` accumulator one pass of the
#: kernel's inner loop keeps in vector registers: the loop's own cost is per
#: pass, so a narrow chunk pays it often (N = 16: 21.4 ms a step at 256
#: columns, 10.9 at 512, 7.75 at 1,024, 7.22 at 2,048, 7.55 at 4,096; N = 2:
#: 47.1 at 512, 12.6 at 2,048, 6.71 at 8,192; N = 32: 13.5 at 512, 9.36 at
#: 1,024, 13.2 at 2,048; same runs)
_STREAM_CHUNK_ELEMENTS = 32768

#: widest chunk (N = 2 and 3 reach it)
_STREAM_CHUNK_MAX = 8192


def _make_stream_kernel(n: int, block_d: int, chunk: int, wire):
    """Kernel body: ``o[:, c] = Σ_j W[:, j] · x[j, c]`` over one resident
    ``[N, block_d]`` block, ``chunk`` columns at a time: every term is a
    lane-broadcast column of ``W`` times a sublane-broadcast row of the
    block, multiplied and added in float32 on the vector unit — no MXU
    pass, and the N-term sum is unrolled at trace time."""

    def _kernel(w_ref, x_ref, o_ref):
        def body(c, carry):
            cols = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            xc = x_ref[:, cols]
            if wire is not None:
                xc = xc.astype(wire)  # the wire's rounding, once an element
            xc = xc.astype(jnp.float32)
            acc = w_ref[:, 0:1] * xc[0:1, :]
            for j in range(1, n):
                acc = acc + w_ref[:, j:j + 1] * xc[j:j + 1, :]
            o_ref[:, cols] = acc.astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, block_d // chunk, body, 0)

    return _kernel


def stream_mix(x: jax.Array, w: jax.Array, *, wire_dtype=None,
               interpret: bool = False) -> jax.Array:
    """One gossip step ``x ← W x`` as one streaming pass over ``x``.

    ``x``: ``[N, D]`` worker state; ``w``: ``f32[N, N]`` mixing matrix (a
    traced value: built from the step's flag row, masked or not).  The grid
    tiles D only: each ``[N, block_d]`` block is read once, mixed in VMEM
    with float32 multiply-adds on the vector unit, and written once over
    the block it was read from (``input_output_aliases``: where ``x`` is
    dead after the exchange, as in the train step and in a scanned chain,
    the step needs no second state-sized buffer and no copy).  Every
    product and sum is float32, so the result is at least as tight as the
    six-pass ``highest`` MXU product it replaces at small N.

    ``wire_dtype`` (through :func:`~matcha_tpu.parallel.gossip.
    resolve_wire_dtype`): the state is rounded to the wire dtype as it is
    read — the caller rounds ``w`` — and products of two bfloat16 values are
    exact in float32, so a bf16 wire reads what one bf16 MXU pass with
    float32 accumulation reads.  A last block past the end of D is padded on
    the read and clipped on the write; columns never mix.
    """
    n, d = x.shape
    if w.shape != (n, n):
        raise ValueError(f"mixing matrix {w.shape} vs state {x.shape}")
    wire = resolve_wire_dtype(wire_dtype)
    chunk = min(_STREAM_CHUNK_MAX,
                max(_STREAM_CHUNK_ELEMENTS // n // 128, 1) * 128, d)
    block_d = min(max(_STREAM_BLOCK_BYTES // (4 * n) // chunk, 1),
                  pl.cdiv(d, chunk)) * chunk
    block = pl.BlockSpec((n, block_d), lambda i: (0, i))
    return pl.pallas_call(
        _make_stream_kernel(n, block_d, chunk, wire),
        grid=(pl.cdiv(d, block_d),),
        in_specs=[pl.BlockSpec((n, n), lambda i: (0, 0)), block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(w.astype(jnp.float32), x)


# ---------------------------------------------------------------------------
# The leaf form of the streamed exchange (one gossip step over a parameter
# tree, every large leaf mixed where it lies)
# ---------------------------------------------------------------------------

#: sublanes and lanes of one float32 vector register, and of one HBM tile
_SUBLANES, _LANES = 8, 128

#: largest share of padding a leaf may carry into the leaf form: its last
#: dimension padded to whole lanes is what the device stores, moves and
#: multiplies, so a 160-wide convolution (256 lanes, 60% padding) pays for
#: 1.6 times its elements.  Cell 1 (16 x WRN-28-10) on the v5e stepped in
#: 272.62 ms with every leaf through the flat state, 242.65 at an eighth (its
#: 640-wide stage in place, 76.2% of its state) and 231.70 at a quarter (the
#: 320-wide stage too, 20% padding: 94.5%; PR 33's chip runs and the ledger's
#: PR 33 line; 233.2 with this PR's kernel, PERF.md section 6); its 160-wide
#: leaves (4.4%) ride the small buffer: not measured in place.
_LEAF_PAD_SHARE = 1 / 4

#: fewest elements (all workers') of a leaf worth a kernel launch of its own
#: (0.07-0.09 ms on the v5e, PR 33's chip runs); smaller ones ride the small
#: buffer.  In the cells ``_LEAF_SHAPE_SHARE`` asks for more than this.
_LEAF_MIN_ELEMENTS = 1 << 18

#: workers whose outputs the leaf kernel writes as straight-line code at a
#: time: the N outputs of a pass are whole groups of this many in a loop, and
#: those past the last whole group (all of them up to this many) straight-line
#: with a static index, so a pass's code is 4 N multiply-adds and not N^2.
#: What the code's size costs is the start: with all 256 terms of N = 16
#: straight-line (PR 33) a site took 0.6-0.7 s to trace and 0.2 s to lower on
#: this PR's host and was 59 KB of the program's text, and the Pallas
#: interpreter compiled it on the CPU for 12 s a shape; in groups of 4 it is
#: 0.1-0.2 s, 0.07 s and 24 KB.  What it costs on the v5e, ms a step of a
#: 20-step chain in place (my chip runs, PR 34; the flat pass over the same
#: elements reads 0.812 and 2.239): ``[16, 5760, 640]`` 0.792 straight-line,
#: 0.864 in groups of 8, 0.843 in groups of 4 (0.940 at 32 registers a pass),
#: 1.158 in groups of 2; as N accumulators carried through a loop over the
#: terms j (N multiply-adds of code) 1.25-1.44; ``[16, 2880, 320]`` 0.316 /
#: 0.338 / 0.383 (at 32) / 0.452; ``[8, 5760, 640]`` 0.424 in groups of 4, what
#: straight-line reads; ``[32, 4096, 1024]`` 2.958 in groups of 4, 4.354 in
#: groups of 8 at 32 registers.  No cell runs above 16 workers on this path.
_LEAF_GROUP = 4

#: vector registers of the block that one pass of the leaf kernel's inner
#: loop reads, all workers together (N = 2: 32 a worker, a 4,096-column
#: chunk; N = 16: 4; N = 32: 2).  Straight-line on the v5e N = 2 read the
#: same from 8 to 64 and ``[16, 5760, 640]`` 0.969 / 0.802 / 0.800 ms at 16 /
#: 32 / 64 (PR 33's chip runs); in groups of 4 the loop over groups is paid a
#: pass, and ``[16, 5760, 640]`` reads 1.428 / 0.940 / 0.843, ``[32, 4096,
#: 1024]`` 4.822 / 4.821 / 2.958 (my chip runs, PR 34).
_LEAF_PASS_VREGS = 64

#: what a leaf shape ``[N, r, c]`` has to be worth to get a kernel site of
#: its own: its leaves together hold this share of the tree's elements or
#: more, and it is among the ``_LEAF_MAX_SHAPES`` that hold the most, so that
#: with the small buffer's ``stream_mix`` a program holds at most a dozen
#: sites.  A site is paid at every start, cache or no cache: traced once a
#: process, lowered to Mosaic each time the program around it is (once a
#: start on the v5e's host: the cost ledger's ``observe``; the ``jit`` call
#: after it finds that work done), and part of the text the cache key is
#: hashed from.  PR 33 launched one ``leaf_mix`` a
#: leaf, 17 sites of 256 straight-line multiply-adds in cell 1, and its
#: start on the v5e grew by 15.5 s warm (PERF.md section 6); ``leaf_mix`` is
#: jitted so that leaves of one shape share one site.  At 1/32 the cells keep
#: 3, 4 and 4 shapes (93.3%, 95.4% and 95.1% of their state in place), and
#: their exchange lowers to 2.4, 2.4 and 2.2 times the flat form's text (at
#: 1/64: 6 shapes in the token cells, 98.9% in place, 3.3 times the text).
_LEAF_SHAPE_SHARE = 1 / 32
_LEAF_MAX_SHAPES = 11


def _round_up(value: int, to: int) -> int:
    return -(-value // to) * to


def leaf_view(shape, dtype=jnp.float32):
    """THE rule for which leaves the leaf form takes, from the shape alone:
    ``(r, c, swapped)`` where :func:`leaf_mix` runs on the leaf where it
    lies, read as ``[N, r, c]``; else a string, the reason the leaf rides
    the small buffer.

    The view has to be one, not a copy: the device tiles a leaf's trailing
    two dimensions in ``(8, 128)``, so leading dimensions collapse into
    ``r`` for nothing where the one before the last is whole sublanes, and a
    leaf ``[N, a, b]`` lies with ``a`` in the lanes where that pads less (the
    v5e compiler's own choice, read off programs compiled for it:
    ``f32[2, 2048, 18992]`` and a 64-wide router lie ``{1,2,0}``, a
    ``[2, 2000, 1000]`` as written): the exchange is elementwise over a
    worker's values, so it reads such a leaf as ``[N, b, a]``
    (``swapped``).  The block the kernel keeps resident is whole tiles: the
    padding of ``c`` to lanes is paid in bytes and in multiply-adds, and
    bounded by ``_LEAF_PAD_SHARE``.
    """
    if len(shape) < 3:
        return "no dimension between the workers and the last"
    if shape[0] < 2:
        return "one worker: the kernel's first two terms are two workers'"
    if jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        return f"stored {jnp.dtype(dtype).name}, not float32"
    n, r, c = shape[0], int(np.prod(shape[1:-1], dtype=np.int64)), shape[-1]
    if n * r * c < _LEAF_MIN_ELEMENTS:
        return f"under {_LEAF_MIN_ELEMENTS} elements"
    if shape[-2] % _SUBLANES or c % _SUBLANES:
        return (f"the trailing ({shape[-2]}, {c}) are not whole sublanes: "
                f"the device does not tile them apart from the workers")
    views = [(r, c, False)] + ([(c, r, True)] if len(shape) == 3 else [])
    # ties go to the leaf as it is written, as the compiler's do
    r, c, swapped = min(views, key=lambda v: v[0] * _round_up(v[1], _LANES))
    lanes = _round_up(c, _LANES)
    if lanes > (1 + _LEAF_PAD_SHARE) * c:
        return f"{lanes / c - 1:.0%} padding to whole lanes of {_LANES}"
    if 4 * 4 * n * _SUBLANES * lanes > SCOPED_VMEM_BYTES // 2:
        return f"eight rows of {n} x {c} do not fit the resident blocks"
    return r, c, swapped


def leaf_views(n: int, shapes, dtypes) -> list:
    """:func:`leaf_view` of every leaf of a tree (``shapes`` without the
    workers' axis), held to the program's budget of kernel sites: one site a
    distinct ``(r, c)``, for the shapes whose leaves together hold
    ``_LEAF_SHAPE_SHARE`` of the tree or more, at most ``_LEAF_MAX_SHAPES``
    of them; the others ride the small buffer."""
    views = [leaf_view((n,) + tuple(shape), dtype)
             for shape, dtype in zip(shapes, dtypes)]
    sizes = [int(np.prod(shape, dtype=np.int64)) for shape in shapes]
    held = {}
    for view, size in zip(views, sizes):
        if not isinstance(view, str):
            held[view[:2]] = held.get(view[:2], 0) + size
    floor = _LEAF_SHAPE_SHARE * sum(sizes)
    # ties by the shape itself, so that the choice is the tree's alone
    kept = set(sorted((rc for rc in held if held[rc] >= floor),
                      key=lambda rc: (-held[rc], rc))[:_LEAF_MAX_SHAPES])

    def gives_way(rc):
        if held[rc] < floor:
            return (f"its shape holds under 1/{round(1 / _LEAF_SHAPE_SHARE)} "
                    f"of the tree")
        return f"its shape is past the {_LEAF_MAX_SHAPES} a program mixes in place"

    return [view if isinstance(view, str) or view[:2] in kept
            else gives_way(view[:2]) for view in views]


def _leaf_geometry(n: int, r: int, c: int, block_rows=None):
    """``(block rows, chunk columns)`` of the leaf kernel at ``[n, r, c]``."""
    lanes = _round_up(c, _LANES)
    chunk = min(max(_LEAF_PASS_VREGS // n, 1) * _LANES, lanes)
    if block_rows is None:
        block_rows = max(
            _STREAM_BLOCK_BYTES // (4 * n * lanes) // _SUBLANES, 1) * _SUBLANES
    return min(block_rows, r), chunk


def _make_leaf_kernel(n: int, rows: int, block_rows: int, c: int,
                      chunk: int, wire):
    """Kernel body over one resident ``[N, block_rows, c]`` block: eight
    rows and ``chunk`` columns at a time, ``o[i] = sum_j W[i, j] x[j]`` with
    the worker axis leading and untiled, so every operand is whole
    ``(8, 128)`` registers at any N and ``W[i, j]`` is a scalar; the same
    pass adds each worker's ``(o[i] - mean_j o[j])^2`` into an accumulator as
    wide as a pass, summed once a block.  Output i sums its terms in the
    order j = 0..N-1, as ``stream_mix`` does; the outputs come in groups of
    ``_LEAF_GROUP``."""
    full = c // chunk                  # whole chunks in a row group
    tail = c - full * chunk            # columns left, static
    ragged = rows % block_rows != 0    # the last block runs past the rows
    inv_n = np.float32(1.0 / n)

    def _kernel(w_ref, x_ref, o_ref, s_ref, acc_ref):
        acc_ref[...] = jnp.zeros_like(acc_ref)
        block = pl.program_id(0)
        row0 = block * block_rows

        def over_workers(body, init):
            # ``body(i, carry)`` for every worker: whole groups of
            # ``_LEAF_GROUP`` workers as a loop, each group unrolled, the
            # workers past the last whole group (all of them up to
            # ``_LEAF_GROUP``) as straight-line code with a static i
            group = _LEAF_GROUP
            whole = n // group if n > group else 0

            def one_group(g, carry):
                for k in range(group):
                    carry = body(g * group + k, carry)
                return carry

            if whole:
                init = jax.lax.fori_loop(0, whole, one_group, init)
            for i in range(whole * group, n):
                init = body(i, init)
            return init

        def mix(rows_at, cols, width, r0):
            def read(j):
                xj = x_ref[j, rows_at, cols]
                if wire is not None:
                    xj = xj.astype(wire)  # the wire's rounding, once
                return xj.astype(jnp.float32)

            xs = [read(j) for j in range(n)]

            def mix_worker(i, total):
                o = w_ref[i, 0] * xs[0]
                for j in range(1, n):
                    o = o + w_ref[i, j] * xs[j]
                o = o.astype(o_ref.dtype)
                o_ref[i, rows_at, cols] = o
                return total + o

            total = over_workers(
                mix_worker, jnp.zeros((_SUBLANES, width), jnp.float32))
            mean = total * inv_n
            if ragged:
                live = (row0 + r0 + jax.lax.broadcasted_iota(
                    jnp.int32, (_SUBLANES, width), 0)) < rows

            def square_worker(i, carry):
                d = o_ref[i, rows_at, cols] - mean
                sq = d * d
                if ragged:
                    sq = jnp.where(live, sq, 0.0)
                acc_ref[i, :, 0:width] = acc_ref[i, :, 0:width] + sq
                return carry

            over_workers(square_worker, 0)

        def row_group(g, carry):
            r0 = pl.multiple_of(g * _SUBLANES, _SUBLANES)
            rows_at = pl.ds(r0, _SUBLANES)
            if full:
                def col_chunk(k, carry):
                    c0 = pl.multiple_of(k * chunk, _LANES)
                    mix(rows_at, pl.ds(c0, chunk), chunk, r0)
                    return carry

                jax.lax.fori_loop(0, full, col_chunk, 0)
            if tail:
                mix(rows_at, pl.ds(full * chunk, tail), tail, r0)
            return carry

        jax.lax.fori_loop(0, block_rows // _SUBLANES, row_group, 0)

        def sum_worker(i, carry):
            s_ref[i, block] = jnp.sum(acc_ref[i])
            return carry

        over_workers(sum_worker, 0)

    return _kernel


@functools.partial(jax.jit,
                   static_argnames=("wire_dtype", "interpret", "block_rows"))
def leaf_mix(x: jax.Array, w: jax.Array, *, wire_dtype=None,
             interpret: bool = False, block_rows: int | None = None):
    """One gossip step ``x <- W x`` over one leaf ``[N, r, c]``, in place,
    and the squares the disagreement needs, in the same pass.  Jitted: in a
    program that mixes many leaves, those of one shape share one traced and
    lowered kernel site (``_LEAF_MAX_SHAPES``).

    Returns ``(x', sq)``: ``x'[i] = sum_j W[i, j] x[j]`` as
    :func:`stream_mix` computes it (float32 products and sums in the same
    order over j, the wire's rounding on the read, ``w`` rounded by the
    caller), written over ``x`` (``input_output_aliases``); ``sq[i, b]`` is
    block ``b``'s ``sum((x'[i] - mean_j x'[j])^2)``: summed over ``b`` they
    are worker ``i``'s share of ``worker_deviation_rows``'s squares.  The
    grid tiles ``r``; a last block past its end is padded on the read,
    clipped on the write and left out of the sums.
    """
    n, r, c = x.shape
    if w.shape != (n, n):
        raise ValueError(f"mixing matrix {w.shape} vs leaf {x.shape}")
    if r % _SUBLANES:
        raise ValueError(f"leaf {x.shape}: {r} rows are not whole sublanes")
    wire = resolve_wire_dtype(wire_dtype)
    block_rows, chunk = _leaf_geometry(n, r, c, block_rows)
    blocks = pl.cdiv(r, block_rows)
    block = pl.BlockSpec((n, block_rows, c), lambda b: (0, b, 0))
    return pl.pallas_call(
        _make_leaf_kernel(n, r, block_rows, c, chunk, wire),
        grid=(blocks,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), block],
        out_specs=[block, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((n, blocks), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, _SUBLANES, min(chunk, c)), jnp.float32)],
        input_output_aliases={1: 0},
        interpret=interpret,
    )(w.astype(jnp.float32), x)


def tree_mix(leaves, w: jax.Array, *, wire_dtype=None,
             interpret: bool = False):
    """One gossip step ``x <- W x`` over a list of ``[N, ...]`` leaves, no
    flat copy of the whole built: every leaf :func:`leaf_views` takes is
    mixed where it lies (:func:`leaf_mix`); the others are flattened into
    one small buffer, go through :func:`stream_mix` and come back by
    slices.

    Returns ``(leaves', sq)`` with ``sq[i] = sum((x'_i - mean_j x'_j)^2)``
    over every element of worker ``i``: what ``worker_disagreement`` and
    ``worker_deviation_rows`` of the flat ``[N, D]`` state reduce, summed a
    block and a leaf at a time.
    """
    n = leaves[0].shape[0]
    mixed = list(leaves)
    sq = jnp.zeros((n,), jnp.float32)
    rest = []
    views = leaf_views(n, [leaf.shape[1:] for leaf in leaves],
                       [leaf.dtype for leaf in leaves])
    for k, (leaf, view) in enumerate(zip(leaves, views)):
        if isinstance(view, str):
            rest.append(k)
            continue
        r, c, swapped = view
        x = jnp.swapaxes(leaf, 1, 2) if swapped else leaf
        x, blocks = leaf_mix(x.reshape(n, r, c), w, wire_dtype=wire_dtype,
                             interpret=interpret)
        sq = sq + jnp.sum(blocks, axis=1)
        mixed[k] = (jnp.swapaxes(x, 1, 2) if swapped
                    else x.reshape(leaf.shape))
    if rest:
        rest_leaves = [leaves[k] for k in rest]
        flattener = WorkerFlattener(rest_leaves)
        flat = stream_mix(flattener.flatten(rest_leaves), w,
                          wire_dtype=wire_dtype, interpret=interpret)
        sq = sq + worker_square_rows([flat])
        for k, leaf in zip(rest, flattener.unflatten(flat)):
            mixed[k] = leaf
    return mixed, sq
