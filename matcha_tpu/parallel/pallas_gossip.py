"""Pallas TPU kernels: multi-step gossip with VMEM-resident state, and the
one-step streamed exchange at small N (``stream_mix``, further down).

The dense gossip backend (``gossip_mix_dense``) above its small-N crossover
runs one MXU matmul ``x ← W_t @ x`` per step, which is HBM-bound: every step
re-reads and re-writes the full ``[N, D]`` worker state (~280 MB round trip at the
north-star scale, 256 workers × ResNet-20).  But the per-step mixing matrix
``W_t = I − Σ_j α·flag[t,j]·L_j`` is tiny (256×256 bf16 = 131 KB), so a whole
*sequence* of gossip steps — the reference's outer iteration loop over
``active_flags`` (/root/reference/communicator.py:133-141) — can run with the
state resident in VMEM:

    grid = (D/block_d, T); the T axis iterates fastest.
    Each D-block of ``x`` is loaded into VMEM once, multiplied by the
    streamed ``W_t`` stack for all T steps (output-block revisiting keeps it
    on-chip), and written back once.

HBM traffic drops from ``T · 2·N·D`` to ``2·N·D + (D/block_d)·T·N²`` — about
two orders of magnitude at T=200 — turning the chain MXU-bound.  Arithmetic
is step-for-step identical to the scan over ``gossip_mix_dense`` (f32
accumulation, state cast to the wire dtype after every step), so intermediate
iterates match the per-step backend; only their HBM materialization is
elided.

The fused kernel sizes its resident blocks against the chip's VMEM before
Mosaic sees them: a block that cannot fit raises
:class:`GossipKernelResourceError` naming the shape, instead of a
compiler allocation dump.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .gossip import mxu_precision, resolve_wire_dtype

__all__ = [
    "GossipKernelResourceError",
    "build_mixing_stack",
    "canonical_chunk",
    "compose_mixing_stack",
    "fused_gossip_run",
    "check_fused_fits",
    "pallas_interpret",
    "stream_mix",
]


#: Mosaic's default scoped-VMEM limit per kernel on the v5e (the compiler's
#: own message: "limit 16.00M"); every resident block of a kernel here,
#: pipeline double-buffers included, must fit under it.
SCOPED_VMEM_BYTES = 16 * 2 ** 20


class GossipKernelResourceError(ValueError):
    """A Pallas gossip kernel's resident blocks exceed the chip's VMEM at
    the requested shape — raised at trace time, before Mosaic."""


def pallas_interpret() -> bool:
    """THE rule for ``interpret=``: the Pallas interpreter runs on the
    ``cpu`` platform and nowhere else — on any accelerator the kernels
    compile for the device or raise, never quietly interpret."""
    return jax.default_backend() == "cpu"


#: default resident D-block width of the fused kernel
_BLOCK_D = 2048


def check_fused_fits(n: int, *, block_d: int = _BLOCK_D, w_window: int = 1,
                     state_itemsize: int, stack_itemsize: int) -> None:
    """Raise :class:`GossipKernelResourceError` unless
    :func:`fused_gossip_run`'s resident VMEM fits: the ``[N, block_d]`` in
    and out blocks and the ``[w_window, N, N]`` W window, each
    double-buffered by the Pallas pipeline.  Slightly conservative at the
    edge (Mosaic accepts bf16 N=256 block_d=8192, 16.25 MiB by this
    count) — the point is a named refusal before the allocator's dump."""
    need_bytes = (4 * n * block_d * state_itemsize
                  + 2 * w_window * n * n * stack_itemsize)
    if need_bytes > SCOPED_VMEM_BYTES:
        raise GossipKernelResourceError(
            f"fused kernel: N={n} rows x block_d={block_d}, "
            f"w_window={w_window} keeps {need_bytes / 2 ** 20:.1f} MiB "
            f"resident in VMEM, over the {SCOPED_VMEM_BYTES / 2 ** 20:.0f} "
            f"MiB scoped limit — ask for a smaller block")


def build_mixing_stack(
    laplacians,
    alpha: float,
    flags: jax.Array,
    dtype=jnp.bfloat16,
) -> jax.Array:
    """``W[t] = I − Σ_j α·flags[t,j]·L_j`` for every step — ``[T, N, N]``.

    The whole stack for a 200-step window at N=256 is ~26 MB bf16; it is the
    *streamed* operand of the fused kernel (the state is the resident one).
    """
    L = jnp.asarray(np.asarray(laplacians), jnp.float32)  # [M, N, N]
    n = L.shape[-1]
    w = alpha * jnp.asarray(flags, jnp.float32)  # [T, M]
    stack = jnp.eye(n, dtype=jnp.float32)[None] - jnp.einsum("tm,mnk->tnk", w, L)
    return stack.astype(dtype)


def canonical_chunk(chunk: int) -> int:
    """The chunk size compose_mixing_stack actually executes: powers of two
    (pairwise doubling); values ≤ 1 disable composition."""
    # operator.index, not int(): chunk rides static_argnames (a trace-time
    # python int by design) and __index__ rejects floats and tracers loudly
    # instead of silently concretizing — the honest spelling of "this must
    # already be an int", and GL002-clean at the source
    chunk = operator.index(chunk)
    return chunk if chunk <= 1 else 1 << (chunk - 1).bit_length()


@functools.partial(jax.jit, static_argnames=("chunk",))
def compose_mixing_stack(stack: jax.Array, chunk: int) -> jax.Array:
    """Collapse runs of ``chunk`` consecutive mixing matrices into their
    product: ``P_c = W_{cS+S−1} ⋯ W_{cS}`` — ``[⌈T/S⌉, N, N]``.

    The gossip chain is a linear time-varying system ``x_{t+1} = W_t x_t``,
    so by associativity applying ``P_c`` once per chunk computes exactly the
    same ``x_T`` while cutting the dominant per-step cost ``2·N²·D`` down to
    ``2·N²·D/S + 2·N³`` (the N×N products are ~D/N ≈ 1000× cheaper than an
    apply at the north-star scale).  Accumulation inside every product is f32
    (``preferred_element_type``); for a bf16 stack the multiply operands
    round to bf16 once per doubling level on TPU — log₂(S) operand roundings
    per chunk versus S state roundings for the step-by-step chain, so the
    composed chain is still strictly *more* accurate than stepping (an f32
    stack composes at HIGHEST and rounds only at the final cast).

    ``chunk`` is rounded up to a power of two: composition runs as log₂(S)
    pairwise-doubling levels, each one big batched ``[T/2ᵏ, N, N]`` matmul —
    on v5e this times ~1.8× faster than per-chunk sequential products
    (the early levels keep the MXU saturated with large batches).

    Trade-off: intermediate iterates ``x_t`` inside a chunk are never
    materialized — right for consensus-only phases and the throughput bench;
    training interleaves one gossip step per SGD step and keeps ``chunk=1``.
    """
    t_steps, n, _ = stack.shape
    chunk2 = canonical_chunk(chunk)  # power-of-two granularity
    if chunk2 <= 1:
        return stack
    levels = chunk2.bit_length() - 1
    pad = (-t_steps) % chunk2
    w = stack.astype(jnp.float32)
    if pad:
        w = jnp.concatenate([w, jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32),
                                                 (pad, n, n))])
    # Precision follows the *wire* dtype of the stack, decided before the f32
    # accumulation cast: a bf16 chain keeps DEFAULT (bf16 MXU passes, f32
    # accumulation — the log₂(S)-roundings contract in the docstring), while
    # an f32 chain gets HIGHEST so f32 means f32 on TPU.  Unconditional
    # HIGHEST would 6x the composition passes, and at chunk=S composition is
    # S·N/D of the apply FLOPs (~24% at the north-star shape) — not free.
    precision = mxu_precision(stack.dtype)
    for _ in range(levels):
        # steps (2i, 2i+1) fuse to W_{2i+1} @ W_{2i}: later steps on the left
        w = jnp.einsum("bij,bjk->bik", w[1::2], w[0::2],
                       precision=precision,
                       preferred_element_type=jnp.float32)
    return w.astype(stack.dtype)


def _make_kernel(w_window: int, precision):
    def _kernel(x_ref, w_ref, o_ref):
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _():
            o_ref[...] = x_ref[...]

        # Cast the state into the W (wire/compute) dtype at each step's
        # input, exactly like gossip_mix_dense does — so fused and per-step
        # dense agree bitwise even when state dtype != compute dtype (no-op
        # when equal).  The window loop is unrolled: each of the w_window
        # steps in this grid visit still executes its own cast-dot-cast in
        # stream order, so the arithmetic is step-for-step identical to
        # w_window=1 — only the grid-step count and W DMA granularity change.
        for k in range(w_window):
            o_ref[...] = jnp.dot(
                w_ref[k], o_ref[...].astype(w_ref.dtype),
                precision=precision,
                preferred_element_type=jnp.float32,
            ).astype(o_ref.dtype)

    return _kernel


@functools.partial(jax.jit, static_argnames=("block_d", "w_window", "interpret"))
def fused_gossip_run(
    x: jax.Array,
    mixing_stack: jax.Array,
    *,
    block_d: int = _BLOCK_D,
    w_window: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """Apply ``T`` gossip steps ``x ← cast(W_t @ x)`` in one kernel launch.

    ``x``: ``[N, D]`` worker state (rows = virtual workers).  ``mixing_stack``:
    ``[T, N, N]`` from :func:`build_mixing_stack`.  Each step accumulates in
    f32 on the MXU and casts back to ``x.dtype`` — bit-matching the per-step
    dense backend in its wire dtype.  ``interpret=True`` runs the Pallas
    interpreter (CPU tests).

    ``w_window``: number of consecutive ``W_t`` processed per grid visit of a
    D-block.  Unlike chunked composition this does NOT change the per-step
    arithmetic (every step's matmul executes, in order, with its own cast) —
    it only shrinks the grid to ``(D/block_d) · T/w`` steps and lets each W
    DMA move ``w·N²`` contiguous bytes, so per-grid-step overhead and DMA
    latency amortize over ``w`` real steps.  Total W traffic is unchanged.
    ``T`` not divisible by ``w_window`` is handled by *front*-padding the
    stack with identity matrices — bitwise exact even in mixed-dtype mode:
    the pad steps produce ``cast_state(I @ cast_wire(x))``, and the first
    real step's input cast makes that indistinguishable from starting at
    ``x`` (back-padding would instead round the final f32 accumulation
    through the wire dtype).
    """
    n, d = x.shape
    t_steps = mixing_stack.shape[0]
    if mixing_stack.shape[1:] != (n, n):
        raise ValueError(f"mixing stack {mixing_stack.shape} vs state {x.shape}")
    if t_steps == 0:
        return x
    block_d = min(block_d, d)
    # operator.index: w_window rides static_argnames (trace-time int);
    # see canonical_chunk — rejects tracers/floats instead of concretizing
    w_window = max(1, min(operator.index(w_window), t_steps))
    pad = (-t_steps) % w_window
    if pad:
        eye = jnp.broadcast_to(
            jnp.eye(n, dtype=mixing_stack.dtype), (pad, n, n))
        mixing_stack = jnp.concatenate([eye, mixing_stack])
    check_fused_fits(n, block_d=block_d, w_window=w_window,
                     state_itemsize=x.dtype.itemsize,
                     stack_itemsize=mixing_stack.dtype.itemsize)
    grid = (pl.cdiv(d, block_d), (t_steps + pad) // w_window)
    return pl.pallas_call(
        _make_kernel(w_window, mxu_precision(mixing_stack.dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, block_d), lambda i, t: (0, i)),
            pl.BlockSpec((w_window, n, n), lambda i, t: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((n, block_d), lambda i, t: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=interpret,
    )(x, mixing_stack)


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
