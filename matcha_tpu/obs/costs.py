"""Compiled-cost introspection + the automatic roofline (DESIGN.md §15).

Until ISSUE 8 every performance ceiling in this repo was hand-derived:
``benchmarks/ROOFLINE.md`` multiplies 2·N²·D by hand, DESIGN.md §9 does the
HBM capacity arithmetic in a prose table, and ``bench.py`` carries its own
FLOP/byte *model* of the kernels it times.  This module extracts those
numbers from the **compiled program itself** instead:

* :func:`analyze_program` lowers + compiles any jitted callable against
  abstract inputs (``jax.ShapeDtypeStruct`` — no buffers are allocated, no
  step is executed) and reads XLA's own ``cost_analysis()`` /
  ``memory_analysis()``: FLOPs, bytes accessed, argument/output/temp/alias
  footprint, compile wall-time, argument shardings.
* :class:`CostLedger` journals one schema-v2 ``compile`` event per distinct
  program the train loop builds (label + jit-cache fingerprint), turning
  the retrace watch's "the cache grew" into "the cache grew *and here is
  the program that was added and what it costs*".
* :class:`Roofline` combines extracted per-step costs with a pinned
  per-chip peak table to emit compute-bound and HBM-bound steps/s ceilings
  — machine-checking the ROOFLINE.md arithmetic — and
  :func:`capacity_report` re-derives the §9 HBM capacity table from
  ``memory_analysis()`` instead of hand multiplication.

Byte semantics (the part worth being precise about): ``cost_analysis()``'s
``bytes accessed`` counts every operand/result of every fused op, so it is
*realized* traffic and backend-dependent — the CPU backend materializes
f32 upcasts a TPU fusion would keep in registers, inflating it ~5× on the
bf16 dense step.  The roofline therefore uses the **program-boundary
traffic** ``hbm_bytes = argument + output − aliased`` bytes from
``memory_analysis()``: the bytes that *must* cross HBM per program run no
matter how well the backend fuses — exactly the quantity ROOFLINE.md's
2·N·D·2B hand model describes.  Both numbers are journaled; the ceiling is
computed from the boundary floor, and ``bytes_accessed`` tells you how far
the realized program is from it.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

__all__ = ["ChipSpec", "CHIP_PEAKS", "CPU_PROVISIONAL", "UnknownChipError",
           "chip_peaks", "resolve_chip", "abstract_args", "program_fingerprint",
           "analyze_program", "CostLedger", "Roofline", "gossip_step_costs",
           "gossip_chain_costs", "elision_epoch_costs", "flat_param_dim",
           "roofline_report",
           "capacity_report", "render_roofline_markdown",
           "render_capacity_markdown"]


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Pinned public per-chip peaks (bf16 matmul TFLOP/s, HBM GB/s, HBM GB).

    Sources: cloud.google.com/tpu/docs/system-architecture-tpu-vm.  The
    ``provisional`` flag marks entries that are placeholders for relative
    arithmetic only (the CPU row), never hardware claims.
    """

    peak_tflops: float
    peak_gbps: float
    hbm_gb: float
    provisional: bool = False


#: device_kind substring → pinned peaks.  This is the ONE chip table in the
#: repo: ``bench.py`` imports :func:`chip_peaks` from here.
CHIP_PEAKS: Dict[str, ChipSpec] = {
    "v6": ChipSpec(918.0, 1640.0, 32.0),
    "v5p": ChipSpec(459.0, 2765.0, 95.0),
    "v5e": ChipSpec(197.0, 819.0, 16.0),
    "v5lite": ChipSpec(197.0, 819.0, 16.0),
    "v4": ChipSpec(275.0, 1228.0, 32.0),
    "v3": ChipSpec(123.0, 900.0, 32.0),
    "v2": ChipSpec(45.0, 700.0, 16.0),
}

#: The CPU row: the roofline's *relative* arithmetic (which bound binds,
#: boundary-byte ratios) must still produce finite ceilings on the
#: CPU test host — order-of-magnitude placeholders for one server core
#: (AVX f32 matmul, DDR stream), flagged provisional in every report so
#: they can never be read as a hardware claim.  Returned only when the
#: platform *is* ``cpu`` or ``chip="cpu"`` was asked for.
CPU_PROVISIONAL = ChipSpec(0.1, 20.0, 64.0, provisional=True)


class UnknownChipError(ValueError):
    """A device kind that is not in :data:`CHIP_PEAKS` — an error, never a
    default: a utilization against the wrong peaks is a wrong number."""


def _lookup_chip(kind: str):
    key = kind.lower().replace(" ", "")
    for name, spec in CHIP_PEAKS.items():
        if name in key:
            return name, spec
    raise UnknownChipError(
        f"unknown chip: device kind {kind!r} is not in obs.costs.CHIP_PEAKS "
        f"({sorted(CHIP_PEAKS)}); add its published peaks with their "
        f"source before measuring on it")


def chip_peaks(device_kind: str):
    """``(peak_tflops, peak_gbps)`` for a device kind;
    :class:`UnknownChipError` when the table does not have it."""
    _, spec = _lookup_chip(device_kind)
    return spec.peak_tflops, spec.peak_gbps


def resolve_chip(chip: Optional[str] = None):
    """``(name, ChipSpec)`` for a chip override or the current backend.

    ``chip`` may name a table key (``"v5e"``) or ``"cpu"``; None matches
    the first jax device: its table row on an accelerator
    (:class:`UnknownChipError` if it has none), the CPU row on the CPU."""
    if chip is not None:
        if "cpu" in chip.lower():
            return "cpu-provisional", CPU_PROVISIONAL
        return _lookup_chip(chip)
    import jax

    device = jax.devices()[0]
    if device.platform == "cpu":
        return "cpu-provisional", CPU_PROVISIONAL
    return _lookup_chip(device.device_kind)


# ---------------------------------------------------------------------------
# Program introspection
# ---------------------------------------------------------------------------

def abstract_args(args):
    """Abstract (ShapeDtypeStruct) twins of a call's arguments.

    Captured *before* the call so a donated/consumed buffer can still be
    lowered from afterwards.  Mesh (Named) shardings ride along — a
    mesh-sharded state must lower to the same partitioned program the loop
    runs.  Single-device shardings are deliberately dropped: a fresh
    ``jnp.asarray`` input is *uncommitted* (jit is free to move it next to
    the sharded state), but an explicit sharding on its abstract twin
    would pin it and make the lowering reject the device mix the real
    call resolves silently."""
    import jax

    def to_spec(leaf):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            sharding = getattr(leaf, "sharding", None)
            if not isinstance(sharding, jax.sharding.NamedSharding):
                sharding = None
            if sharding is not None:
                return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                            sharding=sharding)
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map(to_spec, args)


def program_fingerprint(label: str, spec_args) -> str:
    """Stable 12-hex id of (label, input avals + shardings) — the same key
    axis the jit cache distinguishes programs by, so one fingerprint names
    one compiled program of one call site."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(spec_args)
    h = hashlib.sha1(label.encode())
    h.update(str(treedef).encode())
    for leaf in leaves:
        if hasattr(leaf, "shape"):
            h.update(f"{tuple(leaf.shape)}:{leaf.dtype}:"
                     f"{getattr(leaf, 'sharding', None)}".encode())
        else:
            h.update(repr(leaf).encode())
    return h.hexdigest()[:12]


def analyze_program(fn: Callable, *args, label: str = "program") -> Dict:
    """Lower + compile ``fn`` against abstract twins of ``args`` and read
    the compiled executable's own cost/memory analysis.

    No buffers are allocated and nothing executes — ``args`` may be real
    arrays (their avals/shardings are captured) or ShapeDtypeStructs.  The
    returned dict is the payload of a schema-v2 ``compile`` journal event:

    ``flops`` / ``bytes_accessed``
        XLA cost analysis: arithmetic issued, realized operand+result
        traffic across all (possibly fused) ops.
    ``hbm_bytes``
        program-boundary traffic floor: argument + output − aliased bytes
        (see module docstring — the roofline's byte model).
    ``arg_bytes`` / ``out_bytes`` / ``temp_bytes`` / ``alias_bytes`` /
    ``peak_bytes``
        memory analysis; ``peak_bytes = arg + out + temp − alias`` is the
        program's HBM footprint (what §9's capacity table is made of).
    ``compile_seconds`` / ``arg_shardings``
        compile wall-time of *this* introspection compile, and the input
        sharding per argument leaf.
    """
    spec = abstract_args(args)
    t0 = time.time()
    lowered = fn.lower(*spec) if hasattr(fn, "lower") else None
    if lowered is None:
        raise TypeError(f"{label}: fn has no .lower() — pass a jax.jit "
                        f"wrapped callable")
    compiled = lowered.compile()
    compile_seconds = time.time() - t0
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    arg_b = float(getattr(ma, "argument_size_in_bytes", 0) or 0)
    out_b = float(getattr(ma, "output_size_in_bytes", 0) or 0)
    tmp_b = float(getattr(ma, "temp_size_in_bytes", 0) or 0)
    alias_b = float(getattr(ma, "alias_size_in_bytes", 0) or 0)
    import jax

    # compact sharding record: the deduplicated *specs* across the input
    # leaves, not per-leaf reprs (a TrainState has dozens of identically-
    # sharded leaves; journal lines must stay one-screen readable)
    in_shardings: List[str] = []
    for leaf in jax.tree_util.tree_leaves(spec):
        s = getattr(leaf, "sharding", None)
        desc = "auto" if s is None else \
            f"{type(s).__name__}({getattr(s, 'spec', '')})"
        if desc not in in_shardings:
            in_shardings.append(desc)
    return {
        "label": label,
        "fingerprint": program_fingerprint(label, spec),
        "compile_seconds": round(compile_seconds, 4),
        "flops": float(ca.get("flops", float("nan"))),
        "bytes_accessed": float(ca.get("bytes accessed", float("nan"))),
        "arg_bytes": arg_b,
        "out_bytes": out_b,
        "temp_bytes": tmp_b,
        "alias_bytes": alias_b,
        "hbm_bytes": arg_b + out_b - alias_b,
        "peak_bytes": arg_b + out_b + tmp_b - alias_b,
        "arg_shardings": in_shardings,
    }


class CostLedger:
    """Journal one ``compile`` event per distinct program of the run.

    The train loop calls :meth:`observe` with a call site's label, jitted
    fn, and the arguments it is about to pass (cheap: aval capture + a
    fingerprint hash).  The first time a (label, fingerprint) pair appears
    the program is introspected via :func:`analyze_program` — one extra
    AOT compile per distinct program, paid once and gated behind
    ``config.telemetry`` — and the event flows through the supplied
    ``log_event`` (the Recorder's journal sink).  Every later epoch's
    observe of the same program is a dict lookup.

    This is what upgrades the retrace watch: a growing jit cache now has a
    ``compile`` event naming the program that was added, its cost, and its
    footprint — :meth:`last_fingerprint` lets the watch stamp its
    ``retrace`` event with the offending program's id.
    """

    def __init__(self, log_event: Callable[..., dict]):
        self._log = log_event
        self._seen: Dict[tuple, dict] = {}
        self._last_fp: Dict[str, str] = {}
        # strong refs to observed fns: the dedup key includes id(fn) — a
        # recovery rebuild of an identical-signature program is a real new
        # compile and must journal — and a held ref keeps a freed id from
        # aliasing a later program into silence
        self._refs: List = []

    def observe(self, label: str, fn, *args) -> Optional[dict]:
        """Introspect+journal if this (program, label, input-signature) is
        new.  Returns the compile event when one was journaled, None when
        the program was already on the ledger (a dict lookup)."""
        spec = abstract_args(args)
        fp = program_fingerprint(label, spec)
        self._last_fp[label] = fp
        key = (id(fn), label, fp)
        if key in self._seen:
            return None
        costs = analyze_program(fn, *spec, label=label)
        event = self._log("compile", **costs)
        self._seen[key] = event
        self._refs.append(fn)
        return event

    def last_fingerprint(self, label: str) -> Optional[str]:
        """The most recently observed program id for a call site — what a
        ``retrace`` event stamps so cache growth names its program."""
        return self._last_fp.get(label)

    @property
    def programs(self) -> List[dict]:
        return list(self._seen.values())


# ---------------------------------------------------------------------------
# The automatic roofline
# ---------------------------------------------------------------------------

def flat_param_dim(model_name: str, dataset: str = "synthetic",
                   num_classes: int = 10) -> int:
    """Flat parameter dimension D of a registry model, via ``eval_shape``
    (shapes only — nothing compiles or runs; the same trick bench.py uses
    to size the north-star state)."""
    import jax
    import jax.numpy as jnp

    from ..models import dataset_input_shape, select_model

    try:
        shape = dataset_input_shape(dataset)
    except KeyError as e:
        raise ValueError(f"unknown dataset {dataset!r} for --model dim "
                         f"derivation; pass --dim explicitly") from e
    model = select_model(model_name, dataset, num_classes=num_classes)
    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1,) + tuple(shape)), train=False),
        jax.random.PRNGKey(0))
    return sum(int(np.prod(p.shape))
               for p in jax.tree_util.tree_leaves(variables["params"]))


def gossip_step_costs(n: int, dim: int, decomposed: Sequence[Sequence[tuple]],
                      wire_dtype: str = "bf16") -> Dict:
    """Extracted costs of ONE dense per-step gossip program at shape
    ``[n, dim]`` — the modeled hot path of ROOFLINE.md (every training
    step executes its own ``W_t @ x``).

    Compiled abstractly (ShapeDtypeStructs): the north-star shape is a
    280 MB state, but nothing is allocated here."""
    import jax
    import jax.numpy as jnp

    from ..parallel.gossip import dense_gossip_fn, resolve_wire_dtype
    from ..topology import matching_laplacians

    Ls = matching_laplacians(decomposed, n)
    wire = resolve_wire_dtype(None if wire_dtype == "f32" else wire_dtype)
    compute_dtype = jnp.float32 if wire is None else wire
    fn = jax.jit(dense_gossip_fn(Ls, compute_dtype=compute_dtype))
    x = jax.ShapeDtypeStruct((n, dim), compute_dtype)
    w = jax.ShapeDtypeStruct((len(Ls),), jnp.float32)
    return analyze_program(fn, x, w, label=f"gossip_step_dense_{wire_dtype}")


def gossip_chain_costs(n: int, dim: int, decomposed,
                       wire_dtype: str = "bf16",
                       t_steps: int = 200, block_d: int = 2048) -> Dict:
    """Extracted per-step costs of a T-step *chain* program — the fused
    W-stack kernel, amortized over its ``t_steps`` (the regime the kernel
    exists for: the state is read and written once per chain, and only the
    streamed ``[T, N, N]`` W stack scales with T).

    Compiled abstractly (``.lower().compile()``; interpret mode on the CPU
    only — the same program text tier-1 tests execute): ``hbm_bytes`` is the
    program-boundary argument+output traffic, so the chain's bytes carry
    the ``[T, N, N]`` stack straight from XLA's own statement of what must
    cross HBM.  Per-step fields divide by ``t_steps``.

    ``stream_hbm_bytes_per_step`` subtracts the exactly-known one-time
    state read+write (``2·N·D·state_bytes``) before amortizing: it is the
    *streamed operand* — per step, ``N²·w`` of W stack.  Note the boundary
    counts each operand ONCE per program; the physical per-D-block
    re-stream (``ceil(D/bd)×``) is realized traffic and shows up in
    ``bytes_accessed``, exactly the boundary-vs-realized split the module
    docstring defines.  ``model_*`` fields carry the hand model the
    extraction is checked against (``2·N²·D`` MXU FLOPs/step).
    """
    import jax
    import jax.numpy as jnp

    from ..parallel import fused_gossip_run
    from ..parallel.gossip import resolve_wire_dtype
    from ..parallel.pallas_gossip import pallas_interpret

    wire = resolve_wire_dtype(None if wire_dtype == "f32" else wire_dtype)
    wire_bytes = 4 if wire is None else jnp.dtype(wire).itemsize
    state_dtype = jnp.float32 if wire is None else wire
    interpret = pallas_interpret()
    x = jax.ShapeDtypeStruct((n, dim), state_dtype)
    stack = jax.ShapeDtypeStruct((t_steps, n, n), state_dtype)
    # re-jit a closure over the static kwargs: analyze_program needs a
    # bare .lower(*arrays) surface, and jit-of-jit lowers to the same
    # program (the inner call inlines)
    fn = jax.jit(lambda xx, ss: fused_gossip_run(
        xx, ss, block_d=block_d, interpret=interpret))
    costs = analyze_program(
        fn, x, stack, label=f"gossip_chain_fused_{wire_dtype}")
    # boundary stream: the W stack crosses HBM once per program —
    # N²·w per step (pad rows for T % w_window ride along upstream)
    model_stream = float(n * n * wire_bytes)
    state_bytes = 2.0 * n * dim * jnp.dtype(state_dtype).itemsize
    per_step = {
        "backend": "fused", "t_steps": int(t_steps),
        "block_d": int(block_d), "matchings": len(decomposed),
        "flops_per_step": costs["flops"] / t_steps,
        "hbm_bytes_per_step": costs["hbm_bytes"] / t_steps,
        "stream_hbm_bytes_per_step":
            max(costs["hbm_bytes"] - state_bytes, 0.0) / t_steps,
        "bytes_accessed_per_step": costs["bytes_accessed"] / t_steps,
        # hand model, per step: streamed operand + the amortized one-time
        # state read/write (2·N·D·w/T) — what the extracted boundary
        # number should match
        "model_hbm_bytes": model_stream + state_bytes / t_steps,
        "model_stream_hbm_bytes": model_stream,
        "model_flops": 2.0 * n * n * dim,
    }
    return {**costs, **per_step}


def elision_epoch_costs(n: int, dim: int, decomposed,
                        backend: str = "dense", wire_dtype: str = "bf16",
                        t_steps: int = 200, local_every: int = 1,
                        block_d: int = 2048) -> Dict:
    """Per-epoch gossip-attributed HBM boundary bytes under local-step
    elision (DESIGN.md §24) — the ledger's statement of what universal
    elision removes.

    With ``local_every = L``, the restructured epoch *executes* the mix
    only on steps with ``t % L == 0`` — ``ceil(T/L)`` of ``T`` — and the
    thinned steps' gossip programs never run, so their boundary traffic
    vanishes rather than being multiplied by an identity.  This function
    prices exactly that executed set:

    - ``dense``: the per-step ``W_t @ x`` program's boundary ``hbm_bytes``
      (:func:`gossip_step_costs` — state in+out and the flag row, each a
      real program boundary every executed step) × executed steps.
    - ``fused``: one chain program over the executed steps
      (:func:`gossip_chain_costs` at ``t_steps = ceil(T/L)``), minus the
      one-time state read+write both an L=1 and an L=4 epoch pay once —
      i.e. the *streamed operand* bytes, the term elision actually thins
      (W-stack rows).

    Returns the underlying program costs plus ``exec_steps``,
    ``gossip_hbm_bytes_per_epoch``, and ``gossip_hbm_bytes_per_step``
    (per *scheduled* step, ÷T — the number steps/s improvements track).
    The ≥2× L=1→L=4 reduction acceptance pin lives in
    ``tests/test_overlap.py``; ``bench.py --suite elision_grid`` records
    the same quantity next to measured steps/s.
    """
    local_every = max(int(local_every), 1)
    t_steps = int(t_steps)
    if t_steps < 1:
        raise ValueError(f"t_steps must be >= 1, got {t_steps}")
    exec_steps = -(-t_steps // local_every)  # ceil: t=0 always mixes
    if backend in ("dense", "skip"):
        # skip shares dense's per-executed-step program — its thinning
        # already happened at the flag level, so the executed set is the
        # same program either way
        costs = gossip_step_costs(n, dim, decomposed, wire_dtype=wire_dtype)
        per_epoch = costs["hbm_bytes"] * exec_steps
    elif backend == "fused":
        costs = gossip_chain_costs(
            n, dim, decomposed, wire_dtype=wire_dtype,
            t_steps=exec_steps, block_d=block_d)
        per_epoch = costs["stream_hbm_bytes_per_step"] * exec_steps
    else:
        raise ValueError(
            f"unknown elision backend {backend!r} (dense|skip|fused)")
    return {
        **costs,
        "backend": backend,
        "t_steps": t_steps,
        "local_every": local_every,
        "exec_steps": exec_steps,
        "gossip_hbm_bytes_per_epoch": float(per_epoch),
        "gossip_hbm_bytes_per_step": float(per_epoch) / t_steps,
    }


@dataclasses.dataclass(frozen=True)
class Roofline:
    """Per-chip ceilings from extracted per-step costs.

    ``ceilings(flops, hbm_bytes)`` answers: on this chip, what is the best
    steps/s any implementation of this program could reach, and which wall
    is closer — arithmetic or memory?"""

    chip: str
    spec: ChipSpec

    def ceilings(self, flops_per_step: float,
                 hbm_bytes_per_step: float) -> Dict:
        compute = (self.spec.peak_tflops * 1e12) / max(flops_per_step, 1.0)
        hbm = (self.spec.peak_gbps * 1e9) / max(hbm_bytes_per_step, 1.0)
        return {
            "chip": self.chip,
            "peak_tflops": self.spec.peak_tflops,
            "peak_gbps": self.spec.peak_gbps,
            "provisional": self.spec.provisional,
            "compute_bound_steps_per_sec": compute,
            "hbm_bound_steps_per_sec": hbm,
            "ceiling_steps_per_sec": min(compute, hbm),
            "bound": "compute" if compute <= hbm else "hbm",
        }


def roofline_report(n: int, dim: int, decomposed, wire_dtype: str = "bf16",
                    chip: Optional[str] = None,
                    measured_steps_per_sec: Optional[float] = None,
                    backend: str = "dense") -> Dict:
    """The automatic roofline: extracted per-step costs + the pinned chip
    peaks → ceilings, hand-model deltas, and (when a measured rate is
    supplied) the measured-vs-ceiling ratio.

    ``backend`` selects whose program is priced: ``"dense"`` compiles the
    per-step matmul (the historical report), ``"fused"`` compiles the
    multi-step chain kernel and amortizes per step (its boundary bytes
    carry the ``[T, N, N]`` W stack).  Every ratio derived from a measured
    rate records ``measured_vs_ceiling_backend`` — the ratio must name its
    denominator (a fused rate quoted against the dense ceiling, or vice
    versa, is the mis-citation this field exists to prevent).
    """
    if backend == "fused":
        costs = gossip_chain_costs(n, dim, decomposed,
                                   wire_dtype=wire_dtype)
        # XLA's cost_analysis does not multiply a scanned grid's body by
        # its trip count (the chain kernel lowers to a grid scan), so the
        # extracted chain FLOPs undercount by ~T× — the hand model is the
        # floor of work the formulation must issue, so the ceiling uses
        # whichever is larger; the raw extraction is kept alongside.
        # Boundary bytes are shape-derived and exact either way.
        flops = max(costs["flops_per_step"], costs["model_flops"])
        hbm = costs["hbm_bytes_per_step"]
        model_flops = costs["model_flops"]
        model_hbm = costs["model_hbm_bytes"]
        extra = {"bytes_accessed_per_step": costs["bytes_accessed_per_step"],
                 "stream_hbm_bytes_per_step":
                     costs["stream_hbm_bytes_per_step"],
                 "model_stream_hbm_bytes": costs["model_stream_hbm_bytes"],
                 "extracted_flops_per_step": costs["flops_per_step"],
                 "t_steps": costs["t_steps"], "block_d": costs["block_d"],
                 "matchings": costs["matchings"]}
    elif backend == "dense":
        costs = gossip_step_costs(n, dim, decomposed, wire_dtype=wire_dtype)
        flops = costs["flops"]
        hbm = costs["hbm_bytes"]
        # the hand model this machine-checks (ROOFLINE.md: 2·N²·D FLOPs,
        # 2·N·D·wire_bytes boundary traffic; the N² W-matrix term is the
        # extracted number's honest surplus over the hand model)
        bytes_el = 2 if wire_dtype == "bf16" else 4
        model_flops = 2.0 * n * n * dim
        model_hbm = 2.0 * n * dim * bytes_el
        extra = {"bytes_accessed_per_step": costs["bytes_accessed"]}
    else:
        raise ValueError(f"unknown roofline backend {backend!r} "
                         f"(dense|fused)")
    name, spec = resolve_chip(chip)
    report = {
        "n": int(n), "dim": int(dim), "wire_dtype": wire_dtype,
        "backend": backend,
        "flops_per_step": flops,
        "hbm_bytes_per_step": hbm,
        "peak_bytes": costs["peak_bytes"],
        "compile_seconds": costs["compile_seconds"],
        "fingerprint": costs["fingerprint"],
        **extra,
    }
    report.update(
        model_flops=model_flops, model_hbm_bytes=model_hbm,
        # the model-check ratio always uses the RAW extraction — for the
        # chain backend flops_per_step is the max(extracted, model)
        # ceiling floor, and a ratio of that against the model would read
        # 1.0 exactly when the extraction undercounts, silently disabling
        # the low-side check this field exists for
        flops_vs_model=extra.get("extracted_flops_per_step", flops)
        / model_flops,
        hbm_vs_model=hbm / model_hbm,
    )
    report.update(Roofline(name, spec).ceilings(flops, hbm))
    if measured_steps_per_sec is not None:
        report["measured_steps_per_sec"] = float(measured_steps_per_sec)
        report["measured_vs_ceiling"] = (
            float(measured_steps_per_sec) / report["ceiling_steps_per_sec"])
        # name the denominator: which backend's ceiling this ratio was
        # computed against (it must be impossible to quote it against the
        # wrong kernel)
        report["measured_vs_ceiling_backend"] = backend
        # the Pallas-promotion gate ratio: the fused kernel removes the
        # dense HBM wall (ROOFLINE.md), so its honest ceiling is the
        # compute bound — a measured rate above the dense ceiling_steps is
        # itself the evidence the formulation beat the memory wall
        report["measured_vs_compute_bound"] = (
            float(measured_steps_per_sec)
            / report["compute_bound_steps_per_sec"])
    return report


def _state_update_program(n: int, dim: int, communicator: str):
    """A jitted flat-state momentum-SGD update over every persistent
    ``[N, D]`` buffer the §9 table names — params + momentum, plus CHOCO's
    {x̂, s} carry.  The *footprint* is the object of interest: its
    argument bytes are XLA's own statement of what the buffers occupy."""
    import jax
    import jax.numpy as jnp

    if communicator == "choco":
        def update(x, m, xhat, s):
            m2 = 0.9 * m + x
            x2 = x - 0.1 * m2
            return x2, m2, xhat + 0.1 * s, s - xhat
    else:
        def update(x, m):
            m2 = 0.9 * m + x
            x2 = x - 0.1 * m2
            return x2, m2
    spec = jax.ShapeDtypeStruct((n, dim), jnp.float32)
    nargs = 4 if communicator == "choco" else 2
    return jax.jit(update), (spec,) * nargs


def capacity_report(dim: int, workers: Sequence[int] = (256, 64),
                    communicators: Sequence[str] = ("decen", "choco"),
                    chip: Optional[str] = None) -> Dict:
    """Re-derive the §9 HBM capacity table from ``memory_analysis()``.

    Each row compiles the persistent-state update program at ``[N, dim]``
    abstractly and reads its argument footprint — the bytes the optimizer
    state *must* occupy — then divides by the chip's HBM to answer "how
    many chips does the folded plan need" (state scales as N/C)."""
    name, spec = resolve_chip(chip)
    hbm = spec.hbm_gb * 1e9
    rows = []
    for comm in communicators:
        for n in workers:
            fn, args = _state_update_program(n, dim, comm)
            costs = analyze_program(fn, *args,
                                    label=f"state_update_{comm}_n{n}")
            state_bytes = costs["arg_bytes"]
            rows.append({
                "communicator": comm, "n": int(n), "dim": int(dim),
                "state_bytes": state_bytes,
                "buffers": 4 if comm == "choco" else 2,
                "chips_needed": int(np.ceil(state_bytes / hbm)),
                "fits_one_chip": bool(state_bytes <= hbm),
            })
    return {"chip": name, "hbm_gb": spec.hbm_gb,
            "provisional": spec.provisional, "dim": int(dim), "rows": rows}


# ---------------------------------------------------------------------------
# Markdown artifacts (obs_tpu.py roofline/capacity --md)
# ---------------------------------------------------------------------------

def _gb(x: float) -> str:
    for scale, unit in ((1e12, "TB"), (1e9, "GB"), (1e6, "MB"), (1e3, "kB")):
        if x >= scale:
            return f"{x / scale:.2f} {unit}"
    return f"{x:.0f} B"


#: Per-backend labels for the markdown hand-model column.
_MODEL_LABELS = {
    "dense": ("2·N²·D", "2·N·D·w"),
    "fused": ("2·N²·D", "N²·w + 2·N·D·w/T"),
}
_BACKEND_TITLES = {
    "dense": "dense per-step gossip",
    "fused": "fused W-stack chain (per step)",
}


def render_roofline_markdown(report: Dict, source: str = "") -> str:
    prov = (" (**CPU-provisional peaks** — relative arithmetic only)"
            if report.get("provisional") else "")
    backend = report.get("backend", "dense")
    flops_label, hbm_label = _MODEL_LABELS.get(backend,
                                               _MODEL_LABELS["dense"])
    raw_flops = report.get("extracted_flops_per_step",
                           report["flops_per_step"])
    clamped = raw_flops < report["flops_per_step"]
    lines = [
        f"# Automatic roofline — "
        f"{_BACKEND_TITLES.get(backend, backend)} @ N={report['n']}, "
        f"D={report['dim']}, {report['wire_dtype']} wire", "",
        f"Extracted from the compiled program via `cost_analysis()` / "
        f"`memory_analysis()` (program `{report['fingerprint']}`); chip "
        f"peaks pinned for **{report['chip']}**{prov}.", "",
        "| quantity | extracted | hand model | ratio |",
        "|---|---:|---:|---:|",
        f"| FLOPs/step | {raw_flops:.4g} "
        f"| {report['model_flops']:.4g} ({flops_label}) "
        f"| {report['flops_vs_model']:.4f} |",
        f"| HBM bytes/step (boundary) | {report['hbm_bytes_per_step']:.4g} "
        f"| {report['model_hbm_bytes']:.4g} ({hbm_label}) "
        f"| {report['hbm_vs_model']:.4f} |",
        "",
        f"| ceiling | steps/s |",
        "|---|---:|",
        f"| compute-bound ({report['peak_tflops']} TFLOP/s) "
        f"| {report['compute_bound_steps_per_sec']:.1f} |",
        f"| HBM-bound ({report['peak_gbps']} GB/s) "
        f"| {report['hbm_bound_steps_per_sec']:.1f} |",
        f"| **binding: {report['bound']}** "
        f"| **{report['ceiling_steps_per_sec']:.1f}** |",
    ]
    if clamped:
        lines += ["", f"FLOPs note: XLA's cost analysis does not multiply "
                      f"the chain's grid-scan body by its trip count, so "
                      f"the raw extraction above undercounts; the ceilings "
                      f"use the hand-model floor "
                      f"({report['flops_per_step']:.4g} FLOPs/step)."]
    if "measured_steps_per_sec" in report:
        origin = report.get("measured_backend")
        via = (f" (rate measured on the **{origin}** backend)"
               if origin and origin != backend else "")
        lines += ["", f"Measured: **{report['measured_steps_per_sec']:.1f} "
                      f"steps/s**{via} = "
                      f"{report['measured_vs_ceiling']:.1%} of "
                      f"the **{report.get('measured_vs_ceiling_backend', backend)}** "
                      f"ceiling (the ratio's denominator — quote it against "
                      f"no other backend's)."]
    if source:
        lines += ["", f"Source: `{source}`"]
    lines.append("")
    return "\n".join(lines)


def render_capacity_markdown(report: Dict) -> str:
    prov = (" (**CPU-provisional HBM figure**)" if report.get("provisional")
            else "")
    lines = [
        f"# HBM capacity — D={report['dim']}, per-chip HBM "
        f"{report['hbm_gb']:.0f} GB ({report['chip']}){prov}", "",
        "Derived from `memory_analysis().argument_size_in_bytes` of the "
        "persistent-state update program — XLA's own statement of what the "
        "optimizer state occupies (DESIGN.md §9, machine-checked).", "",
        "| communicator | N | persistent buffers | state bytes | "
        "chips needed (N/C fold) |",
        "|---|---:|---:|---:|---:|",
    ]
    for r in report["rows"]:
        lines.append(
            f"| {r['communicator']} | {r['n']} | {r['buffers']}×[N,D] f32 "
            f"| {_gb(r['state_bytes'])} | {r['chips_needed']} |")
    lines.append("")
    return "\n".join(lines)
