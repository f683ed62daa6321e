"""Compiled-cost introspection + the automatic roofline (DESIGN.md §15).

Until ISSUE 8 every performance ceiling in this repo was hand-derived:
``benchmarks/ROOFLINE.md`` multiplies 2·N²·D by hand, DESIGN.md §9 does the
HBM capacity arithmetic in a prose table.  This module extracts those
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
           "elision_epoch_costs", "flat_param_dim",
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
#: repo.
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
    (shapes only — nothing compiles or runs)."""
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


def elision_epoch_costs(n: int, dim: int, decomposed,
                        backend: str = "dense", wire_dtype: str = "bf16",
                        t_steps: int = 200, local_every: int = 1) -> Dict:
    """Per-epoch gossip-attributed HBM boundary bytes under local-step
    elision (DESIGN.md §24) — the ledger's statement of what universal
    elision removes.

    With ``local_every = L``, the restructured epoch *executes* the mix
    only on steps with ``t % L == 0`` — ``ceil(T/L)`` of ``T`` — and the
    thinned steps' gossip programs never run, so their boundary traffic
    vanishes rather than being multiplied by an identity.  This function
    prices exactly that executed set: the per-step ``W_t @ x`` program's
    boundary ``hbm_bytes`` (:func:`gossip_step_costs` — state in+out and
    the flag row, each a real program boundary every executed step) ×
    executed steps.

    Returns the underlying program costs plus ``exec_steps``,
    ``gossip_hbm_bytes_per_epoch``, and ``gossip_hbm_bytes_per_step``
    (per *scheduled* step, ÷T — the number steps/s improvements track).
    The ≥2× L=1→L=4 reduction acceptance pin lives in
    ``tests/test_overlap.py``.
    """
    local_every = max(int(local_every), 1)
    t_steps = int(t_steps)
    if t_steps < 1:
        raise ValueError(f"t_steps must be >= 1, got {t_steps}")
    exec_steps = -(-t_steps // local_every)  # ceil: t=0 always mixes
    if backend not in ("dense", "skip"):
        raise ValueError(
            f"unknown elision backend {backend!r} (dense|skip)")
    # skip shares dense's per-executed-step program — its thinning already
    # happened at the flag level, so the executed set is the same program
    # either way
    costs = gossip_step_costs(n, dim, decomposed, wire_dtype=wire_dtype)
    per_epoch = costs["hbm_bytes"] * exec_steps
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
                    measured_steps_per_sec: Optional[float] = None) -> Dict:
    """The automatic roofline: the dense per-step program's extracted costs
    + the pinned chip peaks → ceilings, hand-model deltas, and (when a
    measured rate is supplied) the measured-vs-ceiling ratio.
    """
    costs = gossip_step_costs(n, dim, decomposed, wire_dtype=wire_dtype)
    flops = costs["flops"]
    hbm = costs["hbm_bytes"]
    # the hand model this machine-checks (ROOFLINE.md: 2·N²·D FLOPs,
    # 2·N·D·wire_bytes boundary traffic; the N² W-matrix term is the
    # extracted number's honest surplus over the hand model)
    bytes_el = 2 if wire_dtype == "bf16" else 4
    model_flops = 2.0 * n * n * dim
    model_hbm = 2.0 * n * dim * bytes_el
    name, spec = resolve_chip(chip)
    report = {
        "n": int(n), "dim": int(dim), "wire_dtype": wire_dtype,
        "flops_per_step": flops,
        "hbm_bytes_per_step": hbm,
        "peak_bytes": costs["peak_bytes"],
        "compile_seconds": costs["compile_seconds"],
        "fingerprint": costs["fingerprint"],
        "bytes_accessed_per_step": costs["bytes_accessed"],
        "model_flops": model_flops, "model_hbm_bytes": model_hbm,
        "flops_vs_model": flops / model_flops,
        "hbm_vs_model": hbm / model_hbm,
    }
    report.update(Roofline(name, spec).ceilings(flops, hbm))
    if measured_steps_per_sec is not None:
        report["measured_steps_per_sec"] = float(measured_steps_per_sec)
        report["measured_vs_ceiling"] = (
            float(measured_steps_per_sec) / report["ceiling_steps_per_sec"])
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


def render_roofline_markdown(report: Dict, source: str = "") -> str:
    prov = (" (**CPU-provisional peaks** — relative arithmetic only)"
            if report.get("provisional") else "")
    lines = [
        f"# Automatic roofline — dense per-step gossip @ N={report['n']}, "
        f"D={report['dim']}, {report['wire_dtype']} wire", "",
        f"Extracted from the compiled program via `cost_analysis()` / "
        f"`memory_analysis()` (program `{report['fingerprint']}`); chip "
        f"peaks pinned for **{report['chip']}**{prov}.", "",
        "| quantity | extracted | hand model | ratio |",
        "|---|---:|---:|---:|",
        f"| FLOPs/step | {report['flops_per_step']:.4g} "
        f"| {report['model_flops']:.4g} (2·N²·D) "
        f"| {report['flops_vs_model']:.4f} |",
        f"| HBM bytes/step (boundary) | {report['hbm_bytes_per_step']:.4g} "
        f"| {report['model_hbm_bytes']:.4g} (2·N·D·w) "
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
    if "measured_steps_per_sec" in report:
        lines += ["", f"Measured: **{report['measured_steps_per_sec']:.1f} "
                      f"steps/s** = {report['measured_vs_ceiling']:.1%} of "
                      f"the ceiling."]
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
