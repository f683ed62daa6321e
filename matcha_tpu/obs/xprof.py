"""Overlap truth: parse executed-profiler traces, attribute device time.

The ``--overlap 1step`` pipeline's central claim — XLA actually runs the
gossip exchange *under* the next step's compute (DESIGN.md §11) — was
asserted from program structure, never verified against an executed trace.
"From promise to practice" (PAPERS.md) documents exactly this gap: the
predicted comm/comp overlap is where decentralized speedups evaporate.

This module closes it.  ``utils.profiling.trace`` already captures a
``jax.profiler`` trace (a Chrome trace-event ``*.trace.json.gz`` under
``plugins/profile/<run>/``), and ``device_span`` already stamps every
in-graph phase's ops with ``matcha/*`` / ``comm/*`` named scopes that
survive into the executed kernels' rows.  The parser here:

1. reads the trace's **device** lanes only (process names ``/device:...``
   — host python rows prove nothing about kernel concurrency),
2. attributes each executed kernel row to a phase by searching its name
   and metadata for the ``comm/`` and ``matcha/`` scope prefixes,
3. merges each phase's time intervals and intersects them: the comm/comp
   **overlap fraction** is the share of communication device-time that ran
   concurrently with compute — the number that must be ≈0 for
   ``--overlap off`` and materially higher for ``1step``.

Loud limitation (tested): a CPU trace carries only host lanes — there are
no device rows to attribute, so the parser raises :class:`TraceParseError`
instead of reporting a fake 0% overlap.  Overlap truth is a hardware
measurement; the committed miniature fixtures pin the parser's arithmetic,
and no capture from the chip has been reduced yet.
"""

from __future__ import annotations

import gzip
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["TraceParseError", "find_trace_file", "load_trace_events",
           "overlap_report", "profile_report", "render_profile_markdown"]


class TraceParseError(ValueError):
    """A trace that cannot answer the overlap question (missing file,
    malformed JSON, or — the documented CPU case — no device rows)."""


def find_trace_file(source: str) -> str:
    """Resolve a trace source to one ``*.trace.json.gz`` (or ``.json``).

    ``source`` may be the file itself, a profiler log dir (the argument
    ``utils.profiling.trace`` was given — searched recursively), or any
    directory above one.  Multiple captures resolve to the newest."""
    if os.path.isfile(source):
        return source
    if not os.path.isdir(source):
        raise TraceParseError(f"no trace at {source}")
    candidates = []
    for root, _, files in os.walk(source):
        for f in files:
            if f.endswith(".trace.json.gz") or f.endswith(".trace.json"):
                candidates.append(os.path.join(root, f))
    if not candidates:
        raise TraceParseError(
            f"{source} holds no *.trace.json.gz — was the window captured "
            f"with utils.profiling.trace(log_dir)?")
    return max(candidates, key=os.path.getmtime)


def load_trace_events(path: str) -> List[dict]:
    """Parse a Chrome trace-event file (gzipped or plain JSON)."""
    opener = gzip.open if path.endswith(".gz") else open
    try:
        with opener(path, "rt") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        raise TraceParseError(f"{path}: not a readable trace JSON ({e})") \
            from e
    events = data.get("traceEvents") if isinstance(data, dict) else data
    if not isinstance(events, list):
        raise TraceParseError(f"{path}: no traceEvents array")
    return events


def _string_values(obj) -> List[str]:
    if isinstance(obj, str):
        return [obj]
    if isinstance(obj, dict):
        return [s for v in obj.values() for s in _string_values(v)]
    return []


def _phase_of(event: dict) -> str:
    """Attribute one executed row to a phase via the named-scope metadata
    ``device_span`` stamped into the op: ``comm/*`` spans are the exchange
    (begin_mix / apply_mix / step), ``matcha/*`` the training phases.
    Unattributed device rows are still executed kernel work and count as
    compute for the overlap question ("was the wire hidden under *any*
    useful work"), reported separately as ``other``."""
    hay = [event.get("name", "")] + _string_values(event.get("args", {}))
    for s in hay:
        if "comm/" in s:
            return "comm"
    for s in hay:
        if "matcha/" in s:
            return "comp"
    return "other"


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    if not intervals:
        return []
    intervals = sorted(intervals)
    out = [list(intervals[0])]
    for lo, hi in intervals[1:]:
        if lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _intersect_len(a: List[Tuple[float, float]],
                   b: List[Tuple[float, float]]) -> float:
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _span_len(a: List[Tuple[float, float]]) -> float:
    return sum(hi - lo for lo, hi in a)


def overlap_report(events: Sequence[dict], source: str = "trace") -> Dict:
    """Device-time phase attribution + the comm/comp overlap fraction.

    Raises :class:`TraceParseError` when the trace has no device rows —
    the CPU-trace case must fail loudly, not report a fake 0%."""
    proc_names: Dict[int, str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            proc_names[e.get("pid")] = e.get("args", {}).get("name", "")
    device_pids = {pid for pid, name in proc_names.items()
                   if "/device:" in name}
    if not device_pids:
        hosts = sorted(n for n in proc_names.values() if n)
        raise TraceParseError(
            f"{source}: trace contains no device rows (processes: "
            f"{hosts or 'none'}) — a CPU capture carries only host lanes, "
            f"so the comm/comp overlap cannot be measured from it; capture "
            f"on the chip (train_tpu.py --trace-dir)")
    spans: Dict[str, List[Tuple[float, float]]] = {
        "comm": [], "comp": [], "other": []}
    counts: Dict[str, int] = {"comm": 0, "comp": 0, "other": 0}
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        ts = e.get("ts")
        dur = e.get("dur", 0.0)
        if ts is None or not dur:
            continue
        phase = _phase_of(e)
        spans[phase].append((float(ts) * 1e-6, (float(ts) + float(dur)) * 1e-6))
        counts[phase] += 1
    if not any(counts.values()):
        raise TraceParseError(
            f"{source}: device processes exist but carry no complete "
            f"(ph=X) kernel rows — truncated capture?")
    comm = _merge(spans["comm"])
    compute = _merge(spans["comp"] + spans["other"])
    comm_s = _span_len(comm)
    overlap_s = _intersect_len(comm, compute)
    return {
        "source": source,
        "device_processes": sorted(proc_names[p] for p in device_pids),
        "rows": dict(counts),
        "comm_seconds": comm_s,
        "comp_seconds": _span_len(_merge(spans["comp"])),
        "other_seconds": _span_len(_merge(spans["other"])),
        "compute_seconds": _span_len(compute),
        "overlap_seconds": overlap_s,
        # of all communication device-time, the share that ran while
        # compute was also executing — None when the trace has no
        # comm-tagged rows at all (nothing to hide ⇒ no claim either way)
        "overlap_fraction": (overlap_s / comm_s) if comm_s > 0 else None,
    }


def profile_report(source: str) -> Dict:
    """End-to-end: resolve a trace source, parse it, attribute phases."""
    path = find_trace_file(source)
    return overlap_report(load_trace_events(path), source=path)


def render_profile_markdown(reports: Sequence[Dict]) -> str:
    lines = [
        "# Overlap truth — executed-trace comm/comp attribution", "",
        "Device-lane kernel rows attributed via `device_span` named scopes "
        "(`comm/*` = exchange, `matcha/*` = training phases); the overlap "
        "fraction is the share of communication device-time that ran "
        "concurrently with compute.", "",
        "| trace | comm s | compute s | overlap s | overlap fraction |",
        "|---|---:|---:|---:|---:|",
    ]
    for r in reports:
        frac = r.get("overlap_fraction")
        lines.append(
            f"| {os.path.basename(str(r['source']))} "
            f"| {r['comm_seconds']:.6g} | {r['compute_seconds']:.6g} "
            f"| {r['overlap_seconds']:.6g} "
            f"| {'-' if frac is None else f'{frac:.1%}'} |")
    lines.append("")
    return "\n".join(lines)
