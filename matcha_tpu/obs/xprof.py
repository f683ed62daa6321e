"""Device time by ``device_span``: the one device-side reader of the program.

A ``jax.profiler`` capture on the TPU (``<dir>/plugins/profile/<time>/
*.xplane.pb``) holds, for every device, an ``XLA Modules`` line (one row a
run of a compiled program, named ``jit_<function>(<id>)``) and an ``XLA Ops``
line (one row an executed HLO instruction, named by the whole instruction,
nested where a ``while`` or a ``conditional`` holds others).  The rows carry
times and nothing else.  What names them is in the same file: its
``/host:metadata`` plane keeps, under each module's name, the ``HloProto`` of
the executable that ran, and every instruction there carries the ``op_name``
it was traced under, the ``jax.named_scope`` path with ``device_span``'s
``matcha/*`` and ``comm/*`` scopes in it.

So the join is made by construction and never by a second compile:

1. :func:`hlo_modules` reads the capture's own HLO (a forty-line reader of the
   protobuf wire format: ``jax.profiler.ProfileData`` shows the rows and not
   a plane's event metadata, and the program takes no other package for it);
2. :func:`scope_map` gives each instruction of a module its scopes, outermost
   first, and its pass (:data:`PASSES`), off its own ``op_name``: a fusion's
   is the one the compiler kept of the instruction it was built around;
3. :func:`reduce_scopes` gives every leaf row of ``XLA Ops`` (a row that
   holds no other) to the module row that contains it in time, so
   ``fusion.12`` of the epoch program and of the timer's chain are two
   things, and joins it by instruction name to that module's map.

:func:`device_scopes` is the three together on a capture's directory; its
record is the journal's ``device_scopes`` event (``train()`` under
``trace_dir``), ``obs_tpu.py profile``'s table and what the benchmark's
``chipbench/scopes.py`` reads its per-scope metrics from.

A capture with no device plane (the CPU's) raises :class:`TraceParseError`:
device time is a measurement of the chip, and a table of zeros is not one.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

__all__ = ["TraceParseError", "PASSES", "find_capture", "hlo_modules",
           "scope_map", "pass_of", "scopes_of", "reduce_scopes",
           "device_scopes", "main_program", "render_device_scopes"]

#: a ``device_span`` in an ``op_name`` (``utils.profiling.device_span``;
#: also inside ``jvp(matcha/fwd_bwd)`` and ``transpose(jvp(...))``).  (The
#: patterns are strings for ``re``'s own cache: this file calls nothing named
#: ``compile`` or ``lower``, and a test greps that it stays so.)
SCOPE = r"(?:matcha|comm)/\w+"
#: which computation of the step an instruction belongs to, off its
#: ``op_name`` (:func:`pass_of`)
PASSES = ("forward", "recomputed", "recomputed_inner", "backward")
DEVICE_PLANE = r"^/device:TPU:(\d+)$"
HOST_PLANE = "/host:CPU"
METADATA_PLANE = "/host:metadata"
#: ``%fusion.9 = f32[16,8]{...} fusion(...)`` -> ``fusion.9``, ``f32[16,8]``
ROW_NAME = r"^%?(\S+)(?: = \(?([a-z0-9]+\[[^\]]*\])?)?"
TOP = 10


class TraceParseError(ValueError):
    """A capture that cannot answer (no file, not an ``XSpace``, or, the
    documented CPU case, no device plane)."""


# ------------------------------------------------------------ wire format

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one serialized message: an int for a
    varint, a ``memoryview`` for bytes, strings and nested messages."""
    i, n = 0, len(buf)
    try:
        while i < n:
            key, i = _varint(buf, i)
            wire = key & 7
            if wire == 0:
                value, i = _varint(buf, i)
            elif wire == 2:
                size, i = _varint(buf, i)
                value, i = buf[i:i + size], i + size
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
                value, i = buf[i:i + size], i + size
            else:
                raise TraceParseError(f"wire type {wire}: not a protobuf")
            yield key >> 3, value
    except IndexError as e:
        raise TraceParseError("a message ends inside a field") from e


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_value(entry):
    """The value of one ``map<int64, Message>`` entry."""
    return next((v for k, v in _fields(entry) if k == 2), b"")


def hlo_modules(xspace) -> Dict[str, memoryview]:
    """``{"jit_<function>(<id>)": serialized HloModuleProto}`` of a
    serialized ``XSpace``: what its ``/host:metadata`` plane keeps of every
    program that ran while the profiler was open, under the name the
    program's rows of ``XLA Modules`` have."""
    modules = {}
    for number, plane in _fields(memoryview(xspace)):
        if number != 1:
            continue
        fields = list(_fields(plane))  # a device plane: few, long fields
        if not any(k == 2 and _text(v) == METADATA_PLANE for k, v in fields):
            continue
        for k, entry in fields:
            if k != 4:
                continue
            name, proto = None, None
            for f, v in _fields(_map_value(entry)):
                if f == 2:
                    name = _text(v)
                elif f == 5:  # XStat: the one it has is "Hlo Proto"
                    proto = next((b for s, b in _fields(v) if s == 6), proto)
            if name and proto is not None:
                module = next((v for f, v in _fields(proto) if f == 1), None)
                if module is not None:
                    modules[name] = module
    return modules


# ------------------------------------------------------------ the scope map

def scopes_of(op_name: str) -> Tuple[str, ...]:
    """The ``device_span`` scopes of an ``op_name``, outermost first."""
    return tuple(dict.fromkeys(re.findall(SCOPE, op_name)))


def pass_of(op_name: str) -> str:
    """One of :data:`PASSES`.  JAX names what a ``jax.checkpoint`` computes
    again ``.../checkpoint/rematted_computation/...``: under one checkpoint
    it is ``recomputed``; where a second ``checkpoint`` stands before it
    (a checkpoint inside a layer that is itself under ``remat``) it is
    computed a third time, ``recomputed_inner``.  The rest of
    ``transpose(jvp(...))`` is ``backward``, everything else ``forward``."""
    parts = op_name.split("/")
    if "rematted_computation" in parts:
        at = len(parts) - 1 - parts[::-1].index("rematted_computation")
        return ("recomputed_inner" if parts[:at].count("checkpoint") > 1
                else "recomputed")
    return "backward" if "transpose(" in op_name else "forward"


def scope_map(hlo_module) -> Dict[str, Tuple[Tuple[str, ...], str, str]]:
    """``{instruction name: (scopes outermost first, pass, op_name)}`` of one
    serialized ``HloModuleProto``, every instruction of every computation,
    each by its own ``op_name``; an instruction under no scope has ``()``.

    A ``fusion`` too: the compiler gives it the ``op_name`` of the
    instruction it was built around (the convolution or the product where it
    has one, else its root), and that is where its time is.  Counting the
    fused instructions' scopes instead gives a weight-gradient convolution
    to the optimizer's update fused into its epilogue, and a projection's
    product to the norm before it (``PERF.md`` section 6, PR 37)."""
    out = {}
    for k, computation in _fields(memoryview(hlo_module)):
        if k != 3:
            continue
        for f, instruction in _fields(computation):
            if f != 2:
                continue
            name = op_name = ""
            for g, v in _fields(instruction):
                if g == 1:
                    name = _text(v)
                elif g == 7:  # OpMetadata: op_name is its field 2
                    op_name = next(
                        (_text(b) for h, b in _fields(v) if h == 2), "")
            out[name] = (scopes_of(op_name), pass_of(op_name), op_name)
    return out


# ------------------------------------------------------------ intervals

def _merge(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _intersect_len(a, b) -> float:
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


def _span_len(a) -> float:
    return sum(hi - lo for lo, hi in a)


# ------------------------------------------------------------ the reduction

def _rows(line, lo, hi):
    """``[(start, end, name)]`` of a line's events clipped to the window, in
    start order, a row that holds others before them."""
    rows = []
    for ev in line.events:
        a = max(ev.start_ns * 1e-9, lo)
        b = min((ev.start_ns + ev.duration_ns) * 1e-9, hi)
        if b > a:
            rows.append((a, b, ev.name))
    rows.sort(key=lambda r: (r[0], -r[1]))
    return rows


def _leaves(rows):
    """The rows that hold no other row of their line."""
    return [r for r, nxt in zip(rows, rows[1:] + [None])
            if nxt is None or nxt[0] >= r[1]]


def _window(data, marks):
    """(start, end) in seconds: from the start of the first mark to the end
    of the second, two host annotations a caller put around its window; the
    whole capture where ``marks`` is None or the host plane lacks one."""
    if marks:
        found = {}
        for plane in data.planes:
            if plane.name == HOST_PLANE:
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in marks:
                            found[ev.name] = ev
        if len(found) == 2:
            return (found[marks[0]].start_ns * 1e-9,
                    (found[marks[1]].start_ns
                     + found[marks[1]].duration_ns) * 1e-9)
    return float("-inf"), float("inf")


def reduce_scopes(data, maps, marks: Optional[Sequence[str]] = None) -> Dict:
    """The ``device_scopes`` record of a ``ProfileData``.

    ``maps`` is ``{module: scope map}`` or a callable ``module -> scope map
    or None`` asked once a module of the window.  Seconds are averaged over
    the device planes.  The record::

        {window_s, device_planes, comm_s, overlap_s, overlap_fraction,
         programs: {<module>: {device_s, runs, ops_s, matched_s,
                               scopes: {<scope>: {device_s, own_s, ops,
                                                  by_pass: {<pass>: s}}},
                               unmatched_top: [[instruction, s, op_name]]}}}

    ``device_s`` of a program is that of its ``XLA Modules`` rows and
    ``ops_s`` that of the leaf rows inside them; ``matched_s`` of those the
    join could give to a scope.  A scope's ``device_s`` and ``by_pass``
    count the scopes inside it too (``matcha/fwd_bwd`` holds the layers'),
    ``own_s`` only the rows it is the innermost scope of, so the ``own_s``
    of a program sum to its ``matched_s``; ``ops`` is how many distinct
    instructions those are; a scope that the program's map has and no row
    joined to reads 0, one it lacks is absent.  ``unmatched_top`` lists the
    longest instructions under no scope (with their ``op_name``) or missing
    from the map (None).  ``overlap_fraction`` is the share of the ``comm/*`` rows'
    time (leaf rows, and the rows of ``Async XLA Ops``, which count nowhere
    else) during which a leaf row of another scope ran on the same device;
    None where the window has no ``comm/*`` row."""
    lookup = maps if callable(maps) else maps.get
    lo, hi = _window(data, marks)
    planes = [p for p in data.planes if re.match(DEVICE_PLANE, p.name)]
    if not planes:
        raise TraceParseError(
            f"the capture has no device plane (planes: "
            f"{[p.name for p in data.planes]}): a CPU capture holds host "
            f"lanes only, and device time by scope is a measurement of the "
            f"chip (train_tpu.py --trace-dir there)")
    n = len(planes)
    programs: Dict[str, dict] = {}
    asked: Dict[str, Optional[dict]] = {}
    short = {}
    comm_s = overlap_s = 0.0
    first, last = float("inf"), float("-inf")

    def program(module):
        if module not in programs:
            programs[module] = {
                "device_s": 0.0, "runs": 0.0, "ops_s": 0.0, "matched_s": 0.0,
                "scopes": defaultdict(lambda: {
                    "device_s": 0.0, "own_s": 0.0, "ops": set(),
                    "by_pass": dict.fromkeys(PASSES, 0.0)}),
                "unmatched": defaultdict(float)}
            asked[module] = lookup(module)
            # a scope the program has reads 0 where no row joins to it
            # (instructions that all went into other scopes' fusions);
            # one it lacks is absent
            for scopes, _, _ in (asked[module] or {}).values():
                for scope in scopes:
                    programs[module]["scopes"][scope]["own_s"] += 0.0
        return programs[module], asked[module]

    for plane in planes:
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines or "XLA Modules" not in lines:
            raise TraceParseError(
                f"{plane.name} has no 'XLA Ops' and 'XLA Modules' lines "
                f"(lines: {sorted(lines)})")
        modules = _rows(lines["XLA Modules"], lo, hi)
        starts = [m[0] for m in modules]
        for a, b, name in modules:
            entry, _ = program(name)
            entry["device_s"] += (b - a) / n
            entry["runs"] += 1.0 / n
        comm, other = [], []
        for a, b, name in _leaves(_rows(lines["XLA Ops"], lo, hi)):
            first, last = min(first, a), max(last, b)
            at = bisect.bisect_right(starts, (a + b) / 2) - 1
            if at < 0 or modules[at][1] < (a + b) / 2:
                continue  # (a row under no program: none seen on a v5e)
            entry, scope_of = program(modules[at][2])
            if name not in short:
                short[name] = re.match(ROW_NAME, name).groups()
            instruction, shape = short[name]
            secs = (b - a) / n
            entry["ops_s"] += secs
            scopes, which, op_name = (scope_of or {}).get(
                instruction, (None, None, None))
            if not scopes:
                entry["unmatched"][
                    (" ".join(filter(None, (instruction, shape))),
                     op_name)] += secs
                other.append((a, b))
                continue
            entry["matched_s"] += secs
            for scope in scopes:
                row = entry["scopes"][scope]
                row["device_s"] += secs
                row["by_pass"][which] += secs
            entry["scopes"][scopes[-1]]["own_s"] += secs
            entry["scopes"][scopes[-1]]["ops"].add(instruction)
            (comm if scopes[-1].startswith("comm/") else other).append((a, b))
        # an asynchronous exchange is one short row at its start and one at
        # its end on ``XLA Ops``; the line beside it holds the whole of it
        for a, b, name in (_rows(lines["Async XLA Ops"], lo, hi)
                           if "Async XLA Ops" in lines else ()):
            at = bisect.bisect_right(starts, (a + b) / 2) - 1
            scope_of = asked.get(modules[at][2]) if at >= 0 else None
            scopes = (scope_of or {}).get(
                re.match(ROW_NAME, name).group(1), ((),))[0]
            if scopes and scopes[-1].startswith("comm/"):
                comm.append((a, b))
        comm = _merge(comm)
        comm_s += _span_len(comm) / n
        overlap_s += _intersect_len(comm, _merge(other)) / n

    if first > last:
        raise TraceParseError("the device planes hold no operation in the "
                              "window: a truncated capture?")
    for entry in programs.values():
        entry["scopes"] = {k: dict(v, ops=len(v["ops"]))
                           for k, v in entry["scopes"].items()}
        entry["unmatched_top"] = [
            [instruction, secs, op_name] for (instruction, op_name), secs
            in sorted(entry.pop("unmatched").items(),
                      key=lambda kv: -kv[1])[:TOP]]
    lo, hi = (first if lo == float("-inf") else lo,
              last if hi == float("inf") else hi)
    return {"window_s": hi - lo, "device_planes": n, "programs": programs,
            "comm_s": comm_s, "overlap_s": overlap_s,
            "overlap_fraction": overlap_s / comm_s if comm_s > 0 else None}


def find_capture(source: str) -> str:
    """The newest ``*.xplane.pb`` at or under ``source``."""
    if os.path.isfile(source):
        return source
    paths = glob.glob(os.path.join(source, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise TraceParseError(
            f"no *.xplane.pb under {source}: was the window captured "
            f"(train_tpu.py --trace-dir, utils.profiling.trace)?")
    return max(paths, key=os.path.getmtime)


def device_scopes(source: str, marks: Optional[Sequence[str]] = None,
                  keep_maps: Optional[dict] = None) -> Dict:
    """:func:`reduce_scopes` of the newest capture at or under ``source``,
    joined to the capture's own HLO; ``source`` rides the record.
    ``keep_maps``, a dict, is filled with the scope map of every program of
    the window (what ``scopes.json`` keeps beside the capture)."""
    from jax.profiler import ProfileData

    path = find_capture(source)
    try:
        with open(path, "rb") as f:
            raw = f.read()
        data = ProfileData.from_serialized_xspace(raw)
    except (OSError, ValueError, RuntimeError) as e:
        raise TraceParseError(f"{path}: not a readable capture ({e})") from e
    modules = hlo_modules(raw)
    maps = {} if keep_maps is None else keep_maps

    def lookup(module):
        if module not in maps and module in modules:
            maps[module] = scope_map(modules[module])
        return maps.get(module)

    return dict(reduce_scopes(data, lookup, marks), source=path)


# ------------------------------------------------------------ reading a record

def main_program(record: Dict) -> Optional[str]:
    """The program that took most of the window's device time: the compiled
    epoch program, whatever it is named."""
    programs = record["programs"]
    return max(programs, key=lambda m: programs[m]["device_s"],
               default=None)


def render_device_scopes(record: Dict, per: Optional[Dict[str, float]] = None,
                         only: Optional[str] = None) -> List[str]:
    """The record as lines of text: a table a program (scope, ms a run,
    share of the program's device time, the four passes), then its longest
    instructions under no scope.  ``per`` gives a program another divisor
    than its runs (the benchmark: steps); ``only`` keeps one program."""
    lines = []
    for module, p in sorted(record["programs"].items(),
                            key=lambda kv: -kv[1]["device_s"]):
        if only and module != only:
            continue
        runs = (per or {}).get(module) or p["runs"] or 1.0
        ms = lambda s: 1e3 * s / runs
        share = lambda s: 100 * s / p["device_s"] if p["device_s"] else 0.0
        lines.append(
            f"{module}: {ms(p['device_s']):.3f} ms a run, {p['runs']:g} "
            f"rows; under a scope {share(p['matched_s']):.1f}%")
        lines.append(f"  {'scope':<24}{'ms':>10}{'own ms':>10}{'share':>7}  "
                     + "".join(f"{name:>17}" for name in PASSES))
        for scope, row in sorted(p["scopes"].items(),
                                 key=lambda kv: -kv[1]["device_s"]):
            lines.append(
                f"  {scope:<24}{ms(row['device_s']):>10.3f}"
                f"{ms(row['own_s']):>10.3f}{share(row['device_s']):>6.1f}%  "
                + "".join(f"{ms(row['by_pass'][name]):>17.3f}"
                          for name in PASSES))
        rest = p["ops_s"] - p["matched_s"]
        lines.append(f"  {'(no scope)':<24}{ms(rest):>10.3f}{ms(rest):>10.3f}"
                     f"{share(rest):>6.1f}%")
        for instruction, secs, op_name in p["unmatched_top"]:
            where = ("not in the capture's HLO" if op_name is None
                     else op_name or "no op_name")
            lines.append(f"    {ms(secs):>9.3f} ms  {instruction}  [{where}]")
    frac = record.get("overlap_fraction")
    lines.append(
        f"comm/* rows {record['comm_s']:.6g} s of a window of "
        f"{record['window_s']:.6g} s; under other work "
        + ("-" if frac is None else f"{frac:.1%}"))
    return lines
