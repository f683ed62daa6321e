"""From the profiler's ``.xplane.pb`` to numbers: the device's busy union and
idle share, device time of each program, the operations that took most time
and the longest idle gaps with what the host was doing.

Read with ``jax.profiler.ProfileData`` alone.  What a v5e trace holds, looked
at by hand (PR 23): a device plane is named ``/device:TPU:<n>``; its
``XLA Modules`` line has one event per executed program, named
``jit_<function>(<id>)``; its ``XLA Ops`` line has one event per executed HLO
operation, named by the whole HLO instruction, nested where a ``while`` or a
``conditional`` holds others.  Only operations that hold no other count as
busy, so the gaps inside a scanned program are seen.  ``ProfileData`` gives
these rows their times and nothing else: the ``jax.named_scope`` an operation
was traced under is in the file's HLO metadata, not on the rows, so no
per-scope time is read here.  The host plane ``/host:CPU`` holds the
``TraceAnnotation`` spans of the loop (``matcha/...``) and of the harness
(``chipbench/...``).
"""

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
WINDOW_MARKS = ("chipbench/trace_start", "chipbench/trace_stop")
SPAN_PREFIXES = ("matcha/", "chipbench/")
GAP_FLOOR_S = 1e-3
OP_NAME = re.compile(r"^%?(\S+) = \(?([a-z0-9]+\[[^\]]*\])?")


def merge(intervals):
    """Sorted union of ``(lo, hi)`` intervals."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def span_len(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def intersect_len(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip(events, lo, hi):
    return [dict(e, lo=max(e["lo"], lo), hi=min(e["hi"], hi))
            for e in events if min(e["hi"], hi) > max(e["lo"], lo)]


def leaves(events):
    """The events of one line that hold no other event of it."""
    events = sorted(events, key=lambda e: (e["lo"], -e["hi"]))
    return [e for e, nxt in zip(events, events[1:] + [None])
            if nxt is None or nxt["lo"] >= e["hi"]]


def short_op(name: str) -> str:
    """``%fusion.9 = f32[16,8]{...} fusion(...)`` -> ``fusion.9 f32[16,8]``."""
    m = OP_NAME.match(name)
    return " ".join(g for g in m.groups() if g) if m else name[:80]


def _events(line):
    return [{"name": ev.name, "lo": ev.start_ns * 1e-9,
             "hi": (ev.start_ns + ev.duration_ns) * 1e-9}
            for ev in line.events]


def read_planes(data):
    """``{"devices": {n: {"ops", "modules"}}, "host": [spans]}`` from a
    ``ProfileData``, times in seconds on the profiler's clock."""
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "XLA Modules"):
                key = "ops" if line.name == "XLA Ops" else "modules"
                devices.setdefault(int(m.group(1)), {"ops": [], "modules": []}
                                   )[key] += _events(line)
            elif plane.name == HOST_PLANE:
                # (a host line can hold a million runtime events: pick the
                # annotations before building anything)
                host += [{"name": ev.name, "lo": ev.start_ns * 1e-9,
                          "hi": (ev.start_ns + ev.duration_ns) * 1e-9}
                         for ev in line.events
                         if ev.name.startswith(SPAN_PREFIXES)]
    return {"devices": devices, "host": host}


def load(trace_dir):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return read_planes(ProfileData.from_file(paths[-1]))


def traced_window(planes):
    """From the harness's start mark to its stop mark; where a trace has no
    marks, from the first device operation to the last."""
    marks = {s["name"]: s for s in planes["host"] if s["name"] in WINDOW_MARKS}
    if len(marks) == 2:
        return marks[WINDOW_MARKS[0]]["lo"], marks[WINDOW_MARKS[1]]["hi"]
    ops = [e for d in planes["devices"].values() for e in d["ops"]]
    return min(e["lo"] for e in ops), max(e["hi"] for e in ops)


def _where(modules, a, b) -> str:
    """A gap's place among the programs: inside one, or before the next."""
    mid = (a + b) / 2
    for m in modules:
        if m["lo"] <= mid <= m["hi"]:
            return "inside " + m["name"].split("(")[0]
    after = next((m for m in modules if m["lo"] >= mid), None)
    return ("before " + after["name"].split("(")[0] if after
            else "before the window's end")


def reduce_planes(planes):
    """Busy and idle over the traced window, device seconds by program, and
    the breakdown, each averaged over the device planes."""
    devices = planes["devices"]
    if not devices:
        return None
    lo, hi = traced_window(planes)
    n = len(devices)
    busy_s = 0.0
    by_op, by_module, by_gap = (defaultdict(float) for _ in range(3))
    spans = [s for s in planes["host"] if s["name"] not in WINDOW_MARKS]
    for dev in devices.values():
        leaf = leaves(clip(dev["ops"], lo, hi))
        union = merge((e["lo"], e["hi"]) for e in leaf)
        busy_s += span_len(union) / n
        for e in leaf:
            by_op[short_op(e["name"])] += (e["hi"] - e["lo"]) / n
        modules = sorted(clip(dev["modules"], lo, hi), key=lambda e: e["lo"])
        for e in modules:
            by_module[e["name"].split("(")[0]] += (e["hi"] - e["lo"]) / n
        edges = [lo] + [t for iv in union for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a < GAP_FLOOR_S:
                continue
            covered = 0.0
            for name in {s["name"] for s in spans}:
                secs = intersect_len([(a, b)], merge(
                    (s["lo"], s["hi"]) for s in spans if s["name"] == name))
                by_gap[name] += secs / n
                covered += secs
            by_gap["unattributed, " + _where(modules, a, b)] += \
                max(b - a - covered, 0.0) / n

    def top(d):
        return [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:10] if v > 0]

    return {"busy_s": busy_s, "window_s": hi - lo,
            "idle_share": 1.0 - busy_s / (hi - lo),
            "module_s": dict(by_module),
            "breakdown": {"device_ops": top(by_op), "idle_gaps": top(by_gap)}}


def main_program_seconds(trace):
    """Device seconds of the program that took most of the traced window:
    the compiled epoch program, whatever it is named."""
    return max(trace["module_s"].values()) if trace["module_s"] else None
