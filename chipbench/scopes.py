"""Device time of the epoch program by the scope it was traced under, for the
metrics that read it.

The program marks its in-graph phases with ``device_span`` (``matcha/fwd_bwd``,
``matcha/sgd``, ``comm/step``, the models' layers) and has one reader that
joins a profiler capture's operations to them through the HLO the capture
itself carries (``matcha_tpu/obs/xprof.py:device_scopes``, PR 37).  In a
traced run the harness writes its capture to ``<workdir>/trace``, beside the
``<workdir>/job`` it gives the program as ``savePath``, which the journal's
``run_start`` event names.  :func:`record_of` calls the program's reader there
once a run, keeps the record on ``run`` and prints the epoch program's table
as ``# scope`` lines; :func:`scope_ms` and :func:`matched_pct` read it.

A program from before PR 37 has no such reader, an untraced run no capture
and the CPU's capture no device plane: every reader here then returns None.
"""

import time
from pathlib import Path

from .tracered import WINDOW_MARKS

KEY = "device_scopes"


def capture_dir(run):
    """``<workdir>/trace`` of the run whose journal is ``run["events"]``."""
    start = next((e for e in run["events"] if e.get("kind") == "run_start"),
                 None)
    if start is None:
        return None
    return Path(start["config"]["savePath"]).parent / "trace"


def record_of(run):
    """The program's ``device_scopes`` record of the traced window, read at
    the first call, with the name of the program that took most of the
    window under ``"main"``; None where there is nothing to read."""
    if KEY in run:
        return run[KEY]
    run[KEY] = None
    where = capture_dir(run) if run.get("trace") else None
    if where is None or not where.is_dir():
        return None
    try:
        from matcha_tpu.obs.xprof import (device_scopes, main_program,
                                          render_device_scopes)
    except ImportError:  # the program has no device-side reader
        return None
    t0 = time.perf_counter()
    try:
        record = device_scopes(str(where), marks=WINDOW_MARKS)
    except ValueError as e:  # (the reader's TraceParseError is one)
        print(f"# scope none read: {e}", flush=True)
        return None
    main = main_program(record)
    for line in render_device_scopes(
            record, per={main: run["traced_steps"]}, only=main):
        print("# scope " + line)
    print(f"# scope read in {time.perf_counter() - t0:.1f} s "
          f"(ms a step over {run['traced_steps']} traced steps)", flush=True)
    run[KEY] = dict(record, main=main)
    return run[KEY]


def _main(run):
    record = record_of(run)
    return record and record["programs"][record["main"]]


def scope_ms(run, scope):
    """Device milliseconds a step under ``scope`` (the scopes inside it
    counted) in the program that took most of the traced window; None in an
    untraced run, or where that program holds nothing under the scope."""
    program = _main(run)
    row = program and program["scopes"].get(scope)
    return 1e3 * row["device_s"] / run["traced_steps"] if row else None


def matched_pct(run):
    """Of that program's device time, the percent in operations that the
    join gave to a scope."""
    program = _main(run)
    if not program or not program["device_s"]:
        return None
    return 100.0 * program["matched_s"] / program["device_s"]
