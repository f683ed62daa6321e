"""The loop's own host phases, for the metrics that read them.

``train()`` journals one ``spans`` record an epoch period (from one loop top
to the next; ``matcha_tpu/utils/profiling.py:SpanRecorder``, PR 24):
``{epoch, attempt, period, t0, t1, samples, spans}``, each span ``{name, t0,
t1, parent, **counts}`` on ``time.perf_counter()``.  A span whose ``parent``
is the record's ``period`` is a leaf of the period; one opened inside another
names that one and is counted with it.  A program from before PR 24 journals
no such record, and every reader here then returns None.
"""

import statistics

#: the leaves between the two clock reads of ``train()``'s ``epoch_time``
INSIDE_EPOCH_TIME = ("load_batches", "stack_batches", "h2d", "ledger_observe",
                     "dispatch", "epoch_python", "wait_device")
STAGING = ("load_batches", "stack_batches", "h2d")


def window_periods(run, under_profiler=False):
    """The ``spans`` records of the window's epochs that ran to their end.

    In a traced run the profiler is open over the window's first two epochs,
    and it changes two things, each measured on the v5e (``PERF.md`` section
    5).  While it is open, the stacks' copy to the device, which ``h2d``
    only starts, takes 0.6 to 1.6 s longer and shows in ``wait_device``.
    Once it has stopped, ``np.stack`` and (in the first epoch after) the
    loader's gather run ten times faster than in any epoch of an untraced
    run: the buffers it freed stay with the allocator, and the epoch's
    arrays no longer land on pages never touched.  So what the device waits
    for is read from the epochs after the profiler's stop, as
    ``boundary_ms.p50`` reads them, and staging (``under_profiler``) from
    the epochs under it; either from all of the window's epochs where it
    holds none of its kind."""
    epochs = {h["epoch"] for h in run["epochs"]}
    records = [e for e in run["events"] if e.get("kind") == "spans"
               and e["epoch"] in epochs and e["samples"]]
    if run["traced"]:
        stopped = run["traced"][1]
        records = [r for r in records
                   if (r["epoch"] < stopped) == under_profiler] or records
    return records


def leaves(record):
    return [s for s in record["spans"] if s["parent"] == record["period"]]


def seconds(record, names) -> float:
    return sum(s["t1"] - s["t0"] for s in leaves(record)
               if s["name"] in names)


def count(record, name, key):
    return sum(s.get(key, 0) for s in leaves(record) if s["name"] == name)


def per_step_ms(record, names):
    """Milliseconds in the named leaves for each step the period dispatched."""
    steps = count(record, "dispatch", "steps")
    return 1e3 * seconds(record, names) / steps if steps else None


def median_over_window(run, of_period, under_profiler=False):
    """Median over the window's periods of ``of_period(record)``, leaving
    out those where it gives None; None where none is left."""
    values = [v for v in map(of_period, window_periods(run, under_profiler))
              if v is not None]
    return statistics.median(values) if values else None
