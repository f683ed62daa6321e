"""Seconds an epoch in every leaf span outside ``epoch_time`` but
``comm_split_timer``: the hook, re-priming, the snapshot copy, the divergence
check, evaluation, the journal, telemetry flush, heartbeat, recorder flush,
checkpoint.  With ``comm_timer_ms`` it is ``boundary_ms.p50`` from inside.
The two hook calls in which the harness starts and stops the profiler (tens
of seconds to write the file) are the harness's own work and left out, as
``boundary_ms.p50`` leaves them out by stamping the boundary after them.
Median over the window's epochs after the profiler's stop
(`chipbench/spans.py:window_periods`)."""

from chipbench.spans import (INSIDE_EPOCH_TIME, leaves, median_over_window,
                             seconds)


def read(run):
    def rest_ms(record):
        names = {s["name"] for s in leaves(record)} \
            - set(INSIDE_EPOCH_TIME) - {"comm_split_timer"}
        if record["epoch"] in (run["traced"] or ()):
            names.discard("boundary_hook")
        return 1e3 * seconds(record, names)

    return median_over_window(run, rest_ms)
