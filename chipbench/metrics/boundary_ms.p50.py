"""Median over the window's epochs of the wall clock between two boundary
calls less that epoch's ``epoch_time``: telemetry flush, comm-split timer,
divergence check, journal, heartbeat, every tenth epoch the recorder's save.
In a traced run the epochs under the profiler and the boundary that stops it
are left out where the window holds others.  The cells' epochs are cut short,
so this comes more often than in a job over the whole data set."""

import statistics


def read(run):
    stamps = run["boundaries"]
    epochs = run["epochs"]
    if run["traced"]:
        epochs = [h for h in epochs if h["epoch"] >= run["traced"][1]] \
            or epochs
    return 1e3 * statistics.median(
        stamps[h["epoch"] + 1]["t"] - stamps[h["epoch"]]["t"]
        - h["epoch_time"] for h in epochs)
