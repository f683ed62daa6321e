"""Median over the window's epochs of ``epoch_time / steps``: the steady
epoch, which a stalled one does not move; beside ``step_ms`` it says whether
a change in that one is every epoch's or a few epochs'."""

import statistics


def read(run):
    return 1e3 * statistics.median(
        h["epoch_time"] / run["steps"] for h in run["epochs"])
