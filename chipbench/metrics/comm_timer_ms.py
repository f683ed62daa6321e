"""Seconds an epoch in the span ``comm_split_timer``: the gossip-only chains
that ``measure_comm_split`` re-runs after every epoch to fill ``comm_time``.
Median over the window's epochs after the profiler's stop
(`chipbench/spans.py:window_periods`)."""

from chipbench.spans import median_over_window, seconds


def read(run):
    return median_over_window(
        run, lambda r: 1e3 * seconds(r, ("comm_split_timer",)))
