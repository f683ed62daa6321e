"""The bytes ``h2d`` put on the device (its ``bytes`` count: the ``nbytes``
of the stacks) over the seconds of ``load_batches`` + ``stack_batches`` +
``h2d``: the rate at which the host turns a data set into device batches.
Median over the window's epochs under the profiler
(`chipbench/spans.py:window_periods` says why those)."""

from chipbench.spans import STAGING, count, median_over_window, seconds


def gb_per_s(record):
    spent = seconds(record, STAGING)
    return count(record, "h2d", "bytes") / spent / 1e9 if spent else None


def read(run):
    return median_over_window(run, gb_per_s, under_profiler=True)
