"""Host seconds a step from the call of the scanned program to the epoch's
barrier: the spans ``dispatch`` (the call returning) and ``wait_device``
(metrics readback, ``block_until_ready``).  The epoch program's device time
plus whatever lies between the dispatch and the device's start: the stacks'
copy to the device, which ``h2d`` only starts.  Median over the window's
epochs after the profiler's stop (`chipbench/spans.py:window_periods`)."""

from chipbench.spans import median_over_window, per_step_ms


def read(run):
    return median_over_window(
        run, lambda r: per_step_ms(r, ("dispatch", "wait_device")))
