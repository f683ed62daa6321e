"""Of the epoch program's device time in the traced window, the percent in
operations that the join gave to a ``device_span`` (``chipbench/scopes.py``):
what the per-scope metrics can see.  The rest runs under no scope (the
workers' loop, the update outside ``matcha/sgd``, telemetry, copies the
compiler put in) and is listed in the ``# scope`` lines.  None in an untraced
run and on a program with no device-side reader."""

from chipbench.scopes import matched_pct


def read(run):
    return matched_pct(run)
