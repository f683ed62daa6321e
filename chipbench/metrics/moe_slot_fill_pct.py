"""Of the rows the expert layers' products ran over (``moe_rows_computed``:
a fixed number a layer-step for the slots sorted by expert, twice what an
even router would send to the experts held, and every expert held on every
token more in a step whose slots do not fit them), the share that held a
real slot (``moe_slots_held``: a token's choice that landed on an expert
held).  Both are counters the epoch program returns and the period's
``spans`` record carries (``counters``); summed over the window's epochs.
None where the program has no such counter."""

from chipbench.spans import window_periods


def total(run, name):
    values = [r["counters"][name] for r in window_periods(run)
              if name in r.get("counters", {})]
    return sum(values) if values else None


def read(run):
    held, rows = total(run, "moe_slots_held"), total(run, "moe_rows_computed")
    return 100.0 * held / rows if held is not None and rows else None
