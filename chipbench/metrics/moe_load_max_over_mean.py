"""The busiest expert held over the mean expert held, in the layer where
that ratio is largest: ``moe_load[layer][expert held]`` (slots that landed on
each, a counter of the period's ``spans`` record) summed over the window's
epochs.  1 is an even load.  None where the program has no such counter."""

from chipbench.spans import window_periods


def read(run):
    loads = [r["counters"]["moe_load"] for r in window_periods(run)
             if "moe_load" in r.get("counters", {})]
    if not loads:
        return None
    worst = None
    for layer in zip(*loads):  # one layer's rows, an epoch each
        per_expert = [sum(column) for column in zip(*layer)]
        mean = sum(per_expert) / len(per_expert)
        if mean:
            ratio = max(per_expert) / mean
            worst = ratio if worst is None else max(worst, ratio)
    return worst
