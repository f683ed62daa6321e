"""The mean of ``exp(g_t)`` over the Gated DeltaNet layers' gates
(``gdn_decay_sum`` over ``gdn_gates``: token x value head x layer): the share
of its state that a head keeps from one token to the next.  A change of
precision in the gate that makes the layer forget differently moves it
before it moves the loss.  Counters of the period's ``spans`` record.  None
where the program has no such counter."""

from chipbench.spans import window_periods


def read(run):
    records = [r["counters"] for r in window_periods(run)
               if "gdn_gates" in r.get("counters", {})]
    gates = sum(c["gdn_gates"] for c in records)
    if not gates:
        return None
    return sum(c["gdn_decay_sum"] for c in records) / gates
