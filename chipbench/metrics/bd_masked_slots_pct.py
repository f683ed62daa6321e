"""Of the slots the experts held took (``moe_slots_held``: a position's
choice that landed on an expert held, both copies, every layer), the share a
masked position sent (``bd_slots_held_masked``).  Masked positions are about
26% of the positions (52.5% of the noisy half); they all enter layer 0 with
one embedding and route alike there, so whether the experts held are among
their eight swings this share, and with it the expert layer's load.
Counters of the period's ``spans`` record.  None where the program has no
such counter."""

from chipbench.counters import ratio


def read(run):
    return ratio(run, "bd_slots_held_masked", "moe_slots_held", 100.0)
