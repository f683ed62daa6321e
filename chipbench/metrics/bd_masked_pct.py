"""Of the tokens the window's epochs trained on (``bd_tokens``: rows x S),
the share that was masked in the noisy copy and so carried a loss
(``bd_positions_masked``): 52.5% is what ``t ~ U[0.05, 1]`` a block gives,
and a mask drawn otherwise moves this before it moves the loss.  Counters of
the period's ``spans`` record.  None where the program has no such
counter."""

from chipbench.counters import ratio


def read(run):
    return ratio(run, "bd_positions_masked", "bd_tokens", 100.0)
