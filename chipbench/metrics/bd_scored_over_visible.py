"""Pairs of positions the attention's query blocks scored
(``bd_pairs_scored``: a block of 1,024 queries against the clean keys up to
its end and, if noisy, its own 1,024 noisy keys; 0.375 of the ``[2 S, 2 S]``
square at ``S`` 4,096) over the pairs the block-diffusion mask lets see
(``bd_pairs_visible``: the four rules and the document term), both summed
over rows and layers: 1.5 before the document term at that shape, more with
it.  What a change that skips more of what the mask rules out has to move.
Counters of the period's ``spans`` record.  None where the program has no
such counter."""

from chipbench.counters import ratio


def read(run):
    return ratio(run, "bd_pairs_scored", "bd_pairs_visible")
