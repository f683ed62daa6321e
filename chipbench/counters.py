"""The program's counters, for the metrics that are a share of one in
another.

A token model returns counters with its loss; ``train()`` sums them over an
epoch's steps and journals them in the period's ``spans`` record under
``counters`` (``chipbench/spans.py`` has the record).  A program without the
counter asked for journals none, and :func:`ratio` then returns None.
"""

from .spans import window_periods


def ratio(run, over, under, scale=1.0):
    """``scale x sum(over) / sum(under)`` of two counters over the window's
    periods; None where the program counts neither or ``under`` sums to 0."""
    records = [r["counters"] for r in window_periods(run)
               if under in r.get("counters", {})]
    total = sum(c[under] for c in records)
    if not total:
        return None
    return scale * sum(c[over] for c in records) / total
