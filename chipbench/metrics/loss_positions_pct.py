"""Of the positions the window's epochs predicted (``tokens``, which the
``dispatch`` span counts), the share that carried a loss
(``loss_positions``, a counter of the period's ``spans`` record): what the
packing of documents leaves, since the position before a new document is
not judged.  None where the program counts neither."""

from chipbench.spans import count, window_periods


def read(run):
    records = [r for r in window_periods(run)
               if "loss_positions" in r.get("counters", {})]
    tokens = sum(count(r, "dispatch", "tokens") for r in records)
    if not tokens:
        return None
    return 100.0 * sum(r["counters"]["loss_positions"]
                       for r in records) / tokens
