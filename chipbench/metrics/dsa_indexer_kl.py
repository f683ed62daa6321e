"""The indexer's loss over the window: ``dsa_kl_sum`` (the sum over layers
and query positions of ``KL(attention's distribution over the kept keys ||
the indexer's)``) over ``dsa_queries``, counters of the period's ``spans``
record: the second term of the loss the cell trains, in nats.  None where
the program has no such counter."""

from chipbench.spans import window_periods


def read(run):
    records = [r["counters"] for r in window_periods(run)
               if "dsa_kl_sum" in r.get("counters", {})]
    queries = sum(c["dsa_queries"] for c in records)
    if not queries:
        return None
    return sum(c["dsa_kl_sum"] for c in records) / queries
