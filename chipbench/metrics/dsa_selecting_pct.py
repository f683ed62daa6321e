"""Of the layer-queries of the window's epochs (``dsa_queries``), the share
that saw more than ``index_topk`` keys of their own document
(``dsa_queries_selecting``), so that the indexer's choice left keys out:
counters of the period's ``spans`` record.  None where the program has no
such counter."""

from chipbench.spans import window_periods


def read(run):
    records = [r["counters"] for r in window_periods(run)
               if "dsa_queries" in r.get("counters", {})]
    queries = sum(c["dsa_queries"] for c in records)
    if not queries:
        return None
    return 100.0 * sum(c["dsa_queries_selecting"] for c in records) / queries
