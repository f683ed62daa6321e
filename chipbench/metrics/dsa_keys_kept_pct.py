"""Of the keys the window's queries could see (``dsa_keys_visible``: causal,
same document, summed over layers), the share the selection kept
(``dsa_keys_kept``: ``min(visible, index_topk)`` a query): what the main
attention's softmax runs over.  Counters of the period's ``spans`` record.
None where the program has no such counter."""

from chipbench.spans import window_periods


def read(run):
    records = [r["counters"] for r in window_periods(run)
               if "dsa_keys_visible" in r.get("counters", {})]
    visible = sum(c["dsa_keys_visible"] for c in records)
    if not visible:
        return None
    return 100.0 * sum(c["dsa_keys_kept"] for c in records) / visible
