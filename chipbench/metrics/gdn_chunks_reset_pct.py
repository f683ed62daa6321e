"""Of the chunks the Gated DeltaNet layers' scans ran over (``gdn_chunks``:
layer-row chunks of ``gdn_chunk`` tokens), the share in which a token follows
one of another document (``gdn_chunks_reset``), so that the scan drops its
carried state or cuts the pairs inside the chunk there: how often the reset
path does work under this packing.  Counters of the period's ``spans``
record.  None where the program has no such counter."""

from chipbench.spans import window_periods


def read(run):
    records = [r["counters"] for r in window_periods(run)
               if "gdn_chunks" in r.get("counters", {})]
    chunks = sum(c["gdn_chunks"] for c in records)
    if not chunks:
        return None
    return 100.0 * sum(c["gdn_chunks_reset"] for c in records) / chunks
