"""Published peaks of the chips the benchmark may run on, keyed by a
substring of ``device_kind``.  A kind that is not here is an error."""

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at
# 819 GB/s per chip.  (The program's own copy is obs/costs.py:CHIP_PEAKS.)
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
PEAKS = {"v5lite": V5E, "v5e": V5E}


def peaks_of(device_kind: str) -> dict:
    key = device_kind.lower().replace(" ", "")
    for name, row in PEAKS.items():
        if name in key:
            return row
    raise KeyError(f"device kind {device_kind!r} is not in chipbench/peaks.py"
                   f" ({sorted(PEAKS)}): add its published peaks first")
