"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. Source: Google Cloud documentation, "TPU v5e"
(system architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at
819 GB/s per chip."""

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,   # FLOP/s
        "ops_int8": 393e12,     # OP/s
        "hbm_bytes": 16e9,      # B
        "hbm_bw": 819e9,        # B/s
    },
}


def chip_peaks(device_kind: str, allow_unknown: bool = False) -> dict | None:
    """The kind's peaks. An unknown kind is an error; ``allow_unknown``
    (CPU tests only) returns None, and readers that need a peak return
    nothing."""
    if device_kind in PEAKS:
        return PEAKS[device_kind]
    if allow_unknown:
        return None
    raise SystemExit(f"no peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}")
