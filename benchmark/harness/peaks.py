"""Published peaks of one chip, keyed by jax's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM2e at 819 GB/s per chip (the table of bench.HBM_PEAK_GBPS,
copied).  A kind that is not here is an error, never a default."""

PEAKS = {
    "TPU v5 lite": {"hbm_gb_per_s": 819.0, "bf16_tflop_per_s": 197.0,
                    "hbm_gb": 16.0},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add the "
            f"chip to benchmark/harness/peaks.py with its source") from None
