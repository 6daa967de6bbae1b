"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports
it.  A kind that is not here is an error, never a default."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s of
#: HBM bandwidth, 16 GB of HBM per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmark/lib/peaks.py"
            f" (known: {sorted(PEAKS)}); add it with its source") from None
