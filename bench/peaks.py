"""Peak rates of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip. A device
kind that is not in the table is an error: a share of a peak that was
never looked up would be a guess.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str, what: str) -> float:
    """The peak ``what`` (a key of :data:`PEAKS`' entries) of one chip."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind][what]
