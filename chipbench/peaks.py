"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports. A kind that is not here is an error, never a
default: a share of a guessed peak says nothing.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e system architecture)",
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def for_kind(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       "them to chipbench/peaks.py with their source") from None


def least_seconds(flops: float, bytes_: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for the work, and which bound
    sets it ('compute' or 'memory')."""
    t_c = flops / peaks["bf16_flops"]
    t_m = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
