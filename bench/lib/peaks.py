"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip.  The table is kept here, apart from the
program's own, so that no change to the program can move the yardstick.
A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,      # 1,600 Gbit/s = 200 GB/s
    },
}


def peak(device_kind: str, what: str) -> float:
    """One peak of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(f"no peak {what!r} for device kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}") from None
