"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an error,
never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip.
"""
from __future__ import annotations

SOURCE = 'Google Cloud documentation, "TPU v5e"'

_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes": 16e9,
    "hbm_bytes_per_s": 819e9,
    "ici_bits_per_s": 1600e9,
    "source": SOURCE,
}

# JAX names a v5e "TPU v5 lite"
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
