"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e),
system architecture table: per chip 197 TFLOP/s in bf16, 393 TOP/s in
int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect.
JAX names that chip "TPU v5 lite".

A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

SOURCE = ("Google Cloud documentation, 'TPU v5e' "
          "(cloud.google.com/tpu/docs/v5e), per chip")

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


class UnknownDevice(LookupError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"the table has {sorted(PEAKS)}") from None
