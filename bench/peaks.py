"""Published peaks of each accelerator the benchmark may run on, keyed
by JAX's ``device_kind``. A kind that is not here is an error, never a
default: a share of an unknown peak is no number at all."""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    hbm_bytes_per_s: float
    bf16_flops_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e' system "
               "architecture: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s"),
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; add "
            f"them to bench/peaks.py with their source") from None
