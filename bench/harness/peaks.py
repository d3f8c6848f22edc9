"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not here is an error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(
        flops_per_s=197e12,          # bf16 matrix units
        bytes_per_s=819e9,           # HBM
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


class UnknownDevice(RuntimeError):
    pass


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
