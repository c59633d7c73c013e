"""Published peaks of each chip the benchmark may run on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error: a share of
a peak is never computed against a guessed peak."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
