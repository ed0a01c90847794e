"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip.

A device kind that is not in the table is an error: a roofline share or an
MFU computed against a guessed peak would be a number about nothing.
"""

from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e (cloud.google.com/tpu/docs/v5e)"

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


class UnknownDevice(KeyError):
    """The device kind has no entry in ``PEAKS``."""


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``UnknownDevice`` if absent."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} ({SOURCE})") from None
