"""Peaks of the devices the benchmark may run on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page — per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect.  A device that is not in the table is an error,
never a default: a share of somebody else's peak is not a measurement.
(Copied from ``paddle_tpu/observability/mfu.py`` ``DEVICE_SPECS`` and
``interconnect.py``; the benchmark keeps its own so that no PR that claims a
gain can move the yardstick.)
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"gen": "v5e", "bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "ici_bits_s": 1600e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud, TPU v5e"},
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in perfbench.harness.peaks."
            f"PEAKS; add its published peaks with their source") from None
