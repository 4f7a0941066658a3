"""Published peaks of one chip, keyed by the exact PJRT ``device_kind``.

A copy of ``serving/costmodel.py::DEVICE_PEAKS`` (a test holds the two
equal), kept here so that no later PR can move a roofline's denominator.
Source: Google Cloud documentation, system architecture page of each TPU
generation; the v5e row ("TPU v5e") is 197 TFLOP/s in bf16 and 819 GB/s of
HBM bandwidth. A device that is not listed is an error, not a default.
"""

from __future__ import annotations

DEVICE_PEAKS = {  # device_kind: (dense bf16 TFLOP/s, HBM GB/s)
    "TPU v4": (275.0, 1228.0),
    "TPU v5 lite": (197.0, 819.0),
    "TPU v5p": (459.0, 2765.0),
    "TPU v6 lite": (918.0, 1640.0),
}


def device_peak(device_kind: str) -> tuple[float, float]:
    """(peak bf16 FLOP/s, peak HBM bytes/s) of one chip of ``device_kind``."""
    try:
        tf, gb = DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks known for device_kind {device_kind!r} "
                         f"(known: {sorted(DEVICE_PEAKS)})") from None
    return tf * 1e12, gb * 1e9
