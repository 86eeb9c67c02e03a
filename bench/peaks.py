"""Published peak rates per device kind, keyed by ``device.device_kind``.

Source for "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16 and 819 GB/s of HBM
bandwidth per chip. A kind missing from the table is an error: a
roofline share against a guessed peak is not a measurement.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; raises KeyError when unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
