"""Published peaks of each chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s in bf16 per chip.
A device missing from the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "source": "Google Cloud TPU v5e documentation",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]
