"""Production mesh: 16×16 per pod (TPU v5e, 256 chips), 2 pods multi-pod.

A function, not a module constant — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import)."""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over the real local devices (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(1, n // data))
    return jax.make_mesh((data, model), ("data", "model"))


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect = 4 links x 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}

# The production mesh above is a v5e pod: the dry-run roofline's target.
PRODUCTION_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Peak bf16 FLOP/s, HBM bytes/s and ICI bytes/s per link of one chip
    of ``device_kind``; a kind not in :data:`PEAKS` is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None
