"""Pure-jnp oracle for blocked mask pack/unpack (checkpoint hot path).

Format contract (shared with the Pallas kernel): the array is processed in
fixed BLOCK-element tiles; each tile is left-compacted (critical elements
first, in order) and the per-tile critical count is returned.  The
checkpoint writer then streams ``counts[i]`` elements per tile — a single
bandwidth-bound pass with static shapes on device.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

BLOCK = 512

# Elements per bitpack grid tile (→ block/8 output bytes per tile).
BITPACK_BLOCK = 1024


def bitpack_blocks_ref(mag: jnp.ndarray, tol, block: int = BITPACK_BLOCK):
    """Threshold + bit-pack oracle (matches ``kernel.bitpack_blocks_kernel``).

    mag: (N,) float magnitudes, N % block == 0; bit i is ``mag[i] > tol``.
    Bit order matches ``np.packbits`` (big-endian within each byte), so the
    words are directly usable as ``core.bitset.BitMask`` words / bitmap aux.
    Returns (words (N//block, block//8) uint8, counts (N//block,) int32).
    """
    nb = mag.shape[0] // block
    bits = mag > jnp.asarray(tol, mag.dtype)
    w = jnp.asarray([128, 64, 32, 16, 8, 4, 2, 1], jnp.int32)
    words = (bits.reshape(-1, 8).astype(jnp.int32) * w).sum(axis=1)
    counts = bits.reshape(nb, block).sum(axis=1).astype(jnp.int32)
    return words.astype(jnp.uint8).reshape(nb, block // 8), counts


def pack_blocks_ref(flat: jnp.ndarray, mask: jnp.ndarray, block: int = BLOCK):
    """flat: (N,) values; mask: (N,) bool.  N % block == 0.
    Returns (packed (N//block, block), counts (N//block,) int32)."""
    n = flat.shape[0]
    assert n % block == 0
    vb = flat.reshape(-1, block)
    mb = mask.reshape(-1, block)
    pos = jnp.cumsum(mb, axis=1) - 1                       # target slot
    idx = jnp.where(mb, pos, block)                        # block: dropped
    rows = jnp.arange(vb.shape[0])[:, None]
    # a set (not an add onto zeros) keeps every bit, -0.0 included
    packed = jnp.zeros_like(vb).at[rows, idx].set(vb, mode="drop")
    counts = mb.sum(axis=1).astype(jnp.int32)
    return packed, counts


def gather_payload_ref(packed: jnp.ndarray, counts: jnp.ndarray, total: int):
    """Inter-tile gap removal: compact the per-tile critical prefixes of
    ``packed`` (nb, block) into one dense (total,) payload — the only big
    buffer that crosses D2H on save.  ``total`` must equal ``counts.sum()``
    (static; the manager derives it from the criticality report so no
    counts D2H is needed to size the gather)."""
    nb, block = packed.shape
    if total == 0:
        return packed.reshape(-1)[:0]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    j = jnp.arange(total)
    tile = jnp.searchsorted(ends, j, side="right")
    slot = j - starts[tile]
    return packed.reshape(-1)[tile * block + slot]


def unpack_blocks_ref(packed: jnp.ndarray, mask: jnp.ndarray, fill=0.0):
    """Inverse of pack_blocks_ref: scatter compacted values back to their
    positions; uncritical positions get ``fill``."""
    nb, block = packed.shape
    mb = mask.reshape(nb, block)
    pos = jnp.cumsum(mb, axis=1) - 1
    rows = jnp.arange(nb)[:, None]
    vals = packed[rows, jnp.clip(pos, 0, block - 1)]
    out = jnp.where(mb, vals, fill)
    return out.reshape(-1)


def scatter_blocks_ref(payload_pad: jnp.ndarray, starts: jnp.ndarray,
                       mask: jnp.ndarray, fill=0.0, block: int = BLOCK):
    """Oracle for the fused restore tile pass: dense payload + per-tile
    payload offsets + mask → restored flat array (matches
    ``kernel.scatter_blocks_kernel``)."""
    flat_payload = payload_pad.reshape(-1)
    nb = mask.shape[0] // block
    mb = mask.reshape(nb, block)
    pos = jnp.cumsum(mb, axis=1) - 1                 # slot within the tile
    src = starts[:, None] + pos                      # payload index per elem
    vals = flat_payload[jnp.clip(src, 0, flat_payload.shape[0] - 1)]
    out = jnp.where(mb, vals, jnp.asarray(fill, payload_pad.dtype))
    return out.reshape(-1)


def delta_blocks_ref(curr: jnp.ndarray, base: jnp.ndarray, chunk: int):
    """Per-chunk changed flags (matches ``kernel.delta_blocks_kernel``)."""
    nc = curr.shape[0] // chunk
    neq = (curr.reshape(nc, chunk) != base.reshape(nc, chunk))
    return jnp.any(neq, axis=1).astype(jnp.int32)
