"""Public pack/unpack entry: pads to BLOCK, dispatches kernel/oracle.

Device-resident checkpoint fast path (the save hot path):

    packed, counts = pack(flat, mask)          # on device, per-tile compaction
    counts_h = np.asarray(counts)              # D2H: 4 B per tile
    payload  = gather_payload(packed, counts, total=counts_h.sum())
    payload_h = np.asarray(payload)            # D2H: critical bytes only

``pack_critical`` wraps the sequence and reports the D2H byte count; the
checkpoint writer assembles the on-disk format from the payload directly
(repro.checkpoint.packing.pack_leaf_from_payload) — the full array never
crosses the device→host boundary.

The restore direction mirrors it: ``mask_scatter`` moves only the critical
payload H2D (plus the bit-packed mask the caller already holds) and
re-expands into a fill-initialized device buffer via the fused
``scatter_blocks_kernel`` — restore traffic scales with the critical
fraction exactly like save.

``delta_encode`` is the differential-checkpoint primitive: it compares the
current and base payloads' raw bits on device per fixed-size chunk and
moves only changed chunks D2H, so successive saves of a slowly-changing
state cost ∝ changed bytes (disk and PCIe both).

Dtype handling: every path works on the leaf's raw bits (float leaves
are viewed as same-width unsigned ints), so packs, restores and deltas are
bit-exact for every value, NaN payloads and -0.0 included.  The kernels
move 32-bit words (16-bit dtypes zero-extended) and take every 2- and
4-byte dtype; 1- and 8-byte leaves are routed to the pure-jnp oracle
regardless of backend.  Arbitrary leaf sizes are handled by padding to the
BLOCK grid here — the raw kernels require ``N % block == 0``.

Devices: a Pallas kernel runs on one device's data and cannot be
partitioned.  ``threshold_bitpack`` and ``delta_encode`` run it once per
device on operands that span several (:func:`_per_device`); the pack
entry points take one device's data, and the save pipelines hand them
per-device pieces (``distributed.sharding.device_segments``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.mask_pack.kernel import (BITPACK_BLOCK, BLOCK,
                                            bitpack_blocks_kernel,
                                            delta_blocks_kernel,
                                            pack_blocks_kernel,
                                            scatter_blocks_kernel,
                                            unpack_blocks_kernel)
from repro.kernels.mask_pack.ref import (bitpack_blocks_ref, delta_blocks_ref,
                                         gather_payload_ref, pack_blocks_ref,
                                         scatter_blocks_ref,
                                         unpack_blocks_ref)

# itemsizes the word-moving kernels pack (everything else → jnp oracle).
_KERNEL_ITEMSIZES = (2, 4)

# Chunk granularity of the delta format, in bytes — a multiple of every
# leaf itemsize so chunks never split an element.  Single source of truth:
# the host encoder (checkpoint/packing) imports it from here, so host- and
# device-written delta files stay byte-identical.  (This direction avoids
# an import cycle: kernels never import the checkpoint package.)
DELTA_CHUNK_BYTES = 2048


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _spans_devices(x) -> bool:
    return (isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)
            and len(x.devices()) > 1)


def _per_device(kernel, xs, *, tile: int, pads):
    """Run ``kernel`` (a one-device kernel call on 1-D operands whose
    outputs are per-tile along axis 0) on each device's piece of ``xs``,
    operands that span several devices.  A Pallas kernel cannot be
    partitioned, so the operands are laid flat over their devices in
    tile-aligned pieces (padded with ``pads``; a no-op for a layout that
    already is one) and the kernel runs per piece under ``shard_map``:
    each device computes on about ``1/n`` of the data, and the outputs
    come back in global tile order, sharded the same way (the caller
    slices off the padding tiles)."""
    devs = sorted(set().union(*(x.devices() for x in xs)),
                  key=lambda d: d.id)
    n, k = xs[0].shape[0], len(devs)
    pad = -(-n // (k * tile)) * tile * k - n
    mesh = Mesh(np.array(devs), ("d",))
    xs = [jax.device_put(jnp.pad(x, (0, pad), constant_values=v) if pad
                         else x, NamedSharding(mesh, P("d")))
          for x, v in zip(xs, pads)]
    return per_device_fn(kernel, mesh, len(xs))(*xs)


def per_device_fn(kernel, mesh: Mesh, nargs: int):
    """``kernel`` over ``nargs`` 1-D operands laid out ``P("d")`` over the
    1-D ``mesh``: one kernel call per device on its own piece."""
    spec = P("d")
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec,) * nargs,
                         out_specs=spec, check_vma=False)


_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


def _raw(x: jnp.ndarray) -> jnp.ndarray:
    """Same-width unsigned view of a float array, so pad, gather, scatter
    and select move bits, never values: XLA:CPU widens some bf16 ops to
    f32 and back, which rewrites NaN payloads."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jax.lax.bitcast_convert_type(
            x, _UINT[np.dtype(x.dtype).itemsize])
    return x


def _cooked(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of :func:`_raw`."""
    return x if x.dtype == dtype else jax.lax.bitcast_convert_type(x, dtype)


def _use_kernel(flat: jnp.ndarray, use_kernel) -> bool:
    uk = _on_tpu() if use_kernel is None else use_kernel
    return bool(uk) and np.dtype(flat.dtype).itemsize in _KERNEL_ITEMSIZES


def _pack_traced(flat: jnp.ndarray, mask: jnp.ndarray, *, block: int,
                 use_kernel, interpret: bool):
    """Trace-time pack body shared by :func:`pack` and :func:`pack_group`:
    pads to the tile grid and dispatches kernel/oracle, on the leaf's raw
    bits (:func:`_raw`).  Returns raw packed tiles and counts."""
    flat = _raw(flat)
    n = flat.shape[0]
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
        mask = jnp.pad(mask, (0, pad))
    if n and _use_kernel(flat, use_kernel):
        return pack_blocks_kernel(flat, mask.astype(jnp.int8), block=block,
                                  interpret=interpret)
    return pack_blocks_ref(flat, mask, block=block)


@functools.partial(jax.jit,
                   static_argnames=("block", "use_kernel", "interpret"))
def pack(flat: jnp.ndarray, mask: jnp.ndarray, *, block: int = BLOCK,
         use_kernel: bool | None = None, interpret: bool = False):
    """flat: (N,) any dtype; mask: (N,) bool — any N (padded to the grid).
    Returns (packed (ceil(N/block), block), counts (ceil(N/block),))."""
    packed, counts = _pack_traced(flat, mask, block=block,
                                  use_kernel=use_kernel, interpret=interpret)
    return _cooked(packed, flat.dtype), counts


@functools.partial(jax.jit,
                   static_argnames=("block", "n", "use_kernel", "interpret"))
def unpack(packed: jnp.ndarray, mask: jnp.ndarray, *, n: int,
           block: int = BLOCK, fill: float = 0.0,
           use_kernel: bool | None = None, interpret: bool = False):
    """Inverse of :func:`pack`; returns (n,) restored flat array."""
    dtype = packed.dtype
    packed = _raw(packed)
    total = packed.shape[0] * packed.shape[1]
    pad = total - n
    m = jnp.pad(mask, (0, pad)) if pad else mask
    fill = _raw(jnp.asarray(fill, dtype))   # no accidental float promotion
    if _use_kernel(packed, use_kernel):
        out = unpack_blocks_kernel(packed, m.astype(jnp.int8), fill=fill,
                                   interpret=interpret)
    else:
        out = unpack_blocks_ref(packed, m, fill=fill)
    return _cooked(out[:n], dtype)


@functools.partial(jax.jit, static_argnames=("total",))
def gather_payload(packed: jnp.ndarray, counts: jnp.ndarray, *, total: int):
    """Device-side: compact the per-tile critical prefixes into one dense
    (total,) payload — the only big buffer that crosses D2H on save."""
    return _cooked(gather_payload_ref(_raw(packed), counts, total),
                   packed.dtype)


@functools.partial(jax.jit, static_argnames=("totals", "block", "use_kernel",
                                             "interpret"))
def _pack_group_jit(flats, masks, *, totals, block, use_kernel, interpret):
    payloads, counts = [], []
    for f, m, t in zip(flats, masks, totals):
        packed, cnt = _pack_traced(f, m, block=block, use_kernel=use_kernel,
                                   interpret=interpret)
        counts.append(cnt)
        if t:
            payloads.append(gather_payload_ref(packed, cnt, t))
    dtype = flats[0].dtype if flats else jnp.float32
    payload = (_cooked(jnp.concatenate(payloads), dtype) if payloads
               else jnp.zeros((0,), dtype))
    cnt = (jnp.concatenate(counts) if counts
           else jnp.zeros((0,), jnp.int32))
    return payload, cnt


def pack_group(flats, masks, totals, *, block: int = BLOCK,
               use_kernel: bool | None = None, interpret: bool = False):
    """Batched device pack for the pipelined save engine: **one compiled
    call** compacts every leaf of a same-dtype group (pad to the grid, pack,
    per-leaf payload gather, concat) — per-leaf dispatch and recompile
    overhead disappears from the save hot loop.

    ``flats``: same-dtype flat device arrays; ``masks``: matching flat bool
    masks (resident device masks are consumed as-is); ``totals``: *static*
    per-leaf critical counts — the manager reads them off the criticality
    report, so sizing the gather needs **no counts D2H** and the compiled
    call is cached per (treedef shapes, report epoch).

    Returns ``(payload_dev, counts_dev)``: the concatenated per-leaf
    payloads (leaf order — slice with running ``totals`` offsets) and the
    concatenated per-tile counts, both still on device.
    """
    totals = tuple(int(t) for t in totals)
    if len(flats) != len(masks) or len(flats) != len(totals):
        raise ValueError("pack_group: flats/masks/totals length mismatch")
    for f in flats:
        if _spans_devices(f) and _use_kernel(f, use_kernel):
            raise ValueError(
                "pack_group: a leaf spans several devices; a Pallas kernel "
                "cannot be partitioned, so pack one device's piece at a "
                "time (distributed.sharding.device_segments)")
    return _pack_group_jit(tuple(flats), tuple(masks), totals=totals,
                           block=block, use_kernel=use_kernel,
                           interpret=interpret)


def pack_critical(flat: jnp.ndarray, mask, *, block: int = BLOCK,
                  use_kernel: bool | None = None, interpret: bool = False):
    """Device-resident save path for one flat leaf.

    Returns ``(payload, counts, d2h_bytes)`` — ``payload`` is a host numpy
    array of exactly the critical elements (leaf order), ``counts`` the
    per-tile critical counts, and ``d2h_bytes`` the bytes that actually
    crossed device→host (payload + counts; the full leaf never moves).
    """
    mask = jnp.asarray(mask)
    packed, counts = pack(flat, mask, block=block, use_kernel=use_kernel,
                          interpret=interpret)
    counts_h = np.asarray(counts)                  # D2H: 4 B / tile
    total = int(counts_h.sum())
    if total:
        payload_h = np.asarray(
            gather_payload(packed, counts, total=total))  # D2H: critical bytes
    else:
        payload_h = np.zeros(0, dtype=np.dtype(packed.dtype))
    return payload_h, counts_h, payload_h.nbytes + counts_h.nbytes


@functools.partial(jax.jit,
                   static_argnames=("n", "block", "use_kernel", "interpret"))
def _mask_scatter_jit(payload, mask, fill, *, n: int, block: int,
                      use_kernel, interpret: bool):
    dtype = payload.dtype
    payload = _raw(payload)
    total = payload.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    m = jnp.pad(mask, (0, pad)) if pad else mask
    counts = m.reshape(nb, block).sum(axis=1).astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts
    fill = _raw(jnp.asarray(fill, dtype))
    if _use_kernel(payload, use_kernel):
        npb = total // block + 2          # every 2-block window in bounds
        pp = jnp.pad(payload, (0, npb * block - total)).reshape(npb, block)
        out = scatter_blocks_kernel(pp, starts, m.astype(jnp.int8),
                                    fill=fill, block=block,
                                    interpret=interpret)
    else:
        out = scatter_blocks_ref(payload, starts, m, fill=fill, block=block)
    return _cooked(out[:n], dtype)


def mask_scatter(payload, mask, *, n: int, block: int = BLOCK,
                 fill: float = 0.0, use_kernel: bool | None = None,
                 interpret: bool = False):
    """Device-resident restore expand: dense critical ``payload`` + ``mask``
    → (n,) device array with ``fill`` at uncritical positions.

    Inverse of ``pack`` + ``gather_payload`` fused into one pass: per-tile
    counts/starts are derived from the mask *on device*, so the only H2D
    inputs are the payload and the (bit-packable) mask.
    """
    committed = getattr(payload, "committed", False)
    payload = jnp.asarray(payload)
    mask = jnp.asarray(mask)
    if payload.shape[0] == 0:
        out = jnp.full((n,), fill, payload.dtype)
        if committed:           # keep empty segments on the payload's device
            out = jax.device_put(out, next(iter(payload.devices())))
        return out
    return _mask_scatter_jit(payload, mask, fill, n=n, block=block,
                             use_kernel=use_kernel, interpret=interpret)


def unpack_critical(payload, counts, mask, *, n: int, block: int = BLOCK,
                    fill: float = 0.0, use_kernel: bool | None = None,
                    interpret: bool = False):
    """Device-resident restore for one leaf: H2D only the critical payload,
    re-expand on device.  Returns the (n,) device array.  (``counts`` is
    accepted for compatibility; the fused path re-derives it from the mask.)
    """
    del counts
    return mask_scatter(payload, mask, n=n, block=block, fill=fill,
                        use_kernel=use_kernel, interpret=interpret)


def threshold_bitpack(mag: jnp.ndarray, tol=0.0, *,
                      block: int = BITPACK_BLOCK,
                      use_kernel: bool | None = None,
                      interpret: bool = False):
    """Device-resident scrutiny output: threshold magnitudes and bit-pack
    the criticality mask **on device**.

    ``mag``: (N,) non-negative |∂out/∂x| magnitudes (any float dtype; the
    MXU kernel handles f32, everything else routes to the exact jnp
    oracle).  Bit ``i`` of the result is ``mag[i] > tol``, in ``np.packbits``
    (big-endian per byte) order, so the words are directly consumable as
    ``core.bitset.BitMask`` words, the checkpoint bitmap aux encoding, and
    ``expand_mask_bits`` input.  Tail bits of the last byte are always 0.

    Returns ``(words, counts)``: words ``(ceil(N/8),)`` uint8 and per-tile
    int32 critical counts ``(ceil(N/block),)`` — the only scrutiny outputs
    that ever need to cross D2H (1 bit/element + 4 B/tile summaries).
    """
    uk = _on_tpu() if use_kernel is None else use_kernel
    if uk and mag.dtype == jnp.float32 and _spans_devices(mag):
        n = mag.shape[0]
        words, counts = _per_device(
            lambda m: bitpack_blocks_kernel(m, tol, block=block,
                                            interpret=interpret),
            [mag], tile=block, pads=[-jnp.inf])
        return (words.reshape(-1)[:(n + 7) // 8],
                counts[:-(-n // block)])
    return _threshold_bitpack(mag, tol, block=block, use_kernel=uk,
                              interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("block", "use_kernel", "interpret"))
def _threshold_bitpack(mag, tol, *, block: int, use_kernel, interpret: bool):
    n = mag.shape[0]
    pad = (-n) % block
    if pad:
        # -inf padding can never exceed tol, so padded bits (including the
        # tail bits of a kept byte when N % 8 != 0) stay 0.
        mag = jnp.pad(mag, (0, pad), constant_values=-jnp.inf)
    if use_kernel and mag.dtype == jnp.float32:
        words, counts = bitpack_blocks_kernel(mag, tol, block=block,
                                              interpret=interpret)
    else:
        words, counts = bitpack_blocks_ref(mag, tol, block=block)
    return words.reshape(-1)[:(n + 7) // 8], counts


@functools.partial(jax.jit, static_argnames=("n",))
def expand_mask_bits(bits, *, n: int):
    """H2D-cheap mask transfer: ``bits`` is ``np.packbits(mask)`` (uint8,
    big-endian bit order); expands back to the (n,) bool mask on device —
    the mask costs 1 bit/element over PCIe instead of 1 byte."""
    b = jnp.asarray(bits, jnp.uint8)
    x = (b[:, None] >> (7 - jnp.arange(8, dtype=jnp.uint8))[None, :]) & 1
    return x.reshape(-1)[:n].astype(bool)


# --------------------------------------------------------------------------
# Differential (delta) encode: byte-chunk diff on device
# --------------------------------------------------------------------------

def _delta_words(arr):
    """Flat int32 words of a device array for the delta compare, and how
    many bytes each word stands for: 4- and 8-byte dtypes are bitcast to
    32-bit words, 1- and 2-byte dtypes widened one word per element (an
    injective map, so equal words mean equal bytes).  Raises TypeError
    for dtypes bitcast can't handle (complex) — callers fall back to a
    full-entry write."""
    arr = jnp.ravel(jnp.asarray(arr))
    size = np.dtype(arr.dtype).itemsize
    if size >= 4:
        return jax.lax.bitcast_convert_type(arr, jnp.int32).reshape(-1), 4
    if arr.dtype != jnp.bool_:
        arr = jax.lax.bitcast_convert_type(
            arr, jnp.uint8 if size == 1 else jnp.uint16)
    return arr.astype(jnp.int32), size


@functools.partial(jax.jit,
                   static_argnames=("chunk", "use_kernel", "interpret"))
def _delta_flags(curr, base, *, chunk: int, use_kernel, interpret: bool):
    pad = (-curr.shape[0]) % chunk
    if pad:                              # equal zero padding: never "changed"
        curr = jnp.pad(curr, (0, pad))
        base = jnp.pad(base, (0, pad))
    if use_kernel:                       # word compare: exact for any dtype
        flags = delta_blocks_kernel(curr, base, chunk, interpret=interpret)
    else:
        flags = delta_blocks_ref(curr, base, chunk)
    return flags.astype(jnp.int8)        # D2H: 1 B per chunk


@functools.partial(jax.jit, static_argnames=("chunk",))
def _gather_chunks(flat, idx, *, chunk: int):
    flat = _raw(flat)
    pad = (-flat.shape[0]) % chunk
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, chunk)[idx]


def delta_encode(curr, base, *, chunk_bytes: int = DELTA_CHUNK_BYTES,
                 use_kernel: bool | None = None, interpret: bool = False):
    """Differential encode of ``curr`` against ``base`` (both device arrays
    of one dtype and size; ``chunk_bytes`` a multiple of its itemsize),
    comparing raw bits per ``chunk_bytes``-sized chunk on device.

    Returns ``(idx, payload, d2h_bytes)``: ``idx`` the int32 indices of
    changed chunks, ``payload`` the changed chunks' bytes (final chunk
    clipped to the true length) as a host uint8 array, and ``d2h_bytes``
    what actually crossed device→host (1 B of flag per chunk + the changed
    bytes — an unchanged state costs ~0.05 % of its size).
    """
    curr, base = jnp.ravel(jnp.asarray(curr)), jnp.ravel(jnp.asarray(base))
    cw, word_bytes = _delta_words(curr)
    bw, _ = _delta_words(base)
    total = curr.size * np.dtype(curr.dtype).itemsize
    if base.size * np.dtype(base.dtype).itemsize != total:
        raise ValueError(
            f"delta_encode: size mismatch ({total} vs "
            f"{base.size * np.dtype(base.dtype).itemsize} bytes)")
    if total == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.uint8), 0
    chunk = chunk_bytes // word_bytes
    uk = _on_tpu() if use_kernel is None else use_kernel
    if uk and (_spans_devices(cw) or _spans_devices(bw)):
        flags = _per_device(
            lambda c, b: delta_blocks_kernel(c, b, chunk,
                                             interpret=interpret),
            [cw, bw], tile=chunk, pads=[0, 0])
        flags = flags[:-(-cw.shape[0] // chunk)].astype(jnp.int8)
    else:
        flags = _delta_flags(cw, bw, chunk=chunk, use_kernel=uk,
                             interpret=interpret)
    flags_h = np.asarray(flags)              # D2H: 1 B per chunk
    d2h = flags_h.nbytes
    idx = np.flatnonzero(flags_h).astype(np.int32)
    if idx.size == 0:
        return idx, np.zeros(0, np.uint8), d2h
    # changed chunks leave in the leaf's own width; the host views bytes
    chunks = np.asarray(_gather_chunks(
        curr, jnp.asarray(idx),
        chunk=chunk_bytes // np.dtype(curr.dtype).itemsize))
    chunks = chunks.reshape(len(idx), -1).view(np.uint8)
    nc = -(-total // chunk_bytes)
    tail = total - (nc - 1) * chunk_bytes
    if int(idx[-1]) == nc - 1 and tail < chunk_bytes:
        payload = np.concatenate([chunks[:-1].reshape(-1),
                                  chunks[-1][:tail]])
    else:
        payload = chunks.reshape(-1)
    return idx, payload, d2h + payload.nbytes


def pack_to_payload(packed: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Host-side: stream counts[i] leading elements of each tile into the
    final contiguous payload (the I/O write path) — one boolean gather."""
    packed = np.asarray(packed)
    counts = np.asarray(counts)
    if not len(counts):
        return packed.reshape(-1)[:0]
    valid = np.arange(packed.shape[1])[None, :] < counts[:, None]
    return packed[valid]


def payload_to_packed(payload: np.ndarray, counts: np.ndarray,
                      block: int) -> np.ndarray:
    """Host-side inverse of :func:`pack_to_payload` (vectorized scatter)."""
    payload = np.asarray(payload)
    counts = np.asarray(counts)
    nb = len(counts)
    out = np.zeros((nb, block), payload.dtype)
    valid = np.arange(block)[None, :] < counts[:, None]
    out[valid] = payload[: int(counts.sum())]
    return out
