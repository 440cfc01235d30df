"""Pallas TPU kernels for blocked mask pack/unpack, scatter, bitpack, delta.

Every kernel moves raw 32-bit words: the wrappers here view the payload
as int32 (f32/int32 bitcast, 16-bit dtypes zero-extended), so a pack or
restore is bit-exact for every value, NaN payloads and -0.0 included.

Per-tile left-compaction has no scatter unit to lean on, so it runs as a
**log-step shift network** over the tile's lanes.  A critical element at
lane ``j`` must move left by ``s_j`` = the number of uncritical elements
before it.  Stage ``k`` (k = 0 .. log2(block) - 1, lowest bit first) moves
every element whose ``s_j`` has bit ``k`` set left by ``2**k``.  The shifts
are non-decreasing in ``j`` and targets are distinct, so no two elements
ever meet in one lane.  Running the stages in reverse order, to the right,
is the expansion used on restore.  The in-tile prefix count that gives
``s_j`` is a Hillis-Steele scan with the same lane rolls.

Blocks are ``(rows, block)`` int32 tiles (rows a multiple of 8: the f32
(8, 128) tiling).  Per-tile scalars leave a kernel as one lane-dense
``(1, rows)`` row per grid step, computed as a 0/1 matmul
``ones @ bits.T``: bf16 holds 0/1 exactly and the f32 accumulation of at
most ``block`` ones is exact.  Scalars going in (tolerance, fill, the
restore window starts) sit in SMEM.

The restore inverse (``scatter_blocks_kernel``) fuses payload→tile
scatter and tile→position expansion: the 8 tiles of one grid step read
their slices of the dense payload out of a 16-row window (prefetched via
``PrefetchScalarGridSpec``), realign each slice with a dynamic lane roll,
and expand in place.  H2D traffic on restore is just the payload + the
mask, mirroring the save direction.

``delta_blocks_kernel`` is the differential-checkpoint primitive: a
per-chunk changed flag between the current and base payload words,
computed on device so only changed chunks ever cross D2H.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 512

# Elements per bitpack grid tile (→ block/8 = 128 output lanes per tile).
BITPACK_BLOCK = 1024

# Most tiles (rows) one grid step handles; a multiple of 8.
MAX_ROWS = 128


def _grid_rows(nb: int):
    """(rows per grid step, rows the arrays are padded to) for ``nb``
    tiles.  Large arrays are not padded: the last grid step reads a
    partial block, and what it computes past ``nb`` is sliced off."""
    if nb > MAX_ROWS:
        return MAX_ROWS, nb
    rows = max(8, -(-nb // 8) * 8)
    return rows, rows


def _pad_rows(x, rows: int, value=0):
    pad = rows - x.shape[0]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                   constant_values=value)


def to_words(x: jnp.ndarray) -> jnp.ndarray:
    """Raw bits of a 2- or 4-byte dtype as int32 (16-bit zero-extended)."""
    size = np.dtype(x.dtype).itemsize
    if size == 4:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    if size == 2:
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.int32)
    raise TypeError(f"no word view for dtype {x.dtype}")


def from_words(w: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of :func:`to_words`."""
    if np.dtype(dtype).itemsize == 4:
        return jax.lax.bitcast_convert_type(w, dtype)
    return jax.lax.bitcast_convert_type(w.astype(jnp.uint16), dtype)


def _roll(x, shift: int):
    """jnp.roll along lanes by a static non-negative shift."""
    shift %= x.shape[1]
    return pltpu.roll(x, shift, 1) if shift else x


def _row_counts(bits):
    """(rows, n) 0/1 → (1, rows) f32 row sums, lane-dense (exact)."""
    ones = jnp.ones((8, bits.shape[1]), jnp.bfloat16)
    c = jax.lax.dot_general(ones, bits.astype(jnp.bfloat16),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return c[0:1, :]


def _inclusive_count(m, lane):
    """Per-row inclusive prefix sum of a 0/1 int32 tile (Hillis-Steele)."""
    c = m
    sh = 1
    while sh < m.shape[1]:
        c = c + jnp.where(lane >= sh, _roll(c, sh), 0)
        sh *= 2
    return c


def _compact(val, s, ok, lane):
    """Move each ``ok`` lane j left by ``s[j]`` (lowest bit first)."""
    n = val.shape[1]
    bit = 1
    while bit < n:
        in_val, in_s, in_ok = (_roll(a, n - bit) for a in (val, s, ok))
        move = (in_ok > 0) & ((in_s & bit) != 0) & (lane < n - bit)
        stay = (ok > 0) & ((s & bit) == 0)
        val = jnp.where(move, in_val, jnp.where(stay, val, 0))
        s = jnp.where(move, in_s, jnp.where(stay, s, 0))
        ok = (move | stay).astype(jnp.int32)
        bit *= 2
    return val, s, ok


def _expand(val, m, lane):
    """Inverse of the pack network: packed slot i of each row goes to the
    lane of that row's i-th set bit of ``m``; other lanes hold 0."""
    n = val.shape[1]
    c = _inclusive_count(m, lane)
    s = jnp.where(m > 0, lane - c + 1, 0)
    _, s, ok = _compact(s, s, m, lane)          # shift of each packed slot
    bit = n // 2
    while bit >= 1:
        in_val, in_s, in_ok = (_roll(a, bit) for a in (val, s, ok))
        move = (in_ok > 0) & ((in_s & bit) != 0) & (lane >= bit)
        stay = (ok > 0) & ((s & bit) == 0)
        val = jnp.where(move, in_val, jnp.where(stay, val, 0))
        s = jnp.where(move, in_s, jnp.where(stay, s, 0))
        ok = (move | stay).astype(jnp.int32)
        bit //= 2
    return val


def _for_each_octet(rows: int, body):
    """Run ``body(r0)`` over the 8-row slices of a ``rows``-row block."""
    def step(j, carry):
        body(pl.multiple_of(j * 8, 8))
        return carry
    jax.lax.fori_loop(0, rows // 8, step, 0)


def _pack_kernel(v_ref, m_ref, out_ref, cnt_ref, *, rows: int):
    def body(r0):
        m = m_ref[pl.ds(r0, 8), :]
        lane = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
        s = lane - _inclusive_count(m, lane) + m
        val = jnp.where(m > 0, v_ref[pl.ds(r0, 8), :], 0)
        packed, _, _ = _compact(val, jnp.where(m > 0, s, 0), m, lane)
        out_ref[pl.ds(r0, 8), :] = packed
    _for_each_octet(rows, body)
    cnt_ref[0] = _row_counts(m_ref[...]).astype(jnp.int32)


def pack_blocks_kernel(flat: jnp.ndarray, mask_i8: jnp.ndarray,
                       block: int = BLOCK, interpret: bool = False):
    """flat: (N,) 2- or 4-byte dtype; mask_i8: (N,) int8; N % block == 0.
    Returns (packed (N//block, block) in flat.dtype, counts (N//block,) i32):
    tile i's critical elements first, in order, then zeros."""
    nb = flat.shape[0] // block
    rows, nb_pad = _grid_rows(nb)
    vb = _pad_rows(to_words(flat).reshape(nb, block), nb_pad)
    mb = _pad_rows(mask_i8.reshape(nb, block).astype(jnp.int32), nb_pad)
    packed, counts = pl.pallas_call(
        functools.partial(_pack_kernel, rows=rows),
        name="mask_pack_pack",
        grid=(pl.cdiv(nb_pad, rows),),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                  pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                   pl.BlockSpec((1, 1, rows), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb_pad, block), jnp.int32),
                   jax.ShapeDtypeStruct((pl.cdiv(nb_pad, rows), 1, rows),
                                        jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(vb, mb)
    return from_words(packed[:nb], flat.dtype), counts.reshape(-1)[:nb]


def _unpack_kernel(fill_ref, pk_ref, m_ref, out_ref, *, rows: int):
    def body(r0):
        m = m_ref[pl.ds(r0, 8), :]
        lane = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
        val = _expand(pk_ref[pl.ds(r0, 8), :], m, lane)
        out_ref[pl.ds(r0, 8), :] = jnp.where(m > 0, val, fill_ref[0])
    _for_each_octet(rows, body)


def _fill_word(fill, dtype) -> jnp.ndarray:
    return to_words(jnp.asarray(fill, dtype).reshape(1))


def unpack_blocks_kernel(packed: jnp.ndarray, mask_i8: jnp.ndarray,
                         fill: float = 0.0, interpret: bool = False):
    """packed: (nb, block); mask_i8: (nb*block,).  Returns (nb*block,)."""
    nb, block = packed.shape
    rows, nb_pad = _grid_rows(nb)
    pk = _pad_rows(to_words(packed), nb_pad)
    mb = _pad_rows(mask_i8.reshape(nb, block).astype(jnp.int32), nb_pad)
    out = pl.pallas_call(
        functools.partial(_unpack_kernel, rows=rows),
        name="mask_pack_unpack",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(nb_pad, rows),),
            in_specs=[pl.BlockSpec((rows, block), lambda i, f: (i, 0)),
                      pl.BlockSpec((rows, block), lambda i, f: (i, 0))],
            out_specs=pl.BlockSpec((rows, block), lambda i, f: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((nb_pad, block), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(_fill_word(fill, packed.dtype), pk, mb)
    return from_words(out[:nb], packed.dtype).reshape(-1)


def _scatter_kernel(starts_ref, fill_ref, w0_ref, w1_ref, m_ref, out_ref, *,
                    block: int):
    """Fused restore of 8 tiles: payload window + mask → restored lanes.

    ``w0/w1`` are payload rows [8*b0, 8*b0 + 16) with
    ``b0 = starts[8g] // block // 8``; every tile of the step finds its
    slice [starts[t], starts[t] + count) inside them.  Each slice is
    rolled to lane 0 (two rows, one dynamic lane roll), then the 8 packed
    rows expand as in :func:`_unpack_kernel`.
    """
    g = pl.program_id(0)
    win = jnp.concatenate([w0_ref[...], w1_ref[...]], axis=0)    # (16, B)
    sub = jax.lax.broadcasted_iota(jnp.int32, win.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    base = (starts_ref[g * 8] // block // 8) * 8 * block
    packed = jnp.zeros((8, block), jnp.int32)
    rid = jax.lax.broadcasted_iota(jnp.int32, packed.shape, 0)
    for r in range(8):
        rel = starts_ref[g * 8 + r] - base
        q = rel // block
        off = rel - q * block
        lo = jnp.sum(jnp.where(sub == q, win, 0), axis=0, keepdims=True)
        hi = jnp.sum(jnp.where(sub == q + 1, win, 0), axis=0, keepdims=True)
        shift = (block - off) % block
        lo = pltpu.roll(lo, shift, 1)
        hi = pltpu.roll(hi, shift, 1)
        row = jnp.where(lane < block - off, lo, hi)
        packed = jnp.where(rid == r, row, packed)
    m = m_ref[...]
    val = _expand(packed, m, jax.lax.broadcasted_iota(jnp.int32, m.shape, 1))
    out_ref[...] = jnp.where(m > 0, val, fill_ref[0])


def scatter_blocks_kernel(payload_pad: jnp.ndarray, starts: jnp.ndarray,
                          mask_i8: jnp.ndarray, fill=0.0,
                          block: int = BLOCK, interpret: bool = False):
    """Fused inverse of :func:`pack_blocks_kernel` + payload gather.

    payload_pad: (npb, block) dense critical payload, padded so every
    two-block window starting at ``starts[i] // block`` is in bounds
    (``npb >= max(starts) // block + 2``); starts: (nb,) int32 payload
    offset of each tile's slice; mask_i8: (nb*block,).
    Returns the (nb*block,) restored flat array.
    """
    nb = mask_i8.shape[0] // block
    nb_pad = -(-nb // 8) * 8
    npb = payload_pad.shape[0]
    pp = _pad_rows(to_words(payload_pad), (-(-npb // 8) + 1) * 8)
    mb = _pad_rows(mask_i8.reshape(nb, block).astype(jnp.int32), nb_pad)
    st = starts.astype(jnp.int32)
    st = jnp.pad(st, (0, nb_pad - nb), mode="edge") if nb_pad > nb else st

    def window(k):
        return lambda i, s, f: (s[i * 8] // block // 8 + k, 0)

    out = pl.pallas_call(
        functools.partial(_scatter_kernel, block=block),
        name="mask_pack_scatter",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb_pad // 8,),
            in_specs=[pl.BlockSpec((8, block), window(0)),
                      pl.BlockSpec((8, block), window(1)),
                      pl.BlockSpec((8, block), lambda i, s, f: (i, 0))],
            out_specs=pl.BlockSpec((8, block), lambda i, s, f: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((nb_pad, block), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(st, _fill_word(fill, payload_pad.dtype), pp, pp, mb)
    return from_words(out[:nb], payload_pad.dtype).reshape(-1)


def _bitpack_kernel(tol_ref, m_ref, w_ref, c_ref, *, block: int):
    """Threshold + bit-pack ``rows`` tiles of |grad| magnitudes.

    The pack is a 0/1-weighted matmul on the MXU: ``W[j, k] = 2^(7 - j%8)``
    iff ``j // 8 == k``, so ``bits @ W`` yields one byte value per 8
    elements in np.packbits (big-endian) bit order.  Bits and powers of two
    ≤ 128 are exact in bf16, and byte sums ≤ 255 in the f32 accumulator.
    """
    bits = (m_ref[...] > tol_ref[0]).astype(jnp.bfloat16)
    j = jax.lax.broadcasted_iota(jnp.int32, (block, block // 8), 0)
    k = jax.lax.broadcasted_iota(jnp.int32, (block, block // 8), 1)
    weight = jnp.where(j // 8 == k, jnp.int32(1) << (7 - j % 8), 0)
    words = jax.lax.dot_general(bits, weight.astype(jnp.bfloat16),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    w_ref[...] = words.astype(jnp.int32)
    c_ref[0] = _row_counts(bits).astype(jnp.int32)


def bitpack_blocks_kernel(mag: jnp.ndarray, tol,
                          block: int = BITPACK_BLOCK,
                          interpret: bool = False):
    """mag: (N,) float32, N % block == 0.  Returns
    (words (N//block, block//8) uint8 in np.packbits bit order,
    counts (N//block,) int32 per-tile critical counts)."""
    nb = mag.shape[0] // block
    rows, nb_pad = _grid_rows(nb)
    mb = _pad_rows(mag.reshape(nb, block), nb_pad, value=-jnp.inf)
    words, counts = pl.pallas_call(
        functools.partial(_bitpack_kernel, block=block),
        name="mask_pack_bitpack",
        grid=(pl.cdiv(nb_pad, rows),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, block // 8), lambda i: (i, 0)),
                   pl.BlockSpec((1, 1, rows), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb_pad, block // 8), jnp.int32),
                   jax.ShapeDtypeStruct((pl.cdiv(nb_pad, rows), 1, rows),
                                        jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.full((1,), tol, mag.dtype), mb)
    return words[:nb].astype(jnp.uint8), counts.reshape(-1)[:nb]


def _delta_kernel(c_ref, b_ref, out_ref):
    neq = (c_ref[...] != b_ref[...]).astype(jnp.int32)
    out_ref[0] = (_row_counts(neq) > 0).astype(jnp.int32)


def delta_blocks_kernel(curr: jnp.ndarray, base: jnp.ndarray,
                        chunk: int, interpret: bool = False):
    """Per-chunk changed flags: curr/base (N,) int32 words, N % chunk == 0.
    Returns (N // chunk,) int32 (1 = any word differs)."""
    nc = curr.shape[0] // chunk
    rows, nc_pad = _grid_rows(nc)
    flags = pl.pallas_call(
        _delta_kernel,
        name="mask_pack_delta",
        grid=(pl.cdiv(nc_pad, rows),),
        in_specs=[pl.BlockSpec((rows, chunk), lambda i: (i, 0)),
                  pl.BlockSpec((rows, chunk), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 1, rows), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((pl.cdiv(nc_pad, rows), 1, rows),
                                       jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(_pad_rows(curr.reshape(nc, chunk), nc_pad),
      _pad_rows(base.reshape(nc, chunk), nc_pad))
    return flags.reshape(-1)[:nc]
