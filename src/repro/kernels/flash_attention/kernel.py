"""Pallas TPU flash attention with its own backward (GQA + sliding window +
logit softcap, masks from the positions given).

Layout (the caller transposes in XLA): q ``(B, K, G, Tq, D)``, k
``(B, K, Tk, D)``, v ``(B, K, Tk, Dv)`` for K key/value heads of G query
heads each.  One grid step takes the G query heads of one KV head, so one
K/V block feeds all of them, and D is a whole last dim, so every block
keeps the (8, 128) tiling rule.  q comes in already multiplied by the
softmax scale.

- ``flash_attention_fwd``: grid (B, K, q blocks, k blocks), the k axis
  innermost and sequential; online softmax with the running max, the
  denominator and the accumulator in float32 VMEM scratch.  Returns o and
  each row's log-sum-exp ``(B, K, G, Tq)`` float32.
- ``flash_attention_bwd_dkv``: grid over k blocks, q blocks innermost;
  dK and dV accumulate in scratch.  Works on the transposed scores
  ``(bk, bq)``, so the rows' log-sum-exp and ``delta = rowsum(dO * O)``
  (computed in XLA) broadcast as lane-dense rows.
- ``flash_attention_bwd_dq``: grid over q blocks, k blocks innermost.
- Both backward kernels recompute p from q, k and the log-sum-exp.

Operands reach the MXU in their own dtype (bfloat16 on the model's path)
with float32 accumulation; p is rounded to v's dtype for the PV product
and ds to k's (q's) for the dQ (dK) product.

Masks: causal ``q_pos - k_pos >= 0`` and window ``q_pos - k_pos < window``,
from the positions given, and the keys at index ``tk`` and past, which pad
the last k block.  The smallest and largest position of each block are
scalar-prefetched.  A (q block, k block) pair that those bounds prove fully
masked is skipped, and its K/V (Q) block index is clamped to one that is
needed, so that no DMA is issued for it; a pair they prove fully visible
skips the mask.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -2.3819763e38
_TINY = 1e-37
_LANES = 128
_BIG = np.iinfo(np.int32).max
_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_NN = (((1,), (0,)), ((), ()))    # a @ b
_VMEM_LIMIT = 64 * 1024 * 1024
F32 = jnp.float32


class Spec(NamedTuple):
    """What a call masks and how it tiles (hashable: a static argument)."""
    causal: bool
    window: Optional[int]
    attn_cap: Optional[float]
    block_q: int
    block_k: int
    tk: int                 # real keys; the rest pad the last k block
    interpret: bool = False

    def ragged(self, tk_padded: int) -> bool:
        return self.tk < tk_padded


# --------------------------------------------------------------------------
# masks and block bounds (shared by the kernels, XLA and the reference)
# --------------------------------------------------------------------------

def _any(conds):
    return functools.reduce(jnp.logical_or, conds) if conds else None


def _all(conds):
    return functools.reduce(jnp.logical_and, conds) if conds else None


def dead_pairs(spec: Spec, qlo, qhi, klo, khi):
    """True where the bounds prove every (q, k) pair masked; None if no
    pair can be."""
    conds = []
    if spec.causal:
        conds.append(qhi < klo)
    if spec.window is not None:
        conds.append(qlo - khi >= spec.window)
    return _any(conds)


def _open_pairs(spec: Spec, qlo, qhi, klo, khi):
    """True where the bounds prove every (q, k) pair visible; None if all
    pairs always are (positions alone)."""
    conds = []
    if spec.causal:
        conds.append(qlo >= khi)
    if spec.window is not None:
        conds.append(qhi - klo < spec.window)
    return _all(conds)


def visible(spec: Spec, q_pos, k_pos, k_idx):
    """Boolean mask, or None when nothing is masked; ``q_pos`` and
    ``k_pos``/``k_idx`` broadcast against each other."""
    diff = q_pos - k_pos
    conds = []
    if spec.causal:
        conds.append(diff >= 0)
    if spec.window is not None:
        conds.append(diff < spec.window)
    if k_idx is not None:
        conds.append(k_idx < spec.tk)
    return _all(conds)


def block_bounds(pos, block: int, valid: int):
    """Per-block (min, max) of ``pos`` (B, T) over its first ``valid``
    entries, each (B, T // block) int32."""
    B, T = pos.shape
    shape = (B, T // block, block)
    idx = (jax.lax.broadcasted_iota(jnp.int32, shape, 1) * block
           + jax.lax.broadcasted_iota(jnp.int32, shape, 2))
    ok = idx < valid
    p = pos.reshape(shape)
    big = jnp.full(shape, _BIG, jnp.int32)
    return (jnp.min(jax.lax.select(ok, p, big), -1),
            jnp.max(jax.lax.select(ok, p, -big), -1))


def _needed_span(spec: Spec, lo_a, hi_a, lo_b, hi_b, a_is_q: bool):
    """For each block of side a, the first and last block of side b that
    is not dead, flattened over (B, n_a) int32 (0, n_b - 1 if none)."""
    if a_is_q:
        dead = dead_pairs(spec, lo_a[:, :, None], hi_a[:, :, None],
                          lo_b[:, None, :], hi_b[:, None, :])
    else:
        dead = dead_pairs(spec, lo_b[:, None, :], hi_b[:, None, :],
                          lo_a[:, :, None], hi_a[:, :, None])
    n_b = lo_b.shape[1]
    shape = lo_a.shape + (n_b,)
    need = (jnp.ones(shape, bool) if dead is None
            else jnp.logical_not(jnp.broadcast_to(dead, shape)))
    first = jnp.argmax(need, -1)
    last = n_b - 1 - jnp.argmax(need[..., ::-1], -1)
    return (first.reshape(-1).astype(jnp.int32),
            last.reshape(-1).astype(jnp.int32))


def _clamp(idx, lo, hi):
    return jnp.minimum(jnp.maximum(idx, lo), hi)


def _run_pair(spec: Spec, step, qlo, qhi, klo, khi, ragged_block):
    """Run ``step(masked)`` for one (q block, k block) pair: not at all if
    the bounds prove it dead, without the mask if they prove it open."""
    dead = dead_pairs(spec, qlo, qhi, klo, khi)
    opened = _open_pairs(spec, qlo, qhi, klo, khi)
    if ragged_block is not None:
        ragged = jnp.logical_not(ragged_block)
        opened = ragged if opened is None else jnp.logical_and(opened, ragged)
    run = None if dead is None else jnp.logical_not(dead)
    if opened is None:
        if run is None:
            step(False)
        else:
            pl.when(run)(lambda: step(False))
        return
    plain = opened if run is None else jnp.logical_and(run, opened)
    masked = jnp.logical_not(opened)
    masked = masked if run is None else jnp.logical_and(run, masked)
    pl.when(plain)(lambda: step(False))
    pl.when(masked)(lambda: step(True))


def _softcap(spec: Spec, s):
    """(capped scores, tanh) — the tanh feeds the backward's derivative."""
    if spec.attn_cap is None:
        return s, None
    t = jnp.tanh(s * (1.0 / spec.attn_cap))
    return spec.attn_cap * t, t


def _column(row):
    """(1, n) lane-dense row -> (n, 1) column, via a (128, n) transpose."""
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, row.shape[1])))[:, :1]


def _prefetch(spec: Spec, q_pos, k_pos, q_major: bool):
    """The scalar-prefetched bounds: each q and k block's (min, max)
    position, and for each block of the grid's outer side the span of
    blocks of the inner side that is not dead."""
    qlo, qhi = block_bounds(q_pos, spec.block_q, q_pos.shape[1])
    klo, khi = block_bounds(k_pos, spec.block_k, spec.tk)
    if q_major:
        first, last = _needed_span(spec, qlo, qhi, klo, khi, True)
    else:
        first, last = _needed_span(spec, klo, khi, qlo, qhi, False)
    return (qlo.reshape(-1), qhi.reshape(-1), klo.reshape(-1),
            khi.reshape(-1), first, last)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(qlo, qhi, klo, khi, first, last,
                q_ref, k_ref, v_ref, qc_ref, kr_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, *, spec, nq, nk,
                ragged):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    _, _, G, bq, D = q_ref.shape
    bk = k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        q = q_ref[0, 0].reshape(G * bq, D)
        s = jax.lax.dot_general(q, k_ref[0, 0], _NT,
                                preferred_element_type=F32)   # (G*bq, bk)
        s, _ = _softcap(spec, s)
        if masked:
            k_idx = (j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
                     if ragged else None)
            ok = visible(spec, qc_ref[0][:, :1], kr_ref[0], k_idx)
            s = jnp.where(ok[None], s.reshape(G, bq, bk), _NEG)
            s = s.reshape(G * bq, bk)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + p.sum(axis=1, keepdims=True)
        v = v_ref[0, 0]
        acc_scr[...] = corr * acc_scr[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=F32)
        m_scr[...] = m_new

    qb, kb = b * nq + i, b * nk + j
    _run_pair(spec, step, qlo[qb], qhi[qb], klo[kb], khi[kb],
              (j == nk - 1) if ragged else None)

    @pl.when(j == nk - 1)
    def _done():
        l = jnp.maximum(l_scr[...], _TINY)
        o = acc_scr[...] / l
        o_ref[0, 0] = o.reshape(G, bq, o.shape[-1]).astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(l)                      # (G*bq, 1)
        for g in range(G):
            row = jnp.transpose(jnp.broadcast_to(
                lse[g * bq:(g + 1) * bq], (bq, _LANES)))[:1]
            lse_ref[0, 0, g:g + 1, :] = row


def flash_attention_fwd(q, k, v, q_pos, k_pos, spec: Spec):
    """(o (B,K,G,Tq,Dv) in q's dtype, lse (B,K,G,Tq) float32); ``q_pos``
    (B,Tq), ``k_pos`` (B,Tk) int32; Tq, Tk multiples of the blocks."""
    B, K, G, Tq, D = q.shape
    Tk, Dv = k.shape[2], v.shape[-1]
    bq, bk = spec.block_q, spec.block_k
    nq, nk = Tq // bq, Tk // bk
    pre = _prefetch(spec, q_pos, k_pos, q_major=True)

    def kv_map(b, h, i, j, *p):
        return (b, h, _clamp(j, p[4][b * nq + i], p[5][b * nq + i]), 0)

    def kr_map(b, h, i, j, *p):
        return (b, 0, kv_map(b, h, i, j, *p)[2])

    kern = functools.partial(_fwd_kernel, spec=spec, nq=nq, nk=nk,
                             ragged=spec.ragged(Tk))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(pre),
        grid=(B, K, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, bq, D), lambda b, h, i, j, *_: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, Dv), kv_map),
            pl.BlockSpec((1, bq, _LANES), lambda b, h, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, 1, bk), kr_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, bq, Dv), lambda b, h, i, j, *_: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, G, bq), lambda b, h, i, j, *_: (b, h, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G * bq, 1), F32),     # running max
            pltpu.VMEM((G * bq, 1), F32),     # running denominator
            pltpu.VMEM((G * bq, Dv), F32),    # output accumulator
        ])
    return pl.pallas_call(
        kern,
        name="flash_attention_fwd",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, K, G, Tq, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, K, G, Tq), F32)],
        compiler_params=_params(),
        interpret=spec.interpret,
    )(*pre, q, k, v, _cols(q_pos), k_pos[:, None, :])


def _cols(pos):
    """(B, T) -> (B, T, 128): positions as lane-broadcast columns."""
    return jnp.broadcast_to(pos[:, :, None], pos.shape + (_LANES,))


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _dkv_kernel(qlo, qhi, klo, khi, first, last,
                q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, qr_ref, kc_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, spec, nq, nk, ragged):
    b, j, i = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    G = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked):
        k, v = k_ref[0, 0], v_ref[0, 0]
        ok = None
        if masked:
            k_idx = (j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
                     if ragged else None)
            ok = visible(spec, qr_ref[0], kc_ref[0][:, :1], k_idx)  # (bk, bq)
        dk, dv = dk_scr[...], dv_scr[...]
        for g in range(G):
            qg, dog = q_ref[0, 0, g], do_ref[0, 0, g]
            s, t = _softcap(spec, jax.lax.dot_general(
                k, qg, _NT, preferred_element_type=F32))        # (bk, bq)
            if ok is not None:
                s = jnp.where(ok, s, _NEG)
            p = jnp.exp(s - lse_ref[0, 0, g:g + 1, :])
            dv = dv + jax.lax.dot_general(p.astype(dog.dtype), dog, _NN,
                                          preferred_element_type=F32)
            dp = jax.lax.dot_general(v, dog, _NT, preferred_element_type=F32)
            ds = p * (dp - dl_ref[0, 0, g:g + 1, :])
            if t is not None:
                ds = ds * (1.0 - t * t)
            dk = dk + jax.lax.dot_general(ds.astype(qg.dtype), qg, _NN,
                                          preferred_element_type=F32)
        dk_scr[...] = dk
        dv_scr[...] = dv

    qb, kb = b * nq + i, b * nk + j
    _run_pair(spec, step, qlo[qb], qhi[qb], klo[kb], khi[kb],
              (j == nk - 1) if ragged else None)

    @pl.when(i == nq - 1)
    def _done():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd_dkv(q, k, v, q_pos, k_pos, lse, delta, do,
                            spec: Spec):
    """(dk, dv) for scores ``q @ k.T``; ``do`` (B,K,G,Tq,Dv), ``lse`` and
    ``delta`` (B,K,G,Tq) float32."""
    B, K, G, Tq, D = q.shape
    Tk, Dv = k.shape[2], v.shape[-1]
    bq, bk = spec.block_q, spec.block_k
    nq, nk = Tq // bq, Tk // bk
    pre = _prefetch(spec, q_pos, k_pos, q_major=False)

    def qi(b, j, i, p):
        return _clamp(i, p[4][b * nk + j], p[5][b * nk + j])

    def q_map(b, h, j, i, *p):
        return (b, h, 0, qi(b, j, i, p), 0)

    def row_map(b, h, j, i, *p):
        return (b, h, 0, qi(b, j, i, p))

    def kv_map(b, h, j, i, *_):
        return (b, h, j, 0)

    kern = functools.partial(_dkv_kernel, spec=spec, nq=nq, nk=nk,
                             ragged=spec.ragged(Tk))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(pre),
        grid=(B, K, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, G, bq, D), q_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, Dv), kv_map),
            pl.BlockSpec((1, 1, G, bq, Dv), q_map),
            pl.BlockSpec((1, 1, G, bq), row_map),
            pl.BlockSpec((1, 1, G, bq), row_map),
            pl.BlockSpec((1, 1, bq), lambda b, h, j, i, *p: (b, 0, qi(b, j, i, p))),
            pl.BlockSpec((1, bk, _LANES), lambda b, h, j, i, *_: (b, j, 0)),
        ],
        out_specs=[pl.BlockSpec((1, 1, bk, D), kv_map),
                   pl.BlockSpec((1, 1, bk, Dv), kv_map)],
        scratch_shapes=[pltpu.VMEM((bk, D), F32), pltpu.VMEM((bk, Dv), F32)])
    return pl.pallas_call(
        kern,
        name="flash_attention_bwd_dkv",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_params(),
        interpret=spec.interpret,
    )(*pre, q, k, v, do, lse, delta, q_pos[:, None, :], _cols(k_pos))


def _dq_kernel(qlo, qhi, klo, khi, first, last,
               q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, qc_ref, kr_ref,
               dq_ref, dq_scr, lse_scr, dl_scr, *, spec, nq, nk, ragged):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    _, _, G, bq, D = q_ref.shape
    bk, Dv = k_ref.shape[2], v_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        for g in range(G):
            lse_scr[g * bq:(g + 1) * bq, :] = _column(lse_ref[0, 0, g:g + 1, :])
            dl_scr[g * bq:(g + 1) * bq, :] = _column(dl_ref[0, 0, g:g + 1, :])

    def step(masked):
        q = q_ref[0, 0].reshape(G * bq, D)
        k, v = k_ref[0, 0], v_ref[0, 0]
        s, t = _softcap(spec, jax.lax.dot_general(
            q, k, _NT, preferred_element_type=F32))             # (G*bq, bk)
        if masked:
            k_idx = (j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
                     if ragged else None)
            ok = visible(spec, qc_ref[0][:, :1], kr_ref[0], k_idx)
            s = jnp.where(ok[None], s.reshape(G, bq, bk), _NEG)
            s = s.reshape(G * bq, bk)
        p = jnp.exp(s - lse_scr[...])
        do = do_ref[0, 0].reshape(G * bq, Dv)
        dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=F32)
        ds = p * (dp - dl_scr[...])
        if t is not None:
            ds = ds * (1.0 - t * t)
        dq_scr[...] += jax.lax.dot_general(ds.astype(k.dtype), k, _NN,
                                           preferred_element_type=F32)

    qb, kb = b * nq + i, b * nk + j
    _run_pair(spec, step, qlo[qb], qhi[qb], klo[kb], khi[kb],
              (j == nk - 1) if ragged else None)

    @pl.when(j == nk - 1)
    def _done():
        dq_ref[0, 0] = dq_scr[...].reshape(G, bq, D).astype(dq_ref.dtype)


def flash_attention_bwd_dq(q, k, v, q_pos, k_pos, lse, delta, do,
                           spec: Spec):
    """dq for scores ``q @ k.T`` (the layout of q)."""
    B, K, G, Tq, D = q.shape
    Tk, Dv = k.shape[2], v.shape[-1]
    bq, bk = spec.block_q, spec.block_k
    nq, nk = Tq // bq, Tk // bk
    pre = _prefetch(spec, q_pos, k_pos, q_major=True)

    def kv_map(b, h, i, j, *p):
        return (b, h, _clamp(j, p[4][b * nq + i], p[5][b * nq + i]), 0)

    def q_map(b, h, i, j, *_):
        return (b, h, 0, i, 0)

    def row_map(b, h, i, j, *_):
        return (b, h, 0, i)

    kern = functools.partial(_dq_kernel, spec=spec, nq=nq, nk=nk,
                             ragged=spec.ragged(Tk))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(pre),
        grid=(B, K, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, bq, D), q_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, Dv), kv_map),
            pl.BlockSpec((1, 1, G, bq, Dv), q_map),
            pl.BlockSpec((1, 1, G, bq), row_map),
            pl.BlockSpec((1, 1, G, bq), row_map),
            pl.BlockSpec((1, bq, _LANES), lambda b, h, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, 1, bk),
                         lambda b, h, i, j, *p: (b, 0, kv_map(b, h, i, j, *p)[2])),
        ],
        out_specs=pl.BlockSpec((1, 1, G, bq, D), q_map),
        scratch_shapes=[pltpu.VMEM((G * bq, D), F32),
                        pltpu.VMEM((G * bq, 1), F32),
                        pltpu.VMEM((G * bq, 1), F32)])
    return pl.pallas_call(
        kern,
        name="flash_attention_bwd_dq",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_params(),
        interpret=spec.interpret,
    )(*pre, q, k, v, do, lse, delta, _cols(q_pos), k_pos[:, None, :])
