"""Pallas TPU flash attention (GQA + sliding window + logit softcap).

Grid: (B, H, nQ, nK) with the KV axis innermost and *sequential*
(dimension_semantics "arbitrary") so the online-softmax state (m, l, acc)
lives in VMEM scratch across KV steps.  Block shapes are MXU-aligned
(BQ = BK = 128 rows, head_dim lanes); K/V blocks for query head h come from
KV head h // group via the BlockSpec index map — GQA never materializes
repeated KV.

The causal/window masks are computed from block-relative iota, so the
kernel serves gemma2 (local+softcap), recurrentgemma (local MQA), and the
global-attention archs with one body.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 128
DEFAULT_BK = 128
_NEG = -2.3819763e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, attn_cap, nk, bq, bk):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, :, 0, :].astype(jnp.float32) * scale      # (BQ, D)
    k = k_ref[0, :, 0, :].astype(jnp.float32)              # (BK, D)
    v = v_ref[0, :, 0, :].astype(jnp.float32)              # (BK, Dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (BQ, BK)
    if attn_cap is not None:
        s = attn_cap * jnp.tanh(s / attn_cap)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        ok &= q_pos >= k_pos
    if window is not None:
        ok &= q_pos - k_pos < window
    s = jnp.where(ok, s, _NEG)

    m_prev = m_scr[...]                                    # (BQ, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _done():
        denom = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0, :, 0, :] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, scale: float, causal: bool,
                           window: Optional[int], attn_cap: Optional[float],
                           block_q: int = DEFAULT_BQ,
                           block_k: int = DEFAULT_BK,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B,T,H,D); k/v: (B,T,K,D|Dv), T divisible by block sizes."""
    B, Tq, H, D = q.shape
    Tk, K = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // K
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    assert Tq % bq == 0 and Tk % bk == 0, (Tq, Tk, bq, bk)
    nq, nk = Tq // bq, Tk // bk

    grid = (B, H, nq, nk)
    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             window=window, attn_cap=attn_cap,
                             nk=nk, bq=bq, bk=bk)
    return pl.pallas_call(
        kern,
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, D), lambda b, h, i, j: (b, i, h, 0)),
            pl.BlockSpec((1, bk, 1, D), lambda b, h, i, j, G=G: (b, j, h // G, 0)),
            pl.BlockSpec((1, bk, 1, Dv), lambda b, h, i, j, G=G: (b, j, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, Dv), lambda b, h, i, j: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Tq, H, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom l
            pltpu.VMEM((bq, Dv), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
