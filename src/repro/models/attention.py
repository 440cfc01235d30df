"""Attention: GQA/MQA (+ sliding window, logit softcap, M-RoPE) and
DeepSeek-style MLA with a compressed-latent KV cache for decode.

Three execution paths for a whole sequence (``_dispatch_attend``):
- ``flash``: kernels/flash_attention, Pallas TPU kernels for the forward
  and the backward, so no Tq x Tk tensor reaches HBM.  ``auto`` takes it
  on a lone TPU chip wherever it serves the call: Tq == Tk, at least one
  kernel block long, head dims multiples of 64, positions not M-RoPE.
- ``full``: einsum attention over a materialized float32 bias; ``auto``
  elsewhere while Tq * Tk is at most ``_FULL_THRESHOLD`` squared.
- ``chunked``: online-softmax attention, scan over q/kv blocks, for the
  longer sequences off the kernel (keeps compile-time memory at block
  granularity).
``set_attn_impl`` forces one ("pallas" is the kernel).  Decode
(``attention_decode``, ``mla_decode``) attends with ``_attend_full``.

Caches:
- global layers: ``{"k": (B, S, K, D), "v": (B, S, K, D)}``
- local (window) layers: same layout with S = window (ring buffer)
- MLA layers: ``{"c_kv": (B, S, R), "k_pe": (B, S, Dr)}`` — the latent
  cache; decode absorbs the up-projections (the paper's W_UK/W_UV trick).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import ops as fa_ops
from repro.models.layers import (apply_mrope, apply_rope, dense_init,
                                 dtype_of, softcap)
from repro.obs import get_obs

_ATTN_IMPL = "auto"  # auto | full | chunked | pallas
_CHUNK_Q = 512
_CHUNK_KV = 512
_NEG = -2.3819763e38  # finite big-negative (bf16-safe), like flax


def set_attn_impl(impl: str) -> None:
    global _ATTN_IMPL
    assert impl in ("auto", "full", "chunked", "pallas")
    _ATTN_IMPL = impl


def get_attn_impl() -> str:
    return _ATTN_IMPL


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_attention(cfg, key) -> Dict[str, Any]:
    pdt = dtype_of(cfg.param_dtype)
    hd = cfg.resolved_head_dim
    if cfg.mla is not None:
        m = cfg.mla
        ks = jax.random.split(key, 7)
        qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
        return {
            "wq_a": dense_init(ks[0], cfg.d_model, m.q_lora_rank, pdt),
            "wq_b": dense_init(ks[1], m.q_lora_rank, cfg.n_heads * qk_dim, pdt),
            "wkv_a": dense_init(ks[2], cfg.d_model,
                                m.kv_lora_rank + m.qk_rope_head_dim, pdt),
            "wk_b": dense_init(ks[3], m.kv_lora_rank,
                               cfg.n_heads * m.qk_nope_head_dim, pdt),
            "wv_b": dense_init(ks[4], m.kv_lora_rank,
                               cfg.n_heads * m.v_head_dim, pdt),
            "wo": dense_init(ks[5], cfg.n_heads * m.v_head_dim, cfg.d_model, pdt),
        }
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, pdt),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, pdt),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, pdt),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, pdt),
    }
    if cfg.qkv_bias:
        zeros = functools.partial(jnp.zeros, dtype=pdt)
        p["bq"] = zeros((cfg.n_heads * hd,))
        p["bk"] = zeros((cfg.n_kv_heads * hd,))
        p["bv"] = zeros((cfg.n_kv_heads * hd,))
    return p


# --------------------------------------------------------------------------
# core attention maths
# --------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, window: Optional[int], causal: bool):
    """(..., Tq, Tk) additive bias from position tensors (broadcastable)."""
    ok = jnp.ones(jnp.broadcast_shapes(q_pos.shape + (1,),
                                       k_pos.shape[:-1] + (1, k_pos.shape[-1])),
                  bool)
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    if causal:
        ok &= diff >= 0
    if window is not None:
        ok &= diff < window
    return jnp.where(ok, 0.0, _NEG).astype(jnp.float32)


def _attend_full(q, k, v, bias, scale, attn_cap):
    """q: (B,Tq,H,D) k: (B,Tk,K,D) v: (B,Tk,K,Dv) bias: (B,Tq,Tk) fp32."""
    B, Tq, H, D = q.shape
    K = k.shape[2]
    G = H // K
    qf = (q.astype(jnp.float32) * scale).reshape(B, Tq, K, G, D)
    s = jnp.einsum("btkgd,bskd->bkgts", qf, k.astype(jnp.float32))
    s = softcap(s, attn_cap)
    s = s + bias[:, None, None, :, :]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Tq, H, v.shape[-1]).astype(q.dtype)


def _attend_chunked(q, k, v, q_pos, k_pos, window, causal, scale, attn_cap,
                    chunk_q=_CHUNK_Q, chunk_kv=_CHUNK_KV):
    """Online-softmax attention, O(chunk²) live memory.

    q: (B,Tq,H,D); k/v: (B,Tk,K,D); q_pos: (B,Tq); k_pos: (B,Tk).
    """
    B, Tq, H, D = q.shape
    Tk, K = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // K
    cq = min(chunk_q, Tq)
    ck = min(chunk_kv, Tk)
    nq, nk = -(-Tq // cq), -(-Tk // ck)
    pad_q, pad_k = nq * cq - Tq, nk * ck - Tk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad_k)),
                        constant_values=np.iinfo(np.int32).max)

    qs = q.reshape(B, nq, cq, K, G, D).astype(jnp.float32) * scale
    ks = k.reshape(B, nk, ck, K, D).astype(jnp.float32)
    vs = v.reshape(B, nk, ck, K, Dv).astype(jnp.float32)
    qp = q_pos.reshape(B, nq, cq)
    kp = k_pos.reshape(B, nk, ck)

    def q_block(args):
        qb, qpb = args  # (B,cq,K,G,D), (B,cq)

        def kv_step(carry, blk):
            m, l, acc = carry
            kb, vb, kpb = blk  # (B,ck,K,D), (B,ck,K,D), (B,ck)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb)
            s = softcap(s, attn_cap)
            s = s + _mask_bias(qpb, kpb, window, causal)[:, None, None]
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p, vb)
            return (m_new, l, acc), None

        m0 = jnp.full((B, K, G, cq), _NEG, jnp.float32)
        l0 = jnp.zeros((B, K, G, cq), jnp.float32)
        a0 = jnp.zeros((B, K, G, cq, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (ks.swapaxes(0, 1), vs.swapaxes(0, 1), kp.swapaxes(0, 1)))
        o = acc / jnp.maximum(l, 1e-37)[..., None]   # (B,K,G,cq,D)
        return o.transpose(0, 3, 1, 2, 4)            # (B,cq,K,G,D)

    outs = jax.lax.map(q_block, (qs.swapaxes(0, 1), qp.swapaxes(0, 1)))
    o = outs.swapaxes(0, 1).reshape(B, nq * cq, H, Dv)
    return o[:, :Tq].astype(q.dtype)


def _flash_serves(q, k, v, mrope: bool) -> bool:
    """The kernel serves this call: self-attention shaped, at least one
    block long, MXU-sized head dims, 2-D positions."""
    Tq, Tk = q.shape[1], k.shape[1]
    return (Tq == Tk and Tq >= fa_ops.BLOCK and q.shape[-1] % 64 == 0
            and v.shape[-1] % 64 == 0 and not mrope)


def _on_one_tpu() -> bool:
    """One TPU chip: the kernel is not partitioned, so a program that
    spans chips keeps the einsum paths (XLA refuses to split it)."""
    return jax.default_backend() == "tpu" and jax.device_count() == 1


def _attend_impl(q, k, v, mrope: bool) -> str:
    impl = _ATTN_IMPL
    if impl == "pallas" or (impl == "auto" and _on_one_tpu()
                            and _flash_serves(q, k, v, mrope)):
        return "flash"
    # Perf iteration 1 (EXPERIMENTS.md §Perf/phi4): the "full" path
    # materializes a (B,Tq,Tk) fp32 bias whose partial computation over the
    # model axis costs a ~Tq·Tk·4B all-reduce per layer per pass; blockwise
    # iota masks in the chunked path eliminate it.  Threshold 2048² keeps
    # einsum attention only where the bias is genuinely small.
    thr = _FULL_THRESHOLD
    Tq, Tk = q.shape[1], k.shape[1]
    if impl == "full" or (impl == "auto" and Tq * Tk <= thr * thr):
        return "full"
    return "chunked"


def _dispatch_attend(q, k, v, q_pos, k_pos, window, causal, scale, attn_cap):
    """Whole-sequence attention; positions (B, T), or (B, T, 3) under
    M-RoPE, where the first (temporal) component masks."""
    mrope = q_pos.ndim == 3
    if mrope:
        q_pos, k_pos = q_pos[..., 0], k_pos[..., 0]
    impl = _attend_impl(q, k, v, mrope)
    get_obs().registry.counter(f"attention.impl.{impl}").inc()
    if impl == "flash":
        return fa_ops.flash_attention(q, k, v, q_pos, k_pos, window=window,
                                      causal=causal, scale=scale,
                                      attn_cap=attn_cap)
    if impl == "full":
        bias = _mask_bias(q_pos, k_pos, window, causal)
        return _attend_full(q, k, v, bias, scale, attn_cap)
    return _attend_chunked(q, k, v, q_pos, k_pos, window, causal, scale, attn_cap)


_FULL_THRESHOLD = 2048  # baseline used 4096 (materialized (B,T,T) bias)


def set_full_attention_threshold(t: int) -> None:
    global _FULL_THRESHOLD
    _FULL_THRESHOLD = t


# --------------------------------------------------------------------------
# GQA layer entry points
# --------------------------------------------------------------------------

def _project_qkv(cfg, p, x, positions):
    dt = x.dtype
    B, T, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(B, T, cfg.n_heads, hd)
    k = k.reshape(B, T, cfg.n_kv_heads, hd)
    v = v.reshape(B, T, cfg.n_kv_heads, hd)
    if cfg.mrope:
        pos3 = positions if positions.ndim == 3 else jnp.repeat(positions[..., None], 3, -1)
        q = apply_mrope(q, pos3, cfg.rope_theta)
        k = apply_mrope(k, pos3, cfg.rope_theta)
    else:
        pos = positions[..., 0] if positions.ndim == 3 else positions
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    return q, k, v


def attention_train(cfg, p, x, positions, *, window=None, causal=True):
    """Full-sequence self-attention (training / prefill without cache)."""
    q, k, v = _project_qkv(cfg, p, x, positions)
    scale = cfg.resolved_head_dim ** -0.5
    o = _dispatch_attend(q, k, v, positions, positions, window, causal, scale,
                         cfg.attn_softcap)
    B, T = x.shape[:2]
    return o.reshape(B, T, -1) @ p["wo"].astype(x.dtype)


def init_cache(cfg, batch: int, max_len: int, *, window=None, dtype=None):
    dt = dtype or dtype_of(cfg.dtype)
    hd = cfg.resolved_head_dim
    S = min(window, max_len) if window else max_len
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dt),
            "k_pe": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dt),
        }
    return {
        "k": jnp.zeros((batch, S, cfg.n_kv_heads, hd), dt),
        "v": jnp.zeros((batch, S, cfg.n_kv_heads, hd), dt),
    }


def attention_decode(cfg, p, x, cache, pos, *, window=None):
    """One-token decode against a (possibly ring-buffered) cache.

    x: (B, 1, d); pos: scalar int32 — current position (same across batch,
    standard batched-decode contract).  Returns (out, new_cache).
    """
    B = x.shape[0]
    positions = jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32)
    q, k, v = _project_qkv(cfg, p, x, positions)
    S = cache["k"].shape[1]
    slot = jnp.asarray((pos % S) if window else pos, jnp.int32)
    z = jnp.zeros((), jnp.int32)  # literal starts typed to match slot (x64)
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (z, slot, z, z))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (z, slot, z, z))
    if window:
        # ring buffer: absolute position of slot s given write head at pos.
        # Slots not yet written (pos < S) resolve to negative positions —
        # mask them or they'd attend to zero vectors.
        idx = jnp.arange(S)
        k_pos = pos - ((slot - idx) % S)
        k_pos = jnp.where(k_pos >= 0, k_pos, np.iinfo(np.int32).max)
    else:
        idx = jnp.arange(S)
        k_pos = jnp.where(idx <= pos, idx, np.iinfo(np.int32).max)
    k_pos = jnp.broadcast_to(k_pos[None, :], (B, S)).astype(jnp.int32)
    scale = cfg.resolved_head_dim ** -0.5
    o = _attend_full(q, ck, cv,
                     _mask_bias(positions, k_pos, window, True),
                     scale, cfg.attn_softcap)
    out = o.reshape(B, 1, -1) @ p["wo"].astype(x.dtype)
    return out, {"k": ck, "v": cv}


# --------------------------------------------------------------------------
# MLA (DeepSeek-V3)
# --------------------------------------------------------------------------

def mla_train(cfg, p, x, positions):
    m = cfg.mla
    dt = x.dtype
    B, T, _ = x.shape
    H = cfg.n_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim

    q = (x @ p["wq_a"].astype(dt)) @ p["wq_b"].astype(dt)
    q = q.reshape(B, T, H, qk_dim)
    q_nope, q_pe = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]

    kv_a = x @ p["wkv_a"].astype(dt)
    c_kv, k_pe = kv_a[..., :m.kv_lora_rank], kv_a[..., m.kv_lora_rank:]
    k_nope = (c_kv @ p["wk_b"].astype(dt)).reshape(B, T, H, m.qk_nope_head_dim)
    v = (c_kv @ p["wv_b"].astype(dt)).reshape(B, T, H, m.v_head_dim)

    pos = positions[..., 0] if positions.ndim == 3 else positions
    q_pe = apply_rope(q_pe, pos, cfg.rope_theta)
    k_pe = apply_rope(k_pe[:, :, None, :], pos, cfg.rope_theta)  # shared head

    scale = qk_dim ** -0.5
    q_full = jnp.concatenate([q_nope, q_pe], -1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (B, T, H, m.qk_rope_head_dim))], -1)
    o = _dispatch_attend(q_full, k_full, v, pos, pos, None, True, scale, None)
    return o.reshape(B, T, H * m.v_head_dim) @ p["wo"].astype(dt)


def mla_decode(cfg, p, x, cache, pos):
    """Latent-cache decode: scores in the compressed space (absorbed W_UK);
    the cache stores (c_kv, k_pe) — (R + Dr) per token instead of
    2·H·head_dim.  This is the serving-side win scrutinized checkpoints
    inherit (cache suffix beyond ``pos`` is provably uncritical)."""
    m = cfg.mla
    dt = x.dtype
    B = x.shape[0]
    H = cfg.n_heads
    positions = jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32)

    q = (x @ p["wq_a"].astype(dt)) @ p["wq_b"].astype(dt)
    q = q.reshape(B, 1, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_pe = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)

    kv_a = x @ p["wkv_a"].astype(dt)
    c_new, kpe_new = kv_a[..., :m.kv_lora_rank], kv_a[..., m.kv_lora_rank:]
    kpe_new = apply_rope(kpe_new[:, :, None, :], positions,
                         cfg.rope_theta)[:, :, 0, :]
    z = jnp.zeros((), jnp.int32)
    pos32 = jnp.asarray(pos, jnp.int32)
    c_kv = jax.lax.dynamic_update_slice(cache["c_kv"],
                                        c_new.astype(cache["c_kv"].dtype),
                                        (z, pos32, z))
    k_pe = jax.lax.dynamic_update_slice(cache["k_pe"],
                                        kpe_new.astype(cache["k_pe"].dtype),
                                        (z, pos32, z))

    # absorb W_UK into the query: (B,1,H,R)
    wk_b = p["wk_b"].astype(dt).reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, wk_b)

    S = c_kv.shape[1]
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    s = (jnp.einsum("bthr,bsr->bhts", q_lat.astype(jnp.float32),
                    c_kv.astype(jnp.float32))
         + jnp.einsum("bthd,bsd->bhts", q_pe.astype(jnp.float32),
                      k_pe.astype(jnp.float32))) * scale
    idx = jnp.arange(S)
    mask = jnp.where(idx <= pos, 0.0, _NEG)[None, None, None, :]
    prob = jax.nn.softmax(s + mask, axis=-1)
    o_lat = jnp.einsum("bhts,bsr->bthr", prob,
                       c_kv.astype(jnp.float32))          # (B,1,H,R)
    wv_b = p["wv_b"].astype(dt).reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bthr,rhd->bthd", o_lat.astype(dt), wv_b)
    out = o.reshape(B, 1, H * m.v_head_dim) @ p["wo"].astype(dt)
    return out, {"c_kv": c_kv, "k_pe": k_pe}
