"""Pure-jnp oracles for the flash-attention kernels.

``flash_attention_ref`` is the whole attention in the model's layout.
``attention_lse_ref`` and ``attention_bwd_ref`` compute what
``flash_attention_fwd`` and the two backward kernels compute, in the
kernels' layout and with their masks; ``ops`` differentiates them to give
the kernels' own derivatives (second order: reverse over reverse).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import Spec, visible

_NEG = -2.3819763e38


def flash_attention_ref(q, k, v, q_pos=None, k_pos=None, *,
                        window: Optional[int] = None, causal: bool = True,
                        scale: Optional[float] = None,
                        attn_cap: Optional[float] = None) -> jnp.ndarray:
    """q: (B,Tq,H,D) k: (B,Tk,K,D) v: (B,Tk,K,Dv); positions (B,Tq) and
    (B,Tk), arange when not given.  Returns (B,Tq,H,Dv) in q.dtype."""
    B, Tq, H, D = q.shape
    Tk, K = k.shape[1], k.shape[2]
    G = H // K
    if scale is None:
        scale = D ** -0.5
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(Tq), (B, Tq))
    if k_pos is None:
        k_pos = jnp.broadcast_to(jnp.arange(Tk), (B, Tk))
    qf = (q.astype(jnp.float32) * scale).reshape(B, Tq, K, G, D)
    s = jnp.einsum("btkgd,bskd->bkgts", qf, k.astype(jnp.float32))
    if attn_cap is not None:
        s = attn_cap * jnp.tanh(s / attn_cap)
    diff = q_pos[:, :, None] - k_pos[:, None, :]
    ok = jnp.ones(diff.shape, bool)
    if causal:
        ok &= diff >= 0
    if window is not None:
        ok &= diff < window
    s = jnp.where(ok[:, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Tq, H, v.shape[-1]).astype(q.dtype)


def _scores(spec: Spec, q, k, q_pos, k_pos):
    """Masked, capped float32 scores (B,K,G,Tq,Tk) and the cap's tanh."""
    s = jnp.einsum("bkgtd,bksd->bkgts", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    t = None
    if spec.attn_cap is not None:
        t = jnp.tanh(s / spec.attn_cap)
        s = spec.attn_cap * t
    Tk = k.shape[2]
    ok = visible(spec, q_pos[:, :, None], k_pos[:, None, :],
                 jax.lax.broadcasted_iota(jnp.int32, (1, 1, Tk), 2))
    return jnp.where(ok[:, None, None], s, _NEG), t


def attention_lse_ref(spec: Spec, q, k, v, q_pos, k_pos):
    """(o in q's dtype, row log-sum-exp float32), kernel layout: q
    (B,K,G,Tq,D) already scaled, k (B,K,Tk,D), v (B,K,Tk,Dv)."""
    s, _ = _scores(spec, q, k, q_pos, k_pos)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bkgts,bksd->bkgtd", p, v.astype(jnp.float32))
    return o.astype(q.dtype), lse


def attention_bwd_ref(spec: Spec, q, k, v, q_pos, k_pos, lse, delta, do):
    """(dq, dk, dv) from the saved log-sum-exp and ``delta = rowsum(dO *
    O)``, as the backward kernels compute them."""
    s, t = _scores(spec, q, k, q_pos, k_pos)
    p = jnp.exp(s - lse[..., None])
    dof = do.astype(jnp.float32)
    dv = jnp.einsum("bkgts,bkgtd->bksd", p, dof)
    dp = jnp.einsum("bkgtd,bksd->bkgts", dof, v.astype(jnp.float32))
    ds = p * (dp - delta[..., None])
    if t is not None:
        ds = ds * (1.0 - t * t)
    dq = jnp.einsum("bkgts,bksd->bkgtd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bkgts,bkgtd->bksd", ds, q.astype(jnp.float32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)
