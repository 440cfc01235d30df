"""Public entry for flash attention: TPU kernels, interpret mode off-TPU.

Differentiable twice.  The attention is a ``custom_vjp`` whose forward
runs ``flash_attention_fwd`` (saving o and the rows' log-sum-exp) and
whose backward runs ``flash_attention_bwd_dkv`` and ``_dq``.  Each kernel
call sits inside a ``custom_vjp`` of its own too, whose rule is the VJP of
its plain-jnp twin in ``ref``: a step that takes ``jax.grad`` through the
attention keeps ``custom_vjp_call``s, which AD can differentiate again
(reverse over reverse), and no bare ``pallas_call``, which it cannot.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.kernels.flash_attention import kernel as K
from repro.kernels.flash_attention.ref import (attention_bwd_ref,
                                               attention_lse_ref)

BLOCK = 512        # q and k block rows; shorter sequences take one block
#: names of the forward's residuals, for a remat policy that saves them
RESIDUALS = ("flash_attention_o", "flash_attention_lse")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# -- each kernel call, differentiable through its reference -----------------
#
# Their rules run only where the attention is differentiated twice.  The
# residuals go through an optimization barrier: JAX forwards residuals that
# are bare primal inputs, and inside a scan differentiated twice it mixes
# such forwarded residuals up with other values of the primal.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _forward(spec, q, k, v, q_pos, k_pos):
    return K.flash_attention_fwd(q, k, v, q_pos, k_pos, spec)


def _forward_fwd(spec, *args):
    return _forward(spec, *args), jax.lax.optimization_barrier(args)


def _forward_bwd(spec, res, cts):
    q, k, v, q_pos, k_pos = res
    _, vjp = jax.vjp(lambda q, k, v: attention_lse_ref(spec, q, k, v, q_pos,
                                                       k_pos), q, k, v)
    return vjp(cts) + (None, None)


_forward.defvjp(_forward_fwd, _forward_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _backward(spec, q, k, v, q_pos, k_pos, lse, delta, do):
    dk, dv = K.flash_attention_bwd_dkv(q, k, v, q_pos, k_pos, lse, delta, do,
                                       spec)
    dq = K.flash_attention_bwd_dq(q, k, v, q_pos, k_pos, lse, delta, do, spec)
    return dq, dk, dv


def _backward_fwd(spec, *args):
    return _backward(spec, *args), jax.lax.optimization_barrier(args)


def _backward_bwd(spec, res, cts):
    q, k, v, q_pos, k_pos, lse, delta, do = res
    _, vjp = jax.vjp(
        lambda q, k, v, lse, delta, do: attention_bwd_ref(
            spec, q, k, v, q_pos, k_pos, lse, delta, do),
        q, k, v, lse, delta, do)
    dq, dk, dv, dlse, ddelta, ddo = vjp(cts)
    return dq, dk, dv, None, None, dlse, ddelta, ddo


_backward.defvjp(_backward_fwd, _backward_bwd)


# -- the attention ----------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attention(spec, q, k, v, q_pos, k_pos):
    return _forward(spec, q, k, v, q_pos, k_pos)[0]


def _attention_fwd(spec, q, k, v, q_pos, k_pos):
    o, lse = _forward(spec, q, k, v, q_pos, k_pos)
    o = checkpoint_name(o, RESIDUALS[0])
    lse = checkpoint_name(lse, RESIDUALS[1])
    return o, (q, k, v, q_pos, k_pos, o, lse)


def _attention_bwd(spec, res, do):
    q, k, v, q_pos, k_pos, o, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dq, dk, dv = _backward(spec, q, k, v, q_pos, k_pos, lse, delta, do)
    return dq, dk, dv, None, None


_attention.defvjp(_attention_fwd, _attention_bwd)


@functools.partial(jax.jit, static_argnames=(
    "window", "causal", "scale", "attn_cap", "interpret", "block"))
def flash_attention(q, k, v, q_pos=None, k_pos=None, *,
                    window: Optional[int] = None, causal: bool = True,
                    scale: Optional[float] = None,
                    attn_cap: Optional[float] = None,
                    interpret: Optional[bool] = None, block: int = BLOCK):
    """Attention for the train/prefill contract, masked from ``q_pos``
    (B,Tq) and ``k_pos`` (B,Tk) as ``models/attention._mask_bias`` masks
    (arange when not given).

    q: (B,Tq,H,D); k: (B,Tk,K,D); v: (B,Tk,K,Dv).  Moves the G = H/K
    query heads of each KV head next to each other, pads each sequence to
    its block (at most ``block`` rows, the sequence rounded up to 128 if
    shorter), runs the kernels (interpret mode off-TPU), and undoes both.
    """
    B, Tq, H, D = q.shape
    Tk, Kh = k.shape[1], k.shape[2]
    G, Dv = H // Kh, v.shape[-1]
    if scale is None:
        scale = D ** -0.5
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(Tq), (B, Tq))
    if k_pos is None:
        k_pos = jnp.broadcast_to(jnp.arange(Tk), (B, Tk))
    bq = min(block, _round_up(Tq, 128))
    bk = min(block, _round_up(Tk, 128))
    pq, pk = _round_up(Tq, bq) - Tq, _round_up(Tk, bk) - Tk

    # the scale rounds as the einsum path's float32 product does at the
    # default matmul precision
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qs = qs.reshape(B, Tq, Kh, G, D).transpose(0, 2, 3, 1, 4)
    qs = jnp.pad(qs, ((0, 0),) * 3 + ((0, pq), (0, 0)))
    kt = jnp.pad(k.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pk), (0, 0)))
    vt = jnp.pad(v.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pk), (0, 0)))
    q_pos = jnp.pad(q_pos.astype(jnp.int32), ((0, 0), (0, pq)), mode="edge")
    k_pos = jnp.pad(k_pos.astype(jnp.int32), ((0, 0), (0, pk)))

    itp = (not _on_tpu()) if interpret is None else interpret
    spec = K.Spec(causal=causal, window=window, attn_cap=attn_cap,
                  block_q=bq, block_k=bk, tk=Tk, interpret=itp)
    o = _attention(spec, qs, kt, vt, q_pos, k_pos)
    return o[:, :, :, :Tq].transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, Dv)
