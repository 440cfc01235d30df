"""Train step: loss + grad + clip + optimizer, with optional microbatch
accumulation and gradient compression (top-k error feedback / int8) for
bandwidth-constrained DP meshes."""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import loss_fn
from repro.obs import scopes
from repro.train.optim import OptConfig, apply_opt, clip_by_global_norm, init_opt


def make_train_step(cfg, oc: OptConfig = OptConfig(), *,
                    microbatch: Optional[int] = None):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics).  ``microbatch``: split the global batch into N accumulation
    chunks (activation memory / pipeline-style overlap knob)."""

    def grads_of(params, batch):
        return jax.value_and_grad(lambda p: loss_fn(cfg, p, batch))(params)

    def train_step(params, opt_state, batch):
        if microbatch and microbatch > 1:
            def split(x):
                return x.reshape((microbatch, x.shape[0] // microbatch)
                                 + x.shape[1:])

            mb = jax.tree_util.tree_map(split, batch)

            def acc_step(carry, mbatch):
                loss_acc, g_acc = carry
                loss, g = grads_of(params, mbatch)
                g_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (loss_acc + loss, g_acc), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(acc_step, (0.0, g0), mb)
            loss = loss / microbatch
            grads = jax.tree_util.tree_map(lambda g: g / microbatch, grads)
        else:
            loss, grads = grads_of(params, batch)

        with jax.named_scope(scopes.OPTIMIZER):
            if oc.clip_norm:
                grads, gnorm = clip_by_global_norm(grads, oc.clip_norm)
            else:
                gnorm = jnp.zeros(())
            params, opt_state = apply_opt(oc, params, grads, opt_state)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


# --------------------------------------------------------------------------
# gradient compression (DP meshes): top-k error feedback + int8 all-reduce
# --------------------------------------------------------------------------

def topk_ef_compress(grads, errors, frac: float = 0.01):
    """Per-leaf top-|g| selection with error feedback.

    Returns (sparse_grads, new_errors): sparse grads carry only the selected
    fraction (rest zero) — the cross-replica reduction then moves ~frac of
    the bytes; unselected mass accumulates in the error buffer and is
    re-injected next step (convergence-preserving)."""

    def one(g, e):
        g32 = g.astype(jnp.float32) + e
        flat = g32.reshape(-1)
        k = max(1, int(flat.shape[0] * frac))
        thresh = jax.lax.top_k(jnp.abs(flat), k)[0][-1]
        mask = jnp.abs(g32) >= thresh
        sparse = jnp.where(mask, g32, 0.0)
        return sparse, g32 - sparse

    flat_g, tdef = jax.tree_util.tree_flatten(grads)
    flat_e = jax.tree_util.tree_leaves(errors)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (jax.tree_util.tree_unflatten(tdef, [o[0] for o in out]),
            jax.tree_util.tree_unflatten(tdef, [o[1] for o in out]))


def int8_allreduce(grads, axis_name: str):
    """Quantize to int8 with per-leaf scale, psum, dequantize.

    4× reduction bytes vs f32 (2× vs bf16); psum in int32 avoids overflow
    up to 2^24 replicas.  Call inside shard_map over the DP axis."""

    def one(g):
        g32 = g.astype(jnp.float32)
        scale = jnp.max(jnp.abs(g32)) / 127.0 + 1e-12
        q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
        total = jax.lax.psum(q.astype(jnp.int32), axis_name)
        scale_max = jax.lax.pmax(scale, axis_name)
        n = jax.lax.psum(1, axis_name)
        return (total.astype(jnp.float32) * scale_max / n).astype(g.dtype)

    return jax.tree_util.tree_map(one, grads)


def make_compressed_dp_step(cfg, oc: OptConfig, mesh, *, frac=0.01,
                            quantize=True):
    """DP-only train step with explicit compressed gradient exchange via
    shard_map (model axis must be size 1)."""
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    assert mesh.shape.get("model", 1) == 1, "compression demo is DP-only"
    dp = tuple(a for a in mesh.axis_names if a != "model")

    def local_step(params, opt_state, errors, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch))(params)
        grads, errors = topk_ef_compress(grads, errors, frac)
        if quantize:
            grads = int8_allreduce(grads, dp)
        else:
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, dp), grads)
        loss = jax.lax.pmean(loss, dp)
        with jax.named_scope(scopes.OPTIMIZER):
            if oc.clip_norm:
                grads, _ = clip_by_global_norm(grads, oc.clip_norm)
            params, opt_state = apply_opt(oc, params, grads, opt_state)
        return params, opt_state, errors, loss

    rep = P()
    bspec = jax.tree_util.tree_map(lambda _: P(dp), {"tokens": 0, "labels": 0})

    def step(params, opt_state, errors, batch):
        return shard_map(
            local_step, mesh=mesh,
            in_specs=(rep, rep, rep, P(dp)),
            out_specs=(rep, rep, rep, rep),
            check_rep=False,
        )(params, opt_state, errors, batch)

    return step


def init_errors(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
