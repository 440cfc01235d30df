"""Deterministic, sharded, *resumable* synthetic token pipeline.

The pipeline state is part of the checkpoint (exact resume after failure)
and is itself a scrutinize() target: the prefetch ring buffer's consumed
prefix is overwritten before it is read again, so the criticality engine
proves only the unconsumed suffix needs checkpointing — the paper's
write-before-read pattern in the data layer (see examples/ and
tests/test_data_pipeline.py)."""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod

PREFETCH = 4  # batches held in the ring buffer


def init_state(cfg, batch: int, seq: int, seed: int = 0) -> Dict[str, Any]:
    key = jax.random.PRNGKey(seed)
    buf = _fill(cfg, key, 0, batch, seq, PREFETCH)
    return {
        "key": key,
        "step": jnp.zeros((), jnp.int32),
        "buffer": buf,                 # (PREFETCH, B, T) int32
        "cursor": jnp.zeros((), jnp.int32),
    }


def _synth_tokens(cfg, key, batch, seq):
    """Learnable synthetic stream: successor runs with random restarts.

    90 % of positions follow t+1 = t + 1 (mod V); 10 % jump to a random
    token.  A model that learns the successor rule reaches ≪ uniform
    cross-entropy, so training-loss decrease is a meaningful signal."""
    k1, k2, k3 = jax.random.split(key, 3)
    jumps = jax.random.randint(k1, (batch, seq), 0, cfg.vocab, jnp.int32)
    is_jump = jax.random.uniform(k2, (batch, seq)) < 0.1
    start = jax.random.randint(k3, (batch, 1), 0, cfg.vocab, jnp.int32)
    # segment-wise: token = (value at last jump) + distance since jump
    idx = jnp.arange(seq)[None, :]
    jump_pos = jnp.where(is_jump, idx, -1)
    last_jump = jax.lax.associative_scan(jnp.maximum, jump_pos, axis=1)
    seg_val = jnp.where(last_jump >= 0,
                        jnp.take_along_axis(jumps, jnp.maximum(last_jump, 0),
                                            axis=1),
                        start)
    tokens = (seg_val + (idx - jnp.maximum(last_jump, 0))) % cfg.vocab
    return tokens.astype(jnp.int32)


def _fill(cfg, key, start_step, batch, seq, n):
    def one(i):
        k = jax.random.fold_in(key, start_step + i)
        return _synth_tokens(cfg, k, batch, seq)

    return jnp.stack([one(i) for i in range(n)])


def next_batch(cfg, state) -> Tuple[Dict[str, jnp.ndarray], Dict[str, Any]]:
    """Pop one batch; refill the consumed slot deterministically.

    The span ``data.next_batch`` is the train step's host work.  Once the
    device's queue is full, one of its eager dispatches waits there for a
    free slot, so the span's length holds that wait too; the span's
    ``cpu_ms`` is the calling thread's CPU time inside it, which leaves
    the wait out."""
    cpu0 = time.thread_time()
    with obs_mod.get_obs().tracer.span("data.next_batch") as span:
        out = _next_batch(cfg, state)
        span.set(cpu_ms=(time.thread_time() - cpu0) * 1e3)
    return out


def _next_batch(cfg, state):
    cur = state["cursor"]
    tokens = jax.lax.dynamic_index_in_dim(state["buffer"], cur % PREFETCH,
                                          axis=0, keepdims=False)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    step = state["step"] + 1
    refill_key = jax.random.fold_in(state["key"], step + PREFETCH - 1)
    new_slot = jax.random.randint(refill_key, tokens.shape, 0, cfg.vocab,
                                  jnp.int32)
    buf = jax.lax.dynamic_update_index_in_dim(state["buffer"],
                                              new_slot, cur % PREFETCH, 0)
    return batch, {"key": state["key"], "step": step, "buffer": buf,
                   "cursor": cur + 1}


def consume_resume_fn(cfg, n_steps: int):
    """Returns fn(state) -> outputs for scrutinize()/participation():
    'the rest of the program' consumes ``n_steps`` batches.  Buffer slots
    already consumed (and the key, by policy) are provably uncritical."""

    def fn(state):
        s = state
        outs = []
        for _ in range(n_steps):
            b, s = next_batch(cfg, s)
            # tokens feed the train step; their float mean stands in for
            # the differentiable path (int data → participation engine).
            outs.append(b["tokens"])
        return {"consumed": jnp.stack(outs)}

    return fn
