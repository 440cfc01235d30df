"""Pallas TPU kernel: blocked diagonal linear recurrence (RG-LRU hot path).

h_t = a_t ⊙ h_{t-1} + b_t  — the sequential dependence is only along T, so
the grid parallelizes (batch × feature-lane) tiles and walks T in chunks
(sequential "arbitrary" dimension) with the carry h in VMEM scratch.
Inside a chunk the recurrence runs as an unrolled VPU loop over rows — the
kernel is bandwidth-bound (reads a, b; writes h: 12 bytes/element f32).

Feature tiles are 128 lanes wide (VREG lane width); T chunks default 256
rows, so a tile's working set is 3 × 256×128×4 B = 384 KiB ≪ VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BLOCK_T = 256
BLOCK_R = 128


def _kernel(a_ref, b_ref, h0_ref, out_ref, h_scr, *, bt: int, nt: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[0, :].astype(jnp.float32)[None, :]

    a = a_ref[0, :, :].astype(jnp.float32)   # (bt, BLOCK_R)
    b = b_ref[0, :, :].astype(jnp.float32)

    def step(i, h):
        h = a[i] * h + b[i]
        out_ref[0, i, :] = h.astype(out_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bt, step, h_scr[0, :])
    h_scr[...] = h[None, :]


def lru_scan_kernel(a: jnp.ndarray, b: jnp.ndarray, h0: jnp.ndarray,
                    block_t: int = BLOCK_T, block_r: int = BLOCK_R,
                    interpret: bool = False) -> jnp.ndarray:
    """a, b: (B, T, R); h0: (B, R).  T % block_t == 0, R % block_r == 0."""
    B, T, R = a.shape
    bt = min(block_t, T)
    br = min(block_r, R)
    assert T % bt == 0 and R % br == 0, (T, R, bt, br)
    nt, nr = T // bt, R // br
    kern = functools.partial(_kernel, bt=bt, nt=nt)
    return pl.pallas_call(
        kern,
        name="lru_scan",
        grid=(B * nr, nt),
        in_specs=[
            pl.BlockSpec((1, bt, br), lambda g, t, nr=nr: (g // nr, t, g % nr)),
            pl.BlockSpec((1, bt, br), lambda g, t, nr=nr: (g // nr, t, g % nr)),
            pl.BlockSpec((1, br), lambda g, t, nr=nr: (g // nr, g % nr)),
        ],
        out_specs=pl.BlockSpec((1, bt, br),
                               lambda g, t, nr=nr: (g // nr, t, g % nr)),
        out_shape=jax.ShapeDtypeStruct((B, T, R), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, br), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
