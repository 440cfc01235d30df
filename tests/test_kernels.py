"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle, swept
over shapes/dtypes (assignment requirement)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.lru_scan.kernel import lru_scan_kernel
from repro.kernels.lru_scan.ref import lru_scan_ref
from repro.kernels.mask_pack.kernel import (pack_blocks_kernel,
                                            unpack_blocks_kernel)
from repro.kernels.mask_pack.ref import pack_blocks_ref, unpack_blocks_ref
from repro.kernels.mask_pack import ops as mp_ops


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

FA_CASES = [
    # (B, T, H, K, D, window, causal, cap, dtype[, Dv, positions])
    (1, 128, 4, 4, 64, None, True, None, jnp.float32),
    (2, 256, 8, 2, 64, None, True, None, jnp.float32),     # GQA 4:1
    (1, 256, 4, 1, 128, None, True, None, jnp.float32),    # MQA
    (1, 256, 4, 4, 64, 128, True, None, jnp.float32),      # sliding window
    (1, 256, 4, 2, 64, None, True, 50.0, jnp.float32),     # softcap (gemma2)
    (1, 256, 4, 2, 64, 128, True, 50.0, jnp.bfloat16),     # all combined bf16
    (2, 128, 2, 2, 256, None, True, None, jnp.float32),    # gemma-7b head_dim
    (1, 128, 4, 4, 64, None, False, None, jnp.float32),    # non-causal (enc)
    (2, 256, 6, 2, 64, None, True, None, jnp.float32),     # GQA 3:1 (SmolLM2)
    (1, 256, 6, 2, 64, None, False, None, jnp.float32),    # GQA 3:1 non-causal
    (1, 384, 6, 2, 64, 128, True, None, jnp.float32),      # window skips blocks
    (1, 256, 6, 2, 64, None, True, 30.0, jnp.float32),     # softcap, GQA 3:1
    (1, 256, 4, 2, 64, None, True, None, jnp.float32, 32),  # Dv != D (MLA)
    (1, 200, 6, 2, 64, None, True, None, jnp.float32),     # T padded to block
    (1, 200, 4, 4, 64, None, False, None, jnp.float32),    # padded, non-causal
    (2, 256, 6, 2, 64, None, True, None, jnp.float32, None, "offset"),
    (1, 256, 6, 2, 64, 64, True, None, jnp.float32, None, "repeat"),
    (1, 256, 6, 2, 64, 128, True, 50.0, jnp.bfloat16),     # combined, GQA 3:1
]

#: the features of FA_CASES, differentiated: GQA 3:1, causal and not,
#: window, softcap, Dv != D, padding, offset and repeated positions
FA_GRAD_CASES = [FA_CASES[i] for i in (8, 9, 10, 11, 12, 13, 14, 15, 16)]


def _fa_inputs(case, seed=0):
    B, T, H, K, D, window, causal, cap, dt = case[:9]
    Dv = (case[9:10] or [None])[0] or D
    positions = (case[10:11] or ["arange"])[0]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32).astype(dt)
    k = jax.random.normal(ks[1], (B, T, K, D), jnp.float32).astype(dt)
    v = jax.random.normal(ks[2], (B, T, K, Dv), jnp.float32).astype(dt)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    if positions == "offset":       # each row shifted by its own offset
        pos = pos + jnp.arange(B, dtype=jnp.int32)[:, None] * 1000 + 7
    elif positions == "repeat":     # every position twice
        pos = pos // 2
    kw = dict(window=window, causal=causal, scale=D ** -0.5, attn_cap=cap)
    return (q, k, v, pos), kw


def _flash(q, k, v, q_pos, k_pos, **kw):
    # 128-row blocks: the cases span several, so pairs are skipped/masked
    return fa_ops.flash_attention(q, k, v, q_pos, k_pos, interpret=True,
                                  block=128, **kw)


@pytest.mark.parametrize("case", FA_CASES)
def test_flash_attention_vs_ref(case):
    (q, k, v, pos), kw = _fa_inputs(case)
    out = _flash(q, k, v, pos, pos, **kw)
    ref = flash_attention_ref(q, k, v, pos, pos, **kw)
    tol = 2e-2 if q.dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("case", FA_GRAD_CASES)
def test_flash_attention_grad_vs_ref(case):
    """jax.grad through the kernels' custom VJP (the backward kernels)
    against jax.grad of the plain attention."""
    (q, k, v, pos), kw = _fa_inputs(case)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape[:3] + v.shape[-1:])

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, pos, pos, **kw) * w)

    got = jax.grad(loss(_flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(flash_attention_ref), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=5e-5, rtol=5e-5)


def test_flash_attention_ops_padding():
    # T not a multiple of the block: ops-level entry pads and unpads.
    B, T, H, D = 1, 200, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, H, D))
    v = jax.random.normal(ks[2], (B, T, H, D))
    out = fa_ops.flash_attention(q, k, v, interpret=True)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------
# mask pack / unpack
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,block,frac,dtype", [
    (1024, 512, 0.5, jnp.float32),
    (4096, 512, 0.148, jnp.float32),   # BT(u) uncritical rate
    (2048, 256, 0.0, jnp.float32),     # nothing critical
    (2048, 256, 1.0, jnp.float32),     # everything critical
    (1024, 128, 0.3, jnp.bfloat16),
])
def test_mask_pack_kernel_vs_ref(n, block, frac, dtype):
    rng = np.random.RandomState(0)
    vals = jnp.asarray(rng.randn(n), dtype)
    mask = jnp.asarray(rng.rand(n) < frac)
    pk_k, cnt_k = pack_blocks_kernel(vals, mask.astype(jnp.int8),
                                     block=block, interpret=True)
    pk_r, cnt_r = pack_blocks_ref(vals, mask, block=block)
    np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_r))
    # compare only the meaningful (counted) prefix of each tile
    for i, c in enumerate(np.asarray(cnt_k)):
        np.testing.assert_array_equal(np.asarray(pk_k[i, :c]),
                                      np.asarray(pk_r[i, :c]))
    # roundtrip through both unpack paths
    out_k = unpack_blocks_kernel(pk_k, mask.astype(jnp.int8), fill=0.0,
                                 interpret=True)
    out_r = unpack_blocks_ref(pk_r, mask, fill=0.0)
    expect = np.where(np.asarray(mask), np.asarray(vals, np.float32), 0.0)
    np.testing.assert_array_equal(np.asarray(out_k, np.float32), expect)
    np.testing.assert_array_equal(np.asarray(out_r, np.float32), expect)


def test_mask_pack_host_payload_roundtrip():
    rng = np.random.RandomState(3)
    n = 3000  # not block-aligned: ops pads
    vals = jnp.asarray(rng.randn(n), jnp.float32)
    mask = jnp.asarray(rng.rand(n) < 0.4)
    packed, counts = mp_ops.pack(vals, mask, use_kernel=False)
    payload = mp_ops.pack_to_payload(np.asarray(packed), np.asarray(counts))
    assert payload.size == int(np.asarray(mask).sum())
    back = mp_ops.payload_to_packed(payload, np.asarray(counts),
                                    packed.shape[1])
    restored = mp_ops.unpack(jnp.asarray(back), mask, n=n, use_kernel=False)
    expect = np.where(np.asarray(mask), np.asarray(vals), 0.0)
    np.testing.assert_array_equal(np.asarray(restored), expect)


# --------------------------------------------------------------------------
# lru scan
# --------------------------------------------------------------------------

@pytest.mark.parametrize("B,T,R,dtype", [
    (1, 256, 128, jnp.float32),
    (2, 512, 256, jnp.float32),
    (2, 256, 128, jnp.bfloat16),
])
def test_lru_scan_kernel_vs_ref(B, T, R, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    # decay in (0.8, 0.999) like a real RG-LRU; inputs small
    a = (0.8 + 0.199 * jax.random.uniform(ks[0], (B, T, R))).astype(dtype)
    b = (0.1 * jax.random.normal(ks[1], (B, T, R))).astype(dtype)
    h0 = jax.random.normal(ks[2], (B, R)).astype(dtype)
    out = lru_scan_kernel(a, b, h0, interpret=True)
    ref = lru_scan_ref(a, b, h0)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)
