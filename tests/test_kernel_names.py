"""Every Pallas kernel carries a stable name, which a device trace shows
as the kernel's instruction name (``%mask_pack_pack.1 = ...``), so a trace
reduction finds the kernel whatever its Python function is called."""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.lru_scan.kernel import lru_scan_kernel
from repro.kernels.mask_pack import kernel as K

N = 40 * 512
F32, I8, I32 = jnp.float32, jnp.int8, jnp.int32


def _kernel_names(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
    return [e.params["name"] for e in eqns
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("name,fn,shapes", [
    ("mask_pack_pack", lambda v, m: K.pack_blocks_kernel(v, m),
     [((N,), F32), ((N,), I8)]),
    ("mask_pack_unpack", lambda p, m: K.unpack_blocks_kernel(p, m, fill=0.0),
     [((N // K.BLOCK, K.BLOCK), F32), ((N,), I8)]),
    ("mask_pack_scatter",
     lambda p, s, m: K.scatter_blocks_kernel(p, s, m, fill=0.0),
     [((N // K.BLOCK // 2 + 2, K.BLOCK), F32), ((N // K.BLOCK,), I32),
      ((N,), I8)]),
    ("mask_pack_bitpack", lambda m: K.bitpack_blocks_kernel(m, 0.0),
     [((40 * K.BITPACK_BLOCK,), F32)]),
    ("mask_pack_delta", lambda c, b: K.delta_blocks_kernel(c, b, 16),
     [((N,), I32), ((N,), I32)]),
    ("flash_attention",
     lambda q, k, v: flash_attention_kernel(q, k, v, scale=0.125, causal=True,
                                            window=None, attn_cap=None),
     [((1, 256, 4, 64), F32), ((1, 256, 2, 64), F32),
      ((1, 256, 2, 64), F32)]),
    ("lru_scan", lambda a, b, h: lru_scan_kernel(a, b, h),
     [((1, 256, 128), F32), ((1, 256, 128), F32), ((1, 128), F32)]),
])
def test_pallas_call_has_stable_name(name, fn, shapes):
    assert _kernel_names(fn, *shapes) == [name]
