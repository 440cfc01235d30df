"""The save/restore and attention kernels compile for a TPU v5e at real
widths.

Nothing runs here: each kernel is lowered and compiled for one chip of a
described (not attached) ``v5e:2x2`` topology (or, for operands that span
chips, per chip over all four under ``shard_map``), so what the chip's Mosaic
compiler refuses — unaligned blocks, primitives it cannot lower, more
VMEM than a kernel may use — fails on a CPU-only machine.  The widths are
xlstm-125m's embedding leaf (50304 x 768 = 38,633,472 elements), the
largest leaf a full-width save packs; the attention kernels' are
SmolLM2-135M's train step (batch 2 x 2,048, 9 query and 3 key/value heads
of 64) and DeepSeek-V3's MLA head dims (192 for q and k, 128 for v).  The
``kernel`` functions are called directly, since ``ops`` dispatches on the
default backend (CPU).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.flash_attention import kernel as FA
from repro.kernels.mask_pack import kernel as K
from repro.kernels.mask_pack.ops import DELTA_CHUNK_BYTES, per_device_fn

EMBED = 50304 * 768          # xlstm-125m tok_embed leaf


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # noqa: BLE001 - skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.  The
    # program runs these kernels with 32-bit defaults; some test modules
    # switch x64 on for the whole process as they are imported.
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topology):
    return jax.sharding.SingleDeviceSharding(topology.devices[0])


@pytest.fixture(scope="module")
def four_chips(topology):
    return Mesh(np.array(topology.devices[:4]), ("d",))


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n", [EMBED, 40 * 512])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pack_compiles(one_chip, n, dtype):
    c = _compile(lambda v, m: K.pack_blocks_kernel(v, m), one_chip,
                 ((n,), dtype), ((n,), jnp.int8))
    itemsize = jnp.dtype(dtype).itemsize
    # packed tiles come back in the leaf's dtype, one tile per 512 elements
    assert c.memory_analysis().output_size_in_bytes >= n * itemsize


@pytest.mark.parametrize("n", [EMBED, 40 * 512])
def test_unpack_compiles(one_chip, n):
    _compile(lambda p, m: K.unpack_blocks_kernel(p, m, fill=0.0), one_chip,
             ((n // K.BLOCK, K.BLOCK), jnp.float32), ((n,), jnp.int8))


@pytest.mark.parametrize("n", [EMBED, 40 * 512])
def test_scatter_compiles(one_chip, n):
    nb = n // K.BLOCK
    _compile(lambda p, s, m: K.scatter_blocks_kernel(p, s, m, fill=0.0),
             one_chip, ((nb // 2 + 2, K.BLOCK), jnp.float32),
             ((nb,), jnp.int32), ((n,), jnp.int8))


@pytest.mark.parametrize("n", [EMBED, 40 * K.BITPACK_BLOCK])
def test_bitpack_compiles(one_chip, n):
    _compile(lambda m: K.bitpack_blocks_kernel(m, 0.0), one_chip,
             ((n,), jnp.float32))


@pytest.mark.parametrize("n", [EMBED, 40 * 512, 3 * 512])
@pytest.mark.parametrize("chunk", [DELTA_CHUNK_BYTES // 4, 16])
def test_delta_compiles(one_chip, n, chunk):
    _compile(lambda c, b: K.delta_blocks_kernel(c, b, chunk), one_chip,
             ((n,), jnp.int32), ((n,), jnp.int32))


@pytest.mark.parametrize("n", [EMBED, 4 * 40 * K.BITPACK_BLOCK])
def test_per_device_bitpack_compiles(four_chips, n):
    """Bitpack of magnitudes that span four chips: one kernel per chip."""
    fn = per_device_fn(lambda m: K.bitpack_blocks_kernel(m, 0.0),
                       four_chips, 1)
    _compile(fn, NamedSharding(four_chips, P("d")), ((n,), jnp.float32))


@pytest.mark.parametrize("n", [EMBED, 4 * 40 * 512])
def test_per_device_delta_compiles(four_chips, n):
    """Delta flags of words that span four chips: one kernel per chip."""
    chunk = DELTA_CHUNK_BYTES // 4
    fn = per_device_fn(lambda c, b: K.delta_blocks_kernel(c, b, chunk),
                       four_chips, 2)
    _compile(fn, NamedSharding(four_chips, P("d")), ((n,), jnp.int32),
             ((n,), jnp.int32))


#: (B, T, H, K, D, Dv): SmolLM2-135M's train step; MLA's head dims
FA_WIDTHS = {"smollm2": (2, 2048, 9, 3, 64, 64),
             "mla": (1, 2048, 16, 16, 192, 128)}


@pytest.mark.parametrize("widths", sorted(FA_WIDTHS))
@pytest.mark.parametrize("kernel", ["fwd", "bwd_dkv", "bwd_dq"])
def test_flash_attention_compiles(one_chip, kernel, widths):
    """``flash_attention_<kernel>`` at the block size the model path uses,
    causal, bfloat16 operands as in the train step."""
    from repro.kernels.flash_attention.ops import BLOCK

    B, T, H, Kh, D, Dv = FA_WIDTHS[widths]
    G, bf = H // Kh, jnp.bfloat16
    spec = FA.Spec(causal=True, window=None, attn_cap=None, block_q=BLOCK,
                   block_k=BLOCK, tk=T)
    qkv = [((B, Kh, G, T, D), bf), ((B, Kh, T, D), bf), ((B, Kh, T, Dv), bf),
           ((B, T), jnp.int32)]
    rows = [((B, Kh, G, T), jnp.float32)] * 2 + [((B, Kh, G, T, Dv), bf)]
    if kernel == "fwd":
        _compile(lambda q, k, v, p: FA.flash_attention_fwd(q, k, v, p, p, spec),
                 one_chip, *qkv)
    else:
        fn = getattr(FA, "flash_attention_" + kernel)
        _compile(lambda q, k, v, p, lse, dl, do: fn(q, k, v, p, p, lse, dl,
                                                     do, spec),
                 one_chip, *qkv, *rows)
