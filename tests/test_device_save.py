"""Device-resident save path (kernels/mask_pack → packing.pack_leaf_from_payload
→ store): byte-identity with the host path on disk, bit-identical restore,
across dtypes and mask densities; plus the manager/gc satellites.

Everything runs the Pallas kernel in ``interpret=True`` so CPU CI exercises
the same code path as a TPU."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, Level, load_checkpoint,
                              pack_leaf, pack_leaf_from_payload,
                              save_checkpoint, step_of_entry)
from repro.checkpoint.packing import unpack_leaf
from repro.core.criticality import CriticalityReport, LeafReport
from repro.core.policy import LeafPolicy
from repro.core.regions import RegionTable
from repro.checkpoint.packing import delta_encode_host
from repro.kernels.mask_pack import ops as mp_ops
from repro.kernels.mask_pack.kernel import BITPACK_BLOCK, BLOCK, MAX_ROWS
from repro.kernels.mask_pack.ops import DELTA_CHUNK_BYTES

DTYPES = [jnp.float32, jnp.bfloat16, jnp.int32]
DENSITIES = [0.0, 0.03, 0.5, 1.0]


def _vals(n, dtype, seed=0):
    rng = np.random.RandomState(seed)
    if dtype == jnp.int32:
        # large magnitudes: catches any lossy float detour in the pack path
        return jnp.asarray(rng.randint(-2**30, 2**30, n), jnp.int32)
    return jnp.asarray(rng.randn(n), dtype)


def _mask(n, frac, seed=1):
    if frac == 0.0:
        return np.zeros(n, bool)
    if frac == 1.0:
        return np.ones(n, bool)
    return np.random.RandomState(seed).rand(n) < frac


def _report(state, masks):
    leaves = {}
    for name, leaf in state.items():
        n = int(np.prod(leaf.shape)) if leaf.ndim else 1
        mask = masks.get(name, np.ones(n, bool))
        leaves[name] = LeafReport(
            name=name, shape=tuple(leaf.shape), dtype=np.dtype(leaf.dtype),
            policy=LeafPolicy.AD, mask=mask,
            table=RegionTable.from_mask(mask, np.dtype(leaf.dtype).itemsize),
            magnitude=None)
    return CriticalityReport(leaves=leaves)


# --------------------------------------------------------------------------
# payload equality: device pack == host gather, any N / dtype / density
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("frac", DENSITIES)
@pytest.mark.parametrize("use_kernel", [False, True])
def test_pack_critical_matches_host(dtype, frac, use_kernel):
    n = 3000                                   # not BLOCK-aligned: ops pads
    vals = _vals(n, dtype)
    mask = _mask(n, frac)
    payload, counts, moved = mp_ops.pack_critical(
        vals, mask, use_kernel=use_kernel, interpret=True)
    host = np.asarray(vals)
    assert payload.dtype == host.dtype
    np.testing.assert_array_equal(np.asarray(payload), host[mask])
    assert moved == payload.nbytes + counts.nbytes
    assert int(counts.sum()) == int(mask.sum())


@pytest.mark.parametrize("n", [1, 7, 512, 513, 4096, 5000])
def test_pack_padding_any_size(n):
    """Satellite: the raw kernel needs N % block == 0; ops pads any size."""
    vals = _vals(n, jnp.float32, seed=n)
    mask = _mask(n, 0.4, seed=n + 1)
    pk_k, cnt_k = mp_ops.pack(vals, jnp.asarray(mask), use_kernel=True,
                              interpret=True)
    pk_r, cnt_r = mp_ops.pack(vals, jnp.asarray(mask), use_kernel=False)
    np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_r))
    valid = np.arange(pk_k.shape[1])[None, :] < np.asarray(cnt_k)[:, None]
    np.testing.assert_array_equal(np.asarray(pk_k)[valid],
                                  np.asarray(pk_r)[valid])
    back = mp_ops.unpack(pk_k, jnp.asarray(mask), n=n, use_kernel=True,
                         interpret=True)
    expect = np.where(mask, np.asarray(vals), 0.0)
    np.testing.assert_array_equal(np.asarray(back), expect)


@pytest.mark.parametrize("dtype", DTYPES)
def test_device_restore_roundtrip(dtype):
    """unpack_critical re-expands the payload on device."""
    n = 2000
    vals = _vals(n, dtype, seed=3)
    mask = _mask(n, 0.3, seed=4)
    payload, counts, _ = mp_ops.pack_critical(vals, mask, interpret=True)
    restored = mp_ops.unpack_critical(payload, counts, mask, n=n,
                                      interpret=True)
    host = np.asarray(vals)
    np.testing.assert_array_equal(np.asarray(restored)[mask], host[mask])


# --------------------------------------------------------------------------
# on-disk byte identity + bit-identical restore
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("frac", DENSITIES)
def test_packed_leaf_byte_identity(dtype, frac):
    n = 3000
    vals = _vals(n, dtype, seed=5)
    mask = _mask(n, frac, seed=6)
    host_leaf = pack_leaf("x", np.asarray(vals), mask)
    payload, _, _ = mp_ops.pack_critical(vals, mask, interpret=True)
    # mask.all() leaves take the "full" host branch: feed the whole leaf
    if mask.all():
        payload = np.asarray(vals)
    dev_leaf = pack_leaf_from_payload("x", (n,), str(vals.dtype), mask,
                                      payload)
    assert dev_leaf.encoding == host_leaf.encoding
    assert dev_leaf.aux == host_leaf.aux
    assert bytes(dev_leaf.payload) == bytes(host_leaf.payload)
    assert dev_leaf.checksum == host_leaf.checksum
    restored = unpack_leaf(dev_leaf, fill=0)
    expect = np.asarray(vals).copy()
    if not mask.all():
        expect[~mask] = 0
    np.testing.assert_array_equal(restored, expect)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("frac", [0.0, 0.03, 0.5])
def test_manager_device_vs_host_disk_identical(tmp_path, dtype, frac):
    n = 4000
    state = {"w": _vals(n, dtype, seed=7).reshape(40, 100),
             "s": jnp.asarray(5, jnp.int32)}
    masks = {"w": _mask(n, frac, seed=8)}
    report = _report(state, masks)
    dirs = {}
    for mode in ("host", "device"):
        d = str(tmp_path / mode)
        mgr = CheckpointManager([Level(d)], scrutiny_fn=lambda s: report,
                                save_mode=mode, pack_interpret=True,
                                pack_use_kernel=(dtype != jnp.int32))
        mgr.save(1, state, block=True)
        dirs[mode] = d
        if mode == "device":
            st = mgr.last_save_stats
            assert st["mode"] == "device"
            full = sum(np.asarray(v).nbytes for v in state.values())
            assert st["full_bytes"] == full
            if 0.0 < frac <= 0.5:
                assert st["d2h_bytes"] < full
    for fname in ("manifest.json", "shard_0.bin"):
        with open(os.path.join(dirs["host"], "step_1", fname), "rb") as f:
            a = f.read()
        with open(os.path.join(dirs["device"], "step_1", fname), "rb") as f:
            b = f.read()
        assert a == b, f"{fname} differs between host and device save"
    # bit-identical restore through the normal loader
    _, leaves = load_checkpoint(dirs["device"])
    w = np.asarray(state["w"]).reshape(-1).copy()
    if not masks["w"].all():
        w[~masks["w"]] = 0
    np.testing.assert_array_equal(leaves["w"].reshape(-1), w)
    np.testing.assert_array_equal(leaves["s"], 5)


# --------------------------------------------------------------------------
# past MAX_ROWS tiles: the kernels stop padding their grid, the last grid
# step reads a partial block and the wrapper slices off what it computed
# past the end.  Payloads hold -0.0, infinities and NaNs with distinct
# payload bits; every path must keep them bit for bit.
# --------------------------------------------------------------------------

BIG = (MAX_ROWS + 1) * BLOCK + 300              # 130 pack tiles
BIG_BITPACK = (MAX_ROWS + 1) * BITPACK_BLOCK + 300
SPECIAL_BITS = {jnp.float32: [0x80000000, 0x7FC00123, 0xFFC00456,
                              0x7F800000, 0xFF800000],
                jnp.bfloat16: [0x8000, 0x7FC1, 0xFFC3, 0x7F80, 0xFF80]}


def _special_vals(n, dtype, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n).astype(np.dtype(dtype))
    u = x.view(np.uint32 if dtype == jnp.float32 else np.uint16)
    at = rng.choice(n, n // 50, replace=False)
    u[at] = np.resize(SPECIAL_BITS[dtype], at.size)
    u[-1] = SPECIAL_BITS[dtype][1]              # a NaN in the partial tile
    return x


def _bits(x) -> bytes:
    return np.asarray(x).tobytes()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pack_unpack_scatter_past_max_rows(dtype):
    vals = _special_vals(BIG, dtype)
    mask = _mask(BIG, 0.4, seed=2)
    mask[-1] = True
    want_payload = vals[mask]
    want = np.where(mask, vals, np.zeros((), vals.dtype))
    pk_k, cnt_k = mp_ops.pack(jnp.asarray(vals), jnp.asarray(mask),
                              use_kernel=True, interpret=True)
    pk_r, cnt_r = mp_ops.pack(jnp.asarray(vals), jnp.asarray(mask),
                              use_kernel=False)
    assert _bits(cnt_k) == _bits(cnt_r)
    for pk, cnt in ((pk_k, cnt_k), (pk_r, cnt_r)):
        payload = mp_ops.pack_to_payload(np.asarray(pk), np.asarray(cnt))
        assert _bits(payload) == _bits(want_payload)
    back = mp_ops.unpack(pk_k, jnp.asarray(mask), n=BIG, use_kernel=True,
                         interpret=True)
    assert _bits(back) == _bits(want)
    for use_kernel in (True, False):
        out = mp_ops.mask_scatter(jnp.asarray(want_payload),
                                  jnp.asarray(mask), n=BIG,
                                  use_kernel=use_kernel, interpret=True)
        assert _bits(out) == _bits(want)


def test_bitpack_past_max_rows():
    rng = np.random.RandomState(5)
    mag = np.abs(rng.randn(BIG_BITPACK)).astype(np.float32)
    mag[rng.choice(mag.size, 500, replace=False)] = 0.0
    mag[-3:] = [np.inf, 0.5, 0.7]
    w_k, c_k = mp_ops.threshold_bitpack(jnp.asarray(mag), 0.5,
                                        use_kernel=True, interpret=True)
    w_r, c_r = mp_ops.threshold_bitpack(jnp.asarray(mag), 0.5,
                                        use_kernel=False)
    assert _bits(w_k) == _bits(w_r) == _bits(np.packbits(mag > 0.5))
    assert _bits(c_k) == _bits(c_r)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_delta_past_max_rows(dtype):
    itemsize = np.dtype(dtype).itemsize
    n = (MAX_ROWS + 1) * DELTA_CHUNK_BYTES // itemsize + 300
    base = _special_vals(n, dtype, seed=6)
    curr = base.copy()
    u = curr.view(np.uint32 if dtype == jnp.float32 else np.uint16)
    nan, neg_zero = SPECIAL_BITS[dtype][1], SPECIAL_BITS[dtype][0]
    curr[5] = 0.0 if u[5] != 0 else 1.0
    u[n // 2] = neg_zero if u[n // 2] != neg_zero else 0  # -0.0 vs +0.0
    u[-1] = nan + 1 if u[-1] == nan else nan              # NaN payload bits
    got = mp_ops.delta_encode(jnp.asarray(curr), jnp.asarray(base),
                              use_kernel=True, interpret=True)
    ref = mp_ops.delta_encode(jnp.asarray(curr), jnp.asarray(base),
                              use_kernel=False)
    idx, pay = delta_encode_host(curr, base, DELTA_CHUNK_BYTES)
    assert _bits(got[0]) == _bits(ref[0]) == _bits(idx)
    assert _bits(got[1]) == _bits(ref[1]) == _bits(pay)
    assert len(idx) == 3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_manager_device_vs_host_past_max_rows(tmp_path, dtype):
    """The xla save engine (pack kernel) writes the host path's bytes, and
    a device restore (scatter kernel) gives the host restore's bits."""
    state = {"w": jnp.asarray(_special_vals(BIG, dtype, seed=9))}
    mask = _mask(BIG, 0.3, seed=10)
    report = _report(state, {"w": mask})
    files, back = {}, {}
    for mode in ("host", "device"):
        d = str(tmp_path / mode)
        with CheckpointManager([Level(d)], scrutiny_fn=lambda s: report,
                               save_mode=mode, pipeline_engine="xla",
                               pack_interpret=True,
                               pack_use_kernel=True) as mgr:
            mgr.save(1, state, block=True)
            assert mgr.wait()["mode"] == mode
            _, back[mode] = mgr.restore(state, mode=mode)
        files[mode] = {}
        for f in sorted(os.listdir(os.path.join(d, "step_1"))):
            with open(os.path.join(d, "step_1", f), "rb") as fh:
                files[mode][f] = fh.read()
    assert files["device"] and files["device"] == files["host"]
    want = np.where(mask, np.asarray(state["w"]),
                    np.zeros((), np.dtype(dtype)))
    assert _bits(back["device"]["w"]) == _bits(back["host"]["w"]) \
        == _bits(want)


# --------------------------------------------------------------------------
# manager satellites: stray entries in level dirs must not crash gc/latest
# --------------------------------------------------------------------------

def test_gc_and_latest_skip_stray_entries(tmp_path):
    d = str(tmp_path / "lv")
    mgr = CheckpointManager([Level(d, keep_n=2)])
    state = {"w": jnp.arange(8, dtype=jnp.float32)}
    mgr.save(1, state, block=True)
    # stray entries the seed crashed on: step_tmp, unrelated files
    os.makedirs(os.path.join(d, "step_tmp"))
    for stray in ("stray.txt", "step_notanum"):
        with open(os.path.join(d, stray), "w") as f:
            f.write("x")
    mgr.save(2, state, block=True)
    mgr.save(3, state, block=True)
    assert mgr.latest()[0] == 3
    kept = sorted(x for x in os.listdir(d) if step_of_entry(x) is not None)
    assert kept == ["step_2", "step_3"]
    # stray entries survive untouched
    assert os.path.exists(os.path.join(d, "step_tmp"))
    assert os.path.exists(os.path.join(d, "stray.txt"))
    got = mgr.restore(state)
    assert got is not None and got[0] == 3


def test_step_of_entry():
    assert step_of_entry("step_17") == 17
    assert step_of_entry("step_tmp") is None
    assert step_of_entry(".tmp_step_3") is None
    assert step_of_entry("notes.txt") is None
