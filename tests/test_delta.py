"""Differential checkpoint chains + device-resident restore.

Covers the PR-2 acceptance matrix: for f32/bf16/int32 leaves at
0/3/50/100 % critical density, ``save → delta-save ×3 → restore`` via the
device scatter path is bit-identical to the host path (on disk *and* after
restore), and the measured H2D bytes on restore / disk bytes on delta
saves scale with the critical/changed fraction.

Kernels run in ``interpret=True`` so CPU CI exercises the Pallas code
path; jnp-oracle dispatch is exercised alongside.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, DeltaLeaf, Level,
                              apply_delta, chain_steps, delta_encode_host,
                              load_checkpoint, load_checkpoint_raw,
                              read_manifest)
from repro.core.criticality import CriticalityReport, LeafReport
from repro.core.policy import LeafPolicy
from repro.core.regions import RegionTable
from repro.kernels.mask_pack import ops as mp_ops

DTYPES = [jnp.float32, jnp.bfloat16, jnp.int32]
DENSITIES = [0.0, 0.03, 0.5, 1.0]


def _vals(n, dtype, seed=0):
    rng = np.random.RandomState(seed)
    if dtype == jnp.int32:
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


def _tree_bytes(d, step):
    out = {}
    sd = os.path.join(d, f"step_{step}")
    for f in sorted(os.listdir(sd)):
        with open(os.path.join(sd, f), "rb") as fh:
            out[f] = fh.read()
    return out


# --------------------------------------------------------------------------
# op level: device delta == host delta, any dtype; apply inverts encode
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("use_kernel", [False, True])
def test_delta_encode_device_matches_host(dtype, use_kernel):
    n = 5000
    base = _vals(n, dtype, seed=2)
    curr_h = np.asarray(base).copy()
    curr_h[100:110] = curr_h[100:110] + np.asarray(1, curr_h.dtype)
    curr_h[-1] = curr_h[-1] + np.asarray(2, curr_h.dtype)
    idx_d, pay_d, moved = mp_ops.delta_encode(
        jnp.asarray(curr_h), base, use_kernel=use_kernel, interpret=True)
    idx_h, pay_h = delta_encode_host(
        curr_h.view(np.uint8), np.asarray(base).view(np.uint8))
    np.testing.assert_array_equal(idx_d, idx_h)
    np.testing.assert_array_equal(pay_d, pay_h)
    assert moved == pay_d.nbytes + (-(-curr_h.nbytes // 2048))
    # patching the base bytes with the delta reproduces curr exactly
    buf = np.asarray(base).view(np.uint8).reshape(-1).copy()
    apply_delta(buf, idx_d, pay_d.tobytes(), 2048)
    np.testing.assert_array_equal(buf.view(curr_h.dtype), curr_h)


def test_delta_encode_unchanged_is_empty():
    base = _vals(4096, jnp.float32, seed=3)
    idx, pay, moved = mp_ops.delta_encode(base, base, interpret=True)
    assert idx.size == 0 and pay.size == 0
    assert moved == -(-base.nbytes // 2048)    # flags only: 1 B per chunk


def test_mask_scatter_matches_unpack():
    n = 3000
    for frac in DENSITIES:
        vals = _vals(n, jnp.float32, seed=4)
        mask = _mask(n, frac, seed=5)
        host = np.asarray(vals)
        for uk in (False, True):
            out = mp_ops.mask_scatter(jnp.asarray(host[mask]),
                                      jnp.asarray(mask), n=n, fill=7.0,
                                      use_kernel=uk, interpret=True)
            expect = np.where(mask, host, np.float32(7.0))
            np.testing.assert_array_equal(np.asarray(out), expect)


def test_expand_mask_bits_roundtrip():
    for n in (1, 8, 63, 4096, 5001):
        mask = _mask(n, 0.4, seed=n)
        bits = np.packbits(mask)
        got = mp_ops.expand_mask_bits(jnp.asarray(bits), n=n)
        np.testing.assert_array_equal(np.asarray(got), mask)


# --------------------------------------------------------------------------
# acceptance matrix: base → delta ×3 → restore, device == host, bytes scale
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("frac", DENSITIES)
def test_chain_roundtrip_device_vs_host(tmp_path, dtype, frac):
    n = 4096
    w = np.asarray(_vals(n, dtype, seed=6))
    mask = _mask(n, frac, seed=7)
    state = {"w": jnp.asarray(w).reshape(64, 64),
             "s": jnp.asarray(1, jnp.int32)}
    report = _report({"w": state["w"], "s": state["s"]}, {"w": mask})

    mgrs = {}
    for mode in ("host", "device"):
        d = str(tmp_path / mode)
        mgrs[mode] = CheckpointManager(
            [Level(d, keep_n=10, max_chain=5)],
            scrutiny_fn=lambda s, report=report: report,
            save_mode=mode, restore_mode=mode,
            pack_interpret=True, pack_use_kernel=(dtype != jnp.int32))
        mgrs[mode].save(1, state, block=True)

    # three delta saves, mutating a small critical subset each step
    w_t = w.copy()
    hot = np.flatnonzero(mask)[:8]
    for t in (2, 3, 4):
        if hot.size:
            w_t = w_t.copy()
            w_t[hot] = w_t[hot] + np.asarray(t, w_t.dtype)
        state_t = {"w": jnp.asarray(w_t).reshape(64, 64),
                   "s": jnp.asarray(t, jnp.int32)}
        for mode in ("host", "device"):
            mgrs[mode].save(t, state_t, block=True)
            st = mgrs[mode].last_save_stats["levels"]
            assert list(st.values())[0]["kind"] == "delta"

        # on-disk byte identity between host and device save paths
        a = _tree_bytes(str(tmp_path / "host"), t)
        b = _tree_bytes(str(tmp_path / "device"), t)
        assert a == b, f"step {t} differs between host and device delta save"

    # chain metadata
    m = read_manifest(str(tmp_path / "device"), 4)
    assert chain_steps(m) == [1, 2, 3]

    # delta disk bytes scale with the changed fraction, not the state size
    changed = hot.size * np.dtype(np.asarray(w).dtype).itemsize
    if hot.size:
        # each changed element dirties ≤ one 2 KiB chunk
        assert m["payload_bytes"] <= hot.size * 2048 + 64
    else:
        assert m["payload_bytes"] <= 8      # only the int step scalar
    del changed

    # restore: device scatter path bit-identical to the host path
    like = {"w": jnp.zeros((64, 64), dtype), "s": jnp.asarray(0, jnp.int32)}
    results = {}
    for mode in ("host", "device"):
        step, got = mgrs[mode].restore(like)
        assert step == 4
        results[mode] = got
        mgrs[mode].close()
    exp = np.where(mask, w_t, np.zeros(1, w.dtype)) if not mask.all() else w_t
    for mode, got in results.items():
        np.testing.assert_array_equal(
            np.asarray(got["w"]).reshape(-1), exp, err_msg=mode)
        assert np.asarray(got["w"]).dtype == np.asarray(state["w"]).dtype
        np.testing.assert_array_equal(np.asarray(got["s"]), 4)

    # loader-level identity too
    _, lh = load_checkpoint(str(tmp_path / "host"))
    _, ld = load_checkpoint(str(tmp_path / "device"))
    for k in lh:
        np.testing.assert_array_equal(lh[k], ld[k])


def test_restore_h2d_scales_with_density(tmp_path):
    n = 1 << 16
    restores = {}
    for frac in (0.03, 0.5):
        mask = _mask(n, frac, seed=11)
        state = {"w": _vals(n, jnp.float32, seed=12)}
        report = _report(state, {"w": mask})
        d = str(tmp_path / f"f{frac}")
        with CheckpointManager([Level(d)], scrutiny_fn=lambda s: report,
                               save_mode="device", restore_mode="device",
                               pack_interpret=True) as mgr:
            mgr.save(1, state, block=True)
            got = mgr.restore({"w": jnp.zeros(n, jnp.float32)})
            assert got is not None
            stats = mgr.last_restore_stats
            restores[frac] = stats
            assert stats["device_leaves"] == 1
            # payload + bit-packed mask + counts; far below the full state
            bound = frac * n * 4 + n / 8 + 4 * (n / 512 + 2) + 4096
            assert stats["h2d_bytes"] <= bound
    assert restores[0.03]["h2d_bytes"] < restores[0.5]["h2d_bytes"]


# --------------------------------------------------------------------------
# chain mechanics: squash at max_chain, rescrutinize breaks the chain
# --------------------------------------------------------------------------

def test_chain_squashes_at_max_chain(tmp_path):
    n = 2048
    mask = _mask(n, 0.25, seed=13)
    state = {"w": _vals(n, jnp.float32, seed=14)}
    report = _report(state, {"w": mask})
    d = str(tmp_path / "lv")
    with CheckpointManager([Level(d, keep_n=20, max_chain=2)],
                           scrutiny_fn=lambda s: report,
                           save_mode="device", pack_interpret=True) as mgr:
        kinds = []
        for t in range(1, 8):
            mgr.save(t, state, block=True)
            kinds.append(list(mgr.last_save_stats["levels"].values())[0]
                         ["kind"])
    # base, delta, delta, base, delta, delta, base
    assert kinds == ["base", "delta", "delta"] * 2 + ["base"]
    assert chain_steps(read_manifest(d, 6)) == [4, 5]


def test_new_report_forces_new_base(tmp_path):
    n = 2048
    mask = _mask(n, 0.25, seed=15)
    state = {"w": _vals(n, jnp.float32, seed=16)}
    d = str(tmp_path / "lv")
    with CheckpointManager(
            [Level(d, keep_n=20, max_chain=10)],
            scrutiny_fn=lambda s: _report(s, {"w": mask}),  # fresh each call
            rescrutinize_every=2,
            save_mode="device", pack_interpret=True) as mgr:
        kinds = []
        for t in range(1, 5):
            mgr.save(t, state, block=True)
            kinds.append(list(mgr.last_save_stats["levels"].values())[0]
                         ["kind"])
    # report object changes on every rescrutinize → chain restarts
    assert kinds[0] == "base"
    assert "base" in kinds[1:]


def test_structure_change_forces_new_base(tmp_path):
    n = 2048
    mask = _mask(n, 0.25, seed=17)
    state = {"w": _vals(n, jnp.float32, seed=18)}
    report = _report(state, {"w": mask})
    d = str(tmp_path / "lv")
    with CheckpointManager([Level(d, keep_n=20, max_chain=10)],
                           scrutiny_fn=lambda s: report,
                           save_mode="device", pack_interpret=True) as mgr:
        mgr.save(1, state, block=True)
        grown = dict(state, extra=jnp.ones(16, jnp.float32))
        mgr.save(2, grown, block=True)
        assert (list(mgr.last_save_stats["levels"].values())[0]["kind"]
                == "base")
        # and the grown state restores (delta chain did not corrupt it)
        step, got = mgr.restore(
            {"w": jnp.zeros(n, jnp.float32),
             "extra": jnp.zeros(16, jnp.float32)})
        assert step == 2
        np.testing.assert_array_equal(np.asarray(got["extra"]), 1.0)


# --------------------------------------------------------------------------
# chain-aware retention
# --------------------------------------------------------------------------

def test_gc_keeps_live_chain_predecessors(tmp_path):
    n = 2048
    mask = _mask(n, 0.25, seed=19)
    state = {"w": _vals(n, jnp.float32, seed=20)}
    report = _report(state, {"w": mask})
    d = str(tmp_path / "lv")
    with CheckpointManager([Level(d, keep_n=2, max_chain=4)],
                           scrutiny_fn=lambda s: report,
                           save_mode="device", pack_interpret=True) as mgr:
        for t in range(1, 6):
            mgr.save(t, state, block=True)
        # steps 4, 5 are kept; both are deltas on base 1 via 2, 3 → every
        # predecessor must survive retention
        present = sorted(int(x.split("_")[1]) for x in os.listdir(d)
                         if x.startswith("step_"))
        assert present == [1, 2, 3, 4, 5]
        # next base resets the chain; the old one is collectible afterwards
        for t in range(6, 9):
            mgr.save(t, state, block=True)
        present = sorted(int(x.split("_")[1]) for x in os.listdir(d)
                         if x.startswith("step_"))
        assert 1 not in present and 8 in present
        # everything still restorable
        step, got = mgr.restore({"w": jnp.zeros(n, jnp.float32)})
        assert step == 8


def test_sharded_parity_delta_chain(tmp_path):
    """Delta checkpoints ride the same shard/parity machinery: kill one
    shard of a delta step and restore through the chain."""
    n = 4096
    mask = _mask(n, 0.5, seed=21)
    w = np.asarray(_vals(n, jnp.float32, seed=22))
    report = _report({"w": jnp.asarray(w), "b": jnp.zeros(n // 4)},
                     {"w": mask})
    d = str(tmp_path / "lv")
    with CheckpointManager([Level(d, keep_n=10, max_chain=4, shards=3,
                                  parity=True)],
                           scrutiny_fn=lambda s: report,
                           save_mode="device", pack_interpret=True) as mgr:
        state = {"w": jnp.asarray(w), "b": jnp.zeros(n // 4)}
        mgr.save(1, state, block=True)
        w2 = w.copy()
        w2[np.flatnonzero(mask)[:32]] += 1
        state2 = {"w": jnp.asarray(w2), "b": jnp.ones(n // 4)}
        mgr.save(2, state2, block=True)
        os.remove(os.path.join(d, "step_2", "shard_1.bin"))
        step, got = mgr.restore({"w": jnp.zeros(n, jnp.float32),
                                 "b": jnp.zeros(n // 4)})
        assert step == 2
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.where(mask, w2, 0))
        np.testing.assert_array_equal(np.asarray(got["b"]), 1.0)


def test_load_checkpoint_raw_checks_delta_crc(tmp_path):
    n = 2048
    mask = _mask(n, 0.5, seed=23)
    state = {"w": _vals(n, jnp.float32, seed=24)}
    report = _report(state, {"w": mask})
    d = str(tmp_path / "lv")
    with CheckpointManager([Level(d, keep_n=10, max_chain=4)],
                           scrutiny_fn=lambda s: report,
                           save_mode="device", pack_interpret=True) as mgr:
        mgr.save(1, state, block=True)
        w2 = np.asarray(state["w"]).copy()
        w2[np.flatnonzero(mask)[:4]] += 1
        mgr.save(2, {"w": jnp.asarray(w2)}, block=True)
    # corrupt the delta payload: the loader must refuse
    shard = os.path.join(d, "step_2", "shard_0.bin")
    raw = bytearray(open(shard, "rb").read())
    if len(raw):
        raw[0] ^= 0xFF
        with open(shard, "wb") as f:
            f.write(bytes(raw))
        with pytest.raises(IOError):
            load_checkpoint_raw(d, 2)


def test_chain_with_bool_leaf(tmp_path):
    """bool device leaves survive delta saves (bitcast rejects bool; the
    encoder widens to uint8) and restore bit-identically."""
    n = 2048
    mask = _mask(n, 0.25, seed=25)
    state = {"w": _vals(n, jnp.float32, seed=26),
             "flags": jnp.asarray(np.random.RandomState(27).rand(64) < 0.5)}
    report = _report({"w": state["w"]}, {"w": mask})
    d = str(tmp_path / "lv")
    with CheckpointManager([Level(d, keep_n=10, max_chain=4)],
                           scrutiny_fn=lambda s: report,
                           save_mode="device", pack_interpret=True) as mgr:
        mgr.save(1, state, block=True)
        flags2 = np.asarray(state["flags"]).copy()
        flags2[:4] = ~flags2[:4]
        state2 = dict(state, flags=jnp.asarray(flags2))
        mgr.save(2, state2, block=True)
        assert (list(mgr.last_save_stats["levels"].values())[0]["kind"]
                == "delta")
        step, got = mgr.restore({"w": jnp.zeros(n, jnp.float32),
                                 "flags": jnp.zeros(64, bool)})
        assert step == 2
        np.testing.assert_array_equal(np.asarray(got["flags"]), flags2)


def test_delta_leaf_nbytes():
    dl = DeltaLeaf(name="x", shape=(4,), dtype="float32", chunk_bytes=2048,
                   total_bytes=16, idx=np.asarray([0], np.int32),
                   payload=b"abcd", checksum=0)
    assert dl.nbytes == 4 + 4


def test_multidevice_segment_paths():
    """Per-shard pack + per-segment scatter restore on 4 virtual CPU
    devices (XLA device-count flag must be set before jax init → run in a
    subprocess)."""
    prog = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.distributed import sharding as sh
assert len(jax.devices()) == 4
mesh = Mesh(np.array(jax.devices()).reshape(4, 1), ("data", "model"))
s = NamedSharding(mesh, P("data", None))
rng = np.random.RandomState(0)
arr = rng.randn(64, 32).astype(np.float32)
mask = rng.rand(64 * 32) < 0.3
payload = arr.reshape(-1)[mask]
leaf = jax.device_put(jnp.asarray(arr), s)
pd, counts, moved, _ = sh.pack_sharded_payload_device(leaf, mask,
                                                      interpret=True)
np.testing.assert_array_equal(np.asarray(pd), payload)
out, h2d = sh.scatter_sharded_payload(payload, mask, (64, 32), np.float32,
                                      s, fill=0, interpret=True)
np.testing.assert_array_equal(np.asarray(out),
                              np.where(mask, arr.reshape(-1), 0)
                              .reshape(64, 32))
assert len(out.sharding.device_set) == 4
# per-segment transfers: payload + bit-packed masks, nothing more
assert payload.nbytes <= h2d <= payload.nbytes + mask.size // 8 + 64
# a segment with zero critical elements must still land on its own device
mask2 = mask.copy().reshape(64, 32)
mask2[:16] = False                       # device 0's segment: empty payload
mask2 = mask2.reshape(-1)
pay2 = arr.reshape(-1)[mask2]
out2, _ = sh.scatter_sharded_payload(pay2, mask2, (64, 32), np.float32,
                                     s, fill=0, interpret=True)
np.testing.assert_array_equal(np.asarray(out2),
                              np.where(mask2, arr.reshape(-1), 0)
                              .reshape(64, 32))
# every segment empty: each piece still lands on its own device
out3, _ = sh.scatter_sharded_payload(arr.reshape(-1)[:0],
                                     np.zeros(64 * 32, bool), (64, 32),
                                     np.float32, s, fill=0, interpret=True)
assert len(out3.sharding.device_set) == 4
np.testing.assert_array_equal(np.asarray(out3), np.zeros((64, 32)))
print("MULTIDEVICE_OK")
"""
    assert "MULTIDEVICE_OK" in _run_on_four_devices(prog)


def _run_on_four_devices(prog: str) -> str:
    """Run ``prog`` in a fresh interpreter that sees 4 virtual CPU devices
    (the XLA device-count flag must be set before jax initializes)."""
    import subprocess
    import sys

    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    res = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    return res.stdout


def test_multidevice_kernel_paths():
    """Leaves that span several devices reach the Pallas kernels one
    device's piece at a time (a kernel cannot be partitioned): layouts off
    the leading axis are re-tiled into per-device row ranges and counted
    in ``last_save_stats``; bitpack and delta run per device.  Kernels in
    interpret mode; every result byte-identical to the host path."""
    prog = r"""
import os, tempfile
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager, Level
from repro.checkpoint.coordinator import CoordinatedCheckpointManager
from repro.core.criticality import CriticalityReport, LeafReport
from repro.core.policy import LeafPolicy
from repro.core.regions import RegionTable
from repro.distributed import sharding as sh
from repro.kernels.mask_pack import ops as mp
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
put = lambda x, spec: jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))
rng = np.random.RandomState(0)
arr = rng.randn(64, 48).astype(np.float32)
arr.reshape(-1)[:5] = [-0.0, np.nan, np.inf, -np.inf, -0.0]
mask = rng.rand(arr.size) < 0.3
kern = dict(use_kernel=True, interpret=True)

# per-device pack: leading-axis shards as they are, other layouts re-tiled
for spec, retiled in [(P(None, "model"), True), (P("data", "model"), True),
                      (P("data"), False), (P(), False)]:
    pd, _, _, r = sh.pack_sharded_payload_device(put(arr, spec), mask, **kern)
    assert r == retiled, spec
    assert np.asarray(pd).tobytes() == arr.reshape(-1)[mask].tobytes(), spec
try:
    mp.pack_group([jnp.ravel(put(arr, P(None, "model")))], [jnp.asarray(mask)],
                  [int(mask.sum())], **kern)
    raise SystemExit("pack_group took a leaf on several devices")
except ValueError:
    pass

# bitpack and delta on operands that span devices == the one-device oracle
for x in (rng.randn(20000), rng.randn(3 * 4 * 1024), rng.randn(3001)):
    for spec in (P(("data", "model")), P()):
        if spec != P() and x.size % 4:
            continue
        mag = put(np.abs(x).astype(np.float32), spec)
        w, c = mp.threshold_bitpack(mag, 0.5, **kern)
        rw, rc = mp.threshold_bitpack(np.asarray(mag), 0.5, use_kernel=False)
        assert np.asarray(w).tobytes() == np.asarray(rw).tobytes()
        assert np.asarray(c).tobytes() == np.asarray(rc).tobytes()
a = rng.randn(7000).astype(np.float32); b = a.copy(); b[[3, 2500, 6999]] += 1
for spec in (P(("data", "model")), P()):
    got = mp.delta_encode(put(b, spec), put(a, spec), **kern)
    want = mp.delta_encode(jnp.asarray(b), jnp.asarray(a), use_kernel=False)
    assert (got[0] == want[0]).all() and got[1].tobytes() == want[1].tobytes()
    assert got[2] == want[2]

# manager: device base + delta save == host save, file for file
state = {"w": put(arr, P(None, "model")), "v": put(2 * arr, P("data", "model")),
         "u": put(arr[:, :8] + 1, P("data")),
         "k": put(np.arange(12, dtype=np.int32), P()), "s": put(np.float32(3), P())}
masks = {"w": mask, "v": ~mask, "u": mask[:512]}
leaves = {}
for name, leaf in state.items():
    m = masks.get(name, np.ones(max(leaf.size, 1), bool))
    leaves[name] = LeafReport(
        name=name, shape=tuple(leaf.shape), dtype=np.dtype(leaf.dtype),
        policy=LeafPolicy.AD, mask=m, magnitude=None,
        table=RegionTable.from_mask(m, np.dtype(leaf.dtype).itemsize))
report = CriticalityReport(leaves=leaves)
bumped = arr + (np.arange(arr.size).reshape(arr.shape) == 700)
changed = dict(state, w=put(bumped, P(None, "model")), s=put(np.float32(4), P()))
tmp = tempfile.mkdtemp()
files = {}
for mode in ("host", "device"):
    d = os.path.join(tmp, mode)
    with CheckpointManager([Level(d, max_chain=2)], scrutiny_fn=lambda s: report,
                           save_mode=mode, pipeline_engine="xla",
                           pack_interpret=True, pack_use_kernel=True) as mgr:
        mgr.save(1, state, block=True)
        st = mgr.wait()
        if mode == "device":
            assert st["engine"] == "xla" and st["packed_leaves"] == 3
            assert st["retiled_leaves"] == 2
            assert st["retiled_bytes"] == 2 * arr.nbytes
        mgr.save(2, changed, block=True)
        assert list(mgr.wait()["levels"].values())[0]["kind"] == "delta"
        _, back = mgr.restore(state, mode=mode)
    want = np.where(mask, bumped.reshape(-1), 0).reshape(arr.shape)
    assert np.asarray(back["w"]).tobytes() == want.tobytes()
    files[mode] = {}
    for dp, _, fs in os.walk(d):
        for f in fs:
            with open(os.path.join(dp, f), "rb") as fh:
                files[mode][os.path.relpath(os.path.join(dp, f), d)] = fh.read()
assert files["device"] and files["device"] == files["host"]

# coordinated save: a segment of a leaf on several devices packs per device
d = os.path.join(tmp, "coord")
mgr = CoordinatedCheckpointManager(
    [Level(d)], scrutiny_fn=lambda s: report, save_mode="device",
    force_coordinated=True, pipeline_engine="xla", pack_use_kernel=True,
    pack_interpret=True)
mgr.save(1, state)
mgr.wait()
_, got = mgr.restore(state)
mgr.close()
for k, x in state.items():
    want = np.asarray(x).reshape(-1).copy()
    if k in masks:
        want[~masks[k]] = 0
    assert np.asarray(got[k]).reshape(-1).tobytes() == want.tobytes(), k
print("KERNEL_PATHS_OK")
"""
    assert "KERNEL_PATHS_OK" in _run_on_four_devices(prog)
