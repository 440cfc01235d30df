#!/usr/bin/env python3
"""Smoke run of the scrutinized save -> kill -> restore path on a TPU.

    python3 chip_smoke.py              # one chip: phases a, b, c
    python3 chip_smoke.py --chips 4    # four chips: the sharded phase only

Everything runs in this one process (a chip belongs to one process).

a. Find the chip: exit non-zero unless JAX sees a TPU.
b. Train full-width xlstm-125m (74.1 M params, AdamW) through
   ``repro.launch.train.main``: N steps with a ``--scrutinize``d save every
   k steps, then a fresh ``--resume`` call to 2N.  The resumed losses must
   equal those of one uninterrupted 2N-step call, bit for bit.  Reports the
   newest manifest's ``payload_bytes`` / ``full_bytes`` and the device's
   peak memory.
c. Check the save/restore kernels on the trained state.  A criticality
   report with mixed masks (``|x| > mean|x|`` per float leaf) is built on
   device by the bitpack kernel, whose words must equal the jnp oracle's.
   The state is saved once device-packed (``save_mode="device"``: xla
   engine, pack kernel) and once host-side; the files must be identical.
   Device restore (scatter kernel) and host restore must give identical
   arrays, equal to the masked state.  One delta save runs the delta
   kernel.  The compiled ``pack_group`` must hold a ``tpu_custom_call``.

With ``--chips 4`` the same state is sharded over a 2x2 ``(data, model)``
mesh with ``params_shardings``; its report is built on the sharded state
(the bitpack kernel runs once per chip), the state is saved with one pack
per device (leaves sharded off the leading axis are first re-tiled into
per-device row ranges: ``retiled_leaves``/``retiled_bytes``), and
restored onto a different layout and onto one device: both restores must be
bit-identical to each other and to the masked state, and the resharded one
must span all four devices.

Every check raises on failure, so the script then exits non-zero; the last
line of a passing run is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, SingleDeviceSharding  # noqa: E402

from repro.checkpoint import CheckpointManager, Level  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.criticality import (DeviceLeafReport, DeviceReport,  # noqa: E402
                                    _path_str)
from repro.core.policy import LeafPolicy  # noqa: E402
from repro.distributed.sharding import params_shardings  # noqa: E402
from repro.kernels.mask_pack import ops as mask_ops  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.train.optim import OptConfig  # noqa: E402

ARCH = "xlstm-125m"
BATCH, SEQ = 2, 256
STEPS, EVERY = 4, 2             # N and k: saves at 2, 4; resume to 2N = 8
WORK = os.path.join(HERE, ".smoke")   # checkpoints (git-ignored)


def require(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


def find_chip(count: int):
    devs = jax.devices()
    d = devs[0]
    log(f"[a] device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    require(d.platform == "tpu", f"no TPU: JAX platform is {d.platform!r}")
    require(len(devs) >= count, f"{count} chips asked, {len(devs)} found")
    return devs[:count]


def same_bytes(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and \
        a.tobytes() == b.tobytes()


def trees_identical(a, b) -> bool:
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    return ta == tb and all(same_bytes(x, y) for x, y in zip(la, lb))


def train_args(ckpt: str, steps: int, every: int, preset: str):
    return ["--arch", ARCH, "--preset", preset, "--batch", str(BATCH),
            "--seq", str(SEQ), "--steps", str(steps), "--ckpt-dir", ckpt,
            "--ckpt-every", str(every), "--log-every", "1"]


def newest_manifest(ckpt: str):
    found = []
    for path in glob.glob(os.path.join(ckpt, "*", "step_*", "manifest.json")):
        found.append((int(path.split("step_")[-1].split(os.sep)[0]), path))
    require(found, f"no manifest under {ckpt}")
    step, path = max(found)
    with open(path) as f:
        return step, json.load(f)


def phase_train(work: str, *, preset="full"):
    """b: train, save scrutinized, kill, resume; returns the resumed ckpt."""
    t0 = time.perf_counter()
    ckpt = os.path.join(work, "ckpt")
    straight = train.main(train_args(os.path.join(work, "straight"),
                                     2 * STEPS, 10 * STEPS, preset))
    first = train.main(train_args(ckpt, STEPS, EVERY, preset)
                       + ["--scrutinize"])
    resumed = train.main(train_args(ckpt, 2 * STEPS, EVERY, preset)
                         + ["--scrutinize", "--resume"])
    log(f"[b] losses straight {straight}")
    log(f"[b] losses first {first} resumed {resumed}")
    require(first == straight[:STEPS], "first run left the straight run")
    require(resumed == straight[STEPS:],
            "resumed losses differ from the uninterrupted run")
    step, man = newest_manifest(ckpt)
    enc = sorted({leaf["encoding"] for leaf in man["leaves"]})
    log(f"[b] step_{step} manifest: payload_bytes={man['payload_bytes']} "
        f"full_bytes={man['full_bytes']} "
        f"({man['payload_bytes'] / man['full_bytes']:.4f} of full) "
        f"encodings={enc}")
    require(0 < man["payload_bytes"] <= man["full_bytes"],
            "payload larger than the state")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[b] peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"({time.perf_counter() - t0:.1f} s)")
    return ckpt


def fresh_state(preset: str):
    cfg = get_config(ARCH)
    if preset == "smoke":
        cfg = cfg.reduced()
    return cfg, train.build_state(cfg, OptConfig(kind="adamw"), BATCH, SEQ)


def magnitude_report(state) -> DeviceReport:
    """Criticality report of a train state with mixed masks.  Bit i of a
    parameter is ``|p_i| > mean|p|``, thresholded and bit-packed on device
    by :func:`mask_ops.threshold_bitpack` (the bitpack kernel), whose
    words and counts must equal the jnp oracle's on the same magnitudes.
    Each AdamW moment shares its parameter's mask (an element's parameter
    and moments are read together); other leaves are always critical."""
    flat = [(_path_str(p), x)
            for p, x in jax.tree_util.tree_flatten_with_path(state)[0]]
    words = {}
    for name, leaf in flat:
        if name.startswith("params/"):
            mag = jnp.abs(jnp.ravel(leaf)).astype(jnp.float32)
            tol = float(jnp.mean(mag))
            w, c = mask_ops.threshold_bitpack(mag, tol)
            ref_w, ref_c = mask_ops.threshold_bitpack(mag, tol,
                                                      use_kernel=False)
            require(same_bytes(w, ref_w) and same_bytes(c, ref_c),
                    f"bitpack kernel words differ from the oracle on {name}")
            words[name] = (w, int(np.asarray(c).sum()))
    checked = sum(x.size for name, x in flat if name in words)
    leaves = {}
    for name, leaf in flat:
        param = ("params/" + name.split("/", 2)[2]
                 if name.startswith(("opt/mu/", "opt/nu/")) else name)
        if param in words:
            w, crit = words[param]
            leaves[name] = DeviceLeafReport(name, leaf.shape, leaf.dtype,
                                            LeafPolicy.AD, leaf.size,
                                            critical=crit, words_dev=w)
        else:
            leaves[name] = DeviceLeafReport(name, leaf.shape, leaf.dtype,
                                            LeafPolicy.ALWAYS_CRITICAL,
                                            leaf.size, critical=leaf.size)
    log(f"bitpack kernel words == oracle words on {checked} magnitudes")
    return DeviceReport(leaves)


def masked(state, report):
    """The state as a scrutinized restore must give it back (fill 0)."""
    def one(path, leaf):
        rep = report.leaves[_path_str(path)]
        if rep.all_critical:
            return leaf
        mask = rep.device_mask().reshape(leaf.shape)
        return jnp.where(mask, leaf, jnp.zeros((), leaf.dtype))
    return jax.tree_util.tree_map_with_path(one, state)


def step_files(d: str, step: int):
    root = os.path.join(d, f"step_{step}")
    out = {}
    for dirpath, _, files in os.walk(root):
        for fname in files:
            p = os.path.join(dirpath, fname)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = f.read()
    return out


def save_once(d: str, state, report, mode: str, step: int = 1, **kw):
    with CheckpointManager([Level(d, **kw)], scrutiny_fn=lambda s: report,
                           save_mode=mode) as mgr:
        mgr.save(step, state, block=True)
        return mgr.wait()


def phase_kernels(work: str, ckpt: str, *, preset="full"):
    """c: kernel checks on the trained state."""
    t0 = time.perf_counter()
    _, like = fresh_state(preset)
    with CheckpointManager([Level(os.path.join(ckpt, "ram")),
                            Level(os.path.join(ckpt, "disk"))]) as mgr:
        got = mgr.restore(like)
    require(got is not None, "no checkpoint to load")
    step, state = got
    report = magnitude_report(state)
    crit = sum(r.critical for r in report.leaves.values())
    total = sum(r.n for r in report.leaves.values())
    log(f"[c] trained state from step_{step}: {total} elements, "
        f"{crit} critical ({crit / total:.4f})")

    log("[c] device and host saves")
    dirs = {m: os.path.join(work, f"save_{m}") for m in ("device", "host")}
    stats = {m: save_once(dirs[m], state, report, m) for m in dirs}
    log(f"[c] device save: mode={stats['device']['mode']} "
        f"engine={stats['device']['engine']} "
        f"packed_leaves={stats['device']['packed_leaves']} "
        f"d2h_bytes={stats['device']['d2h_bytes']} "
        f"full_bytes={stats['device']['full_bytes']}")
    require(stats["device"]["mode"] == "device"
            and stats["device"]["engine"] == "xla"
            and stats["device"]["packed_leaves"] > 0,
            "device save did not pack on the xla engine")
    require(stats["host"]["mode"] == "host", "host save not host-side")
    files = {m: step_files(dirs[m], 1) for m in dirs}
    require(files["device"] and files["device"] == files["host"],
            "device- and host-packed checkpoint files differ")
    log(f"[c] device- and host-packed files identical: "
        f"{sorted(files['device'])} "
        f"{sum(len(b) for b in files['device'].values())} bytes")

    expect = masked(state, report)
    restored = {}
    for mode in ("device", "host"):
        with CheckpointManager([Level(dirs["device"])]) as mgr:
            _, restored[mode] = mgr.restore(like, mode=mode)
            rs = mgr.last_restore_stats
        log(f"[c] {mode} restore: device_leaves={rs['device_leaves']} "
            f"h2d_bytes={rs['h2d_bytes']} full_bytes={rs['full_bytes']}")
        if mode == "device":
            require(rs["device_leaves"] > 0, "no leaf restored on device")
    require(trees_identical(restored["device"], restored["host"]),
            "device and host restores differ")
    require(trees_identical(restored["device"], expect),
            "restore differs from the masked state")
    log("[c] device restore == host restore == masked state")

    # delta: a second save of a slightly changed state, same report
    log("[c] delta save")
    d = os.path.join(work, "delta")
    leaves = {_path_str(p): x
              for p, x in jax.tree_util.tree_flatten_with_path(state)[0]}
    name = max(leaves, key=lambda k: leaves[k].size)
    changed = jax.tree_util.tree_map_with_path(
        lambda p, x: x.at[(0,) * x.ndim].add(1) if _path_str(p) == name
        else x, state)
    with CheckpointManager([Level(d, max_chain=2)],
                           scrutiny_fn=lambda s: report,
                           save_mode="device") as mgr:
        mgr.save(1, state, block=True)
        mgr.save(2, changed, block=True)
        kind = mgr.wait()["levels"][d]["kind"]
        _, back = mgr.restore(like, mode="device")
    log(f"[c] second save kind={kind}")
    require(kind == "delta", "second save was not a delta")
    require(trees_identical(back, masked(changed, report)),
            "delta restore differs from the masked state")

    log("[c] pack_group compile")
    rep = report.leaves[name]
    text = jax.jit(lambda f, m: mask_ops.pack_group(
        [f], [m], [rep.critical])).lower(
        jnp.ravel(leaves[name]), rep.device_mask()).compile().as_text()
    require("tpu_custom_call" in text, "pack_group holds no Pallas kernel")
    log(f"[c] pack_group on {name} ({rep.n} elements) compiles to a "
        f"tpu_custom_call ({time.perf_counter() - t0:.1f} s)")


def phase_sharded(work: str, devs, *, preset="full"):
    """The four-chip phase: sharded save, resharded and 1-device restore."""
    t0 = time.perf_counter()
    cfg, state = fresh_state(preset)
    mesh = Mesh(np.array(devs).reshape(2, 2), ("data", "model"))
    other = Mesh(np.array(devs).reshape(2, 2).T, ("data", "model"))

    def shardings(m):
        p = params_shardings(cfg, m, state["params"])
        rep = jax.sharding.NamedSharding(m, jax.sharding.PartitionSpec())
        return {"params": p, "opt": {"mu": p, "nu": p, "step": rep},
                "data": jax.tree_util.tree_map(lambda _: rep, state["data"]),
                "step": rep}

    sharded = jax.device_put(state, shardings(mesh))
    spans = {len(x.sharding.device_set)
             for x in jax.tree_util.tree_leaves(sharded)}
    log(f"[4] state sharded over {mesh.shape}: leaves span {spans} devices")
    report = magnitude_report(sharded)      # bitpack kernel once per chip
    d = os.path.join(work, "sharded")
    st = save_once(d, sharded, report, "device")
    log(f"[4] sharded save: mode={st['mode']} engine={st['engine']} "
        f"packed_leaves={st['packed_leaves']} "
        f"retiled_leaves={st['retiled_leaves']} "
        f"retiled_bytes={st['retiled_bytes']} "
        f"d2h_bytes={st['d2h_bytes']} full_bytes={st['full_bytes']}")
    require(st["mode"] == "device" and st["engine"] == "xla"
            and st["packed_leaves"] > 0,
            "sharded save did not pack on device")

    one = SingleDeviceSharding(devs[0])
    targets = {"resharded": shardings(other),
               "one_device": jax.tree_util.tree_map(lambda _: one, state)}
    expect = masked(sharded, report)
    back = {}
    for label, sh in targets.items():
        with CheckpointManager([Level(d)]) as mgr:
            _, back[label] = mgr.restore(state, shardings=sh, mode="device")
            rs = mgr.last_restore_stats
        log(f"[4] {label} restore: device_leaves={rs['device_leaves']} "
            f"h2d_bytes={rs['h2d_bytes']}")
        require(trees_identical(back[label], expect),
                f"{label} restore differs from the masked state")
    require(trees_identical(back["resharded"], back["one_device"]),
            "resharded and one-device restores differ")
    used = set()
    for x in jax.tree_util.tree_leaves(back["resharded"]):
        used |= {s.device for s in x.addressable_shards}
    require(used == set(devs), f"resharded restore spans {len(used)} devices")
    largest = max(jax.tree_util.tree_leaves(back["resharded"]),
                  key=lambda x: x.size)
    require({s.device for s in largest.addressable_shards} == set(devs),
            "largest restored leaf is not spread over all devices")
    log(f"[4] resharded restore spans {len(used)} devices; both restores "
        f"bit-identical to the masked state ({time.perf_counter() - t0:.1f} s)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the sharded save/restore phase")
    args = ap.parse_args(argv)
    devs = find_chip(args.chips)
    shutil.rmtree(WORK, ignore_errors=True)
    if args.chips == 4:
        phase_sharded(WORK, devs)
    else:
        ckpt = phase_train(WORK)
        phase_kernels(WORK, ckpt)
    shutil.rmtree(WORK, ignore_errors=True)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
