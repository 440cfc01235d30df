"""Multi-host coordinated checkpointing: collective two-phase commit over
per-host owned shards, one global manifest, and elastic resharded restore.

The single-process ``CheckpointManager`` owns a directory end to end: one
process scrutinizes, packs, and writes every shard.  A production job is
many processes, each holding (or owning) a slice of the global state — the
``CoordinatedCheckpointManager`` makes the same scrutinized 3-stage save
multi-process-correct:

**Ownership.**  Every leaf's flat element range is partitioned across
processes *deterministically* (``distributed.collective.process_segments``:
the leading-axis tiling of the leaf's ``PartitionSpec`` when its mesh spans
processes, a near-equal contiguous split otherwise; replicated/scalar
leaves belong to the leader).  Each host packs and writes **only the bytes
it owns** — the union covers every element exactly once, so no host ever
materializes (or moves over D2H) another host's shard.

**Two-phase commit.**

::

    host 0..P-1   write shard_h<p>_<k>.bin + manifest.host<p>.json
                  into <level>/.pending_step_<N>          (phase 1)
    all           ── barrier("land") ──
    leader        fuse per-host manifests → manifest.json (global,
                  per-leaf ordered segments), validate exact coverage,
                  rename .pending_step_<N> → step_<N>,
                  write commit.json marker                (phase 2)
    all           ── barrier("commit") ──

A step is *visible* only when committed: ``latest()`` (here and in the
single-process manager) treats a coordinated ``step_<N>`` without its
``commit.json`` as partial — a leader death between the rename and the
marker — and falls back to the newest fully-committed step.  A host death
*before* commit trips the barrier timeout on the survivors: the save
raises, the pending dir stays hidden (dot-prefixed), and the previous step
remains the latest.  Stale pending dirs and dead partial commits are swept
by the leader's retention pass.

**Differential chains** ride along (``Level.max_chain``): each host keeps
its previous owned-segment payloads resident and writes per-segment
byte-chunk deltas; the leader validates every host made the same
base/delta decision before fusing (chains carry the same
``chain`` manifest section as single-process saves).

**Elastic resharded restore.**  The global manifest records every leaf's
global shape and saving layout, so ``restore(state_like, shardings=...)``
on a *different* process/device count reads only the byte ranges of each
saved segment that intersect its local shards: per-segment masks (bitmap /
regions aux) give prefix-sum payload offsets, ``ShardReader.read_range``
fetches exactly those bytes, and the device path expands them through the
``mask_scatter`` kernel per target device — a checkpoint saved on 4
processes restores onto 1, 2, or 8 without any host materializing a full
leaf.  Plain single-process checkpoints restore through the same range
reads (one whole-leaf segment), so every save↔restore topology pair
composes (tests/test_coordinated.py).
"""

from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import json
import os
import shutil
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.levels import (L1_RESIDENT, L2_PARTNER, L3_PARITY,
                                     L4_STORE, L2Stack, LEVEL_ORDER,
                                     ResidentCache, default_l2_root,
                                     partner_map, partner_of)
from repro.checkpoint.manager import (CheckpointManager, Level,
                                      _host_snapshot, update_report)
from repro.checkpoint.packing import (DeltaLeaf, delta_encode_host,
                                      packed_leaf_stub, unpack_leaf)
from repro.checkpoint.pipeline import (BytesSource, ViewSource, as_u8,
                                       fetch_to_host)
from repro.checkpoint.store import (ALIVE_FILE, ShardReader, _delta_entry,
                                    _packed_entry, chain_steps,
                                    committed_steps, fuse_global_manifest,
                                    is_step_committed, load_checkpoint_raw,
                                    pending_step_of_entry, read_manifest,
                                    segment_mask, sweep_retention,
                                    tmp_writer_alive, write_commit_marker,
                                    write_host_entries)
from repro import obs as obs_mod
from repro.obs.trace import _NULL_HANDLE
from repro.core.criticality import _path_str
from repro.distributed.collective import (BarrierTimeout, Collective,
                                          get_collective, owned_ranges,
                                          process_segments)
from repro.distributed.sharding import (device_segments,
                                        leading_axis_device_segments)
from repro.kernels.mask_pack import ops as mask_ops


class StateShapeError(RuntimeError):
    """The restoring state's leaf shape contradicts the checkpoint's.

    Deliberately *not* one of the skip-and-try-next-step errors: a shape
    mismatch is a configuration bug that would fail identically on every
    candidate step, and silently returning ``None`` (→ fresh start) from
    ``restore`` would be data loss."""


@dataclasses.dataclass
class GlobalManifest:
    """Parsed view of a checkpoint manifest with a uniform *segment*
    interface: coordinated leaves expose their per-host segments, plain
    leaves one whole-range pseudo-segment — restore code never branches on
    the on-disk flavor."""
    step: int
    manifest: Dict[str, Any]

    @classmethod
    def load(cls, root: str, step: int) -> "GlobalManifest":
        return cls(step=step, manifest=read_manifest(root, step))

    @property
    def coordinated(self) -> bool:
        return "coordinated" in self.manifest

    @property
    def process_count(self) -> int:
        return int(self.manifest.get("coordinated", {})
                   .get("process_count", 1))

    @property
    def chain(self) -> List[int]:
        return chain_steps(self.manifest)

    def leaves(self) -> Dict[str, Dict[str, Any]]:
        return {e["name"]: e for e in self.manifest["leaves"]}

    @staticmethod
    def segments_of(entry: Dict[str, Any]) -> List[Dict[str, Any]]:
        """Ordered segment entries tiling the leaf's flat range."""
        if entry.get("encoding") == "segmented":
            return sorted(entry["segments"], key=lambda s: int(s["start"]))
        n = int(np.prod(entry["shape"] or [1]))
        return [dict(entry, start=0, stop=n)]


class _LevelFetcher:
    """Per-restore-step resilience cascade: serve one segment byte range
    from the nearest live level — L1 resident payload slice, L2 partner
    replica (CRC'd; any failure falls through), then the shared store
    (whose reader transparently rebuilds torn numbered shards from parity
    = L3).  Every read is attributed in ``stats``: which level served
    each segment fetch (``level_served``) and the per-level byte counts —
    the zero-shared-store-read guarantee of a partner restore is
    ``bytes_read_store == 0``."""

    def __init__(self, mgr, root: str, step: int, rd: ShardReader,
                 l2: Optional[L2Stack], ring_count: int,
                 stats: Dict[str, Any]):
        self.mgr = mgr
        self.root = root
        self.step = step
        self.rd = rd
        self.l2 = l2
        self.ring_count = int(ring_count)
        self.stats = stats

    def read(self, name: str, s: Dict[str, Any], start_b: int,
             nbytes: int) -> bytes:
        stats = self.stats
        key = (name, int(s["start"]), int(s["stop"]))
        length = int(s["length"])
        hit = self.mgr._l1.get(self.root, self.step, key)
        if hit is not None and hit[1].nbytes == length:
            stats["level_served"][L1_RESIDENT] += 1
            stats["bytes_l1"] += nbytes
            return hit[1][start_b:start_b + nbytes].tobytes()
        if self.l2 is not None and "host" in s:
            loc = self.l2.locate(self.step, key, int(s["host"]),
                                 ring_count=self.ring_count)
            if loc is not None:
                store, src, entry, _fabric = loc
                if int(entry["length"]) == length:
                    try:
                        raw = store.read_range(self.step, src, entry,
                                               start_b, nbytes)
                    except (OSError, ValueError):
                        stats["l2_fallbacks"] = \
                            stats.get("l2_fallbacks", 0) + 1
                    else:
                        stats["level_served"][L2_PARTNER] += 1
                        stats["bytes_read_l2"] += nbytes
                        stats["bytes_read"] += nbytes
                        return raw
        before = self.rd.stats["parity_bytes"]
        raw = self.rd.read_range(s, start_b, nbytes)
        parity = self.rd.stats["parity_bytes"] - before
        stats["level_served"][L3_PARITY if parity else L4_STORE] += 1
        stats["bytes_read_store"] += nbytes
        stats["bytes_read"] += nbytes
        return raw


@dataclasses.dataclass
class _CoordChain:
    """Per-level differential-chain bookkeeping of *this host's* owned
    segments (mirrors manager._ChainState at segment granularity).
    ``sources`` is ``None`` while the step's write is still in flight on
    the writer thread (the planner only chains off a landed save — the
    per-level double buffer drains the previous write before planning)."""
    base_step: int
    chain: List[int]
    report: Any
    layout: Tuple                       # ((name, start, stop, dtype), ...)
    sources: Optional[Dict[Tuple[str, int, int], np.ndarray]] = None


class _AliveToken:
    """Rate-limited refresher for a pending dir's shared ``.alive``
    liveness file.

    The async coordinated save runs its long phases (chunked D2H, shard
    writes, land/commit barriers, the degraded wait) on a writer thread;
    every such phase calls this token so ``tmp_writer_alive`` keeps
    judging the pending dir live and a peer leader's ``_gc`` never sweeps
    an in-flight pipelined save as a carcass.  Creating the token creates
    the file, so the window between ``mkdir`` and the first shard write is
    covered too.
    """

    REFRESH_S = 2.0

    def __init__(self, pending: str):
        self.path = os.path.join(pending, ALIVE_FILE)
        with open(self.path, "w"):
            pass
        self._last = time.monotonic()

    def __call__(self) -> None:
        now = time.monotonic()
        if now - self._last < self.REFRESH_S:
            return
        self._last = now
        try:
            os.utime(self.path)
        except OSError:
            try:                        # swept under us: recreate
                with open(self.path, "w"):
                    pass
            except OSError:
                pass


def _segment_pieces(segs, flo: int, fhi: int, row: int):
    """Split the flat range ``[flo, fhi)`` of a leaf over its per-device
    row ranges ``segs`` (``device_segments``): ``[(lo, hi, flat_piece)]``
    in order, each piece on one device."""
    out = []
    for s, e, data in segs:
        lo, hi = max(flo, s * row), min(fhi, e * row)
        if lo < hi:
            out.append((lo, hi, jnp.ravel(data)[lo - s * row:hi - s * row]))
    return out


class _CoordSnapshot:
    """One coordinated save's frozen view of this host's owned segments
    (mirrors ``manager._SaveSnapshot`` at segment granularity).

    Construction runs synchronously inside ``save()`` — it is *all* the
    caller blocks for: ownership/segment classification, snapshot
    isolation (pinned host views, pinned device slices), and the stage-1
    batched pack dispatch — one ``pack_group`` call per (device, dtype)
    group covering every masked owned segment, with payload sizes taken
    from the resident report's critical counts (static, so sizing never
    needs a counts D2H).  ``materialize()`` runs on the writer thread:
    stage-2 chunked D2H of the group payloads plus the host-side gathers,
    producing the exact per-segment payload bytes the pre-pipeline
    per-segment ``pack_critical`` loop produced (byte identity is pinned
    by tests/test_coordinated.py's matrix rows).
    """

    def __init__(self, mgr: "CoordinatedCheckpointManager", state, report):
        self.engine = mgr._engine
        self._pack_opts = mgr._pack_opts
        device = (mgr.save_mode != "host" and report is not None)
        self.segs: List[Dict[str, Any]] = []
        self._views: Dict[str, np.ndarray] = {}       # host leaf -> flat view
        self._pinned: Dict[Tuple[str, int, int], Any] = {}
        self._groups: Dict[Any, Dict[str, Any]] = {}
        self._lock = threading.Lock()
        self._result = None
        self.d2h_bytes = 0
        # save-stats tree + the lock every writer-thread mutation of it
        # holds (freezing a published snapshot iterates the tree, so any
        # concurrent key insert must be excluded); obs fields are filled
        # in by save() at dispatch
        self.stats: Dict[str, Any] = {}
        self.stats_lock = threading.Lock()
        self.obs_handle: Any = _NULL_HANDLE
        self.obs_mark = 0
        self.jobs_left = 0
        self.fused_levels: List[Any] = []   # levels this host leads
        layout = []
        retiled: Dict[str, Any] = {}   # leaf -> its per-device row ranges
        for name, leaf, sh in mgr._flat_state(state)[0]:
            shape = tuple(getattr(leaf, "shape", ()))
            dtype = (str(leaf.dtype) if hasattr(leaf, "dtype")
                     else str(np.asarray(leaf).dtype))
            itemsize = np.dtype(dtype).itemsize
            rep = report.leaves.get(name) if report is not None else None
            segs = owned_ranges(shape, mgr.ctx, sh)
            row = int(np.prod(shape[1:])) if len(shape) > 1 else 1
            distributed = (isinstance(leaf, jax.Array)
                           and not getattr(leaf, "is_fully_addressable",
                                           True))
            for flo, fhi in segs:
                seg_n = fhi - flo
                mask_seg = None
                total = seg_n
                if rep is not None and not rep.all_critical:
                    mask_seg = np.asarray(rep.mask[flo:fhi], bool)
                    total = int(mask_seg.sum())
                seg = {"name": name, "flo": int(flo), "fhi": int(fhi),
                       "shape": shape, "dtype": dtype, "mask": mask_seg,
                       "nbytes": total * itemsize}
                is_dev = isinstance(leaf, jax.Array) and seg_n > 0
                use_xla = self.engine == "xla" and is_dev
                to_pack = use_xla and device and mask_seg is not None
                # a segment of a leaf on several devices is packed in one
                # piece per device (a Pallas kernel runs on one device's
                # data)
                split = (to_pack and not distributed
                         and len(leaf.devices()) > 1)
                if distributed and seg_n > 0:
                    flat_seg = mgr._local_flat_segment(leaf, flo, fhi, row)
                elif use_xla and not split:
                    flat_seg = jnp.ravel(leaf)[flo:fhi]
                else:
                    flat_seg = None
                if to_pack:
                    # stage 1: group members — one compiled pack per
                    # (device, dtype) group, payload size static
                    if split:
                        if name not in retiled:
                            retiled[name] = device_segments(leaf)[0]
                        pieces = _segment_pieces(retiled[name], flo, fhi,
                                                 row)
                    else:
                        pieces = [(int(flo), int(fhi), flat_seg)]
                    seg["kind"] = "group"
                    seg["parts"] = []
                    for lo, hi, piece in pieces:
                        key = (dtype, str(next(iter(piece.devices()))))
                        g = self._groups.setdefault(
                            key, {"flats": [], "masks": [], "totals": [],
                                  "keys": []})
                        m = mask_seg[lo - flo:hi - flo]
                        g["flats"].append(piece)
                        g["masks"].append(jnp.asarray(m))
                        g["totals"].append(int(m.sum()))
                        g["keys"].append((name, lo, hi))
                        seg["parts"].append((key, (name, lo, hi)))
                elif flat_seg is not None:
                    # pinned device slice, fetched (xla) or viewed (host
                    # backend of a distributed leaf) on the writer thread
                    self._pinned[(name, int(flo), int(fhi))] = flat_seg
                    seg["kind"] = "dev"
                else:
                    if name not in self._views and seg_n > 0:
                        self._views[name] = \
                            _host_snapshot(leaf).reshape(-1)
                    seg["kind"] = "host"
                self.segs.append(seg)
                layout.append((name, int(flo), int(fhi), dtype))
        self.layout = tuple(layout)
        for g in self._groups.values():
            payload, _counts = mask_ops.pack_group(
                g["flats"], g["masks"], g["totals"],
                use_kernel=self._pack_opts["use_kernel"],
                interpret=self._pack_opts["interpret"])
            ranges, lo = {}, 0
            for k, t in zip(g["keys"], g["totals"]):
                ranges[k] = (lo, lo + t)
                lo += t
            g["payload"], g["ranges"] = payload, ranges

    def materialize(self, heartbeat=None):
        """Writer-thread half: D2H the batched group payloads (chunked,
        double-buffered), fetch pinned raw segments, run the host-side
        gathers.  Memoized — every level's write job shares one
        materialization.  Returns ``(items, sources)`` where items are
        ``(name, flo, fhi, meta, payload_u8)`` in flat-state order and
        sources map segment keys to the uint8 payload views (the delta
        sources, the L1/L2 payloads, and the stage-3 write views are all
        the same buffers — partner payloads fork off the stage-2 stream
        instead of re-packing)."""
        with self._lock:
            if self._result is not None:
                return self._result
            group_host = {
                key: fetch_to_host([g["payload"]], heartbeat=heartbeat)
                for key, g in self._groups.items()}
            self.d2h_bytes += sum(b.nbytes for b in group_host.values())
            items, sources = [], {}
            for seg in self.segs:
                name, flo, fhi = seg["name"], seg["flo"], seg["fhi"]
                itemsize = np.dtype(seg["dtype"]).itemsize
                mask_seg = seg["mask"]
                if seg["kind"] == "group":
                    parts = []
                    for key, k in seg["parts"]:
                        lo, hi = self._groups[key]["ranges"][k]
                        parts.append(
                            group_host[key][lo * itemsize:hi * itemsize])
                    u8 = parts[0] if len(parts) == 1 else \
                        np.concatenate(parts)
                elif seg["kind"] == "dev":
                    flat_seg = self._pinned[(name, flo, fhi)]
                    if self.engine == "xla" and mask_seg is None:
                        u8 = fetch_to_host([flat_seg], heartbeat=heartbeat)
                        self.d2h_bytes += u8.nbytes
                    else:
                        arr = np.asarray(flat_seg)
                        payload = (np.ascontiguousarray(arr[mask_seg])
                                   if mask_seg is not None
                                   else np.ascontiguousarray(arr))
                        u8 = as_u8(payload)
                        self.d2h_bytes += u8.nbytes
                else:
                    flat = self._views.get(name)
                    seg_arr = (flat[flo:fhi] if flat is not None
                               else np.zeros(0, np.dtype(seg["dtype"])))
                    payload = (seg_arr[mask_seg] if mask_seg is not None
                               else np.ascontiguousarray(seg_arr))
                    u8 = as_u8(payload)
                    self.d2h_bytes += u8.nbytes
                if heartbeat is not None:
                    heartbeat()
                stub = packed_leaf_stub(name, (fhi - flo,), seg["dtype"],
                                        mask_seg, int(u8.nbytes))
                meta = _packed_entry(stub)
                meta.update(shape=list(seg["shape"]), start=flo, stop=fhi)
                items.append((name, flo, fhi, meta, u8))
                sources[(name, flo, fhi)] = u8
            self._result = (items, sources)
            return self._result


class CoordinatedCheckpointManager:
    """Drop-in coordinated variant of ``CheckpointManager``.

    ``collective`` supplies process identity + barriers
    (``distributed.collective.get_collective()`` default: the jax runtime's
    fabric barrier on a real multi-controller job, filesystem rendezvous
    under the ``REPRO_PROCESS_*`` simulation, no-op when single-process).
    On a single-process job every call delegates to an inner
    ``CheckpointManager`` — the fully pipelined async save path — so
    wiring the coordinator in unconditionally costs nothing
    (``force_coordinated=True`` runs the coordinated format/protocol even
    on one process: exercising the commit path, or pre-creating global
    manifests a later multi-host restart will reshard from).

    ``shardings``: optional pytree of ``NamedSharding``s matching the state;
    when a leaf's spec tiles its leading axis over a multi-process mesh,
    ownership follows device placement instead of the uniform split.

    Coordinated saves run the same three-stage async pipeline as the
    single-process manager: ``save(block=False)`` blocks the caller only
    for snapshot isolation + the batched stage-1 pack dispatch, then the
    chunked D2H, shard writes, land/commit barriers, and leader manifest
    fusion all run on a writer thread (per level at most one save is in
    flight — double buffering; ``wait()``/``close()`` drain and surface
    writer errors exactly once, so a barrier timeout from a dead peer
    raises from the *next* ``save``/``wait``/``close``).  Coordinated
    saves do not support precision tiering or parity on per-host files
    (they carry their own checksums; lost-file resilience comes from the
    L2 partner replicas instead).

    **Resilience hierarchy** (``checkpoint.levels``): every save lands at
    four levels — L1 this process's resident packed payloads
    (``l1_keep_n`` steps), L2 a CRC'd replica pushed to the ring partner
    (``partner_replication``; node-local stores under ``l2_root``, default
    ``<level>/.l2``), L3/L4 the shared store.  ``restore`` serves each
    segment from the nearest live level and reports which one in
    ``last_restore_stats``.  With ``degraded_saves``, a host death
    mid-save degrades instead of aborting: the land barrier's
    ``BarrierTimeout`` names the dead hosts, the surviving quorum's
    lowest-index member recovers their current-step segments from their
    partners' L2 replicas into the pending dir, and commit re-runs over
    the survivors — the checkpoint lands complete, marked ``degraded``.

    ``fault_injector``: optional ``repro.testing.faults.FaultInjector``;
    the save path calls its named seams (``pack_done``,
    ``after_replicate``, ``after_land_write``, ``before_commit_barrier``,
    ``after_commit``) so tests can place failures between any two
    protocol phases.
    """

    def __init__(self, levels: Sequence[Level],
                 collective: Optional[Collective] = None,
                 scrutiny_fn=None,
                 rescrutinize_every: int = 0,
                 save_mode: str = "auto",
                 restore_mode: str = "auto",
                 shardings: Any = None,
                 delta_chunk_bytes: int = mask_ops.DELTA_CHUNK_BYTES,
                 pack_use_kernel: Optional[bool] = None,
                 pack_interpret: bool = False,
                 barrier_timeout_s: Optional[float] = None,
                 pending_ttl_s: float = 600.0,
                 pipeline_engine: str = "auto",
                 force_coordinated: bool = False,
                 partner_replication: bool = True,
                 degraded_saves: bool = True,
                 l2_root: Optional[str] = None,
                 l1_keep_n: int = 1,
                 fault_injector: Any = None,
                 soundness_check: Any = None,
                 **manager_kwargs):
        if save_mode not in ("auto", "host", "device"):
            raise ValueError(f"unknown save_mode {save_mode!r}")
        if restore_mode not in ("auto", "host", "device"):
            raise ValueError(f"unknown restore_mode {restore_mode!r}")
        if pipeline_engine not in ("auto", "host", "xla"):
            raise ValueError(f"unknown pipeline_engine {pipeline_engine!r}")
        self.coll = collective if collective is not None else get_collective()
        self.ctx = self.coll.ctx
        # per-host telemetry bundle: own registry + drift tracker, shared
        # enabled switch and trace buffer (thread-simulated hosts merge
        # into one Perfetto-loadable trace); the collective reports its
        # barrier waits through the same registry
        self.obs = obs_mod.scoped(process=self.ctx.index,
                                  process_name=f"host{self.ctx.index}")
        self.coll.obs = self.obs
        self.levels = list(levels)
        self.scrutiny_fn = scrutiny_fn
        self.rescrutinize_every = rescrutinize_every
        # Shared with the single-process manager: cross-check every fresh
        # report before it reduces a checkpoint (every host runs the same
        # deterministic check, so decisions stay aligned).
        self.soundness_check = soundness_check
        self.save_mode = save_mode
        self.restore_mode = restore_mode
        self.shardings = shardings
        self.delta_chunk_bytes = int(delta_chunk_bytes)
        self._pack_opts = dict(use_kernel=pack_use_kernel,
                               interpret=pack_interpret)
        self.barrier_timeout_s = barrier_timeout_s
        self.pending_ttl_s = float(pending_ttl_s)
        self._inner: Optional[CheckpointManager] = None
        self._pool: Optional[cf.ThreadPoolExecutor] = None
        self._io_pool: Optional[cf.ThreadPoolExecutor] = None
        if self.ctx.count == 1 and not force_coordinated:
            self._inner = CheckpointManager(
                levels, scrutiny_fn=scrutiny_fn,
                rescrutinize_every=rescrutinize_every, save_mode=save_mode,
                restore_mode=restore_mode,
                delta_chunk_bytes=delta_chunk_bytes,
                pack_use_kernel=pack_use_kernel,
                pack_interpret=pack_interpret,
                pipeline_engine=pipeline_engine,
                soundness_check=soundness_check, **manager_kwargs)
        else:
            if manager_kwargs:
                # only meaningful on the single-process delegate path;
                # silently discarding them would also hide typos
                raise TypeError(
                    "CoordinatedCheckpointManager (multi-process): "
                    f"unsupported keyword(s) {sorted(manager_kwargs)} — "
                    "these tune the single-process pipelined manager only")
            for lv in self.levels:
                os.makedirs(lv.directory, exist_ok=True)
            # writer pools mirroring the single-process manager: one
            # pipeline job per level (double-buffered), an io pool for
            # overlapped per-shard writes
            max_shards = max((lv.shards for lv in self.levels), default=1)
            self._pool = cf.ThreadPoolExecutor(
                max_workers=max(1, len(self.levels)))
            self._io_pool = cf.ThreadPoolExecutor(
                max_workers=max(2, max_shards))
        if pipeline_engine == "auto":
            pipeline_engine = ("host" if jax.default_backend() == "cpu"
                               else "xla")
        self._engine = pipeline_engine
        self._inflight: Dict[str, cf.Future] = {}
        self._lock = threading.Lock()
        self._seq_done: Dict[str, int] = {}
        self._seq = 0
        self._saves = 0
        self._closed = False
        self._report = None
        self._chains: Dict[str, _CoordChain] = {}
        self.partner_replication = bool(partner_replication)
        self.degraded_saves = bool(degraded_saves)
        self.l2_root = l2_root
        self._l1 = ResidentCache(keep_n=l1_keep_n)
        self._l2_stacks: Dict[str, L2Stack] = {}
        self._faults = fault_injector
        self._live_save_stats: Optional[Dict[str, Any]] = None
        self.last_save_stats: Optional[Dict[str, Any]] = None
        self.last_restore_stats: Optional[Dict[str, Any]] = None
        self.last_scrutiny_stats: Optional[Dict[str, Any]] = None

    # --- lifecycle -------------------------------------------------------

    def __enter__(self) -> "CoordinatedCheckpointManager":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Drain in-flight coordinated saves (surfacing writer errors
        exactly once), shut the writer pools down, close the collective.
        Idempotent."""
        if self._closed:
            return
        try:
            if self._inner is not None:
                self._inner.close()
            else:
                try:
                    self.wait()
                finally:
                    if self._pool is not None:
                        self._pool.shutdown(wait=True)
                        self._pool = None
                    if self._io_pool is not None:
                        self._io_pool.shutdown(wait=True)
                        self._io_pool = None
        finally:
            self._closed = True
            self.coll.close()

    def wait(self):
        """Block until every in-flight save has landed; raise the first
        writer error (each error is raised exactly once — a drained
        future is removed before its result is collected).  Returns the
        *finalized* ``last_save_stats`` snapshot (writer-thread phase
        timings included)."""
        if self._inner is not None:
            return self._inner.wait()
        futs = list(self._inflight.values())
        self._inflight.clear()
        first: Optional[BaseException] = None
        for fut in futs:
            try:
                fut.result()
            except BaseException as e:   # noqa: BLE001 - re-raised below
                if first is None:
                    first = e
        if first is not None:
            raise first
        with self._lock:
            live = self._live_save_stats
        if futs and live is not None:
            # a fresh freeze of the drained save: distinct from the
            # dispatch-time snapshot however early the jobs landed
            self.last_save_stats = self.obs.registry.publish("save", live)
        return self.last_save_stats

    # --- scrutiny --------------------------------------------------------

    def _maybe_report(self, state):
        """Same schedule as the single-process manager (shared
        ``manager.update_report``; every host runs it locally, and
        determinism of ``scrutiny_fn`` keeps decisions aligned — the
        leader additionally validates at fuse time)."""
        with self.obs.tracer.span("scrutiny", saves=self._saves):
            new, ran = update_report(self.scrutiny_fn, self._report,
                                     self._saves, self.rescrutinize_every,
                                     state, check=self.soundness_check)
        if ran:
            # live view, deliberately not frozen: device reports account
            # their lazy mask D2H into these stats after publication; the
            # registry keeps a frozen copy as they stand now
            self.last_scrutiny_stats = getattr(new, "stats", None)
            if self.last_scrutiny_stats is not None:
                self.obs.registry.publish("scrutiny", self.last_scrutiny_stats)
            if new is not None and self.obs.enabled:
                with self.obs.tracer.span("scrutiny.drift"):
                    self.obs.drift.observe(new, step=self._saves)
        self._report = new
        return self._report

    # --- save ------------------------------------------------------------

    def save(self, step: int, state, block: bool = False):
        """Coordinated save, pipelined and async: the caller blocks only
        for scrutiny (when due), snapshot isolation, the stage-1 batched
        pack dispatch, and the chain plan — the chunked D2H, L2
        replication, shard writes, and the whole two-phase commit
        (barriers + leader fusion) run on a writer thread.  Per level at
        most one save is in flight: a second ``save`` first drains the
        previous write (backpressure; also what keeps barrier sequence
        tags aligned across hosts).  Writer errors — including a peer
        death's ``BarrierTimeout`` — surface exactly once, from the next
        ``save``/``wait``/``close`` (or from this call with
        ``block=True``)."""
        if self._inner is not None:
            return self._inner.save(step, state, block=block)
        if self._closed:
            raise RuntimeError("CoordinatedCheckpointManager is closed")
        t0 = time.perf_counter()
        obs_mark = self.obs.buffer.mark()
        report = self._maybe_report(state)
        self._saves += 1
        stats = {"mode": "coordinated", "process": self.ctx.index,
                 "process_count": self.ctx.count, "levels": {},
                 "host_bytes_written": 0, "d2h_bytes": 0, "blocked_s": 0.0}
        with self.obs.tracer.span("save.snapshot", step=step):
            snap = _CoordSnapshot(self, state, report)
        snap.stats = stats
        snap.obs_mark = obs_mark
        snap.obs_handle = self.obs.tracer.begin(
            f"save/step_{step}", step=step, mode="coordinated")
        fired: List[Level] = []
        futs: List[cf.Future] = []
        due = [lv for lv in self.levels if step % lv.interval == 0]
        snap.jobs_left = len(due)
        jobs = []
        for lv in due:
            # double buffer: drain the previous in-flight save for this
            # level on the caller thread (its error propagates here, once)
            prev = self._inflight.pop(lv.directory, None)
            if prev is not None:
                prev.result()
            self._seq += 1
            seq = self._seq
            tag = f"q{seq}.L{self.levels.index(lv)}"
            jobs.append((lv, seq, tag,
                         self._plan_level(lv, step, report, snap)))
        stats["blocked_s"] = time.perf_counter() - t0
        # dispatch snapshot: an immutable view of what the caller blocked
        # for.  Published before any level job exists, so the finalized
        # snapshot (writer phase timings) always replaces it when the jobs
        # drain (wait() returns that one).
        with self._lock:
            self._live_save_stats = stats
        self.last_save_stats = self.obs.registry.publish("save", stats)
        self.obs.registry.counter("save.dispatches").inc()
        for lv, seq, tag, plan in jobs:
            fut = self._pool.submit(self._run_level_job, lv, step, seq,
                                    tag, snap, plan, stats)
            self._inflight[lv.directory] = fut
            fired.append(lv)
            futs.append(fut)
        if not due:
            snap.obs_handle.finish()
        if block:
            first: Optional[BaseException] = None
            for lv, fut in zip(fired, futs):
                if self._inflight.get(lv.directory) is fut:
                    del self._inflight[lv.directory]
                try:
                    fut.result()
                except BaseException as e:  # noqa: BLE001 - re-raised
                    if first is None:
                        first = e
            if first is not None:
                raise first
        return futs

    @staticmethod
    def _shard_leaves(shardings, flat, what: str):
        """Flatten a shardings pytree alongside ``flat`` state leaves,
        refusing silently-truncating mismatches (a dropped leaf here would
        mean a leaf missing from the checkpoint — silent data loss)."""
        if shardings is None:
            return [None] * len(flat)
        out = jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: x is None or hasattr(x, "spec"))
        if len(out) != len(flat):
            raise ValueError(
                f"{what}: shardings pytree has {len(out)} leaves but the "
                f"state has {len(flat)} — they must match one-to-one "
                f"(use None entries for unsharded leaves)")
        return out

    def _flat_state(self, state):
        flat, treedef = jax.tree_util.tree_flatten_with_path(state)
        shard_flat = self._shard_leaves(self.shardings, flat, "save")
        out = []
        for (path, leaf), sh in zip(flat, shard_flat):
            out.append((_path_str(path), leaf,
                        sh if hasattr(sh, "spec") else None))
        return out, treedef

    @staticmethod
    def _local_flat_segment(leaf, flo: int, fhi: int, row: int):
        """Flat ``[flo, fhi)`` of a non-fully-addressable array, served
        from the locally addressable shard that contains it (ownership
        follows device placement, so the bytes this host owns are the
        bytes it already holds).  Raises when no local shard covers the
        range — a layout ``process_segments`` should not have assigned."""
        for shard in getattr(leaf, "addressable_shards", ()) or ():
            idx = shard.index
            if not idx:
                continue
            sl0 = idx[0]
            s = (sl0.start or 0) * row
            e = (leaf.shape[0] if sl0.stop is None else sl0.stop) * row
            if s <= flo and fhi <= e:
                return jnp.ravel(shard.data)[flo - s:fhi - s]
        raise NotImplementedError(
            f"coordinated save: owned range [{flo}, {fhi}) of a "
            f"non-fully-addressable leaf is not covered by any locally "
            f"addressable shard — pass `shardings` whose PartitionSpec "
            f"tiles the leading axis, or keep the state replicated")

    def _delta_ok(self, lv: Level, cs: Optional[_CoordChain], report,
                  layout) -> bool:
        return (cs is not None and cs.sources is not None
                and len(cs.chain) < lv.max_chain
                and report is cs.report and layout == cs.layout)

    def _plan_level(self, lv: Level, step: int, report,
                    snap: _CoordSnapshot) -> Dict[str, Any]:
        """Synchronous chain plan for one level (runs on the caller
        thread, after the previous in-flight save for this level drained,
        so the base/delta decision is identical on every host and the
        chain state is mutated race-free).  The delta *encoding* happens
        on the writer thread from the captured previous sources."""
        cs = self._chains.get(lv.directory)
        if lv.max_chain > 0 and self._delta_ok(lv, cs, report, snap.layout):
            chain = [cs.base_step] + list(cs.chain) + [step]
            prev_sources = cs.sources
            cs.chain.append(step)
            cs.sources = None       # set again when this write lands
            self.obs.registry.gauge("save.delta_chain_len").set(
                len(cs.chain))
            return {"kind": "delta", "chain": chain,
                    "prev_sources": prev_sources, "cs": cs}
        target = None
        if lv.max_chain > 0:
            target = _CoordChain(base_step=step, chain=[], report=report,
                                 layout=snap.layout, sources=None)
            self._chains[lv.directory] = target
        return {"kind": "base", "chain": [], "prev_sources": None,
                "cs": target}

    def _drop_chain(self, lv: Level, cs: Optional[_CoordChain]) -> None:
        """A chained write failed on the writer thread: the chain must
        never reference a step that did not commit.  Identity-guarded so
        a newer chain installed meanwhile is left alone."""
        with self._lock:
            if cs is not None and self._chains.get(lv.directory) is cs:
                del self._chains[lv.directory]

    def _submit_io(self):
        return self._io_pool.submit if self._io_pool is not None else None

    # --- resilience levels ----------------------------------------------

    def _fire(self, point: str, **ctx) -> None:
        """Fault-injection seam (no-op without an injector)."""
        if self._faults is not None:
            self._faults.fire(point, **ctx)

    def _l2_stack(self, lv: Level) -> Optional[L2Stack]:
        """This level's L2 ring view; None when replication is off or the
        job is single-process (a ring of one has no partner)."""
        if not self.partner_replication or self.ctx.count < 2:
            return None
        st = self._l2_stacks.get(lv.directory)
        if st is None:
            root = (os.path.join(self.l2_root,
                                 f"L{self.levels.index(lv)}")
                    if self.l2_root else default_l2_root(lv.directory))
            st = L2Stack(root, self.ctx.index, self.ctx.count)
            st.obs = self.obs
            self._l2_stacks[lv.directory] = st
        return st

    def _l2_for_root(self, root: str) -> Optional[L2Stack]:
        for lv in self.levels:
            if lv.directory == root:
                return self._l2_stack(lv)
        return None

    def _run_level_job(self, lv: Level, step: int, seq: int, tag: str,
                       snap: _CoordSnapshot, plan: Dict[str, Any], stats):
        """Writer-thread wrapper: run the level, then finalize this
        save's published stats when its last level job drains (success
        *or* failure — a failed save still finalizes what it measured)."""
        try:
            return self._run_level(lv, step, seq, tag, snap, plan, stats)
        finally:
            self._level_done(snap, step)

    def _level_done(self, snap: _CoordSnapshot, step: int) -> None:
        with snap.stats_lock:
            snap.jobs_left -= 1
            done = snap.jobs_left <= 0
        if not done:
            return
        if snap.obs_handle is not None:
            snap.obs_handle.finish()
        # identity-guarded: a newer save's dispatch snapshot must not be
        # clobbered by this (older) save's finalization
        with self._lock:
            live = self._live_save_stats is snap.stats
        if live:
            with snap.stats_lock:
                self.last_save_stats = self.obs.registry.publish(
                    "save", snap.stats)
        for lv in snap.fused_levels:
            self._fuse_telemetry(lv, step, snap)

    def _run_level(self, lv: Level, step: int, seq: int, tag: str,
                   snap: _CoordSnapshot, plan: Dict[str, Any], stats):
        """One level's pipelined save, on the writer thread: stage-2
        materialization (chunked D2H / host gathers), L2 replication
        forked off the same host buffers, stage-3 overlapped shard writes
        into the pending dir, then the land/commit protocol.  The
        ``_AliveToken`` heartbeat threads through every long phase so a
        peer's ``_gc`` keeps seeing the pending dir as live."""
        t0 = time.perf_counter()
        kind, chain = plan["kind"], plan["chain"]
        pending = os.path.join(lv.directory, f".pending_step_{step}")
        os.makedirs(pending, exist_ok=True)
        alive = _AliveToken(pending)
        l2 = self._l2_stack(lv)
        survivors = list(range(self.ctx.count))
        lv_stats: Dict[str, Any] = {"kind": kind}
        with snap.stats_lock:
            stats["levels"][lv.directory] = lv_stats
        h = snap.obs_handle
        try:
            tp = time.perf_counter()
            with h.stage("pack", level=lv.directory):
                items, sources = snap.materialize(heartbeat=alive)
            with snap.stats_lock:
                lv_stats["pack_s"] = time.perf_counter() - tp
                d2h_delta = snap.d2h_bytes - stats["d2h_bytes"]
                stats["d2h_bytes"] = snap.d2h_bytes
            if d2h_delta > 0:       # memoized materialization: count once
                self.obs.registry.counter("save.d2h_bytes").inc(
                    int(d2h_delta))
            self._fire("pack_done", name=tag, step=step)
            if l2 is not None:
                tr = time.perf_counter()
                with h.stage("replicate", level=lv.directory):
                    rep = l2.replicate(step, items)
                rep_bytes = rep["l2_local_bytes"] + rep["l2_partner_bytes"]
                with snap.stats_lock:
                    stats.setdefault("l2_bytes_replicated", 0)
                    stats["l2_bytes_replicated"] += rep_bytes
                rep["replicate_s"] = time.perf_counter() - tr
                self.obs.registry.counter(
                    "save.l2_bytes_replicated").inc(int(rep_bytes))
            else:
                rep = {}
            alive()
            self._fire("after_replicate", name=tag, step=step)
            if kind == "delta":
                prev_sources = plan["prev_sources"]
                entries = []
                with h.stage("delta", level=lv.directory):
                    for name, flo, fhi, meta, payload in items:
                        curr = sources[(name, flo, fhi)]
                        prev = prev_sources[(name, flo, fhi)]
                        idx, pay = delta_encode_host(curr, prev,
                                                     self.delta_chunk_bytes)
                        pay_b = pay.tobytes()
                        d = DeltaLeaf(name=name, shape=tuple(meta["shape"]),
                                      dtype=meta["dtype"],
                                      chunk_bytes=self.delta_chunk_bytes,
                                      total_bytes=int(curr.nbytes), idx=idx,
                                      payload=pay_b,
                                      checksum=zlib.crc32(pay_b))
                        dm = _delta_entry(d)
                        dm.update(shape=meta["shape"], start=meta["start"],
                                  stop=meta["stop"])
                        entries.append((dm, len(d.payload),
                                        BytesSource(bytes(d.payload))))
            else:
                # zero-copy chunked streams over the packed host payloads
                # (stage-2 reuse: the writer consumes ViewSource chunks)
                entries = [(meta, int(payload.nbytes), ViewSource([payload]))
                           for _, _, _, meta, payload in items]

            extra = {"step": int(step), "process_count": self.ctx.count,
                     "kind": kind}
            if chain:
                extra["chain"] = [int(s) for s in chain[:-1]]
            tw = time.perf_counter()
            with h.stage("write", level=lv.directory):
                write_host_entries(pending, self.ctx.index, entries,
                                   shards=lv.shards, extra=extra,
                                   submit=self._submit_io())
            written = sum(int(n) for _, n, _ in entries)
            with snap.stats_lock:
                stats["host_bytes_written"] += written
                lv_stats["host_bytes_written"] = written
                lv_stats["write_s"] = time.perf_counter() - tw
                lv_stats.update(rep)
            self.obs.registry.counter("save.host_bytes_written").inc(written)
            self._fire("after_land_write", name=tag, step=step)
            # phase-1 telemetry fragment: lands with the shards so the
            # leader can fuse it post-commit (this host may not survive
            # to the commit barrier); referenced in _fuse_and_commit so
            # the prune keeps it
            if self.obs.enabled:
                self._write_host_telemetry(pending, snap)

            t1 = time.perf_counter()
            with h.stage("land", level=lv.directory):
                survivors, degraded, recovered = self._land(
                    tag, lv, step, pending, kind, l2, lv_stats,
                    snap.stats_lock, heartbeat=alive)
            with snap.stats_lock:
                lv_stats["land_barrier_s"] = time.perf_counter() - t1
            if degraded is not None:
                self.obs.registry.counter("save.degraded").inc()
            if self.ctx.index == survivors[0]:
                t2 = time.perf_counter()
                with h.stage("commit", level=lv.directory):
                    self._fuse_and_commit(lv, step, pending, kind, chain,
                                          host_manifests_override=recovered,
                                          degraded=degraded)
                with snap.stats_lock:
                    lv_stats["commit_s"] = time.perf_counter() - t2
            self._fire("before_commit_barrier", name=tag, step=step)
            with h.stage("commit_barrier", level=lv.directory):
                self._commit_barrier(tag, lv, step, survivors, lv_stats,
                                     snap.stats_lock, heartbeat=alive)
            self._fire("after_commit", name=tag, step=step)
            if self.obs.enabled and self.ctx.index != survivors[0]:
                # non-leaders refresh their committed fragment with the
                # land/commit-barrier timings (the leader's own fragment
                # is refreshed in-memory at fusion time)
                final = os.path.join(lv.directory, f"step_{step}")
                if os.path.isdir(final):
                    self._write_host_telemetry(final, snap)
        except BaseException:
            # the chain must never reference a step that did not commit
            self._drop_chain(lv, plan["cs"])
            raise
        with self._lock:
            if plan["cs"] is not None \
                    and self._chains.get(lv.directory) is plan["cs"]:
                plan["cs"].sources = sources
        if self.obs.enabled and self.ctx.index == survivors[0]:
            # fusion is deferred to _level_done so the fused fragment
            # carries the finalized stats and the span's async-end event
            with snap.stats_lock:
                snap.fused_levels.append(lv)
        self._l1.put(lv.directory, step, items)
        self._cleanup_barriers(lv, seq)
        if self.ctx.index == survivors[0]:
            self._gc(lv)
        if l2 is not None:
            # every host prunes its own node-local replica store to the
            # newest keep_n committed steps — computed from the policy,
            # not the store listing, so it cannot race the leader's _gc
            steps = committed_steps(lv.directory)
            l2.gc(steps[-lv.keep_n:] if lv.keep_n else steps)
        with snap.stats_lock:
            lv_stats["total_s"] = time.perf_counter() - t0

    # --- telemetry -------------------------------------------------------

    def _write_host_telemetry(self, dirpath: str,
                              snap: _CoordSnapshot) -> None:
        """This host's telemetry fragment into ``dirpath`` (the pending
        dir in phase 1, the committed dir for the post-commit refresh).
        The published save snapshot is refreshed first so the fragment's
        stats carry the phase timings measured so far; the write is
        atomic (tmp + replace) because the leader's fusion may read the
        file while a post-commit refresh lands."""
        with snap.stats_lock:
            self.obs.registry.publish("save", snap.stats)
        frag = self.obs.telemetry_fragment(since_mark=snap.obs_mark)
        path = os.path.join(dirpath,
                            f"telemetry.host{self.ctx.index}.json")
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(frag, f)
            os.replace(tmp, path)
        except OSError:
            pass

    def _fuse_telemetry(self, lv: Level, step: int,
                        snap: _CoordSnapshot) -> None:
        """Leader, post-commit: fuse every host's phase-1 fragment into
        the committed step's ``telemetry.json``.  The leader's own
        fragment is refreshed so it carries the land/commit timings;
        writing into the committed dir after the rename is safe — the
        commit marker, not the dir contents, governs validity."""
        final = os.path.join(lv.directory, f"step_{step}")
        if not os.path.isdir(final):
            return
        hosts: Dict[str, Any] = {}
        for p in range(self.ctx.count):
            path = os.path.join(final, f"telemetry.host{p}.json")
            try:
                with open(path) as f:
                    hosts[str(p)] = json.load(f)
            except (OSError, ValueError):
                continue
        with snap.stats_lock:
            self.obs.registry.publish("save", snap.stats)
        hosts[str(self.ctx.index)] = self.obs.telemetry_fragment(
            since_mark=snap.obs_mark)
        doc = {"step": int(step), "kind": "save", "hosts": hosts}
        try:
            with open(os.path.join(final, "telemetry.json"), "w") as f:
                json.dump(doc, f)
        except OSError:
            pass

    def _cleanup_barriers(self, lv: Level, seq: int) -> None:
        """Barrier-file cleanup threshold for concurrent per-level saves:
        drop this process's rendezvous files only below the *minimum*
        completed sequence across levels.  Any seq below that minimum
        belongs to a level whose later save completed — and per-level
        saves are serial, so every participant passed the earlier
        rendezvous; deleting our file for it can never stall a peer.
        In-flight or failed saves freeze the threshold (bounded residue;
        the FileCollective leader sweeps leftovers at construction)."""
        with self._lock:
            done = self._seq_done
            done[lv.directory] = max(done.get(lv.directory, 0), int(seq))
            threshold = min(done.values())
        self.coll.cleanup(threshold)

    # --- failure detection & degraded commit -----------------------------

    def _land(self, tag: str, lv: Level, step: int, pending: str,
              kind: str, l2: Optional[L2Stack], lv_stats, stats_lock,
              heartbeat: Optional[Any] = None):
        """The land barrier, with degradation: on a ``BarrierTimeout`` the
        surviving quorum recovers the dead hosts' current-step segments
        from their partners' L2 replicas and re-runs the rendezvous over
        the survivors only.  Returns ``(survivors, degraded_info,
        recovered_manifests)``."""
        name = f"{tag}.land"
        try:
            self.coll.barrier(name, timeout=self.barrier_timeout_s,
                              heartbeat=heartbeat)
            return list(range(self.ctx.count)), None, None
        except BarrierTimeout as e:
            if not (self.degraded_saves and l2 is not None and e.missing):
                raise
            missing = list(e.missing)
            survivors = [p for p in range(self.ctx.count)
                         if p not in missing]
            if not survivors or self.ctx.index not in survivors:
                raise
            deg_path = os.path.join(pending, f".degraded_{tag}.json")
            recovered = None
            if self.ctx.index == survivors[0]:
                recovered = {}
                try:
                    for d in missing:
                        holder = partner_of(d, self.ctx.count)
                        if holder not in survivors:
                            raise FileNotFoundError(
                                f"host {d}'s partner {holder} is also "
                                f"dead — no L2 replica reachable")
                        recovered[d] = self._recover_host(
                            lv, step, pending, kind, d, holder, lv_stats,
                            stats_lock)
                except (OSError, ValueError) as rec_err:
                    # recovery impossible (host died before replicating,
                    # replica corrupt, partner dead too): the save fails
                    # as it would have without degradation
                    raise e from rec_err
                degraded = {
                    "survivors": survivors, "missing": missing,
                    "recovered_from": {str(d): partner_of(d, self.ctx.count)
                                       for d in missing}}
                tmp = deg_path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(degraded, f)
                os.rename(tmp, deg_path)
            else:
                degraded = self._await_degraded(deg_path, e,
                                                heartbeat=heartbeat)
                survivors = [int(p) for p in degraded["survivors"]]
                if self.ctx.index not in survivors:
                    raise
            with stats_lock:
                lv_stats["degraded"] = degraded
            self.coll.barrier(f"{name}2", timeout=self.barrier_timeout_s,
                              participants=survivors, heartbeat=heartbeat)
            return survivors, degraded, recovered

    def _await_degraded(self, deg_path: str, orig: BarrierTimeout,
                        heartbeat: Optional[Any] = None):
        """Non-leading survivors wait for the recovery leader's degraded
        plan (it is authoritative: per-host ``missing`` views can differ
        by stragglers)."""
        timeout = (self.barrier_timeout_s
                   if self.barrier_timeout_s is not None
                   else getattr(self.coll, "timeout_s", 120.0))
        deadline = time.monotonic() + float(timeout)
        poll = 0.01
        while time.monotonic() <= deadline:
            if heartbeat is not None:
                heartbeat()
            try:
                with open(deg_path) as f:
                    return json.load(f)
            except (OSError, ValueError):
                pass
            time.sleep(poll)
            poll = min(poll * 2, 0.25)
        raise orig

    def _recover_host(self, lv: Level, step: int, pending: str, kind: str,
                      dead: int, holder: int, lv_stats,
                      stats_lock) -> Dict[str, Any]:
        """Materialize a dead host's segments into the pending dir from
        its partner's CRC-verified L2 replica.  The replica holds the full
        current-step packed payloads, so even mid-delta-chain the
        recovered entries simply *replace* that host's segments at this
        step (the chain walk applies full entries as replacements).
        Recovery writes under a distinct shard prefix — a stalled-but-
        alive original writer can never race the recovered bytes."""
        pairs = self._l2_stack(lv).store_of(holder).read_all(step, dead)
        entries = []
        for e, raw in pairs:
            meta = {k: v for k, v in e.items()
                    if k not in ("offset", "length", "checksum", "file")}
            entries.append((meta, len(raw), BytesSource(raw)))
        extra = {"step": int(step), "process_count": self.ctx.count,
                 "kind": kind, "recovered_from": int(holder)}
        write_host_entries(pending, dead, entries, shards=lv.shards,
                           extra=extra, prefix=f"l2r_h{dead}_")
        with stats_lock:
            lv_stats.setdefault("l2_recovered_bytes", 0)
            lv_stats["l2_recovered_bytes"] += sum(len(r) for _, r in pairs)
        with open(os.path.join(pending,
                               f"manifest.host{dead}.json")) as f:
            return json.load(f)

    def _commit_barrier(self, tag: str, lv: Level, step: int,
                        survivors: List[int], lv_stats, stats_lock,
                        heartbeat: Optional[Any] = None) -> None:
        """The commit barrier tolerates members dying *after* the commit
        marker landed: the step is durably visible, so survivors report
        the missing hosts instead of failing a complete checkpoint."""
        participants = (survivors if len(survivors) < self.ctx.count
                        else None)
        try:
            self.coll.barrier(f"{tag}.commit",
                              timeout=self.barrier_timeout_s,
                              participants=participants,
                              heartbeat=heartbeat)
        except BarrierTimeout as e:
            if not is_step_committed(lv.directory, step):
                raise
            with stats_lock:
                lv_stats["commit_barrier_missing"] = list(e.missing)

    def _fuse_and_commit(self, lv: Level, step: int, pending: str,
                         kind: str, chain: List[int],
                         host_manifests_override=None,
                         degraded=None) -> None:
        """Phase 2 (leader): validate host agreement, fuse, rename,
        commit-mark.  ``host_manifests_override`` carries the degraded
        recovery's in-memory manifests for dead hosts — authoritative over
        anything a stalled original writer may still land on disk."""
        override = host_manifests_override or {}
        host_manifests = {}
        for p in range(self.ctx.count):
            if p in override:
                host_manifests[p] = override[p]
                continue
            path = os.path.join(pending, f"manifest.host{p}.json")
            if not os.path.exists(path):
                raise FileNotFoundError(
                    f"coordinated step {step}: host {p} manifest missing")
            with open(path) as f:
                hm = json.load(f)
            if hm.get("kind", "base") != kind:
                raise ValueError(
                    f"coordinated step {step}: host {p} wrote a "
                    f"{hm.get('kind')!r} save but the leader planned "
                    f"{kind!r} — chains diverged")
            host_manifests[p] = hm
        extra = {"resilience": {
            "levels": list(LEVEL_ORDER),
            "l2_partner_map": ({str(p): q for p, q
                                in partner_map(self.ctx.count).items()}
                               if self._l2_stack(lv) is not None else None)}}
        if degraded is not None:
            extra["degraded"] = degraded
        if kind == "delta":
            extra["chain"] = {"base_step": int(chain[0]),
                              "delta_chain": [int(s) for s in chain[:-1]]}
        manifest = fuse_global_manifest(pending, step, self.ctx.count,
                                        manifest_extra=extra,
                                        host_manifests=host_manifests)
        # A crashed prior attempt (possibly with a different process
        # count) may have left foreign host files in the reused pending
        # dir; only files the fused manifest references may be committed.
        referenced = {"manifest.json"}
        referenced.update(f"manifest.host{p}.json"
                          for p in range(self.ctx.count))
        # phase-1 telemetry fragments ride along (only present when
        # observability is enabled); the post-commit fusion reads them
        referenced.add("telemetry.json")
        referenced.update(f"telemetry.host{p}.json"
                          for p in range(self.ctx.count))
        for leaf in manifest["leaves"]:
            referenced.update(s["file"] for s in leaf["segments"])
        for f in os.listdir(pending):
            if f not in referenced:
                path = os.path.join(pending, f)
                (shutil.rmtree if os.path.isdir(path)
                 else os.unlink)(path)
        final = os.path.join(lv.directory, f"step_{step}")
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(pending, final)
        info = {"step": int(step), "process_count": self.ctx.count,
                "kind": kind}
        if degraded is not None:
            info["degraded"] = degraded
        write_commit_marker(final, info)

    # --- retention (leader only) ----------------------------------------

    def _gc(self, lv: Level) -> None:
        try:
            entries = os.listdir(lv.directory)
        except FileNotFoundError:
            return
        for e in entries:
            if pending_step_of_entry(e) is not None:
                # liveness-file mtime (dir mtime fallback), like the tmp
                # sweep: appends to existing shard files don't touch the
                # dir mtime, so a long-streaming phase 1 must be judged by
                # its refreshed .alive
                if not tmp_writer_alive(lv.directory, e,
                                        self.pending_ttl_s):
                    shutil.rmtree(os.path.join(lv.directory, e),
                                  ignore_errors=True)
        sweep_retention(lv.directory, lv.keep_n)

    # --- restore ---------------------------------------------------------

    def latest(self) -> Optional[Tuple[int, str]]:
        if self._inner is not None:
            return self._inner.latest()
        best = None
        for lv in self.levels:
            for s in committed_steps(lv.directory):
                if best is None or s > best[0]:
                    best = (s, lv.directory)
        return best

    def _candidates(self) -> List[Tuple[int, str]]:
        if self._inner is not None:
            return self._inner._candidates()
        out = [(s, lv.directory) for lv in self.levels
               for s in committed_steps(lv.directory)]
        return sorted(out, key=lambda x: -x[0])

    def restore(self, state_like, shardings=None, fill=0,
                mode: Optional[str] = None, local_only: bool = False):
        """Elastic resharded restore: newest committed step → (step, state).

        Reads only the byte ranges of each saved segment intersecting this
        host's target shards.  The target layout comes from ``shardings``
        (per-device leading-axis segments — the real multi-controller
        path, where each host fetches exactly its addressable shards) when
        given; with ``local_only=True`` it falls back to this process's
        deterministic ownership split of the restoring mesh (positions
        outside the owned ranges then hold ``fill`` — for consumers that
        shard the result themselves); otherwise every leaf is read whole
        (replicated state, e.g. the single-controller-per-host train
        loop).  Leaves absent from the checkpoint keep their
        ``state_like`` value.  Delta-chain steps reconstruct segment
        payloads first (chain walk), then slice.

        Each segment range is served from the nearest live resilience
        level — L1 resident payloads (this manager's own recent save),
        L2 partner replica (CRC-checked; any failure falls through), then
        the shared store with transparent L3 parity rebuild.
        ``last_restore_stats`` records ``bytes_read`` (I/O bytes actually
        fetched: L2 + store), ``bytes_read_l2`` / ``bytes_read_store`` /
        ``bytes_l1`` (per-level byte accounting — a pure partner restore
        shows ``bytes_read_store == 0``), ``level_served`` (segment-fetch
        counts per level), and ``h2d_bytes``.
        """
        mode = self.restore_mode if mode is None else mode
        if mode not in ("auto", "host", "device"):
            raise ValueError(f"unknown restore mode {mode!r}")
        skipped: List[Dict[str, Any]] = []
        for step, root in self._candidates():
            try:
                return self._restore_step(root, step, state_like, shardings,
                                          fill, mode, skipped, local_only)
            except (OSError, ValueError, KeyError) as e:
                skipped.append({"step": step, "root": root, "error": str(e)})
                continue
        self.last_restore_stats = self.obs.registry.publish(
            "restore", {"skipped": skipped, "step": None})
        return None

    def _restore_step(self, root, step, state_like, shardings, fill, mode,
                      skipped, local_only=False):
        gm = GlobalManifest.load(root, step)
        stats = {"step": step, "mode": mode, "bytes_read": 0,
                 "bytes_read_l2": 0, "bytes_read_store": 0, "bytes_l1": 0,
                 "level_served": {lvl: 0 for lvl in LEVEL_ORDER},
                 "h2d_bytes": 0, "missing_leaves": [], "skipped": skipped,
                 "chain": bool(gm.chain)}
        # Delta chains (and precision-tiered leaves, whose payloads are
        # variable-width) cannot be range-addressed: reconstruct the full
        # payloads once, then slice locally.  The chain walk reads the
        # shared store (XOR rebuilds attributed to L3).
        tiered = any(s.get("region_tiers")
                     for e in gm.manifest["leaves"]
                     for s in GlobalManifest.segments_of(e))
        chain_packed = None
        if gm.chain or tiered:
            io: Dict[str, int] = {}
            _, chain_packed, _ = load_checkpoint_raw(root, step,
                                                     io_stats=io)
            read = int(io.get("bytes_read", 0)) or int(
                gm.manifest.get("payload_bytes", 0))
            parity = int(io.get("parity_bytes", 0))
            stats["bytes_read"] = read
            stats["bytes_read_store"] = read
            stats["level_served"][L3_PARITY if parity else L4_STORE] += 1

        flat, treedef = jax.tree_util.tree_flatten_with_path(state_like)
        try:
            shard_flat = self._shard_leaves(shardings, flat, "restore")
        except ValueError as e:         # config bug, not a skippable step
            raise StateShapeError(str(e)) from e
        entries = gm.leaves()
        d = os.path.join(root, f"step_{step}")
        out = []
        with self.obs.tracer.span("restore.read", step=step), \
                ShardReader(d, int(gm.manifest.get("shards", 0) or 1)) as rd:
            fetcher = _LevelFetcher(self, root, step, rd,
                                    self._l2_for_root(root),
                                    gm.process_count, stats)
            for (path, leaf), sh in zip(flat, shard_flat):
                name = _path_str(path)
                e = entries.get(name)
                if e is None:
                    stats["missing_leaves"].append(name)
                    arr = np.asarray(leaf)
                    out.append(jax.device_put(arr, sh)
                               if sh is not None else jnp.asarray(arr))
                    continue
                out.append(self._restore_leaf(fetcher, e, leaf, sh, fill,
                                              mode, stats, chain_packed,
                                              local_only))
        self.last_restore_stats = self.obs.registry.publish(
            "restore", stats)
        reg = self.obs.registry
        reg.counter("restore.h2d_bytes").inc(int(stats["h2d_bytes"]))
        reg.counter("restore.bytes_read").inc(int(stats["bytes_read"]))
        if stats["bytes_read_store"] == 0 and (stats["bytes_read_l2"]
                                               or stats["bytes_l1"]):
            # the zero-shared-store-read guarantee of a partner restore
            reg.counter("restore.partner_served").inc()
        return step, jax.tree_util.tree_unflatten(treedef, out)

    def _target_ranges(self, shape, sh, local_only=False):
        """This host's target leading-axis row ranges: per-device from the
        sharding when given, else (``local_only``) this process's
        ownership split, else the whole leaf."""
        if sh is not None:
            segs = leading_axis_device_segments(sh, shape)
            if segs is not None:
                return [(a, b, dev) for a, b, dev in segs], True
        if local_only and self.ctx.count > 1 and shape:
            return [(a, b, None) for a, b, owner
                    in process_segments(shape, self.ctx.count)
                    if owner == self.ctx.index], False
        rows = shape[0] if shape else 1
        return [(0, rows, None)], False

    def _restore_leaf(self, fetcher, e, leaf, sh, fill, mode, stats,
                      chain_packed, local_only=False):
        shape = tuple(e["shape"])
        dtype = np.dtype(e["dtype"])
        want = tuple(getattr(leaf, "shape", ()))
        if want and want != shape:
            raise StateShapeError(
                f"leaf {e['name']}: checkpoint shape {shape} "
                f"vs state {want}")
        row = int(np.prod(shape[1:])) if len(shape) > 1 else 1
        if chain_packed is not None:
            # chain-reconstructed (or tiered) leaves: full unpack, slice
            full = unpack_leaf(chain_packed[e["name"]],
                               fill=fill).reshape(-1)
            targets, devs = self._target_ranges(shape, sh, local_only)
            pieces = [(a, b, dev, full[a * row:b * row])
                      for a, b, dev in targets]
            return self._assemble(e, shape, dtype, leaf, sh, fill, mode,
                                  stats, pieces, devs)

        targets, devs = self._target_ranges(shape, sh, local_only)
        segs = GlobalManifest.segments_of(e)
        itemsize = dtype.itemsize
        # per-segment mask decode + prefix sums, computed once however
        # many target ranges (devices) intersect the segment
        seg_cache: Dict[int, Any] = {}

        def seg_mask_cum(i, s):
            if i not in seg_cache:
                sm = segment_mask(s, int(s["stop"]) - int(s["start"]))
                cum = (None if sm is None
                       else np.concatenate([[0], np.cumsum(sm)]))
                seg_cache[i] = (sm, cum)
            return seg_cache[i]

        def read_checked(s, start_b, nbytes):
            """Level-cascade range read; a read spanning the whole entry
            is CRC-checked against the manifest (partial ranges cannot be
            — they are counted so callers can audit the trade-off)."""
            raw = fetcher.read(e["name"], s, start_b, nbytes)
            if start_b == 0 and nbytes == int(s["length"]):
                if zlib.crc32(raw) != s["checksum"]:
                    raise IOError(
                        f"checksum mismatch for leaf {e['name']} segment "
                        f"[{s['start']}, {s['stop']})")
            else:
                stats["unverified_ranges"] = \
                    stats.get("unverified_ranges", 0) + 1
            return raw

        pieces = []
        for a, b, dev in targets:
            flo, fhi = a * row, b * row
            local_n = fhi - flo
            mask_piece = np.zeros(local_n, bool)
            pay_parts = []
            for i, s in enumerate(segs):
                s0, s1 = int(s["start"]), int(s["stop"])
                lo, hi = max(flo, s0), min(fhi, s1)
                if lo >= hi:
                    continue
                sm, cum = seg_mask_cum(i, s)
                if sm is None:          # full segment: raw element range
                    pay_parts.append(read_checked(
                        s, (lo - s0) * itemsize, (hi - lo) * itemsize))
                    mask_piece[lo - flo:hi - flo] = True
                    continue
                p0, p1 = int(cum[lo - s0]), int(cum[hi - s0])
                if p1 > p0:
                    pay_parts.append(read_checked(
                        s, p0 * itemsize, (p1 - p0) * itemsize))
                mask_piece[lo - flo:hi - flo] = sm[lo - s0:hi - s0]
            payload = np.frombuffer(b"".join(pay_parts), dtype)
            pieces.append((a, b, dev, (payload, mask_piece)))
        return self._assemble(e, shape, dtype, leaf, sh, fill, mode, stats,
                              pieces, devs, packedform=True)

    def _assemble(self, e, shape, dtype, leaf, sh, fill, mode, stats,
                  pieces, per_device, packedform=False):
        """Expand per-target-range pieces and assemble the leaf.

        Device mode expands each range through ``mask_scatter`` (payload +
        bit-packed mask H2D only); with a per-device target layout the
        global array is built from single-device pieces, never
        materializing the full leaf on host.
        """
        want_dtype = getattr(leaf, "dtype", dtype)
        row = int(np.prod(shape[1:])) if len(shape) > 1 else 1
        use_dev = mode in ("auto", "device")

        def expand_host(piece, local_n) -> np.ndarray:
            if not packedform:          # already-dense host slice
                return np.ascontiguousarray(piece)
            payload, mask = piece
            outp = np.full(local_n, fill, dtype)
            outp[mask] = payload
            return outp

        def expand_dev(piece, local_n, device):
            put = (lambda x: jax.device_put(x, device)) \
                if device is not None else jnp.asarray
            if not packedform:
                a = np.ascontiguousarray(piece)
                stats["h2d_bytes"] += a.nbytes
                return put(a)
            payload, mask = piece
            bits = np.packbits(mask)
            m_dev = mask_ops.expand_mask_bits(put(bits), n=local_n)
            arr = mask_ops.mask_scatter(put(payload), m_dev, n=local_n,
                                        fill=fill, **self._pack_opts)
            stats["h2d_bytes"] += payload.nbytes + bits.nbytes
            return arr

        if per_device and sh is not None and use_dev:
            devs = []
            for a, b, dev, piece in pieces:
                local = expand_dev(piece, (b - a) * row, dev)
                local = local.reshape((b - a,) + shape[1:])
                if str(local.dtype) != str(want_dtype):
                    local = local.astype(want_dtype)
                devs.append(local)
            return jax.make_array_from_single_device_arrays(
                tuple(shape), sh, devs)

        # host-local assembly: owned ranges expanded, the rest is fill
        full_n = int(np.prod(shape)) if shape else 1
        if use_dev and sh is None and len(pieces) == 1 \
                and pieces[0][0] == 0 and (pieces[0][1] * row == full_n
                                           or not shape):
            arr = expand_dev(pieces[0][3], full_n, None).reshape(shape)
            if str(arr.dtype) != str(want_dtype):
                arr = arr.astype(want_dtype)
            return arr
        outp = np.full(full_n, fill, dtype)
        for a, b, _dev, piece in pieces:
            outp[a * row:b * row] = expand_host(piece, (b - a) * row)
        outp = outp.reshape(shape).astype(want_dtype, copy=False)
        if sh is not None:
            stats["h2d_bytes"] += outp.nbytes
            return jax.device_put(outp, sh)
        return jnp.asarray(outp)
