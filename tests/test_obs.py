"""Telemetry fabric: spans, metrics, drift, and checkpoint fusion.

Covers the observability layer's load-bearing guarantees:

* cross-thread span parenting and the Chrome trace-event schema
  (``b``/``e`` async pairs matched by ``(cat, id)``, stage sub-spans
  linked via ``args.parent``, per-(pid, tid) metadata);
* the off-by-default fast path — disabled, nothing is buffered, nothing
  registered and no ``telemetry.json`` written, while spans still reach a
  profiler session, on the clock of the calls they enclose;
* the scrutiny walk's phase times, published frozen by both managers;
* drift-tracker bit-exactness: the device XOR/popcount path against a
  numpy ``packbits``/``unpackbits`` oracle, the host-mask path, and the
  identical-report zero-flip fast path;
* published stat snapshots are deep-frozen (the stats-publication race
  fix): mutators raise, JSON export and list comparisons keep working;
* 2-host thread-simulated coordinated save: the leader fuses per-host
  fragments into one ``telemetry.json`` whose merged trace carries spans
  from ≥3 threads, and the report CLI renders it.
"""

import glob
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.checkpoint import CheckpointManager, Level
from repro.checkpoint.coordinator import CoordinatedCheckpointManager
from repro.core import participation
from repro.core.criticality import CriticalityReport, LeafReport
from repro.core.policy import LeafPolicy
from repro.core.regions import RegionTable
from repro.distributed.collective import (BarrierTimeout, FileCollective,
                                          ProcessContext)
from repro.obs import report as report_mod
from repro.obs.drift import DriftTracker
from repro.obs.metrics import (FrozenStats, MetricsRegistry, _NULL_METRIC,
                               freeze_stats)
from repro.obs.trace import ObsState, _NULL_HANDLE


@pytest.fixture
def obs_on():
    """Fresh global bundle with tracing enabled; restores the default
    (disabled, empty) state afterwards."""
    obs.reset()
    obs.enable()
    yield obs.get_obs()
    obs.disable()
    obs.reset()


def _report(state, frac=0.4, seed=1):
    rng = np.random.RandomState(seed)
    leaves = {}
    for name, leaf in state.items():
        n = int(np.prod(np.shape(leaf))) or 1
        mask = rng.rand(n) < frac
        leaves[name] = LeafReport(
            name=name, shape=tuple(np.shape(leaf)),
            dtype=np.dtype(np.asarray(leaf).dtype),
            policy=LeafPolicy.AD, mask=mask,
            table=RegionTable.from_mask(mask, np.asarray(leaf).itemsize),
            magnitude=None)
    return CriticalityReport(leaves=leaves)


# --------------------------------------------------------------------------
# span tracer
# --------------------------------------------------------------------------

def test_cross_thread_span_parenting_and_schema(obs_on):
    """begin() on one thread, stages on three workers, finish() on a
    worker: the async pair matches by (cat, id) and every stage links
    back via args.parent."""
    tracer = obs_on.tracer
    handle = tracer.begin("save.pipeline", step=3)
    done = threading.Barrier(3 + 1)

    def worker(i):
        with handle.stage(f"stage{i}", shard=i):
            pass
        if i == 0:
            handle.finish(ok=True)
        done.wait(timeout=30)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    [t.start() for t in ts]
    done.wait(timeout=30)
    [t.join() for t in ts]

    evs = obs_on.buffer.events_since(0)
    begins = [e for e in evs if e["ph"] == "b"]
    ends = [e for e in evs if e["ph"] == "e"]
    assert len(begins) == len(ends) == 1
    assert (begins[0]["cat"], begins[0]["id"]) == \
        (ends[0]["cat"], ends[0]["id"])
    assert ends[0]["tid"] != begins[0]["tid"]      # finished off-thread
    stages = [e for e in evs if e["ph"] == "X"]
    assert len(stages) == 3
    assert all(e["args"]["parent"] == handle.id for e in stages)
    assert len({e["tid"] for e in stages}) == 3    # one per worker thread
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in evs)
    # schema round-trips as Chrome trace JSON
    doc = json.loads(json.dumps(obs_on.buffer.to_chrome()))
    assert doc["traceEvents"] and doc["displayTimeUnit"] == "ms"
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("M", "X", "b", "e", "i")
        assert "pid" in ev and "tid" in ev


def test_disabled_path_is_noop_singletons():
    """Disabled, nothing is buffered and nothing registered: ``begin()``
    and the metric accessors hand back the shared null objects, and a span
    or a null handle's stage is a profiler annotation alone."""
    obs.reset()
    obs.disable()
    bundle = obs.get_obs()
    n0 = len(bundle.buffer)
    assert bundle.tracer.begin("y") is _NULL_HANDLE
    assert bundle.registry.counter("c") is _NULL_METRIC
    assert bundle.registry.gauge("g") is _NULL_METRIC
    assert bundle.registry.histogram("h") is _NULL_METRIC
    with bundle.tracer.span("x", a=1) as sp:
        sp.set(b=2)
        with _NULL_HANDLE.stage("z"):
            bundle.registry.counter("c").inc(5)
    assert len(bundle.buffer) == n0
    assert bundle.registry.to_dict() == {
        "counters": {}, "gauges": {}, "histograms": {}}


def test_span_reaches_the_profiler_with_obs_off(tmp_path):
    """With REPRO_OBS off a span still enters the profiler: a trace's host
    plane holds it, around the dispatch of the jitted call it encloses
    (one clock), and the Chrome buffer stays empty."""
    from jax.profiler import ProfileData

    obs.reset()
    obs.disable()
    bundle = obs.get_obs()
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64), jnp.float32)
    f(x).block_until_ready()
    n0 = len(bundle.buffer)
    with jax.profiler.trace(str(tmp_path)):
        with bundle.tracer.span("probe.span"):
            f(x).block_until_ready()
    assert len(bundle.buffer) == n0
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [(e.name, e.start_ns, e.end_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    (span,) = [e for e in host if e[0] == "probe.span"]
    calls = [e for e in host if e[0].startswith("PjitFunction")]
    assert calls
    for _, start, end in calls:
        assert span[1] <= start <= end <= span[2]


@pytest.mark.parametrize("on", [False, True])
def test_telemetry_file_only_with_obs_on(tmp_path, on):
    obs.reset()
    (obs.enable if on else obs.disable)()
    try:
        state = {"w": jnp.arange(256, dtype=jnp.float32)}
        with CheckpointManager([Level(str(tmp_path / "lv"))]) as mgr:
            mgr.save(1, state, block=True)
            mgr.wait()
        assert os.path.exists(str(tmp_path / "lv" / "step_1" / "manifest.json"))
        written = os.path.exists(
            str(tmp_path / "lv" / "step_1" / "telemetry.json"))
        assert written == on
        assert (obs.get_obs().buffer.mark() > 0) == on      # events
    finally:
        obs.disable()
        obs.reset()


def test_participation_records_its_phases():
    state = {"w": jnp.arange(64, dtype=jnp.float32),
             "step": jnp.asarray(1, jnp.int32)}
    rep = participation(lambda s: {"y": s["w"][:32] * 2.0}, state)
    stages = rep.stats["stages"]
    assert set(stages) == {"trace_s", "forward_s", "taint_s", "tables_s"}
    assert all(v >= 0 for v in stages.values())
    assert rep["w"].mask.tolist() == [True] * 32 + [False] * 32


@pytest.mark.parametrize("coordinated", [False, True])
def test_manager_publishes_scrutiny_frozen(tmp_path, coordinated):
    """The first save's scrutiny stats go through the registry as a frozen
    ``"scrutiny"`` snapshot, as the save stats do; tracing stays off."""
    obs.reset()
    obs.disable()
    state = {"w": jnp.arange(64, dtype=jnp.float32),
             "step": jnp.asarray(1, jnp.int32)}

    def scrutiny(s):
        return participation(lambda t: {"y": t["w"][:32] * 2.0}, s)

    levels = [Level(str(tmp_path / "lv"))]
    if coordinated:
        coll = FileCollective(str(tmp_path / "rdv"),
                              ctx=ProcessContext(0, 1), timeout_s=30)
        mgr = CoordinatedCheckpointManager(levels, collective=coll,
                                           scrutiny_fn=scrutiny,
                                           force_coordinated=True)
    else:
        mgr = CheckpointManager(levels, scrutiny_fn=scrutiny)
    with mgr:
        mgr.save(1, state, block=True)
        live = mgr.last_scrutiny_stats
        published = mgr.obs.registry.published["scrutiny"]
    assert isinstance(published, FrozenStats)
    assert isinstance(published["stages"], FrozenStats)
    assert dict(published["stages"]) == dict(live["stages"])
    with pytest.raises(TypeError):
        published["stages"]["forward_s"] = 0.0
    assert obs.get_obs().buffer.mark() == 0       # no event buffered


def test_registry_thread_safety(obs_on):
    reg = obs_on.registry
    n_threads, per = 8, 1000

    def worker():
        c = reg.counter("bytes")
        for _ in range(per):
            c.inc(2)

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert reg.to_dict()["counters"]["bytes"] == 2 * n_threads * per


def test_gauge_and_histogram_values(obs_on):
    reg = obs_on.registry
    reg.gauge("gap").set(0.5)
    reg.gauge("gap").set(0.2)
    assert reg.to_dict()["gauges"]["gap"] == {"value": 0.2, "max": 0.5}
    for v in (1.0, 3.0, 2.0):
        reg.histogram("wait").observe(v)
    h = reg.to_dict()["histograms"]["wait"]
    assert h == {"count": 3, "sum": 6.0, "mean": 2.0,
                 "min": 1.0, "max": 3.0, "last": 2.0}


# --------------------------------------------------------------------------
# frozen stat snapshots
# --------------------------------------------------------------------------

def test_freeze_stats_immutability():
    frozen = freeze_stats({"a": 1, "nested": {"b": 2},
                           "levels": ["extra", {"c": 3}]})
    assert isinstance(frozen, FrozenStats)
    assert isinstance(frozen["nested"], FrozenStats)
    for mutate in (lambda: frozen.__setitem__("x", 1),
                   lambda: frozen.pop("a"),
                   lambda: frozen.update(a=2),
                   lambda: frozen["nested"].clear()):
        with pytest.raises(TypeError):
            mutate()
    # lists stay plain lists (callers compare with == [...]) and the
    # whole tree still serializes
    assert frozen["levels"][0:1] == ["extra"]
    assert isinstance(frozen["levels"][1], FrozenStats)
    assert json.loads(json.dumps(frozen)) == \
        {"a": 1, "nested": {"b": 2}, "levels": ["extra", {"c": 3}]}


def test_manager_publishes_frozen_stats(tmp_path):
    """Dispatch publishes one frozen snapshot at save() return; wait()
    finalizes a *different* frozen snapshot — readers never observe a
    half-written dict (publication is on even with tracing disabled)."""
    state = {"w": jnp.arange(256, dtype=jnp.float32),
             "step": jnp.asarray(1, jnp.int32)}
    with CheckpointManager([Level(str(tmp_path / "lv"))]) as mgr:
        mgr.save(1, state, block=False)
        dispatched = mgr.last_save_stats
        assert isinstance(dispatched, FrozenStats)
        with pytest.raises(TypeError):
            dispatched["oops"] = 1
        finalized = mgr.wait()
    assert isinstance(finalized, FrozenStats)
    assert finalized is not dispatched


# --------------------------------------------------------------------------
# drift tracker
# --------------------------------------------------------------------------

class _WordsLeaf:
    """Device-style leaf: packed mask words living in a jnp array."""

    def __init__(self, mask):
        self.n = int(mask.size)
        self.words_dev = jnp.asarray(np.packbits(mask))


class _MaskLeaf:
    """Host-style leaf: a plain boolean mask."""

    def __init__(self, mask):
        self.n = int(mask.size)
        self.mask = mask


def _oracle(mask0, mask1):
    w0, w1 = np.packbits(mask0), np.packbits(mask1)
    x = np.bitwise_xor(w0, w1)
    return int(np.unpackbits(x).sum()), int(np.count_nonzero(x))


@pytest.mark.parametrize("leaf_cls", [_WordsLeaf, _MaskLeaf])
def test_drift_matches_numpy_xor_oracle(leaf_cls):
    """Per-leaf flips and changed words are bit-exact against the numpy
    packbits/XOR/popcount oracle on both the device-words and host-mask
    paths, including a non-byte-aligned leaf (tail pad bits)."""
    rng = np.random.RandomState(0)
    m0 = {"w": rng.rand(4096) < 0.3, "b": rng.rand(37) < 0.5}
    m1 = {k: v.copy() for k, v in m0.items()}
    m1["w"][::7] ^= True
    m1["b"][3] ^= True
    reg = MetricsRegistry(ObsState(True))
    tracker = DriftTracker(reg)
    tracker.observe({k: leaf_cls(v) for k, v in m0.items()}, step=1)
    rec = tracker.observe({k: leaf_cls(v) for k, v in m1.items()}, step=2)
    total = 0
    for name in m0:
        flips, churn = _oracle(m0[name], m1[name])
        e = rec["leaves"][name]
        assert e["flips"] == flips, name
        assert e["changed_words"] == churn, name
        assert e["flip_rate"] == pytest.approx(flips / m0[name].size)
        assert e["critical_count"] == int(m1[name].sum()), name
        total += flips
    assert rec["total_flips"] == total
    assert rec["flip_rate"] == pytest.approx(total / (4096 + 37))
    assert reg.to_dict()["counters"]["drift.sweeps"] == 2


def test_drift_identical_report_fast_path():
    """Re-observing the same leaves object records a zero-flip sweep
    without re-packing (re-scrutiny reuse on the save hot path)."""
    rng = np.random.RandomState(3)
    leaves = {"w": _MaskLeaf(rng.rand(512) < 0.4)}
    reg = MetricsRegistry(ObsState(True))
    tracker = DriftTracker(reg)
    first = tracker.observe(leaves, step=1)
    again = tracker.observe(leaves, step=2)
    assert again["total_flips"] == 0
    assert again["leaves"]["w"]["flip_rate"] == 0.0
    assert again["leaves"]["w"]["critical_count"] == \
        first["leaves"]["w"]["critical_count"]
    assert len(tracker.history) == 2
    assert reg.to_dict()["counters"]["drift.sweeps"] == 2


# --------------------------------------------------------------------------
# instrumented call sites
# --------------------------------------------------------------------------

def test_scrutinize_feeds_registry(obs_on):
    from repro.core import ScrutinyConfig, scrutinize

    state = {"w": jnp.asarray(np.random.RandomState(0).randn(64),
                              jnp.float32)}
    scrutinize(lambda s: {"loss": jnp.sum(s["w"] ** 2)}, state,
               config=ScrutinyConfig(probes=1), key=jax.random.PRNGKey(0))
    snap = obs_on.registry.to_dict()
    assert snap["histograms"]["scrutiny.sweep_s"]["count"] == 1
    assert "scrutiny.d2h_bytes" in snap["counters"]
    names = {e["name"] for e in obs_on.buffer.events_since(0)}
    assert {"scrutiny.prepass", "scrutiny.sweep"} <= names


def test_barrier_metrics_success(obs_on, tmp_path):
    bundles = [obs.scoped(p) for p in range(2)]
    errors = [None, None]

    def host(p):
        try:
            coll = FileCollective(str(tmp_path), ctx=ProcessContext(p, 2),
                                  timeout_s=30)
            coll.obs = bundles[p]
            coll.barrier("sync", timeout=30)
        except BaseException as e:            # pragma: no cover
            errors[p] = e

    ts = [threading.Thread(target=host, args=(p,)) for p in range(2)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert errors == [None, None]
    for p in range(2):
        snap = bundles[p].registry.to_dict()
        assert snap["histograms"]["barrier.wait_s"]["count"] == 1
        gaps = {k for k in snap["gauges"] if
                k.startswith("barrier.arrival_gap_s.")}
        assert gaps == {"barrier.arrival_gap_s.host0",
                        "barrier.arrival_gap_s.host1"}


def test_barrier_timeout_records_arrivals(obs_on, tmp_path):
    coll = FileCollective(str(tmp_path), ctx=ProcessContext(0, 2),
                          timeout_s=30)
    coll.obs = obs_on
    with pytest.raises(BarrierTimeout) as ei:
        coll.barrier("alone", timeout=0.3)
    assert ei.value.arrivals == {0: 0.0}      # peer 1 never arrived
    snap = obs_on.registry.to_dict()
    assert snap["counters"]["barrier.timeouts"] == 1
    assert snap["histograms"]["barrier.wait_s"]["count"] == 1


# --------------------------------------------------------------------------
# fragments + coordinated fusion
# --------------------------------------------------------------------------

def test_fragment_metadata_and_pid_filter(obs_on):
    """A fragment taken after a mark still carries the (pid, tid) name
    metadata emitted before it, and span_snapshot keeps only own-pid
    events (thread-sim hosts share one buffer)."""
    h0, h1 = obs.scoped(0, "simhost0"), obs.scoped(1, "simhost1")
    with h0.tracer.span("early"):
        pass
    mark = h0.buffer.mark()
    with h0.tracer.span("late"):
        pass
    with h1.tracer.span("other"):
        pass
    frag = h0.telemetry_fragment(since_mark=mark)
    names = [e["name"] for e in frag["spans"]]
    assert "late" in names and "early" not in names
    assert "other" not in names               # pid 1 filtered out
    assert "process_name" in names            # metadata survives the mark
    assert all(e["pid"] == 0 for e in frag["spans"])
    assert frag["process"] == 0


def test_coordinated_fusion_and_report_cli(obs_on, tmp_path, capsys):
    """2-host thread-sim save: the leader fuses per-host fragments into
    telemetry.json; the merged trace has spans from >=3 threads and the
    report CLI renders timeline + drift from it."""
    root, coord = str(tmp_path / "lv"), str(tmp_path / "rdv")
    n = 512

    def make_state(seed):
        rng = np.random.RandomState(seed)
        return {"w": jnp.asarray(rng.randn(n, 8), jnp.float32),
                "b": jnp.asarray(rng.randn(40), jnp.float32),
                "step": jnp.asarray(7, jnp.int32)}

    errors = [None, None]

    def host(p):
        try:
            coll = FileCollective(coord, ctx=ProcessContext(p, 2),
                                  timeout_s=30)
            rep = _report(make_state(0))
            mgr = CoordinatedCheckpointManager(
                [Level(root, keep_n=3)], collective=coll,
                scrutiny_fn=lambda s: rep, save_mode="device",
                pack_use_kernel=False, pack_interpret=True)
            mgr.save(1, make_state(0))
            mgr.wait()
            mgr.save(2, make_state(2))
            mgr.wait()
            mgr.close()
        except BaseException as e:
            import traceback
            traceback.print_exc()
            errors[p] = e

    ts = [threading.Thread(target=host, args=(p,)) for p in range(2)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert errors == [None, None]

    tj = os.path.join(root, "step_2", "telemetry.json")
    assert os.path.exists(tj)
    with open(tj) as f:
        doc = json.load(f)
    assert sorted(doc["hosts"]) == ["0", "1"]
    assert doc["step"] == 2
    for p, frag in doc["hosts"].items():
        pids = {e["pid"] for e in frag["spans"]}
        assert pids <= {int(p)}               # no peer spans in a fragment
        assert frag["drift"], p               # drift history rode along
        assert frag["published"].get("save"), p

    merged = report_mod.merge_trace(doc)
    real = [e for e in merged["traceEvents"] if e["ph"] != "M"]
    assert len({(e["pid"], e["tid"]) for e in real}) >= 3
    assert {e["pid"] for e in real} == {0, 1}

    trace_out = str(tmp_path / "trace.json")
    assert report_mod.main([root, "--trace-out", trace_out]) == 0
    rendered = capsys.readouterr().out
    assert "save timeline" in rendered
    assert "criticality drift" in rendered
    assert "host 0" in rendered and "host 1" in rendered
    with open(trace_out) as f:
        assert json.load(f)["traceEvents"]


def test_report_cli_missing_telemetry(tmp_path):
    assert report_mod.main([str(tmp_path)]) == 2
