"""The reduction from a profiler trace to device metrics, on made-up
events and on a small trace recorded on one TPU v5e chip."""

import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tpu_v5e_small.xplane.pb")


def test_busy_is_the_union_of_op_intervals():
    evs = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 22, 25)]
    assert tr.union([(s, e) for _, s, e in evs]) == [(0, 15), (20, 30)]
    assert tr.busy_ns(evs) == 25
    assert tr.gaps(evs, 0, 40) == [(15, 20), (30, 40)]
    assert tr.gaps(evs, -5, 30) == [(-5, 0), (15, 20)]


def test_clip_to_window_and_kernel_time():
    evs = [("pack_kernel", 0, 10), ("fusion.1", 10, 20), ("pack_kernel", 18, 40)]
    inside = tr.clip(evs, 5, 30)
    assert inside == [("pack_kernel", 5, 10), ("fusion.1", 10, 20),
                      ("pack_kernel", 18, 30)]
    assert tr.kernel_ns(inside, ["pack_kernel"]) == 17
    assert tr.op_totals(inside) == {"pack_kernel": 17, "fusion.1": 10}


def test_idle_gaps_named_by_innermost_bench_span():
    trace = {"devices": {"/device:TPU:0": [("op", 0, 10), ("op", 30, 40)]},
             "host": [("bench.traced_window", 0, 50), ("train.step", 0, 12),
                      ("save.dispatch", 12, 35), ("other", 0, 50)]}
    out = tr.reduce(trace, 0, 50, ["bench.traced_window", "train.step",
                                   "save.dispatch"])
    assert out["window_s"] == pytest.approx(50e-9)
    assert out["busy_s"] == pytest.approx(20e-9)
    gaps = dict((k, v) for k, v in out["breakdown"]["idle_gaps"])
    assert gaps["save.dispatch"] == pytest.approx(20e-9)      # 10..30
    assert gaps["bench.traced_window"] == pytest.approx(10e-9)  # 40..50
    assert out["breakdown"]["device_ops"] == [["op", pytest.approx(20e-9)]]


def test_busy_averages_over_chips():
    trace = {"devices": {"/device:TPU:0": [("op", 0, 10)],
                         "/device:TPU:1": [("op", 0, 30)]}, "host": []}
    out = tr.reduce(trace, 0, 40, [])
    assert out["busy_s"] == pytest.approx(20e-9)
    assert tr.kernel_seconds(out, ["op"]) == pytest.approx(20e-9)


@pytest.fixture(scope="module")
def recorded():
    pytest.importorskip("jax")
    if not os.path.exists(RECORDED):
        pytest.fail(f"recorded trace missing: {RECORDED}")
    return tr.read_xplane(RECORDED)


def test_recorded_trace_has_a_device_plane(recorded):
    assert list(recorded["devices"]) == ["/device:TPU:0"]
    assert recorded["devices"]["/device:TPU:0"]
    names = {n for n, _, _ in recorded["host"]}
    assert {"bench.traced_window", "train.step", "save.dispatch"} <= names


def test_recorded_shares_stay_within_100(recorded):
    """Busy share, and the matmul kernel's share of the bf16 peak, on a
    recorded window: calls of tanh(x @ x).sum() on a 1024 x 1024 bf16 x,
    each one ``%fusion`` op of 2 * 1024**3 FLOPs."""
    import peaks

    lo, hi = tr.window_bounds(recorded, tr.WINDOW_SPAN)
    out = tr.reduce(recorded, lo, hi, ["bench.traced_window", "train.step",
                                       "save.dispatch"])
    assert 0 < out["busy_s"] <= out["window_s"]
    idle = 100 * (1 - out["busy_s"] / out["window_s"])
    assert 0 <= idle <= 100
    fusions = [e for e in out["planes"]["/device:TPU:0"]
               if e[0].startswith("%fusion ")]
    assert fusions
    flops = len(fusions) * 2 * 1024 ** 3
    seconds = tr.kernel_seconds(out, ["%fusion "])
    share = 100 * flops / seconds / peaks.peaks("TPU v5 lite")["bf16_flops"]
    assert 50 < share <= 100
    assert len(out["breakdown"]["idle_gaps"]) <= 10
    assert {k for k, _ in out["breakdown"]["idle_gaps"]} <= {
        "bench.traced_window", "train.step", "save.dispatch",
        "no bench span"}


def test_unknown_device_kind_is_an_error():
    import peaks

    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
