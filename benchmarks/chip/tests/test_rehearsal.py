"""A whole run of the training cell on the CPU at a tiny size: the result
line's schema, a correct run, the per-layer metrics of a traced run, and
the refusal to run without a chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
CELL = "smollm2_135m.train_ckpt"


def cpu_trace_as_device(monkeypatch):
    """On the CPU the XLA ops run on a host thread: read that thread as
    the device plane (rehearsal only; no device number comes of it)."""
    import trace_reduce

    real = trace_reduce.read_xplane

    def read(path):
        from jax.profiler import ProfileData

        out = real(path)
        ops = []
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                if line.name.startswith("tf_XLAPjRtCpuClient"):
                    ops.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events if e.duration_ns > 0)
        out["devices"] = {"/device:TPU:0": ops}
        return out

    monkeypatch.setattr(trace_reduce, "read_xplane", read)


def test_train_cell_result_line(tiny_run, capsys):
    import harness
    import run as runmod

    r = tiny_run(CELL)
    line = json.loads(runmod.execute(r))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    spec = harness.benchmark_spec()
    want = {m["name"]: m["unit"] for m in spec["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    assert {"kind", "memory_peak_bytes"} <= set(dev)
    for name, c in line["checks"].items():
        assert set(c) == {"value", "limit"}, name
    err = capsys.readouterr().err
    assert "check restore_leaves_differing" in err
    assert "check disk_restore_leaves_differing" in err


def test_train_cell_traced(tiny_run, monkeypatch):
    import harness
    import run as runmod

    import peaks

    cpu_trace_as_device(monkeypatch)
    # the CPU stands in for the chip here; its shares are only checked to
    # stay within 100%, never reported
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    r = tiny_run(CELL, trace=True)
    line = json.loads(runmod.execute(r))
    assert line["correct"] is True, line["checks"]
    dev = line["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    spec = harness.benchmark_spec()
    layer = {m["name"]: m for m in spec["per_layer"]
             if CELL in m.get("workloads", [CELL])}
    assert set(line["metrics"]) == set(layer)
    for name, m in line["metrics"].items():
        assert m["unit"] == layer[name]["unit"]
        if m["unit"] == "%":
            assert 0 <= m["value"] <= 100, (name, m)
    bd = line["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


@pytest.mark.parametrize("layout", ["checkout", "benchmark_only"])
def test_no_chip_no_result(tmp_path, layout):
    """Without a TPU (here JAX is held to the CPU), and in a directory that
    holds only the benchmark's files, a run exits non-zero and prints no
    result line."""
    if layout == "checkout":
        cwd = ROOT
    else:
        cwd = str(tmp_path)
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), cwd)
        shutil.copytree(BENCH, os.path.join(cwd, "benchmarks", "chip"),
                        ignore=shutil.ignore_patterns(".work", ".cache",
                                                      "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELL,
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
