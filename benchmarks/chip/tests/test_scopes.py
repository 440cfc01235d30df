"""The train step's scopes, and the reduction from a trace to device time
by scope: the scopes the tiny training cut compiles with, the reduction on
made-up events, and on a small trace of that cut recorded on one TPU v5e
chip, with the wire decoder checked against the protobuf classes."""

import json
import os
import re
import sys

import pytest

import harness
import scopes
import trace_reduce as tr
from conftest import TINY_TRAIN

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OLD = os.path.join(DATA, "tpu_v5e_small.xplane.pb")
SCOPED = os.path.join(DATA, "tpu_v5e_tiny_scoped.xplane.pb")
VOCAB = ("embed", "attention", "ffn", "moe", "recurrent", "loss", "optimizer")


def test_vocabulary_is_the_programs():
    from repro.obs.scopes import ALL

    assert tuple(ALL) == VOCAB


def _tiny_step_hlo() -> str:
    import jax

    import harness
    from repro.configs.base import ArchConfig
    from repro.data import pipeline
    from repro.models import init_params
    from repro.train.optim import OptConfig, init_opt
    from repro.train.step import make_train_step

    config = harness.load_json("configs", "smollm2_135m.json")
    model = dict(config["model"], **TINY_TRAIN["model"])
    cfg, oc = ArchConfig(**model), OptConfig(**config["optimizer"])
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch, _ = pipeline.next_batch(cfg, pipeline.init_state(cfg, 2, 16))
    step = jax.jit(make_train_step(cfg, oc))
    return step.lower(params, init_opt(oc, params), batch).compile().as_text()


def test_tiny_step_carries_its_scopes():
    """Every scope the tiny Llama uses is on its compiled instructions,
    through the layer scan's remat and the backward pass, and every
    matmul lies under one."""
    insts = re.findall(r"^\s*(?:ROOT )?%\S+ = .*? ([\w-]+)\(.*"
                       r'op_name="([^"]*)"', _tiny_step_hlo(), re.M)
    assert insts
    found = {scopes.scope_of(op, VOCAB) for _, op in insts}
    assert {"embed", "attention", "ffn", "loss", "optimizer"} <= found
    assert any("transpose(" in op and
               scopes.scope_of(op, VOCAB) == "attention" for _, op in insts)
    dots = [op for opcode, op in insts if opcode == "dot"]
    assert dots
    assert all(scopes.scope_of(op, VOCAB) for op in dots), dots


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/dot_general", "attention"),
    ("jit(train_step)/transpose(jvp(loss))/while/body/dot_general", "loss"),
    ("jit(train_step)/optimizer/attention_like/add", "optimizer"),
    ("jit(train_step)/embed/ffn/mul", "ffn"),
    ("jit(f)/bd,bd->bd/dot_general", None),
    ("", None),
])
def test_innermost_scope(op_name, scope):
    assert scopes.scope_of(op_name, VOCAB) == scope


def _module(insts, comps=None):
    return ({k: (v[0], v[1], tuple(v[2:])) for k, v in insts.items()},
            comps or {})


MODULES = {
    "jit_step(1)": _module({
        "while.1": ("while", "jit(step)/while", 7),
        "fusion.1": ("fusion", "jit(step)/attention/dot_general"),
        "fusion.2": ("fusion", "jit(step)/ffn/mul"),
        "copy.1": ("copy", "jit(step)/while/body/copy"),
        "only.1": ("add", "jit(step)/loss/add")}, {7: ["fusion.1"]}),
    "jit_next(2)": _module({
        "fusion.1": ("fusion", "jit(next)/optimizer/add")}),
}


def test_unnamed_fusion_takes_the_scope_its_body_agrees_on():
    module = _module({
        "fusion.9": ("fusion", "", 1), "fusion.8": ("fusion", "", 2),
        "fusion.7": ("fusion", "", 3), "fusion.6": ("fusion", "", 4),
        "p.0": ("parameter", ""), "dot.1": ("dot", "jit(s)/loss/dot"),
        "mul.1": ("multiply", "jit(s)/ffn/mul"),
        "add.1": ("add", "jit(s)/loss/add"), "cvt.1": ("convert", "")},
        {1: ["p.0", "dot.1", "add.1", "cvt.1"], 2: ["dot.1", "mul.1"],
         3: ["fusion.9"], 4: ["p.0", "cvt.1"]})
    memo = {}
    got = {n: scopes.instruction_scope(module, n, VOCAB, memo)
           for n in ("fusion.9", "fusion.8", "fusion.7", "fusion.6")}
    assert got == {"fusion.9": "loss", "fusion.8": None,
                   "fusion.7": "loss", "fusion.6": None}


def test_by_scope_leaf_ops_of_each_program():
    """A ``while`` is left out (its body's ops are listed on their own);
    an op's program is the one its stats name, else the enclosing run of
    a program; an op of no known program is unscoped."""
    runs = [("jit_step(1)", 0, 100), ("jit_next(2)", 100, 150)]
    ops = [("%while.1 = (f32[]) while(...)", 0, 90),
           ("%fusion.1 = bf16[8] fusion(...)", 10, 30),
           ("%fusion.2 = bf16[8] fusion(...)", 30, 60),
           ("%copy.1 = bf16[8] copy(...)", 60, 70),
           ("%fusion.1 = f32[8] fusion(...)", 110, 120),
           ("only.1", 160, 165),                     # named by its stats
           ("fusion.1", 170, 171),                   # named by its stats
           ("%fusion.1 = f32[8] fusion(...)", 175, 176),   # no program
           ("%unknown.3 = f32[] add(...)", 10, 12)]
    tagged = {("only.1", 160): "jit_step(1)", ("fusion.1", 170): "jit_next(2)"}
    got = scopes.by_scope(ops, runs, tagged, MODULES, VOCAB)
    assert got == {"attention": 20, "ffn": 30, "optimizer": 10 + 1,
                   "loss": 5, None: 10 + 1 + 2}
    clipped = scopes.by_scope(ops, runs, tagged, MODULES, VOCAB, 20, 162)
    assert clipped == {"attention": 10, "ffn": 30, "optimizer": 10,
                       "loss": 2, None: 10}


def test_summarize_clips_averages_and_reads_next_batch():
    trace = {"devices": {
        "/device:TPU:0": [("%fusion.1 = x", 10, 30), ("%fusion.2 = x", 30, 60),
                          ("%copy.1 = x", 60, 70), ("%while.1 = x", 0, 90)],
        "/device:TPU:1": [("%fusion.1 = x", 10, 50)]},
        "host": [("bench.traced_window", 20, 80)]}
    profile = {"runs": {p: [("jit_step(1)", 0, 100)]
                        for p in trace["devices"]},
               "tagged": {},
               "next_batch": [(25, 27, 0.5), (75, 85, None), (5, 21, 1.0)]}
    lo, hi = tr.window_bounds(trace, tr.WINDOW_SPAN)
    out = scopes.summarize(trace, profile, MODULES, lo, hi, VOCAB)
    # plane 0 in [20, 80]: attention 10, ffn 30, unscoped 10; plane 1: 30;
    # the programs' other scopes ran no operation there
    assert out["scope_s"] == {"attention": pytest.approx(20e-9),
                              "ffn": pytest.approx(15e-9),
                              "loss": 0.0, "optimizer": 0.0}
    assert out["unscoped_s"] == pytest.approx(5e-9)
    assert out["leaf_s"] == pytest.approx(40e-9)
    assert out["unscoped_share"] == pytest.approx(0.125)
    assert out["next_batch"] == [(pytest.approx(2e-6), 0.5),
                                 (pytest.approx(10e-6), None)]


def test_wire_decoder_reads_messages():
    # field 1 varint 150; field 2 "hi"; field 3 fixed64 7; field 4 fixed32 9
    buf = (b"\x08\x96\x01" b"\x12\x02hi" b"\x19" + (7).to_bytes(8, "little")
           + b"\x25" + (9).to_bytes(4, "little"))
    f = scopes.message(buf)
    assert f[1] == [150] and bytes(f[2][0]) == b"hi"
    assert f[3] == [7] and f[4] == [9]


def _pb2():
    """The XSpace and HLO protobuf classes, where installed."""
    xplane = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    hlo = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")
    return xplane, hlo


@pytest.mark.parametrize("path", [OLD, SCOPED], ids=["small", "scoped"])
def test_wire_decoder_matches_protobuf_classes(path):
    xplane_pb2, hlo_pb2 = _pb2()
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    want = {}
    for plane in space.planes:
        if plane.name != scopes.METADATA_PLANE:
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for ev in plane.event_metadata.values():
            for stat in ev.stats:
                if names[stat.metadata_id] == scopes.HLO_STAT:
                    proto = hlo_pb2.HloProto()
                    proto.ParseFromString(stat.bytes_value)
                    comps = proto.hlo_module.computations
                    want[ev.name] = (
                        {i.name: (i.opcode, i.metadata.op_name,
                                  tuple(i.called_computation_ids))
                         for c in comps for i in c.instructions},
                        {c.id: [i.name for i in c.instructions]
                         for c in comps})
    assert want
    assert scopes.read_modules(path) == want


@pytest.fixture(scope="module")
def recorded():
    pytest.importorskip("jax")
    if not os.path.exists(SCOPED):
        pytest.fail(f"recorded trace missing: {SCOPED}")
    trace = tr.read_xplane(SCOPED)
    return (trace, scopes.read_profile(SCOPED), scopes.read_modules(SCOPED),
            tr.window_bounds(trace, tr.WINDOW_SPAN))


def test_recorded_tiny_step_by_scope(recorded):
    """The tiny cut's traced window, recorded on the chip: the scoped and
    unscoped time add up to the leaf operations' time, the layer loops are
    left out, and the train step's parts all show."""
    trace, profile, modules, (lo, hi) = recorded
    assert os.path.getsize(SCOPED) < 1 << 20
    out = scopes.summarize(trace, profile, modules, lo, hi, VOCAB)
    assert {"attention", "ffn", "loss", "optimizer"} <= set(out["scope_s"])
    ops = tr.clip(trace["devices"]["/device:TPU:0"], lo, hi)
    all_ns = sum(e - s for _, s, e in ops)
    loops = [(n, s, e) for n, s, e in ops if " while(" in n]
    assert loops
    loop_s = sum(e - s for _, s, e in loops) * 1e-9
    assert out["leaf_s"] + loop_s == pytest.approx(all_ns * 1e-9, rel=1e-9)
    assert out["leaf_s"] == pytest.approx(
        out["unscoped_s"] + sum(out["scope_s"].values()), rel=1e-12)
    assert 0 <= out["unscoped_share"] < 1
    assert out["next_batch"] and all(ms > 0 for ms, _ in out["next_batch"])


def test_next_batch_span_carries_its_cpu_time(tmp_path):
    """On a CPU trace of the pipeline, each ``data.next_batch`` span of the
    traced window carries the thread's CPU time inside it, no more than
    its length; the metric reads their mean."""
    import jax

    from repro.configs.base import ArchConfig
    from repro.data import pipeline

    config = harness.load_json("configs", "smollm2_135m.json")
    cfg = ArchConfig(**dict(config["model"], **TINY_TRAIN["model"]))
    state = pipeline.init_state(cfg, 2, 16)
    _, state = pipeline.next_batch(cfg, state)        # dispatches compiled
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            for _ in range(3):
                batch, state = pipeline.next_batch(cfg, state)
            jax.block_until_ready(batch)
    path = tr.newest_xplane(str(tmp_path))
    trace = tr.read_xplane(path)
    lo, hi = tr.window_bounds(trace, tr.WINDOW_SPAN)
    out = scopes.summarize(trace, scopes.read_profile(path), {}, lo, hi,
                           VOCAB)
    assert len(out["next_batch"]) == 3
    for ms, cpu in out["next_batch"]:
        assert 0 <= cpu <= ms * 1.05 + 0.05

    reader = harness.load_module("metrics", "next_batch_ms.train.py")

    class Run:
        trace, trace_dir, read = True, str(tmp_path), {}

    got = reader.read(Run())
    assert got == pytest.approx(sum(c for _, c in out["next_batch"]) / 3)
