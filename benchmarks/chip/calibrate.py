#!/usr/bin/env python3
"""Readings that the limits of a training cell's check are set from, and
the judgement of each under the cell's limits.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 12 --controls 3 [--first-seed N]

In one process on the chip: for each seed, the program's first steps
through the cell's own job (the same calls and sizes as the timed path)
against the plain reference, which gives the lower readings; for the
first ``--controls`` seeds also the control (the reference computed with
float8 matmul operands, put in the program's place) and the fault "half
of the batch left out, the mean taken over the rest" (planted in the
reference put in the program's place), which give the upper readings.
A state left unchanged reads 1 on ``change_gap`` by definition and needs
no run.

Each reading is judged by ``harness.judge`` against the limits of the
cell's workload file.  One JSON line per seed, then a summary line with
each number's largest program reading and smallest control and fault
readings.  Exits non-zero when a program reading fails the limits or a
control or fault reading passes them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import harness  # noqa: E402

CONTROL_QUANT = "float8_e4m3fn"


def half_batch(rows):
    return rows[: rows.shape[0] // 2]


def judged(gaps, limits):
    """The gaps compared under ``limits``, with the judgement beside."""
    checks = {k: {"value": gaps[k], "limit": v} for k, v in limits.items()}
    return dict(gaps, correct=harness.judge(checks))


def readings(job, wl, seed: int, control: bool):
    drv = harness.load_module("loops", wl["loop"] + ".py")
    n, lim = wl["checked_steps"], wl["limits"]
    job.reset(seed)
    prog = job.checked_steps(n)
    job.state = None
    ref = drv.reference(job, wl, n)
    out = {"seed": seed, "program": judged(drv.gaps(prog, ref), lim)}
    if control:
        out["control"] = judged(drv.gaps(
            drv.reference(job, wl, n, quant=CONTROL_QUANT), ref), lim)
        out["half_batch"] = judged(drv.gaps(
            drv.reference(job, wl, n, rows_of=half_batch), ref), lim)
    return out


def summary(lines, limits):
    """Per number: the program's largest reading, the smallest of the
    control and of the fault; and what was judged wrongly."""
    out = {}
    for key in ("loss_gap", "grad_gap", "change_gap"):
        out[key] = {"limit": limits.get(key)}
        for kind, pick in (("program", max), ("control", min),
                           ("half_batch", min)):
            vals = [ln[kind][key] for ln in lines if kind in ln]
            if vals:
                out[key][kind] = pick(vals)
    out["program_failed"] = [ln["seed"] for ln in lines
                             if not ln["program"]["correct"]]
    out["control_or_fault_passed"] = [
        (ln["seed"], kind) for ln in lines
        for kind in ("control", "half_batch")
        if kind in ln and ln[kind]["correct"]]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=7_000_000_001)
    args = ap.parse_args(argv)
    spec = harness.benchmark_spec()
    cell = next(c for c in spec["workloads"] if c["name"] == args.workload)
    harness.use_compile_cache()
    devices = harness.find_chips(cell["chips"])
    run = harness.Run(cell, spec, args.first_seed, 0, False, devices)
    drv = harness.load_module("loops", run.workload["loop"] + ".py")
    job = drv.Job(run.config, run.workload, args.first_seed)
    lines = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        lines.append(readings(job, run.workload, seed, i < args.controls))
        print(json.dumps(lines[-1]), flush=True)
    out = summary(lines, run.workload["limits"])
    print(json.dumps({"summary": out}), flush=True)
    return 1 if out["program_failed"] or out["control_or_fault_passed"] else 0


if __name__ == "__main__":
    sys.exit(main())
