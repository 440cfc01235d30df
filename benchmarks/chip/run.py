#!/usr/bin/env python3
"""One run of one benchmark cell on the chip this process finds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

In one process: find the chips the cell asks for (exit non-zero, with no
result, when JAX finds no TPU or too few), set up the cell's loop
(weights and inputs from the seed, every shape warmed up), measure for
``--seconds``, check what the timed path produced against the plain
reference, and print one JSON line as the last line of standard output.
With ``--trace 0`` its metrics are the cell's end-to-end metrics; with
``--trace 1`` the loop also profiles a short segment after the window,
and the metrics are the cell's per-layer metrics, read by
``metrics/<name>.py``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def cell_metrics(spec, cell_name: str, kind: str):
    return [m for m in spec[kind]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer(run, spec) -> dict:
    """Each per-layer metric of the cell, from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell_metrics(spec, run.cell["name"], "per_layer"):
        reader = harness.load_module("metrics", m["name"] + ".py")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(run) -> str:
    """Drive the cell, read its metrics, judge the checks; returns the
    result line."""
    spec = run.spec
    run.compiles.install()
    loop = harness.load_module("loops", run.workload["loop"] + ".py")
    res = loop.run(run)

    breakdown = None
    if run.trace:
        import trace_reduce
        summary = trace_reduce.summarize(run)
        run.read["trace"] = summary
        res["device"].update(busy_s=summary["busy_s"],
                             window_s=summary["window_s"])
        breakdown = summary["breakdown"]
        metrics = per_layer(run, spec)
    else:
        e2e = res["e2e"]
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell_metrics(spec, run.cell["name"], "end_to_end")}
    checks = res["checks"]
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return harness.result_line(
        correct=harness.judge(checks), attempted=res["attempted"],
        failed=res["failed"], metrics=metrics, device=res["device"],
        checks=checks, breakdown=breakdown)


def main(argv=None) -> int:
    args = parse(argv)
    spec = harness.benchmark_spec()
    cells = {c["name"]: c for c in spec["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}")
    cell = cells[args.workload]
    # the TPU runtime's logs stay inside the checkout too
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(harness.WORK, "tpu_logs"))
    harness.use_compile_cache()
    devices = harness.find_chips(cell["chips"])
    run = harness.Run(cell, spec, args.seed, args.seconds, bool(args.trace),
                      devices)
    run.t_start = T_PROCESS
    print(execute(run), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
