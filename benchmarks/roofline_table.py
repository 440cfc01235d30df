"""§Roofline: render the 40-cell baseline table from experiments/dryrun."""

from __future__ import annotations

import json
import os

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")

HEADER = ("| arch | shape | mesh | t_comp ms | t_mem ms | t_coll ms "
          "| dominant | useful | roofline |")
SEP = "|---|---|---|---|---|---|---|---|---|"


def rows(mesh_filter=None):
    if not os.path.isdir(DRYRUN_DIR):
        return []
    out = []
    for fn in sorted(os.listdir(DRYRUN_DIR)):
        if not fn.endswith(".json"):
            continue
        with open(os.path.join(DRYRUN_DIR, fn)) as f:
            r = json.load(f)
        if mesh_filter and r.get("mesh") != mesh_filter:
            continue
        out.append(r)
    return out


def _recompute(r):
    """Re-derive useful/roofline from raw stored terms with the *current*
    model_flops (kept comparable across baseline/optimized snapshots)."""
    try:
        from repro.configs import get_config
        from repro.launch.roofline import model_flops
        from repro.launch.specs import SHAPES

        mf = model_flops(get_config(r["arch"]), SHAPES[r["shape"]])
        r = dict(r)
        r["model_flops"] = mf
        chips = r["chips"]
        from repro.launch.roofline import PEAK_FLOPS
        t_model = mf / (chips * PEAK_FLOPS)
        t_bound = max(r["t_compute_ms"], r["t_memory_ms"],
                      r["t_collective_ms"]) / 1e3
        r["useful_fraction"] = mf / r["flops"] if r["flops"] else 0.0
        r["roofline_fraction"] = t_model / t_bound if t_bound else 0.0
    except Exception:
        pass
    return r


def render(out=print, mesh="pod16x16", directory=None):
    global DRYRUN_DIR
    if directory:
        DRYRUN_DIR = directory
    out(f"== Roofline table ({mesh}; {os.path.basename(str(DRYRUN_DIR))}) ==")
    out(HEADER)
    out(SEP)
    n_ok = n_skip = n_fail = 0
    for r in rows(mesh_filter=None):
        if r.get("mesh") not in (mesh, None) and r["status"] == "ok":
            continue
        if r["status"] == "skipped":
            n_skip += 1
            out(f"| {r['arch']} | {r['shape']} | — | — | — | — | skipped "
                f"| — | — |")
            continue
        if r["status"] != "ok":
            n_fail += 1
            out(f"| {r['arch']} | {r['shape']} | — | FAILED: "
                f"{r.get('error','?')[:60]} |")
            continue
        n_ok += 1
        r = _recompute(r)
        out(f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['t_compute_ms']:.2f} | {r['t_memory_ms']:.2f} "
            f"| {r['t_collective_ms']:.2f} | {r['dominant']} "
            f"| {100*r['useful_fraction']:.0f}% "
            f"| {100*r['roofline_fraction']:.2f}% |")
    out(f"\n{n_ok} ok, {n_skip} skipped (assigned), {n_fail} failed")


if __name__ == "__main__":
    render()
