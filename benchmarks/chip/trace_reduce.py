"""From a profiler trace to device metrics.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation that ran.  Host planes hold the bench spans
(``jax.profiler.TraceAnnotation``) on the same clock.

- busy: the union of a chip's operation intervals inside the traced
  window, averaged over the chips used;
- kernel time: the summed durations of the operations whose name (or
  whose stats) contain a kernel's stable name;
- breakdown: the operations that took most time, and the longest idle
  gaps, each named by the innermost bench span open at its middle.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)
Event = Tuple[str, float, float]        # (name, start_ns, end_ns)

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _event_text(ev) -> str:
    """The event's name and its string stats (the HLO op and kernel
    names live there on some backends)."""
    parts = [ev.name]
    parts.extend(value for _, value in ev.stats if isinstance(value, str))
    return " ".join(parts)


def read_xplane(path: str) -> Dict[str, Any]:
    """``{"devices": {plane: [Event]}, "host": [Event]}``; device events
    come from the ``XLA Ops`` line, host events from every host line."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((_event_text(e), e.start_ns, e.end_ns)
                               for e in line.events)
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events)
    return {"devices": devices, "host": host}


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(events: Iterable[Event]) -> float:
    return sum(e - s for s, e in union((s, e) for _, s, e in events))


def gaps(events: Iterable[Event], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of one chip inside [lo, hi]."""
    out, t = [], lo
    for s, e in union((s, e) for _, s, e in events):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def kernel_ns(events: Iterable[Event], names: Sequence[str]) -> float:
    """Summed device time of the operations matching any of ``names``."""
    return sum(e - s for n, s, e in events if any(k in n for k in names))


def op_totals(events: Iterable[Event]) -> Dict[str, float]:
    tot: Dict[str, float] = {}
    for n, s, e in events:
        key = n.split(" ")[0]
        tot[key] = tot.get(key, 0.0) + (e - s)
    return tot


def span_at(spans: Sequence[Event], t: float, names: Sequence[str]) -> str:
    """The innermost (latest-starting) bench span open at ``t``."""
    best, best_start = "no bench span", None
    for n, s, e in spans:
        if s <= t <= e and n in names and (best_start is None
                                           or s >= best_start):
            best, best_start = n, s
    return best


def reduce(trace: Dict[str, Any], lo: float, hi: float,
           span_names: Sequence[str]) -> Dict[str, Any]:
    """Busy and idle time, per-op totals and the breakdown, for the window
    [lo, hi] (ns, the trace's clock), averaged over the device planes."""
    planes = {k: clip(v, lo, hi) for k, v in trace["devices"].items()}
    n = max(len(planes), 1)
    window = (hi - lo) * 1e-9
    busy = sum(busy_ns(evs) for evs in planes.values()) / n * 1e-9
    totals: Dict[str, float] = {}
    for evs in planes.values():
        for k, v in op_totals(evs).items():
            totals[k] = totals.get(k, 0.0) + v / n * 1e-9
    spans = [ev for ev in trace["host"] if ev[0] in span_names]
    idle: List[Tuple[str, float]] = []
    for evs in planes.values():
        for s, e in gaps(evs, lo, hi):
            idle.append((span_at(spans, (s + e) / 2, span_names),
                         (e - s) * 1e-9))
    idle.sort(key=lambda x: -x[1])
    top_ops = sorted(totals.items(), key=lambda x: -x[1])[:TOP]
    return {
        "window_s": window,
        "busy_s": busy,
        "planes": planes,
        "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                      "idle_gaps": [[k, v] for k, v in idle[:TOP]]},
    }


def window_bounds(trace: Dict[str, Any], span_name: str
                  ) -> Optional[Tuple[float, float]]:
    """The traced window on the trace's clock: the bench span that the
    loop opens around it."""
    found = [(s, e) for n, s, e in trace["host"] if n == span_name]
    if not found:
        return None
    return min(s for s, _ in found), max(e for _, e in found)


WINDOW_SPAN = "bench.traced_window"


def summarize(run) -> Dict[str, Any]:
    """Reduce the run's trace; what metric readers use is kept with it."""
    path = newest_xplane(run.trace_dir)
    if path is None:
        raise RuntimeError(f"no trace under {run.trace_dir}")
    trace = read_xplane(path)
    bounds = window_bounds(trace, WINDOW_SPAN)
    if bounds is None:
        raise RuntimeError(f"no {WINDOW_SPAN} span in {path}")
    names = sorted({s["name"] for s in run.spans.items})
    out = reduce(trace, bounds[0], bounds[1], names)
    if out["busy_s"] <= 0:
        raise RuntimeError("no operation ran on the device in the window")
    return out


def kernel_seconds(summary: Dict[str, Any], names: Sequence[str]) -> float:
    """A kernel's device seconds in the traced window, per chip."""
    planes = summary["planes"]
    n = max(len(planes), 1)
    return sum(kernel_ns(evs, names) for evs in planes.values()) / n * 1e-9
