"""Checkpoint telemetry fabric: spans, metrics, and criticality drift.

One :class:`Observability` bundle = a span :class:`~repro.obs.trace.Tracer`
(bound to a host's Chrome-trace ``pid``), a
:class:`~repro.obs.metrics.MetricsRegistry`, and a
:class:`~repro.obs.drift.DriftTracker`.  The module-level singleton
(:func:`get_obs`) serves single-process users; a coordinated manager calls
:func:`scoped` with its process index so every simulated/real host gets
its own registry + drift tracker and its own process-track in the shared
trace buffer — all hosts of a thread-simulated run land in *one*
Perfetto-loadable file.

Two switches.  The profiler is the switch for annotations: every
same-thread span (``span()``, a handle's ``stage()``) also enters a
``jax.profiler.TraceAnnotation`` under its own name, which records only
inside a profiler session, on the device trace's clock.  ``REPRO_OBS``
is the switch for the Chrome buffer, the metrics and ``telemetry.json``;
it is **off by default**, and then ``begin()`` returns a null handle,
``span()`` an annotation alone, and metric accessors a null metric, so
nothing is buffered, registered or written.  Enable it with
:func:`enable`, the ``REPRO_OBS=1`` environment variable, or per-test
via ``enable()``/``disable()`` in a try/finally.  ``repro.obs.scopes``
names the train step's parts on the device trace.

The managers' ``last_*_stats`` attributes are published *through* the
registry (:meth:`MetricsRegistry.publish`) as immutable deep-frozen
snapshots regardless of the enabled switch — freezing is correctness
(the old dicts raced with writer threads), not telemetry.
"""

from __future__ import annotations

import os
from typing import Optional

from repro.obs.drift import DriftTracker
from repro.obs.metrics import (FrozenStats, MetricsRegistry, freeze_stats)
from repro.obs.trace import ObsState, TraceBuffer, Tracer

__all__ = [
    "Observability", "get_obs", "scoped", "enable", "disable", "enabled",
    "reset", "FrozenStats", "freeze_stats", "MetricsRegistry", "Tracer",
    "TraceBuffer", "DriftTracker", "ObsState",
]


class Observability:
    """One host's telemetry bundle over the shared state + trace buffer."""

    def __init__(self, state: ObsState, buffer: TraceBuffer,
                 process: int = 0, process_name: Optional[str] = None):
        self.state = state
        self.buffer = buffer
        self.process = int(process)
        self.tracer = Tracer(state, buffer, pid=self.process,
                             process_name=process_name)
        self.registry = MetricsRegistry(state)
        self.drift = DriftTracker(self.registry)

    @property
    def enabled(self) -> bool:
        return self.state.enabled

    #: newest drift records carried per fragment (full history stays on
    #: the tracker) — keeps per-checkpoint telemetry O(1) over a long run
    DRIFT_TAIL = 64

    def span_snapshot(self, since_mark: int = 0) -> list:
        """Own-pid events since ``since_mark``: thread-simulated hosts
        share one buffer, and a fragment must not duplicate its peers'
        spans (the report merges fragments back into one trace)."""
        return [ev for ev in self.buffer.events_since(since_mark)
                if ev.get("pid") == self.process]

    def telemetry_fragment(self, since_mark: int = 0,
                           events: Optional[list] = None, **extra) -> dict:
        """This host's share of a checkpoint's ``telemetry.json``.

        ``events``: a pre-captured :meth:`span_snapshot` — pass one when
        the fragment is serialized off the save path (io pool), so later
        saves' spans don't smear into this checkpoint's fragment.
        """
        frag = {
            "process": self.process,
            "metrics": self.registry.to_dict(),
            "published": {k: dict(v) for k, v
                          in list(self.registry.published.items())},
            "drift": list(self.drift.history[-self.DRIFT_TAIL:]),
            "spans": (self.span_snapshot(since_mark) if events is None
                      else events),
        }
        frag.update(extra)
        return frag


_STATE = ObsState(os.environ.get("REPRO_OBS", "") not in ("", "0"))
_BUFFER = TraceBuffer(_STATE)
_GLOBAL = Observability(
    _STATE, _BUFFER,
    process=int(os.environ.get("REPRO_PROCESS_INDEX", "0") or 0))


def get_obs() -> Observability:
    """The process-wide default bundle (host/pid from REPRO_PROCESS_INDEX)."""
    return _GLOBAL


def scoped(process: int, process_name: Optional[str] = None) -> Observability:
    """A per-host bundle: fresh registry + drift, shared switch and trace
    buffer (so thread-simulated hosts export one merged trace)."""
    return Observability(_STATE, _BUFFER, process=process,
                         process_name=process_name)


def enable() -> None:
    _STATE.enabled = True


def disable() -> None:
    _STATE.enabled = False


def enabled() -> bool:
    return _STATE.enabled


def reset() -> None:
    """Test hygiene: drop buffered spans and the global registry state."""
    global _GLOBAL
    _BUFFER.clear()
    _GLOBAL = Observability(
        _STATE, _BUFFER,
        process=int(os.environ.get("REPRO_PROCESS_INDEX", "0") or 0))
