"""Mean CPU time of the program's ``data.next_batch`` span (``next_batch``
in ``data/pipeline.py``: the step's eager host dispatches) over the spans
that start in the traced window: the ``cpu_ms`` stat each span carries on
the trace's host plane (``scopes.py`` reads them).  The span's length is
not read: once the steps dispatched ahead fill the device's queue, one of
its dispatches waits there for a free slot, and that wait costs the
thread no CPU time."""

import scopes


def read(run):
    out = scopes.of(run)
    cpu = [c for _, c in out["next_batch"] if c is not None] if out else []
    if not cpu:
        return None
    return sum(cpu) / len(cpu)
