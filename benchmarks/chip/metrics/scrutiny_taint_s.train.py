"""Seconds of the scrutiny walk's ``taint`` phase at the first save: the
``stages["taint_s"]`` that ``participation`` records and the manager
publishes as ``"scrutiny"`` in the program's metrics registry."""


def read(run):
    from repro import obs

    stats = obs.get_obs().registry.published.get("scrutiny") or {}
    return (stats.get("stages") or {}).get("taint_s")
