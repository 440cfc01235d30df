"""Share of the traced training window in which no operation ran on the
chip: 1 - (union of the device's operation intervals) / window."""


def read(run):
    trace = run.read.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
