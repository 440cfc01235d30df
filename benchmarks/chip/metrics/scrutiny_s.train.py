"""Host time of the ``participation`` call that scrutinizes the train
state at the first save (in set-up): the bench span around it."""


def read(run):
    spans = run.spans.named("scrutiny.participation")
    if not spans:
        return None
    return sum(s["t1"] - s["t0"] for s in spans)
