"""Model FLOP utilization of the train step in the traced window: the
step's model FLOPs (``flops.train_step_flops``) times the steps
completed there, over the traced window and the chip's bf16 peak."""

import peaks


def read(run):
    trace = run.read.get("trace")
    steps = run.read.get("traced_steps")
    if not trace or not steps:
        return None
    peak = peaks.peaks(run.devices[0].device_kind)["bf16_flops"]
    chips = len(run.devices)
    return 100.0 * steps * run.read["step_flops"] / trace["window_s"] / (
        peak * chips)
