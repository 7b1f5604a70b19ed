"""Bytes the traced decode steps needed (weights once, live K/V read, one
row written per slot; ``work.py``) over the decode programs' device time
in the trace, over the chip's peak HBM bytes/s."""
from measures import decode_runs, steps_traced
from work import decode_step_bytes

DECODE_PROGRAM = "_scheduler_step"


def read(run):
    runs = decode_runs(run, DECODE_PROGRAM)
    steps = steps_traced(run)
    if run.peaks is None or not runs or not steps:
        return None
    per_step = sum(decode_step_bytes(run.dims, s.positions)
                   for s in steps) / len(steps)
    device_s = sum(e.end - e.start for e in runs)
    return 100.0 * per_step * len(runs) / (
        device_s * run.peaks["hbm_bytes_per_s"])
