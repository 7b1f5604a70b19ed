"""Median host span of ``ContinuousScheduler.step`` in the window: one
decode step over the slots, the sampler's solves, the tokens read back."""
from measures import quantile, steps_in_window


def read(run):
    q = quantile([s.end - s.start for s in steps_in_window(run)], 0.5)
    return None if q is None else q * 1e3
