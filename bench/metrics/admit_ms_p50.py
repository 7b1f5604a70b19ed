"""Median host span of ``ContinuousScheduler.admit`` in the window: the
B=1 prefill into a slot and the first token's sample, read back."""
from measures import admits_in_window, quantile


def read(run):
    q = quantile([a.end - a.start for a in admits_in_window(run)], 0.5)
    return None if q is None else q * 1e3
