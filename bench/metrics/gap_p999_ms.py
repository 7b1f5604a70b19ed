"""99.9th percentile of the gaps between consecutive output tokens of one
request, over all requests, for tokens emitted in the window (host
clock): the longest stalls a streaming user sees, each a decode step
held up by another request's admission prefill."""
from measures import quantile, token_gaps


def read(run):
    q = quantile(token_gaps(run), 0.999)
    return None if q is None else q * 1e3
