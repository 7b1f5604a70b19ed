"""Model FLOPs the window needed (prompt tokens prefilled, tokens
decoded, attention over live positions only; ``work.py``) over the
window's length (less the profiler's start, in a traced run) times the
chip's peak bf16 FLOP/s."""
from measures import admits_in_window, stalled, steps_in_window
from work import decode_token_flops, prefill_flops


def read(run):
    if run.peaks is None:
        return None
    dm = run.dims
    flops = sum(prefill_flops(dm, a.length) for a in admits_in_window(run))
    flops += sum(decode_token_flops(dm, p)
                 for s in steps_in_window(run) for p in s.positions)
    if not flops:
        return None
    seconds = run.seconds - stalled(run, 0.0, run.seconds)
    return 100.0 * flops / (seconds * run.peaks["bf16_flops_per_s"])
