"""Device time of the sampler's solver kernels over device time of the
decode programs, in the trace."""
from devtrace import op_name
from measures import decode_runs

DECODE_PROGRAM = "_scheduler_step"
# The solver's Pallas kernels, as their custom calls are named in the trace
# ("%multi_mass.13 = f32[8,1,128] custom-call(...)"): after the jnp
# wrappers in kernels/*.py, since the calls set no name of their own.
SOLVER_KERNELS = ("multi_count", "multi_mass", "multi_entropy",
                  "multi_entropy_moments", "runahead_topk_threshold")


def solver_ops(run):
    tr = run.trace
    if tr is None or tr.window is None or not tr.ops:
        return []
    return [e for e in tr.clip(next(iter(tr.ops.values())))
            if op_name(e.name) in SOLVER_KERNELS]


def read(run):
    runs = decode_runs(run, DECODE_PROGRAM)
    ops = solver_ops(run)
    if not runs or not ops:
        return None
    return 100.0 * sum(e.end - e.start for e in ops) / sum(
        e.end - e.start for e in runs)
