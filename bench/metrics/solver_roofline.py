"""The solver kernels' share of their roofline: one read of the (slots,
vocab) float32 logits for each solve the sampler config asks for (the
least any implementation reads; ``work.py``), over the kernels' device
time in the trace, over the chip's peak HBM bytes/s."""
from devtrace import op_name
from measures import decode_runs, steps_traced
from work import solve_bytes

DECODE_PROGRAM = "_scheduler_step"
SOLVER_KERNELS = ("multi_count", "multi_mass", "multi_entropy",
                  "multi_entropy_moments", "runahead_topk_threshold")


def read(run):
    tr = run.trace
    if run.peaks is None or tr is None or tr.window is None or not tr.ops:
        return None
    ops = [e for e in tr.clip(next(iter(tr.ops.values())))
           if op_name(e.name) in SOLVER_KERNELS]
    runs = decode_runs(run, DECODE_PROGRAM)
    steps = steps_traced(run)
    if not ops or not runs or not steps:
        return None
    s = run.traffic["sampler"]
    solves = int(s["top_k"] > 0) + int(s["top_p"] > 0)
    sampled = sum(1 for st in steps if st.sampled) / len(steps)
    need = (solves * solve_bytes(run.serve["n_slots"], run.dims.vocab)
            * sampled * len(runs))
    kernel_s = sum(e.end - e.start for e in ops)
    return 100.0 * need / (kernel_s * run.peaks["hbm_bytes_per_s"])
