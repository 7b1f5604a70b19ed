#!/usr/bin/env python3
"""Readings that the limits in ``configs/<config>.json`` are set from.

For each seed, in one process: the cell's set-up with that seed's
weights, a window at the cell's own load, then the compared numbers of
the program (the lower readings) and, at each position of the same
prompts and served tokens, those of the control: the reference computed
in fp8 (one precision step below the configuration's bf16) put in the
program's place (the upper readings).  Both are judged against the
limits of the configuration file: each line says whether the program
and the control come out correct, and the last line on standard error
whether the limits told them apart on every seed.  The benchmark's own
runs do not run this.

  python3 bench/control.py --workload qwen3-4b.chat --seconds 20 \\
      --seeds 101,102,103 --out control.jsonl
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import run as bench_run
import spec

CHECKED = ("greedy_gap", "kept_gap")


def main(argv=None, out: list | None = None) -> int:
    """``out``, when given, receives each seed's readings."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--out", help="also append the JSON lines here")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    jax, clock, devices, peaks = bench_run.start_jax(cell, args.reduced)
    import check
    import serve
    from traffic import Stream

    out = [] if out is None else out
    for seed in (int(s) for s in args.seeds.split(",")):
        st = bench_run.Setup(cell, seed, args.reduced)
        stream = Stream(cell.traffic, seed, st.dims.vocab)
        serve.run_window(st.server, st.rec, stream, cell.traffic, st.serve,
                         args.seconds)
        records = list(st.rec.records.values())
        st.free_server()
        chosen = check.sample(records, seed)
        found = check.readings(st.weights, st.dims, chosen,
                               cell.traffic["sampler"], st.serve["context"],
                               seed, quant="fp8")
        n_bad = check.bad_lengths(records, st.dims.vocab)
        limits = cell.config["limits"]
        low = {name: found["control_" + name] for name in CHECKED}
        found.update(workload=cell.name, seed=seed, bad_lengths=n_bad,
                     finished=sum(r.tokens is not None for r in records),
                     program_correct=check.judge(found, limits, n_bad)[0],
                     control_correct=check.judge(low, limits, 0)[0])
        out.append(found)
        print(json.dumps(found), flush=True)
        del st
        gc.collect()
    apart = all(f["program_correct"] and not f["control_correct"]
                for f in out)
    print(f"control: the limits {cell.config['limits']} "
          f"{'tell' if apart else 'do NOT tell'} the program from the "
          f"control on every seed", file=sys.stderr, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            for line in out:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
