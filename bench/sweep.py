#!/usr/bin/env python3
"""Finds the knee of an open-loop cell: the highest arrival rate at which
requests are admitted without waiting.  One process, one set-up (the
first seed's weights); each rate and traffic seed gets a fresh server
(empty slots) and its own window, as long as the cell's.

  python3 bench/sweep.py --workload qwen3-4b.chat --seeds 11,12,13 \\
      --rates 0.6,0.7,0.8,0.9 --seconds 50

Prints one JSON line per rate and seed: the requests waiting at each
third of the window, the least-squares growth of the waiting count over
the window's second half (requests/s), the 90th percentile and the
largest of the queue waits, times to first token, and the cell's own
numbers at that rate.  Run on the chip; with --reduced it rehearses on
the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run as bench_run
import spec


def waiting_at(records, t: float) -> int:
    return sum(1 for r in records if r.arrival <= t
               and (r.admit_start is None or r.admit_start > t))


def growth_per_s(records, lo: float, hi: float, n: int = 20) -> float:
    ts = [lo + (hi - lo) * i / (n - 1) for i in range(n)]
    ws = [waiting_at(records, t) for t in ts]
    mt, mw = sum(ts) / n, sum(ws) / n
    return (sum((t - mt) * (w - mw) for t, w in zip(ts, ws))
            / sum((t - mt) ** 2 for t in ts))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--out", help="also append the JSON lines here")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        return bench_run.fail("the sweep is for open-loop cells")
    seeds = [int(x) for x in args.seeds.split(",")]
    jax, clock, devices, peaks = bench_run.start_jax(cell, args.reduced)
    import measures
    import serve
    from traffic import Stream

    st = bench_run.Setup(cell, seeds[0], args.reduced)
    lines = []
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in seeds:
            st.new_server()
            n0 = clock.compiles
            stream = Stream(cell.traffic, seed, st.dims.vocab, rate=rate)
            win = serve.run_window(st.server, st.rec, stream, cell.traffic,
                                   st.serve, args.seconds)
            recs = list(st.rec.records.values())
            run = measures.Run(
                cell=cell.name, seconds=args.seconds, setup_s=0.0,
                dims=st.dims, serve=st.serve, traffic=cell.traffic,
                records=recs, steps=st.rec.steps, admits=st.rec.admits,
                peaks=peaks)
            T = args.seconds
            occupancy = [len(s.positions)
                         for s in measures.steps_in_window(run)]
            waits = measures.queue_waits(run)
            ttft = measures.ttfts(run)
            line = {
                "rate_per_s": rate, "seed": seed,
                "submitted": win["submitted"],
                "waiting": [waiting_at(recs, T * k / 3) for k in (1, 2, 3)],
                "growth_per_s": growth_per_s(recs, T / 2, T),
                "finished": sum(r.tokens is not None for r in recs),
                "mean_occupancy": (sum(occupancy) / len(occupancy)
                                   if occupancy else 0.0),
                "compiles_in_window": clock.compiles - n0,
                "queue_wait_p90_ms": 1e3 * measures.quantile(waits, 0.9),
                "queue_wait_max_ms": 1e3 * max(waits),
                "ttft_p50_ms": 1e3 * measures.quantile(ttft, 0.5),
                "ttft_p90_ms": 1e3 * measures.quantile(ttft, 0.9),
            }
            for name in ("step_ms_p50", "gap_p999_ms", "tokens_per_s"):
                line[name] = measures.load_reader(name)(run)
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
