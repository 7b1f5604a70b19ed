#!/usr/bin/env python3
"""The chip benchmark: one cell of BENCHMARK.json, one run.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Serves the cell's traffic mix (``bench/traffic/<mix>.json``) to its
configuration (``bench/configs/<config>.json``) through the program's
``RunaheadServer`` on one TPU chip, with weights made on the device from
the seed.  Set-up warms every program the window runs; the window then
serves for ``--seconds`` on the benchmark's own clock.  Afterwards the
served tokens are checked against the float32 reference (``check.py``).
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled part of the window.  The last line of
standard output is the result, as JSON.

With no TPU, fewer chips than the cell asks for, or a device kind that
``peaks.json`` does not list, it exits non-zero and prints no result.
``--reduced`` is a rehearsal on the CPU at a tiny width: every phase
runs, and it exits 3 without a result line:

  JAX_PLATFORMS=cpu python3 bench/run.py --workload qwen3-4b.chat \\
      --seed 1 --seconds 3 --trace 1 --reduced
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import spec  # noqa: E402

# A --trace 1 run profiles the window's last TRACE_S seconds (at most a
# quarter of it): writing the trace out then happens after the window.
TRACE_S = 4.0


class CompileClock:
    """Seconds jax spent in XLA compiles (a persistent-cache hit counts
    its load instead), how many it made, and how many of them the
    persistent cache served."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._on_event)
        monitoring.register_event_listener(self._on_hit)

    def _on_event(self, name, secs, **_):
        if name == self.EVENT:
            self.seconds += secs
            self.compiles += 1

    def _on_hit(self, name, **_):
        if name == self.HIT:
            self.hits += 1


def say(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str, code: int = 2) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


def program_config(dims, arch: str, reduced: bool):
    """The program's ModelConfig for ``arch``, with the norm epsilon the
    configuration file states; raises where it departs from the file."""
    import dataclasses

    from repro.configs.registry import get_config

    cfg = dataclasses.replace(get_config(arch), norm_eps=dims.eps)
    want = dict(n_layers=dims.layers, d_model=dims.d, n_heads=dims.heads,
                n_kv_heads=dims.kv_heads, head_dim=dims.head_dim,
                d_ff=dims.ff, vocab=dims.vocab, rope_theta=dims.rope_theta,
                norm_eps=dims.eps, tie_embeddings=dims.tied,
                qk_norm=dims.qk_norm, qkv_bias=False, act="swiglu",
                norm="rmsnorm", family="dense", learned_pos=False)
    if reduced:
        cfg = cfg.scaled(n_layers=dims.layers, d_model=dims.d,
                         n_heads=dims.heads, n_kv_heads=dims.kv_heads,
                         d_head=dims.head_dim, d_ff=dims.ff,
                         vocab=dims.vocab)
    diff = {k: (getattr(cfg, k), v) for k, v in want.items()
            if getattr(cfg, k) != v}
    if diff:
        raise spec.SpecError(f"the program's {arch} departs from the "
                             f"configuration file (program, file): {diff}")
    return cfg


def check_layout(weights, cfg) -> None:
    """The benchmark's weights have the tree, shapes and dtypes of the
    program's own parameters."""
    import jax
    import jax.numpy as jnp

    from repro.models.transformer import init_params

    want = jax.eval_shape(lambda k: init_params(cfg, k, jnp.bfloat16),
                          jax.random.PRNGKey(0))
    tu = jax.tree_util
    got = tu.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                      weights)
    if (tu.tree_structure(want) != tu.tree_structure(got)
            or tu.tree_leaves(want) != tu.tree_leaves(got)):
        raise spec.SpecError("the program's parameter tree differs from the "
                             "layout model.py makes")


def seed_key(seed: int):
    import jax
    import numpy as np

    data = np.random.SeedSequence([seed, 4]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(data)


class Setup:
    """One cell made ready on the device: configuration, weights, server."""

    def __init__(self, cell, seed: int, reduced: bool):
        import jax

        import serve
        from model import Dims, make_weights
        from traffic import lengths_used

        self.cell = cell
        conf = cell.config
        small = conf["cpu_rehearsal"] if reduced else {}
        self.dims = Dims.of(conf["model"], {k: v for k, v in small.items()
                                            if k in conf["model"]})
        self.serve = dict(conf["serve"], **{k: v for k, v in small.items()
                                            if k in conf["serve"]})
        self.cfg = program_config(self.dims, conf["arch"], reduced)
        self.weights = make_weights(self.dims, seed_key(seed))
        jax.block_until_ready(self.weights)
        check_layout(self.weights, self.cfg)
        self.lengths = lengths_used(cell.traffic)
        self.server = None
        self.new_server()
        self.n_warm = serve.warm_up(self.server, self.rec, cell.traffic,
                                    self.serve, self.lengths, self.dims.vocab)
        jax.block_until_ready(self.server.scheduler.cache)

    def new_server(self) -> None:
        """A fresh server (empty slots, new cache) over the same weights;
        the compiled programs are shared.  The old one's cache is freed
        first: one chip does not hold two."""
        import serve
        from repro.serving.server import RunaheadServer

        if self.server is not None:
            self.free_server()
        self.server = RunaheadServer(
            self.cfg, self.weights, n_slots=self.serve["n_slots"],
            context=self.serve["context"], spec_k=self.serve["spec_k"],
            rounds=self.serve["rounds"], backend=self.serve["backend"])
        self.rec = serve.Recorder(self.server.scheduler)

    def counters(self) -> tuple:
        s = self.server.scheduler
        return (s.n_admissions, s.n_decode_steps, s.n_dispatches,
                s.n_host_syncs)

    def free_server(self) -> None:
        """Drop the server's device state (its cache)."""
        import gc

        self.server.scheduler.cache = None
        self.server = None
        self.rec.scheduler = None
        gc.collect()


def start_jax(cell, reduced: bool):
    """Point jax's compile cache into the checkout and find the device.
    Returns (jax, clock, devices, peaks) or raises SpecError."""
    src = os.path.join(spec.ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise spec.SpecError(f"no program under {src}")
    sys.path.insert(0, src)
    cache_dir = os.path.join(spec.ROOT, ".jax_cache")
    os.makedirs(cache_dir, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock(jax.monitoring)
    devices = jax.devices()
    dev = devices[0]
    say(f"device: platform {dev.platform}, kind {dev.device_kind!r}, "
        f"count {len(devices)}")
    if reduced:
        if dev.platform == "tpu":
            raise spec.SpecError("--reduced is the CPU rehearsal; run it "
                                 "with JAX_PLATFORMS=cpu")
        return jax, clock, devices, None
    if dev.platform != "tpu":
        raise spec.SpecError(f"no TPU (jax found {dev.platform}); nothing "
                             "run")
    if len(devices) < cell.chips:
        raise spec.SpecError(f"the cell asks for {cell.chips} chips, jax "
                             f"found {len(devices)}")
    return jax, clock, devices, spec.peaks_for(devices[0].device_kind)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU rehearsal at a tiny width; never prints a "
                         "result line")
    ap.add_argument("--keep-trace", metavar="PATH",
                    help="copy the profiler's .xplane.pb here")
    ap.add_argument("--keep-run", metavar="PATH",
                    help="write the window's host records here (JSON), to "
                         "read metrics again later")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    return execute(parse(argv))[0]


def execute(args) -> tuple[int, dict | None]:
    """One run; (exit code, result).  The result is printed as the last
    line only on a TPU; a --reduced rehearsal returns it with code 3."""

    try:
        cell = spec.load_cell(args.workload)
        jax, clock, devices, peaks = start_jax(cell, args.reduced)
        import check
        import devtrace
        import measures
        import serve
        from traffic import Stream

        st = Setup(cell, args.seed, args.reduced)
    except (OSError, KeyError, ValueError, spec.SpecError) as e:
        return fail(f"{e}"), None
    dev = devices[0]
    dims, serve_cfg, traffic = st.dims, st.serve, cell.traffic
    say(f"cell {cell.name}: {cell.config['name']} ({dims.layers} layers, d "
        f"{dims.d}, vocab {dims.vocab}), {serve_cfg['n_slots']} slots x "
        f"{serve_cfg['context']}, seed {args.seed}, window {args.seconds} s")
    before = st.counters()
    compiles_setup, compile_s = clock.compiles, clock.seconds
    hits_setup = clock.hits
    stream = Stream(traffic, args.seed, dims.vocab)
    setup_s = time.perf_counter() - T_START
    say(f"set-up: {setup_s:.3f} s; warm-up served {st.n_warm} requests over "
        f"prompt lengths {st.lengths}; {compiles_setup} compiles or cache "
        f"loads ({hits_setup} from the persistent cache), {compile_s:.3f} s")

    trace_dir = os.path.join(spec.ROOT, ".bench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    tw = serve.TraceWindow(st.rec, trace_dir) if args.trace else None
    trace_s = min(TRACE_S, args.seconds / 4)
    win = serve.run_window(st.server, st.rec, stream, traffic, serve_cfg,
                           args.seconds, trace=tw,
                           trace_at=args.seconds - trace_s)
    in_window = clock.compiles - compiles_setup
    stats = dev.memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")
    counters = [b - a for a, b in zip(before, st.counters())]
    say(f"window: {win['submitted']} requests submitted, loop ended at "
        f"{win['end']:.3f} s; scheduler counters: {counters[0]} admissions, "
        f"{counters[1]} decode steps, {counters[2]} dispatches, "
        f"{counters[3]} host syncs")
    say(f"compiles inside the window: {in_window}")
    say(f"memory_peak_bytes: {peak_bytes}")

    rec = st.rec
    records = list(rec.records.values())
    run = measures.Run(
        cell=cell.name, seconds=args.seconds, setup_s=setup_s, dims=dims,
        serve=serve_cfg, traffic=traffic, records=records, steps=rec.steps,
        admits=rec.admits, peaks=peaks)
    st.free_server()         # the reference runs after the program's state
    if tw is not None and tw.stall is not None:
        run.stall = tw.stall
        say(f"profiler start stalled the host {tw.stall[1] - tw.stall[0]} s "
            "between two steps (mfu leaves it out of its window)")

    if args.keep_run:
        with open(args.keep_run, "w") as f:
            json.dump(measures.dump(run, (tw.on, tw.off) if tw else None), f)

    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    if args.trace:
        path = devtrace.find(trace_dir)
        if path is None:
            return fail("the profiler wrote no trace"), None
        if args.keep_trace:
            shutil.copyfile(path, args.keep_trace)
        run.trace = devtrace.load(path)
        run.trace_host = (tw.on, tw.off)
        busy = devtrace.busy_s(run.trace)
        device["busy_s"] = busy
        device["window_s"] = run.trace.window_s
        breakdown = {"device_ops": devtrace.top_ops(run.trace),
                     "idle_gaps": devtrace.idle_gaps(run.trace)}
        shutil.rmtree(trace_dir, ignore_errors=True)
        say(f"trace: window {run.trace.window_s} s, device busy {busy} s")

    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        v = measures.load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        say(f"metric {m['name']}: {v} {m['unit']}")

    chosen = check.sample(records, args.seed)
    found = check.readings(st.weights, dims, chosen, traffic["sampler"],
                           serve_cfg["context"], args.seed)
    n_bad = check.bad_lengths(records, dims.vocab)
    correct, checks = check.judge(found, cell.config["limits"], n_bad)
    n_done = sum(r.tokens is not None for r in records)
    say(f"check: {len(chosen)} of {n_done} finished requests against the "
        f"reference, {found['greedy_tokens']} greedy and "
        f"{found['sampled_tokens']} sampled tokens")
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)

    result = {"correct": bool(correct), "attempted": win["submitted"],
              "failed": n_bad, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    if args.reduced:
        print("bench: rehearsal passed every phase; the result line is "
              "printed only on a TPU at full width", file=sys.stderr)
        say("rehearsal result (not a result line): " + json.dumps(result))
        return 3, result
    say(json.dumps(result))
    return 0, result


if __name__ == "__main__":
    sys.exit(main())
