"""The reduction of the program's ``serve.*`` spans (``spans.py``): its
arithmetic on made-up events, its reading of a trace recorded here on
the CPU, and the recorded v5e trace, which holds no such span."""
import glob
import os

import pytest

import devtrace
import spans
from devtrace import Event, Trace
from spans import Program, Span
from test_trace import recorded

DEV = "/device:TPU:0"


def _step(t0, launch, read, t1):
    """A serve.step span from t0 to t1 with its four phases."""
    return [Span("serve.step", t0, t1, {"live": 2}),
            Span("serve.step.prepare", t0, launch),
            Span("serve.step.launch", launch, launch + 0.2),
            Span("serve.step.readback", launch + 0.2, read),
            Span("serve.step.commit", read, t1)]


def made_up():
    """Steps A and B back to back, an admission, then step C."""
    program = Program(
        spans=sorted(
            _step(1.0, 1.2, 2.8, 3.0) + _step(3.2, 3.3, 4.8, 5.0)
            + [Span("serve.queue", 5.05, 6.05),
               Span("serve.admit", 5.1, 6.0, {"rid": "x", "length": 7}),
               Span("serve.admit.prefill", 5.1, 5.3),
               Span("serve.admit.sample", 5.3, 5.8),
               Span("serve.admit.book", 5.8, 6.0)]
            + _step(6.1, 6.2, 7.8, 8.0),
            key=lambda s: (s.start, -s.end)),
        launches=[Span("convert_element_type", 1.05, 1.06),
                  Span("broadcast_in_dim", 1.1, 1.11),
                  Span("_scheduler_step", 1.25, 1.3),
                  Span("scatter", 5.85, 5.86),
                  Span("_scheduler_step", 11.0, 11.1)])   # past the window
    runs = [("jit__scheduler_step(1)", 1.3, 2.7),
            ("jit__scheduler_step(1)", 3.4, 4.7),
            ("jit__admit_slot(2)", 5.2, 5.5),
            ("jit__admit_sample(3)", 5.6, 5.7),
            ("jit_scatter(4)", 5.9, 5.95),
            ("jit__scheduler_step(1)", 6.3, 7.7)]
    trace = Trace(window=(0.0, 10.0),
                  host=[Event("bench.window", 0.0, 10.0)],
                  ops={DEV: [Event("op", a, b) for _, a, b in runs]},
                  modules={DEV: [Event(n, a, b) for n, a, b in runs]})
    return trace, program


def test_step_gap_skips_a_pair_with_an_admission_between():
    # A -> B: B's launch at 3.3 less A's readback end 2.8; B -> C skipped
    assert spans.step_gap_ms_p50(*made_up()) == pytest.approx(500.0)


def test_device_step_gap_skips_a_pair_with_an_admission_between():
    trace, _ = made_up()
    assert spans.device_step_gap_ms_p50(trace) == pytest.approx(700.0)


def test_step_gap_parts_add_up_to_the_device_gap():
    # A's program ends at 2.7, its read-back at 2.8; B launches at 3.3,
    # its program starts at 3.4
    parts = spans.step_gap_parts(*made_up())
    assert parts == pytest.approx({"tail": 100.0, "host": 500.0,
                                   "head": 100.0, "device": 700.0,
                                   "ordered": 1.0})


def test_launches_per_step_counts_every_program():
    # 6 programs ran, 3 step launches: admissions' programs spread over them
    assert spans.launches_per_step(*made_up()) == pytest.approx(2.0)


def test_admit_idle_is_the_admission_less_device_time():
    # [5.1, 6.0] holds device work 0.3 + 0.1 + 0.05
    assert spans.admit_idle_ms_p50(*made_up()) == pytest.approx(450.0)


def test_idle_by_span_splits_stretches_at_span_edges():
    trace, program = made_up()
    got = spans.idle_by_span(trace, program)
    want = {"none": 3.3, "serve.step.commit": 0.6,
            "serve.step.prepare": 0.4, "serve.step.launch": 0.3,
            "serve.step.readback": 0.3, "serve.admit.sample": 0.2,
            "serve.admit.book": 0.15, "serve.admit.prefill": 0.1,
            "serve.queue": 0.1}
    assert got == pytest.approx(want)
    idle = trace.window_s - devtrace.busy_s(trace)
    assert sum(got.values()) == pytest.approx(idle)


def test_launches_by_span_names_the_innermost_span():
    assert spans.launches_by_span(*made_up()) == {
        "serve.step.prepare": {"convert_element_type": 1,
                               "broadcast_in_dim": 1},
        "serve.step.launch": {"_scheduler_step": 1},
        "serve.admit.book": {"scatter": 1}}


def test_longest_idle_named_by_span():
    trace, program = made_up()
    # midpoints 9.0, 0.65, 3.05 and 4.95
    assert spans.longest_idle(trace, program, n=4) == [
        ("none", pytest.approx(2.3)), ("none", pytest.approx(1.3)),
        ("none", pytest.approx(0.7)),
        ("serve.step.commit", pytest.approx(0.5))]


def test_segments_of_nested_spans():
    segs = spans.segments([Span("a", 0.0, 4.0), Span("b", 1.0, 2.0),
                           Span("c", 1.5, 1.8), Span("d", 3.0, 5.0)])
    assert segs == [(0.0, 1.0, "a"), (1.0, 1.5, "b"), (1.5, 1.8, "c"),
                    (1.8, 2.0, "b"), (2.0, 3.0, "a"), (3.0, 5.0, "d")]


def test_no_spans_reads_none():
    trace, _ = made_up()
    empty = Program(spans=[], launches=[])
    for f in (spans.idle_by_span, spans.step_gap_ms_p50,
              spans.launches_per_step, spans.admit_idle_ms_p50,
              spans.launches_by_span, spans.step_gap_parts):
        assert f(trace, empty) is None


def test_load_keeps_serve_spans_and_stats_apart_from_host(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    @jax.jit
    def double(x):
        return 2 * x

    x = jnp.ones(3)
    double(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("serve.admit", rid="r1", length=3):
                with TraceAnnotation("serve.admit.prefill"):
                    double(x).block_until_ready()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    program = spans.load(path)
    assert [s.name for s in program.spans] == ["serve.admit",
                                               "serve.admit.prefill"]
    assert program.spans[0].stats == {"rid": "r1", "length": 3}
    assert [e.name for e in program.launches
            if e.name == "double"] == ["double"]
    assert [h.name for h in devtrace.load(path).host] == ["bench.window"]


# What the benchmark's readers, and devtrace's breakdown, read on the
# recorded v5e trace before the program carried spans of its own.
RECORDED_METRICS = {
    "decode_hbm_share": 33.040867930909286,
    "solver_share": 5.022703466048417,
    "solver_roofline": 0.7161113678289308,
    "device_idle_share": 11.9869691931767,
    "admit_ms_p50": 40.53574900000001,
    "step_ms_p50": 35.82607299999552,
    "mfu": 4.297563772439391,
}
RECORDED_IDLE_GAPS = ["step", "step", "admit", "admit", "admit", "step",
                      "step", "admit", "step", "step"]


@pytest.fixture(scope="module")
def recorded_run(tmp_path_factory):
    """The recorded run, and the path of its unpacked trace."""
    tmp = tmp_path_factory.mktemp("recorded")
    return recorded(tmp), str(tmp / "trace.xplane.pb")


@pytest.mark.parametrize("name", sorted(RECORDED_METRICS))
def test_recorded_metrics_read_as_before(recorded_run, name):
    import measures

    v = measures.load_reader(name)(recorded_run[0])
    assert v == pytest.approx(RECORDED_METRICS[name], rel=1e-12)


def test_recorded_breakdown_reads_as_before(recorded_run):
    tr = recorded_run[0].trace
    assert [w for w, _ in devtrace.idle_gaps(tr)] == RECORDED_IDLE_GAPS
    assert devtrace.top_ops(tr)[0] == ("%while.56",
                                       pytest.approx(1.9966202849999988))


def test_recorded_trace_has_no_program_spans(recorded_run):
    run, path = recorded_run
    program = spans.load(path)
    assert program.spans == [] and program.launches
    for f in (spans.idle_by_span, spans.step_gap_ms_p50,
              spans.launches_per_step, spans.admit_idle_ms_p50,
              spans.step_gap_parts):
        assert f(run.trace, program) is None
    # the device's own gap between decode steps is there without spans
    assert 2.5 < spans.device_step_gap_ms_p50(run.trace) < 3.2
