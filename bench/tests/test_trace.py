"""The trace reduction: interval arithmetic on made-up events, and the
reading of a small trace recorded on a TPU v5e (``data/``)."""
import os

import pytest

import devtrace
import spec
from devtrace import Event, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "qwen3-4b.chat.v5e.xplane.pb.gz")
RECORDED_RUN = os.path.join(DATA, "qwen3-4b.chat.v5e.run.json.gz")


def made_up():
    ops = [Event("a", 0.0, 1.0), Event("b", 0.5, 2.0), Event("c", 3.0, 4.0),
           Event("a", 4.5, 9.0)]
    host = [Event("bench.window", 0.5, 5.0), Event("bench.step", 0.0, 2.2),
            Event("bench.admit", 2.5, 3.5)]
    return Trace(window=(0.5, 5.0), host=host, ops={"/device:TPU:0": ops},
                 modules={"/device:TPU:0": [Event("jit__scheduler_step",
                                                   0.0, 2.0)]})


def test_union_merges_overlaps():
    evs = [Event("x", 0, 1), Event("y", 0.5, 2), Event("z", 3, 4)]
    assert devtrace.union_s(evs) == pytest.approx(3.0)
    assert devtrace.union_s([]) == 0.0


def test_busy_is_clipped_to_the_window():
    # busy in [0.5, 5): [0.5, 2) + [3, 4) + [4.5, 5) = 3.0
    assert devtrace.busy_s(made_up()) == pytest.approx(3.0)


def test_idle_gaps_named_by_host_span():
    gaps = devtrace.idle_gaps(made_up())
    # [2, 3) inside bench.admit's [2.5, 3.5)? midpoint 2.5 -> admit;
    # [4, 4.5) in no span -> host
    assert gaps == [("admit", pytest.approx(1.0)),
                    ("host", pytest.approx(0.5))]


def test_top_ops_sums_by_name():
    top = dict(devtrace.top_ops(made_up()))
    assert top["b"] == pytest.approx(1.5)
    assert top["a"] == pytest.approx(0.5 + 0.5)


def recorded(tmp_path):
    """The last 4 s of a qwen3-4b.chat window traced on a TPU v5e, and the
    window's host records, measured on one v5e chip."""
    import gzip
    import json
    import shutil

    import measures

    path = tmp_path / "trace.xplane.pb"
    with gzip.open(RECORDED) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(RECORDED_RUN) as f:
        d = json.load(f)
    cell = spec.load_cell("qwen3-4b.chat")
    dims = spec.reference(cell.config).Dims.of(cell.config["model"])
    run = measures.from_dump(d, dims,
                             cell.config["serve"], cell.traffic,
                             spec.peaks_for("TPU v5 lite"))
    run.trace = devtrace.load(str(path))
    return run


def test_recorded_trace(tmp_path):
    run = recorded(tmp_path)
    tr = run.trace
    assert list(tr.ops) == ["/device:TPU:0"]
    assert 3.9 < tr.window_s < 4.1
    busy = devtrace.busy_s(tr)
    assert 0.8 * tr.window_s < busy <= tr.window_s
    names = {devtrace.op_name(e.name) for e in tr.ops["/device:TPU:0"]}
    assert {"multi_mass", "runahead_topk_threshold"} <= names
    gaps = devtrace.idle_gaps(tr)
    assert len(gaps) == 10 and all(s > 0 for _, s in gaps)
    assert {where for where, _ in gaps} <= {"step", "admit", "host"}


@pytest.mark.parametrize("name", ["decode_hbm_share", "solver_share",
                                  "solver_roofline", "device_idle_share"])
def test_recorded_shares_lie_in_range(tmp_path, name):
    import measures

    v = measures.load_reader(name)(recorded(tmp_path))
    assert v is not None and 0.0 < v < 100.0, v


# Every metric's reading of the recorded run and trace, as the readers gave
# it when the work counts sat in ``work.py``: reaching the model through
# the reference module moves no reading.
KEPT = {
    "admit_ms_p50": 40.53574900000001,
    "decode_hbm_share": 33.040867930909286,
    "device_idle_share": 11.9869691931767,
    "gap_p999_ms": 181.23586000000103,
    "mfu": 4.297563772439391,
    "setup_s": 72.243815369,
    "solver_roofline": 0.7161113678289308,
    "solver_share": 5.022703466048417,
    "step_ms_p50": 35.82607299999552,
    "tokens_per_s": 160.2,
}


def test_recorded_run_reads_unmoved(tmp_path):
    import measures

    run = recorded(tmp_path)
    assert {n: measures.load_reader(n)(run) for n in KEPT} == KEPT
