"""The trace reduction, on a small trace recorded on a v5e chip
(``record_trace.py``) and on events worked by hand."""
import json
import lzma
import os

import pytest

from bench import devtrace, spans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_hand_worked_union_gaps_and_ops():
    tr = devtrace.from_events(
        {"/device:TPU:0": {"XLA Ops": [("a", 2e6, 4e6), ("b", 4e6, 4e6),
                                       ("a", 20e6, 5e6), ("c", 29e6, 3e6)]}},
        mark_ns=1e6, mark_wall_ms=1000.0, window_ms=(1000.0, 1030.0))
    dev = "/device:TPU:0"
    # wall ms: a [1001, 1005), b [1003, 1007), a [1019, 1024), c [1028, 1031)
    assert devtrace.busy_ms(tr, dev) == pytest.approx(6 + 5 + 2)
    assert devtrace.idle_gaps(tr, dev) == pytest.approx(
        [(1000.0, 1001.0), (1007.0, 1019.0), (1024.0, 1028.0)])
    top = devtrace.top_ops(tr)
    assert [n for n, _ in top] == ["a", "b", "c"]
    assert [s for _, s in top] == pytest.approx([0.009, 0.004, 0.002])
    host = [("router.submit", 1006.0, 1020.0), ("plan.pack", 1010.0, 1015.0)]
    gaps = devtrace.idle_gaps(tr, dev)
    labelled = devtrace.label_gaps(gaps, host)
    assert [n for n, _ in labelled] == ["plan.pack", "no request",
                                        "no request"]
    assert [s for _, s in labelled] == pytest.approx([0.012, 0.004, 0.001])


def test_span_self_time_and_union():
    assert spans.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert spans.union_length([(0, 10)], 2, 5) == 3
    s = {"name": "x", "t0_ms": 0.0, "dur_ms": 10.0, "children": [
        {"name": "y", "t0_ms": 1.0, "dur_ms": 3.0},
        {"name": "z", "t0_ms": 2.0, "dur_ms": 3.0},
        {"name": "service.execute", "t0_ms": 8.0, "dur_ms": 4.0,
         "attrs": {"queued_ms": 1.0}}]}
    # children cover [1, 5) and [7, 10): self time 10 - 4 - 3
    assert spans.self_ms(s) == pytest.approx(3.0)


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    # kept xz-compressed: the raw trace holds every compiled program's HLO
    path = tmp_path_factory.mktemp("trace") / "trace.xplane.pb"
    with lzma.open(os.path.join(DATA, "trace.xplane.pb.xz")) as f:
        path.write_bytes(f.read())
    meta = json.load(open(os.path.join(DATA, "trace.json")))
    return devtrace.load(str(path), meta["mark_wall_ms"],
                         meta["window_ms"]), meta


def test_recorded_trace_reduces(chip_trace):
    tr, meta = chip_trace
    dev = "/device:TPU:0"
    lo, hi = tr.window_ms
    busy = devtrace.busy_ms(tr, dev)
    assert 0 < busy < hi - lo
    gaps = devtrace.idle_gaps(tr, dev)
    assert sum(b - a for a, b in gaps) + busy == pytest.approx(hi - lo)
    top = devtrace.top_ops(tr)
    assert 0 < len(top) <= 10
    # operations nest on the op line: their sum is at least the busy time
    # of the ones counted
    assert sum(s for _, s in devtrace.top_ops(tr, 1000)) >= busy / 1e3 - 1e-9


def test_recorded_ops_line_up_with_the_launch_spans(chip_trace):
    # every launch the program timed on the host brackets device work: the
    # mark puts the trace on the spans' wall clock
    tr, meta = chip_trace
    dev = "/device:TPU:0"
    launches = [s for t in meta["traces"] for s in spans.flatten(t)
                if s["name"] == "kernel.ca_search"]
    assert launches
    for s in launches:
        a, b = spans.interval(s)
        inside = [e for e in tr.ops[dev] if a - 1.0 <= e[1] and e[2] <= b + 1.0]
        assert inside, s
