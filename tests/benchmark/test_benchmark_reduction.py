"""The trace reduction on a small recorded trace: busy union, idle share,
operation sums by name, gap naming; and the span readers on its spans."""
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import readers, tracered

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture()
def recorded():
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        raw = json.load(f)
    trace = tracered.Trace([tracered.Event(*e) for e in raw["events"]],
                           tuple(raw["anchor"]))
    spans = [SimpleNamespace(**s) for s in raw["spans"]]
    r = readers.Readings(ops=[tuple(raw["op"])], traced=[tuple(raw["op"])],
                         spans=spans, epoch_ns=raw["epoch_ns"], trace=trace)
    return trace, spans, r


def test_busy_is_the_union_of_single_operations(recorded):
    trace, _, r = recorded
    # fusion.1 [1000,2500) and fusion.2 [2000,3000) overlap; the Steps line
    # and the modules line are not operations
    assert tracered.busy_intervals(trace, "/device:TPU:0") == [
        (1000, 3000), (3500, 5000), (7000, 9000), (12000, 13000)]
    (win,) = r.traced_windows()
    assert win == (1000.0, 15000.0)
    assert tracered.busy_seconds(trace, [win]) == pytest.approx(6500e-9)


def test_idle_share_and_busy_per_operation(recorded):
    _, _, r = recorded
    assert readers.device_busy({}, r) == pytest.approx(6500e-9)
    assert readers.device_idle_pct({}, r) == pytest.approx(
        100 * (1 - 6500 / 14000))


def test_operation_sums_by_name(recorded):
    trace, _, r = recorded
    sums = tracered.op_sums(trace.events, "fusion", "XLA Ops")
    assert sums == {"fusion.1": pytest.approx(2500e-9),
                    "fusion.2": pytest.approx(1000e-9)}
    top = tracered.top_ops(trace, r.traced_windows())
    assert top[0] == ["jit_prog", pytest.approx(5000e-9)]
    assert top[1] == ["jit__predict_rf_chain_batch", pytest.approx(2000e-9)]
    spec = {"pattern": "_predict_rf_chain_batch", "line": "XLA Modules"}
    assert readers.device_op_sum(spec, r) == pytest.approx(2000e-9)


def test_a_window_cuts_the_operations_it_does_not_hold(recorded):
    trace, _, _ = recorded
    assert tracered.busy_seconds(trace, [(2500.0, 8000.0)]) == pytest.approx(
        (500 + 1500 + 1000) * 1e-9)


def test_gaps_are_named_by_the_innermost_span(recorded):
    trace, spans, r = recorded
    (win,) = r.traced_windows()
    idle = tracered.gaps(tracered.busy_intervals(trace, "/device:TPU:0"),
                         *win)
    assert idle == [(3000, 3500), (5000, 7000), (9000, 12000),
                    (13000, 15000)]
    host = tracered.to_trace_clock(spans, trace.anchor, r.epoch_ns)
    named = dict(tracered.name_gaps(idle, host))
    # trace time = span ts + 1000. [3000,3500) and [5000,7000) lie under
    # the OneHot fit (ends 7000); [9000,10400) under its transform,
    # [10400,10500) under the selector's fit before the sweep opens,
    # [10500,12000) under sweep.family; [13000,14900) the selector's fit,
    # [14900,15000) workflow.train alone
    assert named["stage.fit_OneHotVectorizer_"] == pytest.approx(2500e-9)
    assert named["stage.transform_OneHotVectorizerModel_"] == pytest.approx(
        1400e-9)
    assert named["sweep.family"] == pytest.approx(1500e-9)
    assert named["stage.fit_ModelSelector_"] == pytest.approx(2000e-9)
    assert named["workflow.train"] == pytest.approx(100e-9)
    assert sum(named.values()) == pytest.approx(7500e-9)


def test_span_readers(recorded):
    _, _, r = recorded
    fe = {"name": r"stage\.(fit|transform)",
          "attrs": {"stage": ".*Vectorizer(Model)?|VectorsCombiner"}}
    assert readers.span_sum(fe, r) == pytest.approx(9400e-9)
    # the last jit_prog program ends at 13000, the operation at 15000
    tail = {"pattern": r"^jit_prog\(", "line": "XLA Modules"}
    assert readers.after_last_device_op(tail, r) == pytest.approx(2000e-9)
    # workflow.train covers [1000,15000) of the trace: 14000 less 6500 busy
    assert readers.span_minus_device({"name": r"workflow\.train"},
                                     r) == pytest.approx(7500e-9)


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = readers.Readings()
    for kind in readers.KINDS:
        spec = {"kind": kind, "name": "x", "pattern": "x", "key": "k",
                "events": ["e"], "line": "x"}
        assert readers.KINDS[kind](spec, empty) is None, kind


def test_merge_and_short_names():
    assert tracered.merge([(5, 6), (1, 3), (2, 4), (6, 6)]) == [(1, 4),
                                                                  (5, 6)]
    assert tracered.short_name("jit_prog(8123)") == "jit_prog"
    assert tracered.short_name("fusion.12") == "fusion"
