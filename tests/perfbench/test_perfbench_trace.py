"""trace_reduce.py: the interval arithmetic on synthetic events, and the
reader on a trace recorded on the chip (perfbench/fixtures/)."""

import glob
import gzip
import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)

from perfbench import trace_reduce as tr  # noqa: E402


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2), (5, 4)]) == [(0, 2), (3, 4)]
    assert tr.total(tr.union([(0, 1), (0.5, 2), (3, 4)])) == 3


def test_idle_share_over_a_window():
    busy = [(0, 1), (0.5, 2), (3, 4), (9, 12)]
    # window 0..10: busy 0-2, 3-4, 9-10 = 4 of 10
    assert tr.idle_share(busy, 0, 10) == pytest.approx(0.6)
    assert tr.idle_share([], 0, 1) == 1.0
    with pytest.raises(ValueError):
        tr.idle_share(busy, 1, 1)


def test_gaps_and_their_attribution_to_host_spans():
    busy = tr.union([(0, 2), (3, 4), (9, 10)])
    assert tr.gaps(busy, 0, 10) == [(2, 3), (4, 9)]
    spans = [("engine.step", 0, 3.2), ("add_request", 3.2, 8),
             ("engine.step", 8, 10)]
    # gap 2-3 (middle 2.5) -> engine.step; gap 4-9 (middle 6.5) -> add_request
    assert tr.gaps_by_span(busy, spans, 0, 10) == [("add_request", 5),
                                                   ("engine.step", 1)]
    assert tr.gaps_by_span(busy, [], 0, 10) == [("none", 6)]
    # the innermost (latest-started) open span wins
    assert tr.span_at([("a", 0, 10), ("b", 2, 4)], 3) == "b"
    assert tr.span_at([("a", 0, 10), ("b", 2, 4)], 5) == "a"


def test_sums_by_name_clip_to_the_window():
    events = [("fusion.1", 0, 2), ("fusion.1", 9, 12), ("copy", 3, 4)]
    assert tr.sums_by_name(events, 0, 10) == [("fusion.1", 3), ("copy", 1)]


def test_self_time_cuts_nested_operations_out_of_their_parents():
    # a while loop 0-10 around two bodies, one of which holds a fusion
    events = [("while", 0, 10), ("body", 1, 4), ("fusion", 2, 3), ("body", 5, 9),
              ("copy", 12, 13)]
    assert sorted(tr.self_events(events)) == [
        ("body", 2), ("body", 4), ("copy", 1), ("fusion", 1), ("while", 3)]
    assert tr.self_sums_by_name(events, 0, 20) == [
        ("body", 6), ("while", 3), ("copy", 1), ("fusion", 1)]
    assert sum(s for _n, s in tr.self_events(events)) == tr.total(
        tr.union((s, e) for _n, s, e in events))


def test_busy_inside_named_spans():
    busy = [(0, 2), (3, 4), (9, 10)]
    spans = [("add_request", 1, 3.5), ("engine.step", 3.5, 10)]
    assert tr.busy_inside(busy, spans, {"add_request"}, 0, 10) == pytest.approx(1.5)
    assert tr.busy_inside(busy, spans, {"engine.step"}, 0, 10) == pytest.approx(1.5)


def test_reduce_trace_on_synthetic_planes():
    ops = {0: [("fusion", 0.0, 1.0), ("dot", 1.5, 2.0)],
           1: [("fusion", 0.0, 2.0)]}
    host = [("train.step", 0.0, 1.2), ("train.loss_read", 1.2, 2.0)]
    out = tr.reduce_trace(tr.Trace(ops, host, []), ("train.step",
                                                    "train.loss_read"))
    assert out["window_s"] == pytest.approx(2.0)
    assert out["busy_s"] == pytest.approx((1.5 + 2.0) / 2)   # mean over chips
    assert out["device_ops"][0] == ["fusion", pytest.approx(1.5)]
    assert out["idle_gaps"] == [["train.loss_read", pytest.approx(0.25)]]
    assert tr.reduce_trace(tr.Trace({}, host, []), ("train.step",)) is None


def test_reader_on_the_trace_recorded_on_the_chip(tmp_path):
    """perfbench/fixtures/*.xplane.pb.gz was recorded on a v5e in PR 23 (a few
    steps of train-mistral7b-seq4k, 0.6 s): the reader finds the device plane,
    the benchmark's spans, and a busy time above zero."""
    found = glob.glob(os.path.join(_REPO, "perfbench", "fixtures", "*.xplane.pb.gz"))
    assert found, "the recorded trace is missing"
    path = tmp_path / "fixture.xplane.pb"
    with gzip.open(found[0]) as f:
        path.write_bytes(f.read())
    trace = tr.read_xplane(str(path), keep_host={"train.step", "train.loss_read"})
    assert sorted(trace.device_ops) == [0]
    assert any(n == "train.step" for n, _s, _e in trace.host_events)
    out = tr.reduce_trace(trace, ("train.step", "train.loss_read"))
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_ops"] and out["device_ops"][0][1] > 0
    assert all(len(name) <= 96 for name, _s in out["device_ops"])
    # the chip was busy nearly all of these steps, and the gaps lie under the
    # benchmark's own spans
    assert 0.9 < out["busy_s"] / out["window_s"] <= 1.0
    assert {name for name, _s in out["idle_gaps"]} <= {
        "train.step", "train.loss_read", "none"}


def test_short_name_keeps_the_instruction_and_its_kind():
    long = ("%fusion.225 = (bf16[4096,32768]{1,0:T(8,128)(2,1)}, f32[4096,32768]) "
            "fusion(f32[] %sub.187), kind=kOutput, calls=%fused_computation.411")
    assert tr.short_name(long) == "fusion kOutput -> (bf16[4096,32768], f32[4096,32768])"
    assert tr.short_name("%broadcast.6707 = f32[32,8,2,1536,128]{4,3,2,1,0:T(8,128)} "
                         "broadcast(f32[32,8,1536,128]{3,2,1,0:T(8,128)} %x.1), "
                         "dimensions={0,1,3,4}") == "broadcast -> f32[32,8,2,1536,128]"
    assert len(tr.short_name(long)) <= 96
    assert tr.short_name("train.step") == "train.step"
