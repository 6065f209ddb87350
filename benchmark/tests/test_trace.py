"""The trace reduction on a small recorded trace (data/trace_small.json: the
first rounds of a traced window of resnet9_sketch_1c on a TPU v5 lite, cut to
a few hundred device operations) and on hand-made intervals.

Run by hand: python -m pytest benchmark/tests -q   (not part of tests/)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce as tr  # noqa: E402


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        raw = json.load(f)
    t = tr.Trace()
    t.device_ops["/device:TPU:0"] = [tuple(o) for o in raw["device_ops"]]
    t.host_spans = [tuple(s) for s in raw["host_spans"]]
    return t, raw["window"]


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 3), (2, 4), (7, 9)]) == [(0, 4), (5, 9)]
    assert tr.total(tr.union([(0, 10), (2, 3)])) == 10


def test_gaps_are_the_complement():
    busy = [(2, 4), (6, 7)]
    assert tr.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.total(busy) + tr.total(tr.gaps(busy, 0, 10)) == 10


def test_intersect_and_subtract_partition():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (28, 29), (45, 60)]
    inside, outside = tr.intersect(a, b), tr.subtract(a, b)
    assert inside == [(5, 10), (20, 25), (28, 29), (45, 50)]
    assert outside == [(0, 5), (25, 28), (29, 30), (40, 45)]
    assert tr.total(inside) + tr.total(outside) == tr.total(a)


def test_self_time_leaves_out_children():
    t = tr.Trace(host_spans=[("fed_round", 0, 100), ("fed_drain", 40, 60),
                             ("fed_round", 200, 300)])
    assert tr.self_seconds(t, "fed_round", ("fed_drain",), 0, 400) \
        == pytest.approx(180e-9)


def test_gap_goes_to_the_innermost_span():
    t = tr.Trace(
        device_ops={"/device:TPU:0": [("op", 0, 10), ("op", 50, 60)]},
        host_spans=[("bench_window", 0, 100), ("fed_round", 5, 40),
                    ("fed_client_phase", 20, 30)])
    got = tr.attribute_gaps(t, 0, 100, bubble_ns=0)
    # idle 10..50 and 60..100: client phase 10, the round's rest 20, the
    # window's rest 50
    assert got == {"fed_client_phase": pytest.approx(10e-9),
                   "fed_round": pytest.approx(20e-9),
                   "bench_window": pytest.approx(50e-9)}


def test_recorded_busy_plus_idle_is_the_window(recorded):
    t, (lo, hi) = recorded
    busy = tr.busy_seconds(t, lo, hi)
    idle = sum(tr.attribute_gaps(t, lo, hi).values())
    assert busy > 0 and idle > 0
    assert busy + idle == pytest.approx((hi - lo) / 1e9, rel=1e-9)
    # the union never exceeds the window, the plain sum of durations may
    ops = t.device_ops["/device:TPU:0"]
    assert busy <= (hi - lo) / 1e9
    assert busy <= sum(e - s for _, s, e in ops) / 1e9 + 1e-12


def test_recorded_ops_and_spans_are_found(recorded):
    t, (lo, hi) = recorded
    by_name = tr.op_seconds(t, lo, hi)
    assert by_name and all(v >= 0 for v in by_name.values())
    assert sum(by_name.values()) > 0
    assert tr.span_seconds(t, "fed_round", lo, hi) > 0
    gaps = tr.attribute_gaps(t, lo, hi)
    assert set(gaps) <= set(tr.HOST_SPANS) | {"other", "op_bubbles"}
    assert gaps["op_bubbles"] > 0
