"""The program's own names in a trace (metrics/_program_trace.py): on a small
recorded list (data/program_trace_small.json: the first rounds of a traced
window of resnet9_sketch_1c on a TPU v5 lite, chip run of PR 26: every device
operation with its scope path and program, and the program's spans with their
metadata), on hand-made lists, and on a few hand-encoded bytes of xplane.proto.

Run by hand: python -m pytest benchmark/tests -q   (not part of tests/)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "metrics"))

import _program_trace as pt  # noqa: E402
import trace_reduce as tr  # noqa: E402


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "program_trace_small.json")) as f:
        raw = json.load(f)
    trace = pt.ProgramTrace(
        ops={"/device:TPU:0": pt.stages_of(
            [tuple(o) for o in raw["device_ops"]])},
        spans={name: [(s, e, meta) for s, e, meta in spans]
               for name, spans in raw["spans"].items()})
    return trace, raw


def _ctx(trace, lo, hi, rounds, monkeypatch, tmp_path):
    """A reader's ctx over ``trace``, as the harness would hand it."""
    monkeypatch.setenv("COMMEFFICIENT_RUN_DIR", str(tmp_path))
    monkeypatch.setitem(pt._LOADED, os.path.join(str(tmp_path), "trace"),
                        trace)
    return {"tr": tr, "lo": lo, "hi": hi, "rounds": rounds}


# ---- scope path -> stage ----------------------------------------------------

@pytest.mark.parametrize("path,stage", [
    ("jit(client_step)/fed_client_grad/while/body/closed_call/"
     "transpose(jvp(vmap(ResNet9)))/conv_general_dilated:", "fed_client_grad"),
    ("jit(client_step)/transpose(jvp(fed_client_grad))/mul:",
     "fed_client_grad"),
    ("jit(client_step)/vmap(fed_client_compress)/reduce_sum:",
     "fed_client_compress"),
    # a kernel's own name is a path component too, and no stage
    ("jit(server_step)/fed_server_resketch/jit(_sketch_vec_pallas)/"
     "fed_sketch_vec/pallas_call:", "fed_server_resketch"),
    ("jit(_check)/jit(_sketch_vec_pallas)/fed_sketch_vec/pallas_call:", None),
    # the outermost stage wins
    ("jit(f)/fed_server_apply/fed_server_topk/add:", "fed_server_apply"),
    ("jit(_threefry_split)/add:", None),
    ("batch['inputs']:", None),
    ("", None),
])
def test_stage_of_matches_the_component(path, stage):
    assert pt.stage_of(path) == stage


def test_an_operation_the_compiler_made_takes_the_stage_before_it():
    ops = [("jit(c)/fed_client_grad/dot:", 7, 0, 10),
           ("", 7, 10, 12),                     # a copy inside program 7
           ("", 8, 12, 14),                     # another program: unscoped
           ("batch['inputs']:", 7, 14, 16),     # a path without a stage
           ("", 7, 16, 18),                     # still the grad's
           ("jit(c)/fed_client_compress/add:", 7, 18, 20),
           ("", 7, 20, 22)]
    assert [s for s, _, _ in pt.stages_of(ops)] == [
        "fed_client_grad", "fed_client_grad", None, None, "fed_client_grad",
        "fed_client_compress", "fed_client_compress"]


# ---- the recorded list ------------------------------------------------------

def test_recorded_stages_are_the_ones_the_round_runs(recorded):
    trace, raw = recorded
    lo, hi = raw["window"]
    found = {st for st, s, e in trace.ops["/device:TPU:0"]
             if st and e > lo and s < hi}
    assert found == {"fed_client_grad", "fed_client_compress",
                     "fed_server_estimate", "fed_server_topk",
                     "fed_server_resketch", "fed_server_apply",
                     "fed_telemetry_metrics", "fed_accounting"}
    assert pt.names_stages(trace, lo, hi)


def test_recorded_union_per_stage(recorded):
    trace, raw = recorded
    lo, hi = raw["window"]
    for stage, want_ms in raw["stage_ms_a_round"].items():
        got = pt.stage_seconds(trace, (stage,), lo, hi, tr) \
            / raw["rounds"] * 1e3
        assert got == pytest.approx(want_ms, rel=1e-6), stage
    # what the histograms cost and what the model costs, as PERF.md has it
    assert raw["stage_ms_a_round"]["fed_telemetry_metrics"] \
        == pytest.approx(84, abs=3)
    assert raw["stage_ms_a_round"]["fed_client_grad"] \
        == pytest.approx(67, abs=3)


def test_recorded_stages_and_unscoped_make_up_busy(recorded):
    trace, raw = recorded
    lo, hi = raw["window"]
    busy = tr.Trace(device_ops={"/device:TPU:0": [
        ("op", s, e) for _, s, e in trace.ops["/device:TPU:0"]]})
    busy_s = tr.busy_seconds(busy, lo, hi)
    parts = [pt.stage_seconds(trace, (st,), lo, hi, tr)
             for st in pt.STAGES + (None,)]
    # stages do not overlap on one chip: the unions add up
    assert sum(parts) == pytest.approx(busy_s, rel=1e-9)
    assert pt.stage_seconds(trace, pt.STAGES + (None,), lo, hi, tr) \
        == pytest.approx(busy_s, rel=1e-9)
    assert parts[-1] < 0.02 * busy_s, "unscoped above 2% of busy"
    # compress_ms is the four compression stages, topk_ms one of them
    compress = pt.stage_seconds(
        trace, ("fed_client_compress", "fed_server_estimate",
                "fed_server_topk", "fed_server_resketch"), lo, hi, tr)
    assert compress > pt.stage_seconds(trace, ("fed_server_topk",), lo, hi,
                                       tr) > 0


def test_recorded_spans_carry_their_round(recorded):
    trace, raw = recorded
    lo, hi = raw["window"]
    waited = pt.span_rounds(trace, "fed_window_wait", lo, hi)
    sent = pt.span_rounds(trace, "fed_h2d", lo, hi)
    assert sent == sorted(sent) and len(sent) == raw["rounds"]
    # the window wait names the round waited FOR: two behind the dispatch
    assert waited and all(w < max(sent) for w in waited)
    assert pt.span_rounds(trace, "fed_input_produce", lo, hi) == []
    assert pt.span_seconds(trace, "fed_window_wait", lo, hi, tr) > 0
    assert pt.span_seconds(trace, "fed_h2d", lo, hi, tr) \
        < pt.span_seconds(trace, "fed_window_wait", lo, hi, tr)


def test_span_seconds_clips_to_the_window():
    trace = pt.ProgramTrace(spans={"fed_h2d": [
        (0, 10, {"round": "0"}), (20, 40, {"round": "1"}),
        (90, 120, {"round": "2"})]})
    assert pt.span_seconds(trace, "fed_h2d", 5, 100, tr) \
        == pytest.approx((5 + 20 + 10) / 1e9)
    assert pt.span_rounds(trace, "fed_h2d", 5, 100) == [1, 2]
    assert pt.span_seconds(trace, "fed_nothing", 0, 100, tr) == 0.0


# ---- the readers: a number, or None -----------------------------------------

def _reader(name):
    import importlib

    return importlib.import_module(name)


def test_readers_on_the_recorded_list(recorded, monkeypatch, tmp_path):
    trace, raw = recorded
    ctx = _ctx(trace, *raw["window"], raw["rounds"], monkeypatch, tmp_path)
    got = {name: _reader(name).read(ctx) for name in (
        "client_grad_ms", "server_apply_ms", "telemetry_device_ms",
        "accounting_ms", "compress_ms", "topk_ms", "unscoped_ms",
        "window_wait_ms", "h2d_ms", "input_produce_ms")}
    assert all(v is not None and v > 0 for v in got.values()), got
    device = sum(got[k] for k in (
        "client_grad_ms", "server_apply_ms", "telemetry_device_ms",
        "accounting_ms", "compress_ms", "unscoped_ms"))
    assert device == pytest.approx(raw["busy_ms_a_round"], rel=1e-6)
    assert got["topk_ms"] < got["compress_ms"]


def test_readers_find_nothing_in_a_program_without_names(monkeypatch,
                                                         tmp_path):
    """The parent of the PR that brought the names: operations with paths
    but no stage, no ``fed_window_wait`` / ``fed_h2d`` spans."""
    bare = pt.ProgramTrace(ops={"/device:TPU:0": pt.stages_of([
        ("jit(client_step)/while/body/dot:", 1, 0, 50),
        ("", 1, 50, 60),
        ("jit(_mark_changed)/select_n:", 2, 70, 80)])})
    ctx = _ctx(bare, 0, 100, 1, monkeypatch, tmp_path)
    for name in ("client_grad_ms", "server_apply_ms", "telemetry_device_ms",
                 "accounting_ms", "compress_ms", "topk_ms", "unscoped_ms",
                 "window_wait_ms", "h2d_ms", "input_produce_ms"):
        assert _reader(name).read(ctx) is None, name


def test_no_trace_to_read_is_none_not_an_error(monkeypatch, tmp_path):
    monkeypatch.setenv("COMMEFFICIENT_RUN_DIR", str(tmp_path / "nowhere"))
    ctx = {"tr": tr, "lo": 0, "hi": 1, "rounds": 1}
    assert pt.of(ctx) is None
    assert _reader("client_grad_ms").read(ctx) is None
    assert _reader("unscoped_ms").read(ctx) is None
    assert _reader("h2d_ms").read(ctx) is None


# ---- the bytes ----------------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        if n < 0x80:
            out.append(n)
            return bytes(out)
        out.append((n & 0x7F) | 0x80)
        n >>= 7


def _len(no, payload):
    return _varint(no << 3 | 2) + _varint(len(payload)) + payload


def _int(no, n):
    return _varint(no << 3) + _varint(n)


def _xspace():
    """One device plane (two operations, one with a ``tf_op``), one host
    plane that must be passed over, as xplane.proto lays them out."""
    stat_meta = (_len(5, _int(1, 1) + _len(2, _int(1, 1) + _len(2, b"tf_op")))
                 + _len(5, _int(1, 2) + _len(2, _int(1, 2)
                                            + _len(2, b"program_id"))))
    staged = (_int(1, 11) + _len(2, b"%fusion.1 = f32[8] fusion()")
              + _len(5, _int(1, 1) + _len(
                  5, b"jit(s)/transpose(jvp(fed_client_grad))/mul:"))
              + _len(5, _int(1, 2) + _int(3, 77)))
    bare = _int(1, 12) + _len(2, b"%copy.1 = f32[8] copy()") \
        + _len(5, _int(1, 2) + _int(3, 77))
    event_meta = (_len(4, _int(1, 11) + _len(2, staged))
                  + _len(4, _int(1, 12) + _len(2, bare)))
    ops = (_int(1, 1) + _len(2, b"XLA Ops") + _int(3, 1000)
           + _len(4, _int(1, 11) + _int(2, 5_000_000) + _int(3, 2_000_000))
           + _len(4, _int(1, 12) + _int(2, 8_000_000) + _int(3, 1_000_000)))
    other = _int(1, 2) + _len(2, b"Steps") + _int(3, 1000) \
        + _len(4, _int(1, 11) + _int(2, 0) + _int(3, 9))
    device = _int(1, 1) + _len(2, b"/device:TPU:0") + _len(3, ops) \
        + _len(3, other) + event_meta + stat_meta
    host = _int(1, 2) + _len(2, b"/host:CPU") + _len(3, _int(1, 9) + _len(
        2, b"python3") + _len(4, _int(1, 11) + _int(2, 1) + _int(3, 1)))
    return _len(1, device) + _len(1, host) + _len(4, b"hostname")


def test_device_paths_from_the_files_bytes(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    got = pt.device_paths(str(path), tr)
    assert got == {"/device:TPU:0": [
        ("jit(s)/transpose(jvp(fed_client_grad))/mul:", 77, 6000.0, 8000.0),
        ("", 77, 9000.0, 10000.0)]}
    assert [s for s, _, _ in pt.stages_of(got["/device:TPU:0"])] \
        == ["fed_client_grad", "fed_client_grad"]
