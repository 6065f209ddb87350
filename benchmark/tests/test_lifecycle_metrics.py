"""The nine readers of the program's own record of its start-up and device
memory (``metrics/_lifecycle.py``), on a recorded event log: a warm traced
run of ``resnet9_sketch_1c`` on one TPU v5e chip (PR 36, seed 2147510002; 24
warm-up rounds, a validation pass, a window of 56 rounds; the ``round`` lines
cut to their stamps). Each reader returns the number that run's result line
printed; "before the window" moves with ``ctx['rounds']``; against a log
without the records (any program before PR 36), a torn one or none, every
reader returns ``None`` and raises nothing."""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "metrics"))

import _lifecycle  # noqa: E402
import data_setup_s  # noqa: E402
import hbm_at_rest_gib  # noqa: E402
import hbm_in_use_peak_gib  # noqa: E402
import hbm_reserved_peak_gib  # noqa: E402
import import_s  # noqa: E402
import model_setup_s  # noqa: E402
import program_load_s  # noqa: E402
import program_trace_s  # noqa: E402
import programs_compiled  # noqa: E402

RECORDED = os.path.join(HERE, "data", "lifecycle_resnet9_warm.jsonl")
# what the run's own result line read (chiprun_out/pr36, my chip run, PR 36)
WANT = {
    import_s: 14.75,
    data_setup_s: 0.363,
    model_setup_s: 7.536,
    program_trace_s: 10.4308,
    program_load_s: 1.2354,
    programs_compiled: 0.0,
    hbm_in_use_peak_gib: 445455360 / 2**30,
    hbm_reserved_peak_gib: 5777637376 / 2**30,
    hbm_at_rest_gib: 166969856 / 2**30,
}


@pytest.fixture
def run_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("COMMEFFICIENT_RUN_DIR", str(tmp_path))
    _lifecycle._EVENTS.clear()
    return tmp_path


def _records():
    with open(RECORDED) as f:
        return [json.loads(line) for line in f]


def _write(run_dir, records, tail=""):
    with open(run_dir / "telemetry.jsonl", "w") as f:
        f.write("".join(json.dumps(r) + "\n" for r in records) + tail)
    _lifecycle._EVENTS.clear()


@pytest.mark.parametrize("reader", list(WANT), ids=lambda m: m.__name__)
def test_reader_returns_the_runs_number(run_dir, reader):
    shutil.copy(RECORDED, run_dir / "telemetry.jsonl")
    assert reader.read({"rounds": 56}) == pytest.approx(WANT[reader],
                                                        abs=1e-9)


def test_the_two_peaks_are_the_addends_of_peak_hbm(run_dir):
    shutil.copy(RECORDED, run_dir / "telemetry.jsonl")
    ctx = {"rounds": 56}
    both = hbm_in_use_peak_gib.read(ctx) + hbm_reserved_peak_gib.read(ctx)
    # the same run's device.memory_peak_bytes
    assert round(both * 2**30) == 6223092736


def test_before_the_window_follows_the_rounds(run_dir):
    """The window is the run's last ``ctx['rounds']`` rounds: with all 80
    rounds in it the round's own programs (``client_step`` at round 0) are no
    longer set-up's; a count the log cannot hold reads None."""
    shutil.copy(RECORDED, run_dir / "telemetry.jsonl")
    whole = program_trace_s.read({"rounds": 56})
    early = program_trace_s.read({"rounds": 80})
    assert early < whole - 1.0
    named = {p["name"] for p in _lifecycle.programs_before_window(
        {"rounds": 80})}
    assert "jit(make)" in named and "jit(client_step)" not in named
    assert program_trace_s.read({"rounds": 81}) is None
    assert program_trace_s.read({"rounds": 0}) is None
    # the phases and the memory samples do not depend on the window
    assert import_s.read({"rounds": 0}) == 14.75
    assert hbm_at_rest_gib.read({"rounds": 0}) == WANT[hbm_at_rest_gib]


def test_a_compile_is_counted_a_small_or_loaded_program_is_not(run_dir):
    records = _records()
    first = next(r for r in records if r["ev"] == "program"
                 and r["name"] == "jit(client_step)")
    first.update(cache="miss", backend_s=13.0, stored=True)
    small = next(r for r in records if r["ev"] == "program"
                 and r["name"] == "jit(val_step)")
    small.update(cache="miss", backend_s=0.05)
    _write(run_dir, records)
    assert programs_compiled.read({"rounds": 56}) == 1.0
    assert program_load_s.read({"rounds": 56}) == pytest.approx(
        1.2354 - 0.2786 + 13.0 - 0.0216 + 0.05)


def test_at_rest_is_the_last_sample_with_nothing_in_flight(run_dir):
    records = _records()
    drains = [r for r in records if r["ev"] == "drain"]
    drains[-1]["inflight"] = 2
    drains[-1]["memory"]["bytes_in_use"] = 999
    drains[-2]["memory"]["bytes_in_use"] = 3 * 2**30
    _write(run_dir, records)
    assert hbm_at_rest_gib.read({"rounds": 56}) == 3.0
    assert hbm_in_use_peak_gib.read({"rounds": 56}) == \
        WANT[hbm_in_use_peak_gib]


@pytest.mark.parametrize("log", ["parent", "cpu", "torn", "none"])
def test_without_the_records_every_reader_reads_none(run_dir, log):
    """The parent's log has rounds and drains and none of the new events or
    fields; a CPU run's samples are null; a torn last line ends the read; no
    log at all."""
    if log == "parent":
        old = [r for r in _records() if r["ev"] in ("run_start", "round",
                                                    "drain", "run_end")]
        for r in old:
            for key in ("memory", "inflight", "programs"):
                r.pop(key, None)
        _write(run_dir, old)
    elif log == "cpu":
        cpu = _records()
        for r in cpu:
            if "memory" in r:
                r["memory"] = None
        _write(run_dir, [r for r in cpu if r["ev"] != "setup"])
    elif log == "torn":
        _write(run_dir, [{"ev": "run_start", "t": 1.0}], tail='{"ev": "set')
    readers = list(WANT)
    if log == "cpu":   # the programs are there; phases and memory are not
        assert program_trace_s.read({"rounds": 56}) == pytest.approx(10.4308)
        readers = [import_s, data_setup_s, model_setup_s,
                   hbm_in_use_peak_gib, hbm_reserved_peak_gib,
                   hbm_at_rest_gib]
    for reader in readers:
        assert reader.read({"rounds": 56}) is None, reader.__name__
