"""A CPU rehearsal of run.py at tiny width (kernels interpreted): the last
line's keys; the planted faults and the lower-precision control come out as
not correct; without --rehearse and without a TPU nothing is printed.

Slow (a minute or two a run). Run by hand: python -m pytest benchmark/tests"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


def run(workload, *extra, rehearse=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               COMMEFFICIENT_PALLAS_SKETCH="interpret")
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", workload, "--seed", "2147484000", "--seconds", "1",
           *extra] + (["--rehearse"] if rehearse else [])
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=1500)
    return p


def last_line(p):
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_result_line_keys(workload):
    line = last_line(run(workload, "--trace", "0"))
    assert list(line)[:4] == ["correct", "attempted", "failed", "metrics"]
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"rounds_per_s", "peak_hbm_gib", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["attempted"] > 0 and line["attempted"] % 8 == 0
    assert line["failed"] == 0
    for e in line["compared"].values():
        assert e["value"] <= e["limit"]


def test_traced_line_has_window_and_breakdown():
    line = last_line(run(CELLS[0], "--trace", "1"))
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"input_wait_ms", "val_ms"} <= set(line["metrics"])


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "control"])
def test_a_broken_timed_path_is_not_correct(fault):
    line = last_line(run(CELLS[0], "--variant", fault))
    assert line["correct"] is False
    over = [n for n, e in line["compared"].items() if e["value"] > e["limit"]]
    assert over, line["compared"]


def test_without_a_chip_nothing_is_printed():
    p = run(CELLS[0], "--trace", "0", rehearse=False)
    assert p.returncode != 0 and p.stdout.strip() == ""
