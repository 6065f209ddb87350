"""The readers of the scopes inside the client phase and of the routing
counters, on a small hand-made list (no trace file): an operation counts
under an inner scope by a component of its path, whatever transform wrapped
it; XLA:TPU's grouped-product custom calls count by their own name; a program
that names nothing gives None."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "metrics"))

import _inner_scopes  # noqa: E402
import mla_attn_ms  # noqa: E402
import moe_expert_mfu  # noqa: E402
import moe_expert_ms  # noqa: E402
import moe_load_imbalance  # noqa: E402
import moe_route_ms  # noqa: E402
import trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns
BASE = "jit(client_step)/fed_client_grad/while/body/closed_call/"
OPS = [  # (scope path, program, start ns, end ns)
    (BASE + "jvp(JoyAIFlash)/h1/moe/fed_moe_route/top_k", 1, 0, 2 * MS),
    (BASE + "jvp(JoyAIFlash)/h1/moe/cond/branch_1_fun/fed_moe_experts/mul",
     1, 2 * MS, 3 * MS),
    ("ragged-dot-none", 1, 3 * MS, 7 * MS),
    (BASE + "transpose(jvp(JoyAIFlash))/jvp(JoyAIFlash)/checkpoint/h1/moe/"
     "cond/branch_1_fun/transpose(jvp(fed_moe_experts))/mul", 1, 7 * MS,
     8 * MS),
    (BASE + "jvp(JoyAIFlash)/h1/attn/fed_mla_attn/dot_general", 1, 8 * MS,
     18 * MS),
    (BASE + "jvp(JoyAIFlash)/h1/attn/dot_general", 1, 18 * MS, 20 * MS),
    ("jit(server_step)/fed_server_topk/sort", 2, 20 * MS, 21 * MS),
]


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    monkeypatch.setenv("COMMEFFICIENT_RUN_DIR", str(tmp_path))
    with open(tmp_path / "telemetry.jsonl", "w") as f:
        f.write(json.dumps({"ev": "run_start"}) + "\n")
        for r, pairs in enumerate([900.0, 1000.0, 1100.0]):
            f.write(json.dumps({"ev": "round", "round": r, "model": {
                "moe_local_pairs": pairs, "moe_absent_pairs": 5.0,
                "moe_load_max_over_mean": 1.0 + r}}) + "\n")
    _inner_scopes._PATHS.clear()
    monkeypatch.setattr(_inner_scopes, "_paths", lambda ctx: ctx["paths"])
    return {"paths": {"/device:TPU:0": OPS}, "tr": tr, "lo": 0,
            "hi": 21 * MS, "rounds": 2,
            "config": {"hidden_size": 2048, "moe_intermediate_size": 768},
            "device": {"kind": "TPU v5 lite", "count": 1},
            "peaks": {"TPU v5 lite": {"bf16_flops": 197e12}}}


def test_scopes_are_read_by_component(ctx):
    assert moe_route_ms.read(ctx) == pytest.approx(1.0)
    assert moe_expert_ms.read(ctx) == pytest.approx(3.0)   # 1 + 4 + 1, / 2
    assert mla_attn_ms.read(ctx) == pytest.approx(5.0)


def test_counters_are_the_windows_last_rounds(ctx):
    assert moe_load_imbalance.read(ctx) == pytest.approx(2.5)
    flops = moe_expert_mfu.pair_flops(2048, 768) * 2100.0
    assert moe_expert_mfu.read(ctx) == pytest.approx(
        flops / 6e-3 / 197e12 * 100.0)


def test_a_program_that_names_nothing_reads_none(ctx, tmp_path):
    ctx["paths"] = {"/device:TPU:0": [
        ("jit(client_step)/fed_client_grad/dot_general", 1, 0, MS)]}
    os.remove(tmp_path / "telemetry.jsonl")
    for reader in (moe_route_ms, moe_expert_ms, mla_attn_ms, moe_expert_mfu,
                   moe_load_imbalance):
        assert reader.read(ctx) is None
    ctx["paths"] = None
    assert moe_expert_ms.read(ctx) is None
