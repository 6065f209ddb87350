"""The readers of the delta-rule mixer's scopes (``kda_attn_ms``,
``kda_core_ms``, ``kda_core_roofline``) on a small hand-made list (no trace
file): ``fed_kda_attn`` and ``fed_kda_core`` are matched as whole components
of a scope path, forward, recomputed and backward, and the core is nested in
the mixer; the roofline share divides the least time of the reference's
``kda_core_cost`` by the core's time and stays under 100% on the cell's own
shapes and costs; a program or a reference without them reads None."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for sub in (BENCH, os.path.join(BENCH, "metrics"),
            os.path.join(BENCH, "configs")):
    sys.path.insert(0, sub)

import _inner_scopes  # noqa: E402
import kda_attn_ms  # noqa: E402
import kda_core_ms  # noqa: E402
import kda_core_roofline  # noqa: E402
import trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns
BASE = "jit(client_step)/fed_client_grad/while/body/closed_call/"
FWD = BASE + "jvp(KimiLinear)/h{}/attn/fed_kda_attn/"
BWD = (BASE + "transpose(jvp(KimiLinear))/jvp(KimiLinear)/checkpoint/h{}/"
       "attn/fed_kda_attn/")
OPS = [  # (scope path, program, start ns, end ns)
    (FWD.format(1) + "convolution_add_fusion", 1, 0, 1 * MS),
    (FWD.format(1) + "fed_kda_core/triangular_solve", 1, 1 * MS, 3 * MS),
    (FWD.format(1) + "fed_kda_core/while/body/dot_general", 1, 3 * MS,
     6 * MS),
    (BWD.format(2) + "fed_kda_core/transpose(jvp(while))/body/dot_general",
     1, 6 * MS, 14 * MS),
    (BWD.format(2) + "mul", 1, 14 * MS, 16 * MS),       # the gated norm
    # the projections around the mixer, and a name that only starts alike
    (BASE + "jvp(KimiLinear)/h1/attn/dot_general", 1, 16 * MS, 20 * MS),
    (BASE + "jvp(KimiLinear)/h3/attn/fed_kda_core_not/mul", 1, 20 * MS,
     21 * MS),
]


class Ref:
    def kda_core_cost(self, batch_shapes):
        assert batch_shapes["input_ids"] == (4, 4, 1, 1024)
        return 1.0e12, 1.5e10


@pytest.fixture
def ctx(monkeypatch):
    _inner_scopes._PATHS.clear()
    monkeypatch.setattr(_inner_scopes, "_paths", lambda ctx: ctx["paths"])
    return {"paths": {"/device:TPU:0": OPS}, "tr": tr, "lo": 0,
            "hi": 21 * MS, "rounds": 2, "ref_model": Ref(),
            "batch_shapes": {"input_ids": (4, 4, 1, 1024)},
            "device": {"kind": "TPU v5 lite", "count": 1},
            "peaks": {"TPU v5 lite": {"bf16_flops": 197e12,
                                      "hbm_bytes_per_s": 819e9}}}


def test_mixer_and_core_are_whole_components_of_the_path(ctx):
    assert kda_attn_ms.read(ctx) == pytest.approx(8.0)      # 0..16 ms / 2
    assert kda_core_ms.read(ctx) == pytest.approx(6.5)      # 1..14 ms / 2


def test_the_roofline_share_is_the_larger_bound_over_the_cores_time(ctx):
    # bytes bound: 1.5e10 / 819e9 = 18.3 ms a round against 5.2 ms of FLOPs
    want = 1.5e10 / 819e9 * 2 / 13e-3 * 100.0
    assert kda_core_roofline.read(ctx) == pytest.approx(want)
    assert want > 100.0          # this list's core is faster than the chip
    # ... which a real cell's is not: at the cell's shapes and costs, a core
    # that reads the least bytes at the chip's peak bandwidth reads 100%
    import kimi_linear_ep32_ref as ref_file

    with open(os.path.join(BENCH, "configs", "kimi_linear_ep32.json")) as f:
        ref = ref_file.Model(json.load(f))
    flops, bytes_ = ref.kda_core_cost({"input_ids": (4, 4, 1, 1024)})
    assert flops / 197e12 < bytes_ / 819e9
    ctx["ref_model"] = ref
    least = bytes_ / 819e9 * ctx["rounds"]
    ctx["paths"] = {"/device:TPU:0": [
        (FWD.format(1) + "fed_kda_core/dot_general", 1, 0,
         int(least * 1e9))]}
    ctx["hi"] = int(least * 1e9)
    assert kda_core_roofline.read(ctx) == pytest.approx(100.0, rel=1e-6)


def test_a_program_or_a_reference_without_them_reads_none(ctx):
    ctx["ref_model"] = object()           # another configuration's reference
    assert kda_core_roofline.read(ctx) is None
    ctx["ref_model"] = Ref()
    ctx["paths"] = {"/device:TPU:0": [OPS[-2], OPS[-1]]}
    for reader in (kda_attn_ms, kda_core_ms, kda_core_roofline):
        assert reader.read(ctx) is None
    ctx["paths"] = None
    assert kda_core_ms.read(ctx) is None \
        and kda_core_roofline.read(ctx) is None
