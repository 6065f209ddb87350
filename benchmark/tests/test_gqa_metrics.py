"""The readers of the grouped-query attention's scopes and kernels
(``gqa_attn_ms``, ``gqa_attn_full_ms``, ``gqa_attn_mfu``) on a small
hand-made list (no trace file): ``fed_gqa_attn`` and ``fed_gqa_attn_full``
are matched as whole components of a scope path and do not shadow each
other; a Mosaic call that lost its scope path counts by its kernel's name;
the share of the peak divides the reference's ``attention_core_flops`` by the
two kernels' time alone; a program that names nothing gives None."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "metrics"))

import _inner_scopes  # noqa: E402
import gqa_attn_full_ms  # noqa: E402
import gqa_attn_mfu  # noqa: E402
import gqa_attn_ms  # noqa: E402
import mla_attn_ms  # noqa: E402
import trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns
BASE = "jit(client_step)/fed_client_grad/while/body/closed_call/"
FWD = BASE + "jvp(LagunaXS2)/h{}/attn/fed_gqa_attn/fed_gqa_attn_{}/"
BWD = (BASE + "transpose(jvp(LagunaXS2))/h{}/attn/fed_gqa_attn/"
       "fed_gqa_attn_{}/")
OPS = [  # (scope path, program, start ns, end ns)
    (FWD.format(0, "full") + "mul", 1, 0, 1 * MS),                 # RoPE
    (FWD.format(0, "full") + "fed_gqa_attn_fwd/pallas_call", 1, 1 * MS,
     5 * MS),
    (FWD.format(1, "window") + "fed_gqa_attn_fwd/pallas_call", 1, 5 * MS,
     7 * MS),
    (BWD.format(1, "window") + "fed_gqa_attn_bwd/pallas_call", 1, 7 * MS,
     10 * MS),
    # a call whose path kept its kernel's name only
    ("fed_gqa_attn_bwd/pallas_call", 1, 10 * MS, 16 * MS),
    # the projections around the core, and another model's scope
    (BASE + "jvp(LagunaXS2)/h0/attn/dot_general", 1, 16 * MS, 20 * MS),
    (BASE + "jvp(JoyAIFlash)/h1/attn/fed_mla_attn/dot_general", 1, 20 * MS,
     21 * MS),
]
KERNEL = ('%{}.{} = f32[4,4096,8192]{{2,1,0}} custom-call(...), '
          'custom_call_target="tpu_custom_call"')
NAMED = [  # (HLO text, start, end): what trace_reduce keeps of an operation
    ("%fusion.7 = f32[4,4096,64,128] fusion(...), kind=kLoop", 0, 1 * MS),
    (KERNEL.format("fed_gqa_attn_fwd", 3), 1 * MS, 5 * MS),
    (KERNEL.format("fed_gqa_attn_fwd", 4), 5 * MS, 7 * MS),
    (KERNEL.format("fed_gqa_attn_bwd", 2), 7 * MS, 10 * MS),
    (KERNEL.format("fed_gqa_attn_bwd", 1), 10 * MS, 16 * MS),
    (KERNEL.format("fed_sketch_accum", 1), 16 * MS, 18 * MS),
]


class Ref:
    def attention_core_flops(self, batch_shapes):
        assert batch_shapes["input_ids"] == (4, 1, 1, 4096)
        return 3.0e12


@pytest.fixture
def ctx(monkeypatch):
    _inner_scopes._PATHS.clear()
    monkeypatch.setattr(_inner_scopes, "_paths", lambda ctx: ctx["paths"])
    return {"paths": {"/device:TPU:0": OPS}, "tr": tr,
            "trace": tr.Trace(device_ops={"/device:TPU:0": NAMED}),
            "lo": 0, "hi": 21 * MS, "rounds": 2, "ref_model": Ref(),
            "batch_shapes": {"input_ids": (4, 1, 1, 4096)},
            "device": {"kind": "TPU v5 lite", "count": 1},
            "peaks": {"TPU v5 lite": {"bf16_flops": 197e12}}}


def test_scopes_do_not_shadow_each_other(ctx):
    assert gqa_attn_ms.read(ctx) == pytest.approx(8.0)        # 16 ms / 2
    assert gqa_attn_full_ms.read(ctx) == pytest.approx(2.5)   # 0..5 ms / 2
    assert mla_attn_ms.read(ctx) == pytest.approx(0.5)


def test_a_kernel_without_its_scope_counts_by_name(ctx):
    ctx["paths"] = {"/device:TPU:0": [OPS[4]]}
    assert gqa_attn_ms.read(ctx) == pytest.approx(3.0)
    assert gqa_attn_full_ms.read(ctx) is None


def test_share_of_the_peak_is_over_the_kernels_time(ctx):
    # 15 ms in the four calls, RoPE and the sketch's kernel not among them
    want = 3.0e12 * 2 / 15e-3 / 197e12 * 100.0
    assert gqa_attn_mfu.read(ctx) == pytest.approx(want)
    # an operation list that names no kernel: the scope paths' components
    ctx["trace"] = tr.Trace(device_ops={"/device:TPU:0": NAMED[:1]})
    assert gqa_attn_mfu.read(ctx) == pytest.approx(want)


def test_a_program_or_a_reference_without_them_reads_none(ctx):
    ctx["ref_model"] = object()           # another configuration's reference
    assert gqa_attn_mfu.read(ctx) is None
    ctx["ref_model"] = Ref()
    ctx["paths"] = {"/device:TPU:0": [
        ("jit(client_step)/fed_client_grad/dot_general", 1, 0, MS)]}
    ctx["trace"] = tr.Trace(device_ops={"/device:TPU:0": NAMED[:1]})
    for reader in (gqa_attn_ms, gqa_attn_full_ms, gqa_attn_mfu):
        assert reader.read(ctx) is None
    ctx["paths"] = None
    assert gqa_attn_ms.read(ctx) is None and gqa_attn_mfu.read(ctx) is None
