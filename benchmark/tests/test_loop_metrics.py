"""The readers of a recurrent stack's scopes (``loop_body_ms``,
``loop_head_ms``, ``loop_body_mfu``) on a small hand-made list (no trace
file): ``fed_loop_body`` and ``fed_loop_head`` are matched as whole
components of a scope path, under whatever transform wrapped them; a Mosaic
call that lost its scope path counts in the body by its kernel's name; the
share of the peak divides the reference's ``loop_body_flops`` by the body's
time; a program that names nothing gives None, never 0. The attention
nested in the body is read by the grouped-query readers the benchmark had
(``gqa_attn_ms``, ``gqa_attn_full_ms``, ``gqa_attn_mfu``): they find it in
such a program too."""

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
import loop_body_mfu  # noqa: E402
import loop_body_ms  # noqa: E402
import loop_head_ms  # noqa: E402
import trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns
BASE = "jit(client_step)/fed_client_grad/while/body/closed_call/"
FWD = BASE + "jvp(Ouro)/fed_loop_body/h{}/"
ATTN = "attn/fed_gqa_attn/fed_gqa_attn_full/"
BWD = (BASE + "transpose(jvp(Ouro))/fed_loop_body/fed_client_grad/jvp(Ouro)/"
       "fed_loop_body/checkpoint/")
OPS = [  # (scope path, program, start ns, end ns)
    (FWD.format(0) + "mlp/up/dot_general", 1, 0, 2 * MS),
    (FWD.format(0) + ATTN + "fed_gqa_attn_fwd/pallas_call", 1, 2 * MS,
     5 * MS),
    (BWD + "rematted_computation/h1/" + ATTN + "fed_gqa_attn_fwd/pallas_call",
     1, 5 * MS, 8 * MS),
    (BWD + "h1/" + ATTN + ATTN[5:] + "fed_gqa_attn_bwd/pallas_call", 1, 8 * MS,
     14 * MS),
    # a call whose path kept its kernel's name only
    ("fed_gqa_attn_bwd/pallas_call", 1, 14 * MS, 20 * MS),
    # what is read after a pass: forward, and its backward
    (BASE + "jvp(Ouro)/exit/fed_loop_head/dot_general", 1, 20 * MS, 23 * MS),
    (BASE + "transpose(jvp(Ouro))/exit/fed_loop_head/checkpoint/dot_general",
     1, 23 * MS, 27 * MS),
    # the embedding, outside both; a name that only starts like the scope
    (BASE + "jvp(Ouro)/embed/gather", 1, 27 * MS, 28 * MS),
    (BASE + "jvp(Ouro)/fed_loop_body_not/mul", 1, 28 * MS, 30 * MS),
]
KERNEL = ('%{}.{} = f32[4,1024,2048]{{2,1,0}} custom-call(...), '
          'custom_call_target="tpu_custom_call"')
NAMED = [  # (HLO text, start, end): what trace_reduce keeps of an operation
    ("%fusion.7 = f32[4,1024,5632] fusion(...), kind=kOutput", 0, 2 * MS),
    (KERNEL.format("fed_gqa_attn_fwd", 3), 2 * MS, 5 * MS),
    (KERNEL.format("fed_gqa_attn_fwd", 4), 5 * MS, 8 * MS),
    (KERNEL.format("fed_gqa_attn_bwd", 2), 8 * MS, 14 * MS),
    (KERNEL.format("fed_gqa_attn_bwd", 1), 14 * MS, 20 * MS),
    (KERNEL.format("fed_sketch_accum", 1), 27 * MS, 28 * MS),
]


class Ref:
    def loop_body_flops(self, batch_shapes):
        assert batch_shapes["input_ids"] == (4, 2, 1, 1024)
        return 4.0e13

    def attention_core_flops(self, batch_shapes):
        return 1.6e12


@pytest.fixture
def ctx(monkeypatch):
    _inner_scopes._PATHS.clear()
    monkeypatch.setattr(_inner_scopes, "_paths", lambda ctx: ctx["paths"])
    return {"paths": {"/device:TPU:0": OPS}, "tr": tr,
            "trace": tr.Trace(device_ops={"/device:TPU:0": NAMED}),
            "lo": 0, "hi": 30 * MS, "rounds": 2, "ref_model": Ref(),
            "batch_shapes": {"input_ids": (4, 2, 1, 1024)},
            "device": {"kind": "TPU v5 lite", "count": 1},
            "peaks": {"TPU v5 lite": {"bf16_flops": 197e12}}}


def test_body_and_head_are_whole_components_of_the_path(ctx):
    # 0..20 ms, the pathless call among them; not ``fed_loop_body_not``
    assert loop_body_ms.read(ctx) == pytest.approx(10.0)
    assert loop_head_ms.read(ctx) == pytest.approx(3.5)      # 20..27 ms / 2


def test_a_kernel_without_its_scope_counts_by_name_beside_the_scope(ctx):
    ctx["paths"] = {"/device:TPU:0": [OPS[0], OPS[4]]}
    assert loop_body_ms.read(ctx) == pytest.approx(4.0)      # (2 + 6) / 2
    # ... and not in a program that names no recurrent stack at all
    ctx["paths"] = {"/device:TPU:0": [OPS[4]]}
    assert loop_body_ms.read(ctx) is None
    assert gqa_attn_ms.read(ctx) == pytest.approx(3.0)


def test_shares_of_the_peak(ctx):
    assert loop_body_mfu.read(ctx) == pytest.approx(
        4.0e13 * 2 / 20e-3 / 197e12 * 100.0)


def test_the_grouped_query_readers_find_the_attention_in_the_body(ctx):
    # the four calls, 2..20 ms; all of them full attention but the pathless
    assert gqa_attn_ms.read(ctx) == pytest.approx(9.0)
    assert gqa_attn_full_ms.read(ctx) == pytest.approx(6.0)
    # 18 ms in the four calls, the sketch's kernel not among them
    want = 1.6e12 * 2 / 18e-3 / 197e12 * 100.0
    assert gqa_attn_mfu.read(ctx) == pytest.approx(want)
    # an operation list that names no kernel: the scope paths' components
    ctx["trace"] = tr.Trace(device_ops={"/device:TPU:0": NAMED[:1]})
    assert gqa_attn_mfu.read(ctx) == pytest.approx(want)


def test_a_program_or_a_reference_without_them_reads_none(ctx):
    ctx["ref_model"] = object()           # another configuration's reference
    assert loop_body_mfu.read(ctx) is None
    ctx["ref_model"] = Ref()
    ctx["paths"] = {"/device:TPU:0": [
        ("jit(client_step)/fed_client_grad/dot_general", 1, 0, MS)]}
    ctx["trace"] = tr.Trace(device_ops={"/device:TPU:0": NAMED[:1]})
    for reader in (loop_body_ms, loop_head_ms, loop_body_mfu):
        assert reader.read(ctx) is None
    ctx["paths"] = None
    assert loop_body_ms.read(ctx) is None and loop_body_mfu.read(ctx) is None
