"""L4 kernels: the recurrent stack's block applications' share of the chip's
bf16 peak: their model FLOPs in the traced rounds (the configuration's
reference file, ``loop_body_flops``: R x L applications of the projections,
the SwiGLU and the attention cores, forward + backward) over the device time
under ``fed_loop_body`` (``loop_body_ms``). Recomputation (every block
application runs forward twice a round) is time and no work here, so the
share understates what the unit does and cannot pass 100%."""

import loop_body_ms


def read(ctx):
    flops_of = getattr(ctx["ref_model"], "loop_body_flops", None)
    s = loop_body_ms.seconds(ctx)
    if not flops_of or not s or not ctx["rounds"]:
        return None
    kind = ctx["device"]["kind"]
    if kind not in ctx["peaks"]:
        raise SystemExit(f"bench: no published peak for device_kind {kind!r}")
    flops = flops_of(ctx["batch_shapes"]) * ctx["rounds"]
    peak = ctx["peaks"][kind]["bf16_flops"] * ctx["device"]["count"]
    return flops / s / peak * 100.0
