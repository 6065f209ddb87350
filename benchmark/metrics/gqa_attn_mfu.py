"""L4 kernels: the attention kernels' share of the chip's bf16 peak: model
FLOPs of the attention cores of the traced rounds (the configuration's
reference file, ``attention_core_flops``: the (query, key) pairs the masks
let through, forward + backward) over the device time of the Mosaic calls
``fed_gqa_attn_fwd`` / ``fed_gqa_attn_bwd``. Recomputation (the forward
kernel runs twice a round, the backward kernel rebuilds the scores) and the
masked halves of edge tiles are time and no work here, so the share
understates what the unit does and cannot pass 100%."""

import _inner_scopes
import gqa_attn_ms
import sketch_kernel_ms


def kernel_seconds(ctx) -> float:
    """Seconds in the two kernels: by the call's name in the operation's
    HLO text, else by its name as a component of the scope path."""
    s = sum(ctx["tr"].op_seconds(
        ctx["trace"], ctx["lo"], ctx["hi"],
        lambda n: sketch_kernel_ms.is_kernel(n)
        and any(k in n for k in gqa_attn_ms.KERNELS)).values())
    return s or _inner_scopes.seconds(ctx, gqa_attn_ms.KERNELS)


def read(ctx):
    flops_of = getattr(ctx["ref_model"], "attention_core_flops", None)
    s = kernel_seconds(ctx) if flops_of and ctx["rounds"] else 0.0
    if not s:
        return None
    kind = ctx["device"]["kind"]
    if kind not in ctx["peaks"]:
        raise SystemExit(f"bench: no published peak for device_kind {kind!r}")
    flops = flops_of(ctx["batch_shapes"]) * ctx["rounds"]
    peak = ctx["peaks"][kind]["bf16_flops"] * ctx["device"]["count"]
    return flops / s / peak * 100.0
