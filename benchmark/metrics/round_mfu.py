"""L3 round step: the whole round's share of the chip's bf16 peak, idle time
included: model FLOPs of a round (forward + backward, from the
configuration's reference file) x traced rounds / traced seconds / chips /
peak."""


def read(ctx):
    if not ctx["busy_s"] or not ctx["rounds"]:
        return None
    kind = ctx["device"]["kind"]
    if kind not in ctx["peaks"]:
        raise SystemExit(f"bench: no published peak for device_kind "
                         f"{kind!r}; add it to benchmark/peaks.json")
    flops = ctx["ref_model"].train_flops(ctx["batch_shapes"]) * ctx["rounds"]
    peak = ctx["peaks"][kind]["bf16_flops"] * ctx["device"]["count"]
    return flops / ctx["window_s"] / peak * 100.0
