"""L4 kernels: the delta rule's chunked core against the chip's roofline: the
least time the work needs, the larger of its FLOPs over the bf16 peak and its
bytes over the memory's, over the device time under ``fed_kda_core``
(``kda_core_ms``). The work is the configuration's reference file's
``kda_core_cost``: the chunkwise form's FLOPs at C = 64 and the least bytes
(q, k, v, log alpha, beta in and o out forward; those, dO in and their five
gradients out backward), forward + backward, whatever implements the core.
Recomputation is time and no work here, so the share cannot pass 100%."""

import kda_core_ms


def read(ctx):
    cost_of = getattr(ctx["ref_model"], "kda_core_cost", None)
    s = kda_core_ms.seconds(ctx)
    if not cost_of or not s:
        return None
    kind = ctx["device"]["kind"]
    if kind not in ctx["peaks"]:
        raise SystemExit(f"bench: no published peak for device_kind {kind!r}")
    peak = ctx["peaks"][kind]
    flops, bytes_ = cost_of(ctx["batch_shapes"])
    chips = ctx["device"]["count"]
    least = max(flops / (peak["bf16_flops"] * chips),
                bytes_ / (peak["hbm_bytes_per_s"] * chips))
    return least * ctx["rounds"] / s * 100.0
