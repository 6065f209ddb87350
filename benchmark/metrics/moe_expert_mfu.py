"""L4 kernels: the grouped products' share of the chip's bf16 peak: model
FLOPs of the (token, expert) pairs the program counted in the window's rounds
(``moe_local_pairs`` of the event log) over the device time under
``fed_moe_experts`` and in the grouped products XLA:TPU makes of
``ragged_dot`` (as ``moe_expert_ms`` counts it). Recomputation is time and no work here, so the share
understates what the unit does."""

import _inner_scopes


def pair_flops(hidden: int, width: int) -> float:
    """Forward + backward FLOPs of one pair through its expert's SwiGLU:
    three hidden x width products, 2 FLOPs a MAC, backward twice the
    forward."""
    return 3.0 * 2.0 * 3 * hidden * width


def read(ctx):
    s = _inner_scopes.seconds(ctx, ("fed_moe_experts",),
                              _inner_scopes.GROUPED_PRODUCT)
    pairs = sum(r.get("moe_local_pairs", 0.0)
                for r in _inner_scopes.round_counters(ctx))
    if not s or not pairs:
        return None
    kind = ctx["device"]["kind"]
    if kind not in ctx["peaks"]:
        raise SystemExit(f"bench: no published peak for device_kind {kind!r}")
    cfg = ctx["config"]
    flops = pair_flops(cfg["hidden_size"], cfg["moe_intermediate_size"]) * pairs
    peak = ctx["peaks"][kind]["bf16_flops"] * ctx["device"]["count"]
    return flops / s / peak * 100.0
