"""On-chip measurement batch for the current HEAD.

Timing rule: chain dependent calls inside one loop and end every timed
region by materializing a scalar of the result, so the region covers the
device work and not just the enqueue.

Measures: the CIFAR and GPT-2 (f32/bf16) fused federated rounds and per-op
sketch/estimates/top-k costs at both FetchSGD geometries. The touched-cells
A/B (sparse-scatter replacement for the server's dense re-sketch) was
DECIDED on-chip 2026-07-31: flatnonzero+scatter measured 63.8 ms vs 2.17 ms
for the dense re-sketch at d=6.5M — dropped, the dense re-sketch stays.

Run on the chip (this process owns it; no leg may start a child that needs
it):  python scripts/tpu_measure.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from commefficient_tpu.utils import configure_compile_cache  # noqa: E402

configure_compile_cache()

import numpy as np
import jax
import jax.numpy as jnp

import bench as B
from commefficient_tpu.ops import sketch as sk
from commefficient_tpu.ops.topk import topk


def drain(x):
    return float(jnp.asarray(x).ravel()[0])


def rtt_measure(x):
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        drain(x)
        best = min(best, time.perf_counter() - t0)
    return best


def time_rounds(steps, state0, batch, iters=20, reps=3, lr=0.1, rng=None):
    """Returns (seconds/round, rtt, final_state). train_step donates
    ps_weights and client_states (donate_argnums=(0, 2)), so the caller's
    state0 buffers are DELETED by the first call — reuse the returned
    state, never the originals."""
    if rng is None:
        rng = jax.random.key(0)
    state = state0
    for _ in range(3):
        out = steps.train_step(*state, batch, lr, rng)
        state = out[:4]
        drain(state[0])
    rtt = rtt_measure(state[0])
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = steps.train_step(*state, batch, lr, rng)
            state = out[:4]
        drain(state[0])
        best = min(best, max(time.perf_counter() - t0 - rtt, 1e-9))
    return best / iters, rtt, state


def chained(f, x0, n=5, K=20):
    @jax.jit
    def body(x):
        for _ in range(K):
            x = f(x)
        return x

    r = body(x0)
    drain(r)
    rtt = rtt_measure(r)
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        r = body(x0)
        drain(r)
        best = min(best, (time.perf_counter() - t0 - rtt) / K * 1e3)
    return best


def matmul_peak_probe():
    """Achievable-matmul-rate ceiling on this chip, bf16 and f32: the MFU
    denominator sanity check (v5e nominal bf16 peak is 197 TFLOP/s; what a
    big clean GEMM actually sustains is the honest ceiling for our MFU
    numbers)."""
    for dt, tag in ((jnp.bfloat16, "bf16"), (jnp.float32, "f32 ")):
        n = 4096
        x = jnp.asarray(np.random.RandomState(0).randn(n, n), dt)
        ms = chained(lambda a: a @ a / jnp.float32(n).astype(dt), x, K=10)
        tflops = 2 * n**3 / (ms * 1e-3) / 1e12
        print(f"matmul {tag} {n}x{n}: {ms:.3f} ms = {tflops:.1f} TFLOP/s",
              flush=True)


def gpt2_phase_split(steps, ps, cs, batch, round_ms, tag):
    """Time the client phase (fwd/bwd + compression) on its own —
    BASELINE.md attributes ~50 of ~83 ms to client fwd/bwd; this pins where
    round-3 perf effort should go."""
    rng = jax.random.key(0)

    # client_step is phase 1 of the same round the fused step runs
    def client_scalar(p):
        ctx = steps.client_step(p, cs, {}, batch, 0.1, rng)[0]
        return p + ctx.gradient.reshape(-1)[0] * 1e-30

    t_client = chained(client_scalar, ps, n=3, K=5)
    print(f"GPT-2 {tag} client phase: {t_client:.2f} ms of "
          f"{round_ms:.2f} ms round -> server+glue "
          f"{round_ms - t_client:.2f} ms", flush=True)


def leg(name, fn, *a, **kw):
    """Run one measurement leg, printing its result immediately; a failed
    leg (compile blowup) reports and is skipped instead of killing the
    rest of the batch."""
    try:
        return fn(*a, **kw)
    except Exception as e:  # noqa: BLE001
        print(f"LEG FAILED [{name}]: {type(e).__name__}: "
              f"{str(e)[:300]}", flush=True)
        return None


def cifar_leg():
    steps, ps, ss, cs, batch = B.build(tiny=False)
    dt, rtt, _ = time_rounds(steps, (ps, ss, cs, {}), batch)
    print(f"CIFAR round: {dt * 1e3:.2f} ms ({1 / dt:.1f} r/s), "
          f"rtt {rtt * 1e3:.0f} ms", flush=True)


def sketch_ops_leg(d):
    """Cheap legs first; the compile-heavy chained pieces (deep
    while_loop HLOs, pallas A/B) last so a mid-leg abort costs the least
    information."""
    geo = sk.make_sketch(d, c=500_000, r=5, seed=42, num_blocks=20)
    v = jnp.asarray(np.random.RandomState(0).randn(d).astype(np.float32))
    tbl = sk.sketch_vec(geo, v)
    est = sk.estimates(geo, tbl)
    upd = topk(est, 50_000)
    drain(upd)
    t_sv = leg("sketch_vec", chained,
               lambda x: x + sk.sketch_vec(geo, x)[0, 0] * 1e-38, v)
    if t_sv is not None:
        print(f"d={d}: sketch_vec {t_sv:.2f} ms", flush=True)
    t_es = leg("est+sketch", chained,
               lambda t: sk.sketch_vec(geo, sk.estimates(geo, t)), tbl)
    if t_es is not None:
        print(f"d={d}: est+sketch {t_es:.2f} ms", flush=True)
    t_resk = leg("resketch", chained,
                 lambda u: u + sk.sketch_vec(geo, u)[0, 0] * 1e-38, upd)
    if t_resk is not None:
        print(f"d={d}: resketch {t_resk:.2f} ms", flush=True)
    # topk's radix descent is a while_loop — chain a SHORT unroll (K=4)
    # to keep the HLO small
    t_topk = leg("topk", chained, lambda x: topk(x, 50_000), est, K=4)
    if t_topk:
        print(f"d={d}: topk {t_topk:.2f} ms", flush=True)

    # single radix pass in isolation: 15 compares + count over d.
    # Ideal = one HBM read (4B*d); if measured GB/s is far below the
    # ~800 GB/s class, XLA is materializing the (d,15) broadcast and a
    # Pallas count kernel is worth writing (topk is 8 of these passes).
    ts = jnp.arange(1, 16, dtype=jnp.int32) << 24

    def one_pass(x):
        m = x.view(jnp.int32) & 0x7FFFFFFF
        counts = jnp.sum(m[:, None] >= ts[None, :], axis=0)
        return x + counts[0].astype(jnp.float32) * 1e-38

    t_pass = leg("radix-pass", chained, one_pass, est)
    if t_pass:
        print(f"d={d}: one radix count pass {t_pass:.2f} ms = "
              f"{4 * d / (t_pass * 1e-3) / 1e9:.0f} GB/s effective",
              flush=True)

    # Pallas count-pass A/B (kernel is default-off; flip
    # COMMEFFICIENT_PALLAS_TOPK=1 in bench/entrypoints if this wins
    # and the outputs match exactly)
    from commefficient_tpu.ops.topk import _topk_threshold_1d_pallas

    t_ptopk = float("nan")
    try:
        same = bool(jnp.all(_topk_threshold_1d_pallas(est, 50_000)
                            == topk(est, 50_000)))
        t_ptopk = chained(
            lambda x: _topk_threshold_1d_pallas(x, 50_000), est, K=4)
        print(f"d={d}: pallas topk {t_ptopk:.2f} ms vs XLA "
              f"{t_topk if t_topk else float('nan'):.2f} "
              f"ms | outputs equal: {same}", flush=True)
    except Exception as e:  # noqa: BLE001
        print(f"d={d}: pallas topk failed: {str(e)[:300]}", flush=True)


def topk_ab_leg(d):
    """Cheap standalone top-k A/B at one geometry: XLA descent vs per-pass
    Pallas vs the fused whole-descent kernel (one pallas_call for all 8
    passes, SMEM prefix carry; default-off behind
    COMMEFFICIENT_PALLAS_TOPK_FUSED=1 — flip only if it beats the per-pass
    kernel here with equal output). Any dense vector exercises the same
    code; no sketch build needed, so this costs minutes, not the full
    wedge-prone ops chain.

    Since the d-scalable blocking landed (ops/topk._sub_for,
    docs/fused_epilogue.md) both kernels run 1 MiB blocks above the 32M
    gate — THE re-run this leg exists for: if the per-pass or fused kernel
    now beats XLA at d=124M with equal outputs, move (or delete)
    _PALLAS_TOPK_MAX_D and record the table in docs/fused_epilogue.md."""
    from commefficient_tpu.ops.topk import (
        _sub_for,
        _topk_threshold_1d,
        _topk_threshold_1d_fused,
        _topk_threshold_1d_pallas,
    )

    v = jnp.asarray(np.random.RandomState(0).randn(d).astype(np.float32))
    print(f"d={d}: kernel block sublanes = {_sub_for(d)} "
          f"({_sub_for(d) * 128 * 4 // 1024} KiB blocks)", flush=True)
    ref = _topk_threshold_1d(v, 50_000)
    drain(ref)
    t_x = chained(lambda x: _topk_threshold_1d(x, 50_000), v, K=4)
    print(f"d={d}: XLA-descent topk {t_x:.2f} ms", flush=True)
    t_p = chained(lambda x: _topk_threshold_1d_pallas(x, 50_000), v, K=4)
    same_p = bool(jnp.all(_topk_threshold_1d_pallas(v, 50_000) == ref))
    print(f"d={d}: per-pass pallas topk {t_p:.2f} ms | outputs equal: "
          f"{same_p}", flush=True)
    t_f = chained(lambda x: _topk_threshold_1d_fused(x, 50_000), v, K=4)
    same_f = bool(jnp.all(_topk_threshold_1d_fused(v, 50_000) == ref))
    print(f"d={d}: fused-descent topk {t_f:.2f} ms vs per-pass pallas "
          f"{t_p:.2f} ms | outputs equal: {same_f}", flush=True)


def fused_epilogue_leg(d):
    """Fused server epilogue A/B (docs/fused_epilogue.md): the composed
    topk_dense_nd + sketch_chunks pair vs fused_epilogue_chunks on real
    estimate chunks at the FetchSGD sketch geometry. Both arms chain
    through an estimates_chunks round-trip (table -> est -> epilogue ->
    table) so the chained scalar forces the whole pipeline; the arms
    differ only in the epilogue, so the delta IS the fusion win. Output
    equality is checked bitwise (update) / by == (table, ±0 allowed)."""
    from commefficient_tpu.ops.topk import topk_dense_nd

    geo = sk.make_sketch(d, c=500_000, r=5, seed=42, num_blocks=20)
    if not sk.fused_epilogue_supported(geo):
        print(f"d={d}: fused epilogue unsupported at this geometry "
              f"(VMEM guard)", flush=True)
        return
    tbl = jnp.asarray(
        np.random.RandomState(0).randn(*geo.table_shape), jnp.float32)
    est = sk.estimates_chunks(geo, tbl)
    k = 50_000
    upd_c = topk_dense_nd(est, k)
    tbl_c = sk.sketch_chunks(geo, upd_c)
    upd_f, tbl_f = sk.fused_epilogue_chunks(geo, est, k)
    same_u = bool(jnp.all(upd_f == upd_c))
    same_t = bool(jnp.all(tbl_f == tbl_c))
    print(f"d={d}: fused epilogue outputs equal: update={same_u} "
          f"table={same_t}", flush=True)

    def composed(t):
        u = topk_dense_nd(sk.estimates_chunks(geo, t), k)
        return sk.sketch_chunks(geo, u)

    def fused(t):
        return sk.fused_epilogue_chunks(geo, sk.estimates_chunks(geo, t),
                                        k)[1]

    t_c = leg("epilogue-composed", chained, composed, tbl, K=4)
    if t_c is not None:
        print(f"d={d}: composed epilogue chain {t_c:.2f} ms", flush=True)
    t_f = leg("epilogue-fused", chained, fused, tbl, K=4)
    if t_f is not None:
        print(f"d={d}: fused epilogue chain {t_f:.2f} ms"
              + (f" (delta {t_c - t_f:+.2f} ms = the fusion win)"
                 if t_c is not None else ""), flush=True)


def stream_sketch_leg():
    """Streaming client-phase sketch A/B (docs/stream_sketch.md): the
    composed fused round (flat gradient built, then one sketch) vs
    --stream_sketch (leaf-streamed table carry) at the headline CIFAR
    geometry, same batch, same state. One round from identical state is
    compared first: with the bench wd=5e-4 the weight-decay term rides a
    separate segment-sketch, so the comparison is allclose, not bitwise —
    the wd=0 bit-identity (and both server planes × both epilogues) is
    pinned on CPU in tests/test_stream_sketch.py. The delta of the two
    timed legs IS the movement win (the builds differ only in
    RoundConfig.stream_sketch)."""
    steps_c, ps_c, ss_c, cs_c, batch = B.build(tiny=False)
    steps_s, ps_s, ss_s, cs_s, _ = B.build(tiny=False, stream_sketch=True)
    # one-round output comparison from identical state. train_step
    # donates ps/server/client state, so the comparison runs on COPIES —
    # the timed loops below still own the original buffers.
    def _copies(t):
        return jax.tree_util.tree_map(jnp.copy, t)

    out_c = steps_c.train_step(_copies(ps_c), _copies(ss_c), _copies(cs_c),
                               {}, batch, 0.1, jax.random.key(7))
    out_s = steps_s.train_step(_copies(ps_s), _copies(ss_s), _copies(cs_s),
                               {}, batch, 0.1, jax.random.key(7))
    a = np.asarray(steps_c.layout.unchunk(out_c[0]))
    b = np.asarray(steps_s.layout.unchunk(out_s[0]))
    close = bool(np.allclose(a, b, rtol=1e-5, atol=1e-7))
    print(f"stream-sketch one-round ps allclose: {close} "
          f"(max |Δ| {float(np.abs(a - b).max()):.2e}; wd!=0 reorders f32 "
          f"sums — wd=0 bit-identity pinned in tests/test_stream_sketch.py)",
          flush=True)
    dt_c, rtt, _ = time_rounds(steps_c, (ps_c, ss_c, cs_c, {}), batch)
    print(f"stream-sketch A/B composed round: {dt_c * 1e3:.2f} ms "
          f"({1 / dt_c:.1f} r/s), rtt {rtt * 1e3:.0f} ms", flush=True)
    dt_s, _, _ = time_rounds(steps_s, (ps_s, ss_s, cs_s, {}), batch)
    print(f"stream-sketch A/B streaming round: {dt_s * 1e3:.2f} ms "
          f"({1 / dt_s:.1f} r/s) | delta {(dt_c - dt_s) * 1e3:+.2f} ms = "
          f"the movement win", flush=True)


def sketch_coalesce_leg():
    """Coalesced client-phase sketch A/B (docs/stream_sketch.md): the
    per-leaf --stream_sketch round vs --sketch_coalesce at the headline
    CIFAR geometry, same batch, same state. UNLIKE the stream-vs-composed
    A/B this one is BIT-exact (wd included): coalescing replays the
    per-leaf fold's per-cell add order, so the one-round output compare
    asserts array equality, not allclose. The delta of the two timed legs
    is the launch-overhead + table row-block RMW win (per-leaf re-reads
    2·r·c_pad·4 bytes per leaf; coalesced once per chunk-range group)."""
    steps_p, ps_p, ss_p, cs_p, batch = B.build(tiny=False,
                                               stream_sketch=True)
    steps_c, ps_c, ss_c, cs_c, _ = B.build(tiny=False, stream_sketch=True,
                                           sketch_coalesce=True)
    # one-round output comparison from identical state (train_step donates
    # its buffers — compare on copies, time on the originals)
    def _copies(t):
        return jax.tree_util.tree_map(jnp.copy, t)

    out_p = steps_p.train_step(_copies(ps_p), _copies(ss_p), _copies(cs_p),
                               {}, batch, 0.1, jax.random.key(7))
    out_c = steps_c.train_step(_copies(ps_c), _copies(ss_c), _copies(cs_c),
                               {}, batch, 0.1, jax.random.key(7))
    a = np.asarray(steps_p.layout.unchunk(out_p[0]))
    b = np.asarray(steps_c.layout.unchunk(out_c[0]))
    equal = bool(np.array_equal(a, b))
    print(f"sketch-coalesce one-round ps bit-equal: {equal} "
          f"(max |Δ| {float(np.abs(a - b).max()):.2e}; the coalesced fold "
          f"replays the per-leaf add order — equality pinned in "
          f"tests/test_sketch_coalesce.py)", flush=True)
    # a mismatch HERE is the compiled kernel diverging on real hardware
    # (the CPU suite covers only interpreter/pure paths) — fail the leg
    # so tpu_batch never records the timed delta as flip-the-default
    # evidence off a wrong kernel
    assert equal, "coalesced round != per-leaf round on this backend"
    dt_p, rtt, _ = time_rounds(steps_p, (ps_p, ss_p, cs_p, {}), batch)
    print(f"sketch-coalesce A/B per-leaf round: {dt_p * 1e3:.2f} ms "
          f"({1 / dt_p:.1f} r/s), rtt {rtt * 1e3:.0f} ms", flush=True)
    dt_c, _, _ = time_rounds(steps_c, (ps_c, ss_c, cs_c, {}), batch)
    print(f"sketch-coalesce A/B coalesced round: {dt_c * 1e3:.2f} ms "
          f"({1 / dt_c:.1f} r/s) | delta {(dt_p - dt_c) * 1e3:+.2f} ms = "
          f"the launch/table-RMW win", flush=True)


def compressed_collectives_leg():
    """Compressed-collectives A/B (docs/compressed_collectives.md): the
    sharded headline round at the fp32 plan vs the full-int8 plan
    (--collective_plan int8 — table exchange AND downlink gather
    quantized, dres/qres EF carries live). Prints each plan's ACHIEVED
    wire bytes/round straight from telemetry.collective_ledger (the same
    payload_bytes formula the collectives implement — tests pin they
    cannot disagree) plus the step-time delta, and one quantize->
    dequantize micro-probe per wire dtype at the real downlink chunk
    block so the auto-tuner's probe numbers have an on-chip anchor."""
    from commefficient_tpu.ops import collectives as C
    from commefficient_tpu.telemetry import collective_ledger

    steps_f, ps_f, ss_f, cs_f, batch = B.build(tiny=False,
                                               server_shard=True)
    steps_q, ps_q, ss_q, cs_q, _ = B.build(tiny=False, server_shard=True,
                                           collective_plan="int8")
    geo = sk.make_sketch(6_568_640, c=500_000, r=5, seed=42, num_blocks=20)
    n_shard = jax.device_count()
    for tag, plan in (("fp32", C.FP32_PLAN),
                      ("int8", C.plan_from_reduce_dtype("int8"))):
        led = collective_ledger("sketch", geo.d, sketch=geo,
                                n_shard=n_shard, plan=plan)
        wire = sum(row["bytes_per_round"] for name, row in led.items()
                   if name != "client_uplink")
        rows = ", ".join(f"{name}={row['bytes_per_round']:,}B"
                         for name, row in led.items()
                         if name != "client_uplink")
        print(f"plan {tag}: ledger wire bytes/round {wire:,} ({rows})",
              flush=True)
    dt_f, rtt, _ = time_rounds(steps_f, (ps_f, ss_f, cs_f, {}), batch)
    print(f"compressed-collectives A/B fp32-plan round: {dt_f * 1e3:.2f} ms "
          f"({1 / dt_f:.1f} r/s), rtt {rtt * 1e3:.0f} ms", flush=True)
    dt_q, _, _ = time_rounds(steps_q, (ps_q, ss_q, cs_q, {}), batch)
    print(f"compressed-collectives A/B int8-plan round: {dt_q * 1e3:.2f} ms "
          f"({1 / dt_q:.1f} r/s) | delta {(dt_q - dt_f) * 1e3:+.2f} ms = "
          f"the quantize/EF-carry cost (ICI-byte win needs a multi-chip "
          f"mesh)", flush=True)
    # per-dtype quantize->dequantize micro-probe at the downlink chunk
    # block (the auto-tune candidate geometry)
    block = geo.sublanes * 128
    x = jnp.asarray(np.random.RandomState(0)
                    .randn(4096, block).astype(np.float32))
    key = jax.random.key(0)
    for dt in C.QUANT_DTYPES:
        f = jax.jit(lambda v, k, dt=dt: C.dequantize_blocks(
            *C.quantize_blocks(v, k, dt), dt, block))
        y = f(x, key)
        drain(y)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            drain(f(x, key))
            best = min(best, time.perf_counter() - t0)
        rel = float(jnp.linalg.norm(x - y) / jnp.linalg.norm(x))
        print(f"quantize-roundtrip {dt}: {best * 1e3:.2f} ms for "
              f"{x.size:,} elems (rel err {rel:.4f})", flush=True)


def multihost_leg():
    """2D (clients x shard) server plane + per-MESH-AXIS quantized
    collectives A/B (docs/multihost.md): the sharded headline round on
    the 2D mesh under the all-fp32 plan vs the per-axis plan that keeps
    the shard (ICI) hop fp32 and quantizes the clients hop — the one
    that spans DCN on a real multi-host mesh. Prints the ledger's
    per-axis ICI/DCN byte split for both plans (the >= 3.99x DCN win is
    static; tests/test_multihost.py pins it) and the step-time delta =
    the hierarchical-lowering + per-level quantize/EF-carry cost. On a
    single-host mesh both hops ride ICI, so the timing is the honest
    no-regression number and the DCN bytes are the projection."""
    from commefficient_tpu.ops import collectives as C
    from commefficient_tpu.parallel.mesh import (
        default_client_mesh,
        server_reduce_axes,
    )
    from commefficient_tpu.telemetry import collective_ledger

    if jax.device_count() < 4:
        print(f"multihost leg needs >= 4 devices for the 2D "
              f"(clients x shard=2) mesh; found {jax.device_count()} — "
              "skipping", flush=True)
        return
    per_axis = ("table=shard:fp32/clients:int8,"
                "downlink=shard:fp32/clients:int8")
    steps_f, ps_f, ss_f, cs_f, batch = B.build(tiny=False,
                                               server_shard=True,
                                               shard_devices=2)
    steps_q, ps_q, ss_q, cs_q, _ = B.build(tiny=False, server_shard=True,
                                           shard_devices=2,
                                           collective_plan=per_axis)
    geo = sk.make_sketch(6_568_640, c=500_000, r=5, seed=42, num_blocks=20)
    mesh = default_client_mesh(8, shard_devices=2)
    axes = server_reduce_axes(mesh)
    sizes = {a: int(mesh.shape[a]) for a in
             ((axes,) if isinstance(axes, str) else axes)}
    n_shard = 1
    for v in sizes.values():
        n_shard *= v
    # on-pod placement (clients spans DCN); single-host runs project it
    placement = {"shard": "ici", "clients": "dcn"}
    for tag, spec in (("fp32", ""), ("per-axis", per_axis)):
        plan = C.parse_collective_plan(spec)
        low = {l: C.resolve_leg_lowering(getattr(plan, l), axes, placement)
               for l in C.PLAN_LEGS} if plan.per_axis else None
        led = collective_ledger("sketch", geo.d, sketch=geo,
                                n_shard=n_shard, plan=plan, lowering=low,
                                axis_sizes=sizes,
                                axis_placement=placement)
        split = {"ici": 0, "dcn": 0}
        for name, row in led.items():
            if name == "client_uplink":
                continue
            pa = row.get("bytes_per_axis")
            if pa:
                for ax, lvl in pa.items():
                    split[lvl["placement"]] += lvl["bytes_per_round"]
            else:
                # flat legs cross every hop of the mesh once
                for ax, pl in placement.items():
                    if ax in sizes:
                        split[pl] += row["bytes_per_round"]
        print(f"plan {tag}: projected ICI {split['ici']:,} B/round, "
              f"DCN {split['dcn']:,} B/round", flush=True)
    dt_f, rtt, _ = time_rounds(steps_f, (ps_f, ss_f, cs_f, {}), batch)
    print(f"multihost A/B 2D fp32-plan round: {dt_f * 1e3:.2f} ms "
          f"({1 / dt_f:.1f} r/s), rtt {rtt * 1e3:.0f} ms", flush=True)
    dt_q, _, _ = time_rounds(steps_q, (ps_q, ss_q, cs_q, {}), batch)
    print(f"multihost A/B 2D per-axis-plan round: {dt_q * 1e3:.2f} ms "
          f"({1 / dt_q:.1f} r/s) | delta {(dt_q - dt_f) * 1e3:+.2f} ms = "
          "hierarchical lowering + per-level quantize/EF-carry cost "
          "(the DCN-byte win itself needs a multi-host window)",
          flush=True)


def participation_leg():
    """Partial-cohort participation A/B (docs/fault_tolerance.md §client
    faults): the headline sketched round at --participation 1.0 vs 0.5 vs
    0.1, the partial legs with 10% injected client drops on top — the
    deployment regime the FL practicality survey (arXiv:2405.20431) calls
    central. XLA's static shapes mean the masked slots still run their
    zeroed compute, so the expected result is FLAT rounds/sec across the
    sweep (a partial cohort costs no more than full participation); a
    partial leg running SLOWER than full would be a masking-path
    regression worth a profile. Builds differ only in the batch masks —
    one compile serves all three legs."""
    rows = []
    for p, drops in ((1.0, 0.0), (0.5, 0.1), (0.1, 0.1)):
        steps, ps, ss, cs, batch = B.build(tiny=False, participation=p,
                                           drop_frac=drops)
        dt, rtt, _ = time_rounds(steps, (ps, ss, cs, {}), batch)
        live = int(np.asarray(batch["worker_mask"]).sum())
        rows.append((p, dt))
        print(f"participation {p:g} (drops {drops:g}, {live}/8 live "
              f"slots) round: {dt * 1e3:.2f} ms ({1 / dt:.1f} r/s), "
              f"rtt {rtt * 1e3:.0f} ms", flush=True)
    if len(rows) == 3:
        base = rows[0][1]
        deltas = ", ".join(f"p={p:g}: {(dt - base) * 1e3:+.2f} ms"
                           for p, dt in rows[1:])
        print(f"participation sweep vs full cohort: {deltas} "
              f"(expected ~0 — static shapes)", flush=True)


def async_leg(d=6_568_640):
    """Async buffered-fold device half (docs/async.md): the K-deep masked
    fold a --async_buffer K server runs at every K-th dispatch — per
    buffered contribution one finiteness verdict (landing time) and one
    select + scaled add into the accumulating (sum, count) pair, then the
    clamped normalize. Timed at the FetchSGD gradient geometry so the
    number reads as ms added to the fold dispatch; the standing cost is
    the K un-folded d-sized transmits parked in HBM (K·d·4 B — the async
    analogue of the straggler hold, printed for the leg_budgets row). The
    host half (controller bookkeeping, exact-staleness tags) is numpy on
    a handful of scalars — bench.py --run-cfg async prices it."""
    from commefficient_tpu.federated import participation as P

    K = 4
    rng = np.random.RandomState(0)
    contribs = [jnp.asarray(rng.randn(d).astype(np.float32))
                for _ in range(K - 1)]
    base = jnp.asarray(rng.randn(d).astype(np.float32))
    oks = [P._finite_ok(c) for c in contribs]

    def fold():
        grad = P._transmit_sum(base, np.float32(8.0))
        cnt = np.float32(8.0)
        for j, (c, ok) in enumerate(zip(contribs, oks)):
            w = P.staleness_weight(j % 3, 0.5)
            grad = P._masked_fold(grad, c, np.float32(w), ok)
            cnt = P._masked_count(cnt, np.float32(w * 8.0), ok)
        return P._safe_mean(grad, cnt)

    drain(fold())  # compile
    rtt = rtt_measure(fold())
    best = float("inf")
    iters = 20
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fold()
        drain(r)
        best = min(best, max(time.perf_counter() - t0 - rtt, 1e-9))
    ms = best / iters * 1e3
    land_ms = chained(lambda x: x + P._finite_ok(x).astype(jnp.float32),
                      base, K=10)
    hbm = K * d * 4
    print(f"async fold d={d:,} K={K}: {ms:.3f} ms/fold "
          f"({ms / (K - 1):.3f} ms/buffered contribution), landing "
          f"verdict {land_ms:.3f} ms; standing buffer {hbm / 2**20:.1f} "
          f"MiB HBM ({K} pending transmits)", flush=True)


def watch_leg():
    """Continuous-observability overhead A/B (docs/observability.md):
    the headline sketched round with telemetry scalars only (schema v2)
    vs scalars + the v3 histogram block (--telemetry_hist — the device
    half of histograms + watch), plus the host half timed directly: a
    WatchEngine with the default rule set evaluating one drained round
    record. Gate: <= 2% rounds/sec with histograms + watch enabled (the
    bench `watch` leg is the same A/B vs the no-telemetry headline)."""
    rows = {}
    for hist in (False, True):
        steps, ps, ss, cs, batch = B.build(tiny=False, telemetry=True,
                                           telemetry_hist=hist)
        dt, rtt, _ = time_rounds(steps, (ps, ss, cs, {}), batch)
        rows[hist] = dt
        print(f"telemetry round ({'v3 hists' if hist else 'v2 scalars'}): "
              f"{dt * 1e3:.2f} ms ({1 / dt:.1f} r/s), "
              f"rtt {rtt * 1e3:.0f} ms", flush=True)
    if len(rows) == 2:
        delta = rows[True] - rows[False]
        print(f"histogram block cost: {delta * 1e3:+.3f} ms/round "
              f"({delta / rows[False] * 100:+.2f}% — gate <= 2%)",
              flush=True)
    # the host half: default watch rules over one drained round record
    # (pure host arithmetic — meant to be negligible next to the round)
    from commefficient_tpu.telemetry import (
        DEFAULT_WATCH_RULES,
        WatchEngine,
        metric_schema,
        parse_watch_rules,
    )

    w = WatchEngine(parse_watch_rules(",".join(DEFAULT_WATCH_RULES)))
    rec0 = {"round": 0, "loss": 1.0, "occupancy": 2, "dispatch_ms": 1.0,
            "t_dispatch": 0.0,
            "metrics": {k: 1.0 for k in metric_schema(True)}}
    n = 10_000
    t0 = time.perf_counter()
    for i in range(n):
        rec = dict(rec0)
        rec["round"] = i
        rec["t_dispatch"] = i * 0.01
        w.observe(rec)
    per = (time.perf_counter() - t0) / n
    print(f"watch rule evaluation ({len(w.rules)} default rules): "
          f"{per * 1e6:.1f} us/round on host", flush=True)


def host_offload_scale_leg():
    """Host-offload data plane at population scale (docs/host_offload.md):
    the headline sketched round with disk-tier (sparse memmap) per-client
    error state at a 10^5-client synthetic population, prefetch ON vs OFF
    A/B. ON overlaps round t+1's W-row read+upload with round t's device
    compute (host_state.CohortPrefetcher); OFF serializes it on the
    dispatch path — the delta IS the data plane's hidden cost. One
    COMPILE serves both legs (the round step never sees the population;
    it runs on the W-row proxy either way — the rebuild between legs only
    re-inits the donated state)."""
    import shutil
    import tempfile

    from commefficient_tpu.federated.host_state import (
        CohortPrefetcher,
        MemmapRowStore,
    )
    from commefficient_tpu.federated.rounds import ClientStates
    from commefficient_tpu.parallel.mesh import default_client_mesh

    # train_step donates its client_states argument, so the pre-round
    # proxy rows are copied for the delta (the aggregator reads them from
    # the undonated round ctx; the fused step has no ctx)
    _copy_rows = jax.jit(jnp.copy)
    n = int(os.environ.get("HOST_OFFLOAD_SCALE_CLIENTS", "100000"))
    steps = ps = ss = cs = batch = None
    W = mesh = row_shape = None
    iters = 20
    rows = []
    for prefetch in (True, False):
        # (re)build per leg: train_step donates the state buffers, so the
        # second leg needs fresh ones — the COMPILE is shared via the jit
        # cache, only the init re-runs
        steps, ps, ss, cs, batch = B.build(tiny=False, error_type="local")
        if W is None:
            W = int(np.asarray(batch["worker_mask"]).shape[0])
            mesh = default_client_mesh(W)
            row_shape = tuple(int(x) for x in cs.errors.shape[1:])
        batch = dict(batch)
        batch["client_ids"] = jnp.arange(W, dtype=jnp.int32)
        store_dir = tempfile.mkdtemp(prefix="host_offload_scale_")
        store = MemmapRowStore(store_dir, n, {"errors": row_shape},
                               mesh=mesh)
        pf = CohortPrefetcher(store.gather_async, enabled=prefetch)
        rng = np.random.RandomState(11)
        cohorts = [rng.choice(n, W, replace=False)
                   for _ in range(iters + 2)]

        def run_rounds(k, ps_, ss_, ms):
            pf.prefetch(cohorts[0])
            for i in range(k):
                stream, _ = pf.take(cohorts[i])
                old = ClientStates(None, _copy_rows(stream.proxy.errors),
                                   None)
                o = steps.train_step(ps_, ss_, stream.proxy, ms, batch,
                                     0.1, jax.random.key(i))
                ps_, ss_, new_proxy, ms = o[:4]
                store.scatter(stream, old, new_proxy)
                pf.prefetch(cohorts[i + 1])
            store.drain()
            return ps_, ss_, ms

        state = run_rounds(1, ps, ss, {})  # compile + touch rows
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            state = run_rounds(iters, *state)
            drain(state[0])
            best = min(best, (time.perf_counter() - t0) / iters)
        tag = "prefetch on " if prefetch else "prefetch off"
        rows.append((prefetch, best))
        print(f"host_offload_scale n={n} {tag}: {best * 1e3:.2f} ms/round "
              f"({1 / best:.1f} r/s; {pf.hits} hits/{pf.misses} misses, "
              f"gather io {store.last_gather_ms:.2f} ms, scatter io "
              f"{store.last_scatter_ms:.2f} ms)", flush=True)
        store.close()
        shutil.rmtree(store_dir, ignore_errors=True)
    if len(rows) == 2:
        on, off = rows[0][1], rows[1][1]
        print(f"host_offload_scale A/B: prefetch saves "
              f"{(off - on) * 1e3:+.2f} ms/round "
              f"({off / on:.2f}x serial gather cost hidden)", flush=True)


def io_faults_leg():
    """Storage-fault-plane A/B (docs/fault_tolerance.md §storage faults):
    the disk-tier gather -> headline sketched round -> scatter cycle,
    clean vs injection-idle (the armed-but-silent seam + retry ladder +
    watchdog — gate <= 2% rounds/sec) vs seeded transient faults below
    the retry budget (the retries' cost priced, the final rows pinned
    BIT-identical to the clean leg — retried I/O lands the same
    bytes)."""
    import shutil
    import tempfile

    from commefficient_tpu.federated.host_state import (
        CohortPrefetcher,
        MemmapRowStore,
        parse_io_fault,
    )
    from commefficient_tpu.federated.rounds import ClientStates
    from commefficient_tpu.parallel.mesh import default_client_mesh

    _copy_rows = jax.jit(jnp.copy)
    n = int(os.environ.get("IO_FAULTS_CLIENTS", "100000"))
    iters = 20
    rows = []
    finals = {}
    W = mesh = None
    for tag, spec in (
            ("clean", None),
            ("idle", "eio=0,short=0,torn=0,stall=0,seed=0"),
            ("transient", "eio=0.02,short=0.01,torn=0.01,stall=0.01,"
                          "stall_ms=2,seed=11")):
        steps, ps, ss, cs, batch = B.build(tiny=False, error_type="local")
        if W is None:
            W = int(np.asarray(batch["worker_mask"]).shape[0])
            mesh = default_client_mesh(W)
        row_shape = tuple(int(x) for x in cs.errors.shape[1:])
        batch = dict(batch)
        batch["client_ids"] = jnp.arange(W, dtype=jnp.int32)
        store_dir = tempfile.mkdtemp(prefix=f"io_faults_{tag}_")
        store = MemmapRowStore(store_dir, n, {"errors": row_shape},
                               mesh=mesh,
                               inject=parse_io_fault(spec) if spec
                               else None,
                               io_backoff_ms=0.5)
        pf = CohortPrefetcher(store.gather_async)
        rng = np.random.RandomState(11)
        cohorts = [rng.choice(n, W, replace=False)
                   for _ in range(iters + 2)]

        def run_rounds(k, ps_, ss_, ms):
            pf.prefetch(cohorts[0])
            for i in range(k):
                stream, _ = pf.take(cohorts[i])
                old = ClientStates(None, _copy_rows(stream.proxy.errors),
                                   None)
                o = steps.train_step(ps_, ss_, stream.proxy, ms, batch,
                                     0.1, jax.random.key(i))
                ps_, ss_, new_proxy, ms = o[:4]
                store.scatter(stream, old, new_proxy)
                pf.prefetch(cohorts[i + 1])
            store.drain()
            return ps_, ss_, ms

        state = run_rounds(1, ps, ss, {})  # compile + touch rows
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            state = run_rounds(iters, *state)
            drain(state[0])
            best = min(best, (time.perf_counter() - t0) / iters)
        counts = store.io_counters()
        rows.append((tag, best))
        finals[tag] = store.read_full("errors")
        print(f"io_faults {tag}: {best * 1e3:.2f} ms/round "
              f"({1 / best:.1f} r/s; {counts['retries']} retries, "
              f"{counts['errors']} exhausted, "
              f"{counts['quarantined']} quarantined)", flush=True)
        store.close()
        shutil.rmtree(store_dir, ignore_errors=True)
    if len(rows) == 3:
        clean, idle, transient = (dt for _, dt in rows)
        print(f"io_faults A/B: idle injection costs "
              f"{(idle - clean) * 1e3:+.3f} ms/round "
              f"({(idle / clean - 1) * 100:+.2f}% — gate <= 2%), "
              f"transient faults cost "
              f"{(transient - clean) * 1e3:+.3f} ms/round", flush=True)
        same = (np.array_equal(finals["clean"], finals["idle"])
                and np.array_equal(finals["clean"], finals["transient"]))
        print(f"io_faults rows bit-identical across legs: {same}",
              flush=True)
        assert same, ("transient-fault rows diverged from the clean leg "
                      "— retries are NOT invisible to the trajectory")


def integrity_leg():
    """Integrity-plane A/B (docs/fault_tolerance.md §silent corruption):
    the disk-tier gather -> headline sketched round -> scatter cycle,
    per-row checksums OFF vs ON-idle (the verify-every-read CRC pass —
    gate <= 2% rounds/sec) vs ON + a 32-row/round background scrub on
    the ordered worker (overlapped, prices the full audit cadence); the
    final rows pinned BIT-identical across all three legs (verification
    only reads)."""
    import shutil
    import tempfile

    from commefficient_tpu.federated.host_state import (
        CohortPrefetcher,
        MemmapRowStore,
    )
    from commefficient_tpu.federated.rounds import ClientStates
    from commefficient_tpu.parallel.mesh import default_client_mesh

    _copy_rows = jax.jit(jnp.copy)
    n = int(os.environ.get("INTEGRITY_CLIENTS", "100000"))
    iters = 20
    rows = []
    finals = {}
    W = mesh = None
    for tag, checksums, scrub in (("off", False, 0),
                                  ("on_idle", True, 0),
                                  ("scrub", True, 32)):
        steps, ps, ss, cs, batch = B.build(tiny=False, error_type="local")
        if W is None:
            W = int(np.asarray(batch["worker_mask"]).shape[0])
            mesh = default_client_mesh(W)
        row_shape = tuple(int(x) for x in cs.errors.shape[1:])
        batch = dict(batch)
        batch["client_ids"] = jnp.arange(W, dtype=jnp.int32)
        store_dir = tempfile.mkdtemp(prefix=f"integrity_{tag}_")
        store = MemmapRowStore(store_dir, n, {"errors": row_shape},
                               mesh=mesh, checksums=checksums,
                               scrub_rows=scrub)
        pf = CohortPrefetcher(store.gather_async)
        rng = np.random.RandomState(11)
        cohorts = [rng.choice(n, W, replace=False)
                   for _ in range(iters + 2)]

        def run_rounds(k, ps_, ss_, ms):
            pf.prefetch(cohorts[0])
            for i in range(k):
                stream, _ = pf.take(cohorts[i])
                old = ClientStates(None, _copy_rows(stream.proxy.errors),
                                   None)
                o = steps.train_step(ps_, ss_, stream.proxy, ms, batch,
                                     0.1, jax.random.key(i))
                ps_, ss_, new_proxy, ms = o[:4]
                store.scatter(stream, old, new_proxy)
                store.scrub_async()
                pf.prefetch(cohorts[i + 1])
            store.drain()
            return ps_, ss_, ms

        state = run_rounds(1, ps, ss, {})  # compile + touch rows
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            state = run_rounds(iters, *state)
            drain(state[0])
            best = min(best, (time.perf_counter() - t0) / iters)
        counts = store.io_counters()
        assert counts["corrupt"] == 0, (
            f"integrity {tag}: clean leg detected corruption")
        rows.append((tag, best))
        finals[tag] = store.read_full("errors")
        print(f"integrity {tag}: {best * 1e3:.2f} ms/round "
              f"({1 / best:.1f} r/s; {counts['scrub_checked']} rows "
              f"scrubbed)", flush=True)
        store.close()
        shutil.rmtree(store_dir, ignore_errors=True)
    if len(rows) == 3:
        off, idle, scrub = (dt for _, dt in rows)
        print(f"integrity A/B: checksums-on costs "
              f"{(idle - off) * 1e3:+.3f} ms/round "
              f"({(idle / off - 1) * 100:+.2f}% — gate <= 2%), "
              f"background scrub costs "
              f"{(scrub - off) * 1e3:+.3f} ms/round", flush=True)
        same = (np.array_equal(finals["off"], finals["on_idle"])
                and np.array_equal(finals["off"], finals["scrub"]))
        print(f"integrity rows bit-identical across legs: {same}",
              flush=True)
        assert same, ("checksum-on rows diverged from checksums-off — "
                      "verification must only READ")


def serving_leg():
    """Live serving replica (docs/service.md): price the snapshot
    handoff on this host — the weights-only, checksum-verified load of a
    d=6.5M run state (the hot-swap cost), the pin-lease I/O around it,
    and a ``query`` answer against the loaded weights. (The full
    trainer-interference A/B runs on CPU in bench.py --run-cfg serving —
    a chip belongs to one process at a time; this is the per-swap /
    per-answer number that story rests on.)"""
    import json as _json
    import shutil
    import tempfile
    import time as _time
    import zlib as _zlib

    from commefficient_tpu.federated.serving import (
        ServingReplica,
        read_response,
        submit_request,
    )

    D = 6_568_640
    work = tempfile.mkdtemp(prefix="serving_leg_")
    ckpt = os.path.join(work, "ckpt")
    serve = os.path.join(work, "serve")
    os.makedirs(ckpt)

    def write_state(rounds, seed):
        # a real run_state's serving-relevant shape: flat ps_weights +
        # checksummed meta (checkpoint._content_checksum contract)
        w = np.random.RandomState(seed).standard_normal(D) \
            .astype(np.float32)
        crc = _zlib.crc32("ps_weights".encode())
        crc = _zlib.crc32(str(w.dtype).encode(), crc)
        crc = _zlib.crc32(np.ascontiguousarray(w), crc)
        meta = {"checksum": crc, "rounds_dispatched": rounds}
        path = os.path.join(ckpt, f"run_state_ep1_r{rounds}.npz")
        np.savez(path, ps_weights=w,
                 meta_json=np.frombuffer(
                     _json.dumps(meta).encode(), np.uint8))
        return path

    try:
        write_state(8, seed=0)
        replica = ServingReplica(ckpt, serve, owner="tpu_measure")
        t0 = _time.perf_counter()
        replica.step()  # discovery + first weights load
        load_s = _time.perf_counter() - t0
        assert replica.tracker.version == 8, (
            f"tracker loaded version {replica.tracker.version}, want 8")
        print(f"serving swap (d={D / 1e6:.1f}M weights, checksummed "
              f"npz): {load_s * 1e3:.1f} ms", flush=True)

        lats = []
        for i in range(20):
            rid = submit_request(serve, op="query", probe_seed=i)
            t0 = _time.perf_counter()
            replica.step()
            lats.append(_time.perf_counter() - t0)
            resp = read_response(serve, rid, timeout=5, poll=0.005)
            assert resp["model_version"] == 8, resp
        lats.sort()
        print(f"serving query answer (file queue round trip): p50 "
              f"{lats[len(lats) // 2] * 1e3:.1f} ms over {len(lats)} "
              f"queries", flush=True)

        write_state(16, seed=1)  # training advanced: hot swap mid-serve
        rid = submit_request(serve, op="query", probe_seed=0)
        t0 = _time.perf_counter()
        replica.step()
        swap_s = _time.perf_counter() - t0
        resp = read_response(serve, rid, timeout=5, poll=0.005)
        assert resp["model_version"] == 16, (
            f"answer after hot swap served version "
            f"{resp['model_version']}, want 16 (monotone handoff)")
        print(f"serving hot swap + answer under load: "
              f"{swap_s * 1e3:.1f} ms (version 8 -> 16, monotone)",
              flush=True)
        replica.close()
    finally:
        shutil.rmtree(work, ignore_errors=True)


def gpt2_leg(bf16):
    steps, ps, ss, cs, batch, tokens = B.build_gpt2(bf16=bf16)
    # train_step donates ps/client_states: after this call the local
    # ps/cs buffers are dead — every later leg must use `st`
    dt, _, st = time_rounds(steps, (ps, ss, cs, {}), batch, iters=10)
    tag = "bf16" if bf16 else "f32 "
    print(f"GPT-2 {tag} round: {dt * 1e3:.2f} ms = "
          f"{tokens / dt:,.0f} tokens/s", flush=True)
    if not bf16:
        # dropout-PRNG A/B: the round generates ~113M random dropout
        # values (3 masks x 12 layers x 4096 x 768); threefry is
        # ALU-bound on TPU while rbg uses the hardware RNG. Same jit,
        # different key impl -> isolates mask-generation cost.
        for impl in ("rbg", "unsafe_rbg"):
            try:
                dt2, _, st = time_rounds(steps, st, batch, iters=10,
                                         rng=jax.random.key(0, impl=impl))
                print(f"GPT-2 f32 round ({impl} dropout keys): "
                      f"{dt2 * 1e3:.2f} ms = {tokens / dt2:,.0f} "
                      f"tokens/s", flush=True)
            except Exception as e:  # noqa: BLE001
                print(f"GPT-2 {impl} leg failed: {e}", flush=True)
    leg(f"gpt2-{tag.strip()}-phase-split", gpt2_phase_split,
        steps, st[0], st[2], batch, dt * 1e3, tag.strip())


def imagenet_leg(bf16, microbatch):
    """The reference's only tuned large-scale config (reference
    imagenet.sh:1-21): FixupResNet50, 7 workers x local bs 64 = 448 imgs
    per uncompressed round, virtual momentum 0.9, wd 1e-4 — at the real
    224x224 shapes, microbatched to fit a single chip's HBM.  Synthetic
    pixels (no ImageNet in the zero-egress image): the measured quantity
    is the round's compute, which does not depend on pixel values."""
    from commefficient_tpu import models
    from commefficient_tpu.federated.losses import make_cv_losses
    from commefficient_tpu.federated.rounds import (
        RoundConfig, build_round_step, init_client_states)
    from commefficient_tpu.federated.server import (
        ServerConfig, init_server_state)
    from commefficient_tpu.federated.worker import WorkerConfig
    from commefficient_tpu.ops.flat import ravel_pytree
    from commefficient_tpu.parallel.mesh import default_client_mesh

    # reference geometry by default; env overrides for the CPU smoke run
    W = int(os.environ.get("IMAGENET_W", "7"))
    BS = int(os.environ.get("IMAGENET_BS", "64"))
    HW = int(os.environ.get("IMAGENET_HW", "224"))
    model = models.FixupResNet50(num_classes=1000)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, HW, HW, 3), jnp.float32),
                        train=False)["params"]
    flat, unravel = ravel_pytree(params)
    d = int(flat.size)
    print(f"imagenet: FixupResNet50 d={d:,} W={W} bs={BS} "
          f"mb={microbatch} bf16={bf16}", flush=True)
    wcfg = WorkerConfig(mode="uncompressed", error_type="none",
                        num_workers=W, weight_decay=1e-4,
                        microbatch_size=microbatch)
    scfg = ServerConfig(mode="uncompressed", error_type="none",
                        grad_size=d, virtual_momentum=0.9)
    cfg = RoundConfig(worker=wcfg, server=scfg, grad_size=d)
    loss_train, loss_val = make_cv_losses(
        model, compute_dtype=jnp.bfloat16 if bf16 else None)
    mesh = default_client_mesh(W)
    steps = build_round_step(loss_train, loss_val, unravel,
                             lambda t: ravel_pytree(t)[0], cfg, sketch=None,
                             mesh=mesh)
    server_state = init_server_state(scfg, None)
    client_states = init_client_states(W, d, wcfg)
    rng_np = np.random.RandomState(0)
    batch = {
        "inputs": jnp.asarray(rng_np.randn(W, BS, HW, HW, 3), jnp.float32),
        "targets": jnp.asarray(rng_np.randint(0, 1000, (W, BS))),
        "mask": jnp.ones((W, BS), jnp.float32),
        "client_ids": jnp.asarray(np.arange(W), jnp.int32),
        "worker_mask": jnp.ones(W, jnp.float32),
    }
    dt, rtt, _ = time_rounds(steps, (flat, server_state, client_states, {}),
                             batch, iters=5)
    imgs = W * BS
    # fwd+bwd ~= 3x fwd; FixupResNet50 fwd ~= 4.1 GFLOP/img at 224^2,
    # scaling ~quadratically with spatial resolution (conv-dominated)
    tflops = 3 * 4.1e9 * (HW / 224) ** 2 * imgs / dt / 1e12
    print(f"ImageNet {'bf16' if bf16 else 'f32'} round: {dt * 1e3:.1f} ms = "
          f"{imgs / dt:,.0f} imgs/s ({1 / dt:.2f} r/s), ~{tflops:.1f} "
          f"TFLOP/s model compute, rtt {rtt * 1e3:.0f} ms", flush=True)


def main():
    """Leg names via argv select a subset (default: all)."""
    known = {"matmul", "cifar", "ops", "gpt2", "imagenet", "topk_ab",
             "fused_epilogue", "stream_sketch", "sketch_coalesce",
             "compressed_collectives", "participation",
             "host_offload_scale", "watch", "io_faults", "integrity",
             "multihost", "async", "serving"}
    want = set(sys.argv[1:])
    unknown = want - known
    if unknown:
        sys.exit(f"unknown legs {sorted(unknown)}; choose from "
                 f"{sorted(known)}")

    def sel(name):
        return not want or name in want

    print("backend:", jax.default_backend(), flush=True)
    if sel("matmul"):
        leg("matmul", matmul_peak_probe)
    if sel("cifar"):
        leg("cifar", cifar_leg)
    if sel("ops"):
        leg("ops-6.5M", sketch_ops_leg, 6_568_640)
        leg("ops-124M", sketch_ops_leg, 124_444_417)
    if sel("gpt2"):
        leg("gpt2-f32", gpt2_leg, False)
        leg("gpt2-bf16", gpt2_leg, True)
    if sel("imagenet"):
        mb = int(os.environ.get("IMAGENET_MICROBATCH", "8"))
        leg("imagenet-bf16", imagenet_leg, True, mb)
        leg("imagenet-f32", imagenet_leg, False, mb)
    if sel("topk_ab"):
        leg("topk_ab-6.5M", topk_ab_leg, 6_568_640)
        leg("topk_ab-124M", topk_ab_leg, 124_444_417)
    if sel("fused_epilogue"):
        leg("fused_epilogue-6.5M", fused_epilogue_leg, 6_568_640)
        leg("fused_epilogue-124M", fused_epilogue_leg, 124_444_417)
    if sel("stream_sketch"):
        leg("stream_sketch", stream_sketch_leg)
    if sel("sketch_coalesce"):
        leg("sketch_coalesce", sketch_coalesce_leg)
    if sel("compressed_collectives"):
        leg("compressed_collectives", compressed_collectives_leg)
    if sel("multihost"):
        leg("multihost", multihost_leg)
    if sel("participation"):
        leg("participation", participation_leg)
    if sel("async"):
        leg("async-6.5M", async_leg, 6_568_640)
        leg("async-124M", async_leg, 124_444_417)
    if sel("host_offload_scale"):
        leg("host_offload_scale", host_offload_scale_leg)
    if sel("watch"):
        leg("watch", watch_leg)
    if sel("io_faults"):
        leg("io_faults", io_faults_leg)
    if sel("integrity"):
        leg("integrity", integrity_leg)
    if sel("serving"):
        leg("serving", serving_leg)


if __name__ == "__main__":
    main()
