"""Per-op on-chip profile of the fused CIFAR federated round.

VERDICT r3 weak #3: the round is compression-dominated (3.71 ms round vs
2.17 ms standalone re-sketch at d=6.5M) but no committed per-op profile
shows where the remaining ~80% of the round goes. This script captures a
jax.profiler trace around the steady-state fused train step (the exact
bench.py geometry: full ResNet9 d=6.5M, 8 workers, sketch 5x500k k=50k),
parses the XLA-op plane out of the xplane.pb protobuf directly (no
tensorboard UI in this image's loop), and writes a per-op and per-category
breakdown to docs/measurements/tpu_profile.md.

Run on the chip (the process owns it):  python scripts/tpu_profile.py
Parser self-test on CPU:  TPU_PROFILE_ALLOW_CPU=1 python scripts/tpu_profile.py
"""

from __future__ import annotations

import glob
import os
import re
import sys
import time
from collections import defaultdict

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from commefficient_tpu.utils import configure_compile_cache  # noqa: E402

configure_compile_cache()

ROUNDS = int(os.environ.get("TPU_PROFILE_ROUNDS", 10))
# "cifar" (default) or "gpt2" — which workload's fused round to trace
TARGET = os.environ.get("TPU_PROFILE_TARGET", "cifar")
if TARGET not in ("cifar", "gpt2"):
    sys.exit(f"unknown TPU_PROFILE_TARGET {TARGET!r} (cifar|gpt2)")
# TPU_PROFILE_FUSED=1 profiles the --fused_epilogue round and writes a
# *_fused.md capture next to the composed one, so the fused-epilogue
# before/after is two runs of this script + one profile_diff
# (--preset fused-epilogue) — no hand-editing of captures.
# TPU_PROFILE_STREAM=1 does the same for the --stream_sketch client phase
# (*_stream.md capture; gate with profile_diff --preset stream-sketch).
# TPU_PROFILE_COALESCE=1 profiles --stream_sketch --sketch_coalesce
# (*_coalesce.md capture; gate with profile_diff --preset sketch-coalesce
# AGAINST THE *_stream.md CAPTURE — the per-leaf streaming build is the
# baseline whose launch count coalescing shrinks).
FUSED = os.environ.get("TPU_PROFILE_FUSED") == "1"
STREAM = os.environ.get("TPU_PROFILE_STREAM") == "1"
COALESCE = os.environ.get("TPU_PROFILE_COALESCE") == "1"
if sum([FUSED, STREAM, COALESCE]) > 1:
    sys.exit("set only one of TPU_PROFILE_FUSED / TPU_PROFILE_STREAM / "
             "TPU_PROFILE_COALESCE per capture — a combined capture has "
             "no baseline to diff against")
_SUFFIX = "_fused" if FUSED else (
    "_stream" if STREAM else ("_coalesce" if COALESCE else ""))
OUT_MD = os.path.join(
    _REPO, "docs", "measurements",
    f"tpu_profile{_SUFFIX}.md" if TARGET == "cifar"
    else f"tpu_profile_{TARGET}{_SUFFIX}.md")
_TITLES = {
    "cifar": ("fused CIFAR federated round",
              "full bench geometry (ResNet9 d={d}, 8 workers, sketch "
              "5x500k k=50k)"),
    "gpt2": ("fused GPT-2 PersonaChat federated round",
             "full bench geometry (GPT-2 124M double-heads bf16 d={d}, "
             "4 workers, sketch 5x500k k=50k)"),
}


# The per-round counter registry: every optimization that claims to
# remove a class of per-round device work pins that claim on ONE counter —
# the span count of its category bucket divided by the traced rounds.
# One schema and one "## Per-round counters" markdown table (parsed
# generically by scripts/profile_diff.py) replace the hand-rolled
# paragraph each optimization used to append: a new counter is a new row
# here, not new prose in write_report and new parsing downstream.
# rows: (category key, slug, gating profile_diff preset, doc)
COUNTERS = (
    ("server epilogue (d-plane sweeps)", "epilogue_sweeps",
     "fused-epilogue", "docs/fused_epilogue.md"),
    ("client flatten/movement (d-sized)", "client_movement",
     "stream-sketch", "docs/stream_sketch.md"),
    ("reduce (transmit collectives)", "transmit_collectives",
     "sharded-server", "docs/sharded_server.md"),
    # client-phase sketch-accumulate kernel launches/round: the running-
    # table accumulate kernels are exclusively client-phase, so their
    # span count IS the launch count --sketch_coalesce shrinks from
    # ~leaf count to group count (docs/stream_sketch.md)
    ("client sketch accumulate (launches)", "client_sketch_launches",
     "sketch-coalesce", "docs/stream_sketch.md"),
)


def _category(op_name: str) -> str:
    """Bucket an XLA op span name into a coarse category. Fusion names carry
    the fused root op after the kind tag (e.g. 'loop_fusion' wrapping adds);
    we bucket by the leading mnemonic which is how the TPU op profiler
    groups too."""
    n = op_name.lower()
    for pat, cat in (
        # conv(?!ert): real convolutions only — the old bare "conv" also
        # swept every convert_* dtype/pad fusion (d-plane traffic on
        # GPT-2, which has zero convolutions) into the MXU bucket, which
        # the fused-epilogue preset now gates as "model stays flat"
        (r"convolution|conv(?!ert)", "convolution (MXU)"),
        (r"\bdot\b|matmul|gemm", "matmul (MXU)"),
        # The server epilogue's d-plane sweeps (docs/fused_epilogue.md):
        # every op that reads or writes a model-sized plane between the
        # aggregated transmit and the weight update — the estimates query
        # kernel, the radix-descent count passes (s32[15]/s32[7] fusions on
        # the XLA path, the count/descent Pallas kernels otherwise), the
        # threshold compare_select mask, the re-sketch (fused megakernel),
        # and the lr-scale/EF multiply_subtract. The fused-epilogue claim
        # is that this bucket's span count and ms/round SHRINK
        # (profile_diff --preset fused-epilogue gates it). Caveat:
        # _sketch_vec_pallas is NOT bucketed here — the same kernel name
        # serves the worker-side gradient sketch, so the composed
        # re-sketch's share hides under custom-call; the fused kernel
        # (_fused_epilogue_pallas) has its own name exactly so the
        # epilogue share becomes attributable.
        # (the kernels' names since their pallas_calls carry name=:
        # profiling.KERNEL_NAMES; the jit wrappers' names before)
        (r"_fused_epilogue_pallas|_estimates_pallas|_count_ge_pallas"
         r"|_descent_pallas|fed_epilogue|fed_estimates|fed_topk_count"
         r"|fed_topk_descent|compare_select_fusion|multiply_subtract_fusion"
         r"|convert_reduce_fusion[^=]*= s32\[(15|7|16)\]",
         "server epilogue (d-plane sweeps)"),
        # Client-phase sketch-accumulate launches (docs/stream_sketch.md):
        # the RUNNING-TABLE accumulate kernels are exclusively client-
        # phase — the --stream_sketch per-leaf path launches
        # _sketch_accum_pallas once per gradient leaf (each re-reading/
        # re-writing the 2·r·c_pad·4-byte table row block), the
        # --sketch_coalesce megakernel launches _sketch_segments_pallas
        # once per coalesced group — so this bucket's span count/round IS
        # the client phase's kernel-launch count, the quantity the
        # sketch-coalesce preset gates at zero growth. Deliberately NOT
        # _sketch_vec_pallas: that zero-init kernel also serves the
        # composed client sketch AND the server re-sketch, which would
        # pollute the launch count with server-phase spans.
        (r"_sketch_accum_pallas|_sketch_segments_pallas|fed_sketch_accum",
         "client sketch accumulate (launches)"),
        # Client flatten/movement (docs/stream_sketch.md): the d-sized
        # 1-D layout ops the streaming sketch exists to delete — the
        # flat-gradient concatenate of the backward pass, the pad/reshape
        # pairs into and out of the (T, S, 128) chunk plane, the bf16/f32
        # converts of the flat vector, and the flat slices/copies of the
        # weight unravel. Matched by the leading mnemonic AND a 1-D result
        # ≥ 10^6 elements (7+ digits — covers both the d=6.5M CIFAR and
        # d=124M GPT-2 planes), so model activations (multi-dim) and the
        # small per-leaf ops the streaming path keeps stay out of the
        # bucket.
        # Must come AFTER the epilogue pattern (its d-plane fusions keep
        # their own bucket) and BEFORE the generic data-movement bucket.
        # Caveat: the (T, S, 128)-RESULT half of a flat→chunk conversion
        # (e.g. reshape.950) stays under "data movement" — its 1-D pad
        # twin is in this bucket and the pair lives or dies together, so
        # the gate still fires on any regression.
        (r"\b(concatenate|pad|reshape|convert|slice|split|copy)[-_.\w]*\s*="
         r"\s*\(?(f32|bf16|f16|s32|u32|pred)\[\d{7,}\]",
         "client flatten/movement (d-sized)"),
        # the sharded server plane's transmit collectives (reduce-scatter
        # of the round transmit, update all-gather, the int8 collective's
        # all-to-all — docs/sharded_server.md) get their own bucket so
        # profile_diff can gate them separately from activation psums.
        # Deliberately NOT all-reduce: lax.psum lowers to all-reduce, so
        # that pattern would sweep the seq/model/expert activation and
        # metric psums (and the sketch-table psum) into the transmit
        # bucket and dilute the gate — those stay under "collectives".
        # Caveat: Ulysses sequence parallelism also emits all_to_all
        # (parallel/ulysses.py) — profile the sharded-server legs without
        # --seq_parallel ulysses (the bench `shard` leg doesn't use it)
        # or this bucket mixes in attention activation traffic.
        (r"all-gather|reduce-scatter|all-to-all",
         "reduce (transmit collectives)"),
        (r"all-reduce|collective|permute", "collectives"),
        (r"scatter", "scatter (sketch accumulate)"),
        (r"gather", "gather"),
        (r"sort", "sort"),
        (r"while", "while (top-k radix)"),
        (r"custom-call", "custom-call (pallas)"),
        (r"copy|transpose|reshape|bitcast", "data movement"),
        (r"rng|threefry", "rng"),
        (r"reduce", "reduce"),
        (r"fusion", "elementwise fusion"),
    ):
        if re.search(pat, n):
            return cat
    return "other"


def aggregate_xplane(xplane_path: str):
    """Parse one xplane.pb; return (plane_name, line_name,
    {op_name: (count, total_ps)}) for the busiest XLA-op line found.

    TPU traces carry a '/device:TPU:N' plane with lines 'XLA Modules' /
    'XLA Ops'; CPU traces put XLA op spans on host threads. We prefer an
    'XLA Ops' line on a device plane, then any line whose events' metadata
    look like HLO op names, ranked by total busy time."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    xspace = xplane_pb2.XSpace()
    with open(xplane_path, "rb") as f:
        xspace.ParseFromString(f.read())

    candidates = []  # (score, plane_name, line_name, {name: [count, ps]})
    for plane in xspace.planes:
        meta = {mid: m.name for mid, m in plane.event_metadata.items()}
        for line in plane.lines:
            agg: dict = defaultdict(lambda: [0, 0])
            for ev in line.events:
                name = meta.get(ev.metadata_id, str(ev.metadata_id))
                a = agg[name]
                a[0] += 1
                a[1] += ev.duration_ps
            if not agg:
                continue
            total_ps = sum(v[1] for v in agg.values())
            is_device = ("TPU" in plane.name or "device" in plane.name
                         or "Device" in plane.name)
            is_xla_line = line.name in ("XLA Ops", "XLA Modules", "XLA TraceMe")
            score = (2 * int(is_device and line.name == "XLA Ops")
                     + int(is_device) + int(is_xla_line))
            candidates.append((score, total_ps, plane.name, line.name, agg))
    if not candidates:
        return None
    candidates.sort(key=lambda t: (t[0], t[1]), reverse=True)
    _, _, plane_name, line_name, agg = candidates[0]
    return plane_name, line_name, agg


def write_report(plane, line, agg, wall_ms_per_round, backend, d, tiny,
                 out_md):
    total_ps = sum(v[1] for v in agg.values())
    rows = sorted(agg.items(), key=lambda kv: -kv[1][1])
    cats: dict = defaultdict(lambda: [0, 0])
    for name, (cnt, ps) in agg.items():
        c = cats[_category(name)]
        c[0] += cnt
        c[1] += ps
    cat_rows = sorted(cats.items(), key=lambda kv: -kv[1][1])

    title, geom_t = _TITLES[TARGET]
    geom = (f"tiny CPU-fallback geometry (ResNet9 d={d:,}) — parser "
            f"self-test, NOT a perf artifact" if tiny else
            geom_t.format(d=f"{d:,}"))
    if FUSED:
        geom += ", --fused_epilogue"
    if STREAM:
        geom += ", --stream_sketch"
    if COALESCE:
        geom += ", --stream_sketch --sketch_coalesce"
    os.makedirs(os.path.dirname(out_md), exist_ok=True)
    with open(out_md, "w") as f:
        f.write(f"# Per-op profile: {title}\n\n")
        f.write(f"Captured {time.strftime('%Y-%m-%d %H:%M:%S')} on backend "
                f"`{backend}`, {geom}, {ROUNDS} steady-state "
                f"rounds traced.\n\n")
        f.write(f"Wall clock: **{wall_ms_per_round:.2f} ms/round**. "
                f"Trace plane `{plane}` line `{line}`, device busy time "
                f"{total_ps / 1e9 / ROUNDS:.2f} ms/round "
                f"({total_ps / 1e9:.1f} ms total).\n\n")
        f.write("## By category\n\n")
        f.write("| category | spans | total ms | ms/round | % busy |\n")
        f.write("|---|---|---|---|---|\n")
        for cat, (cnt, ps) in cat_rows:
            f.write(f"| {cat} | {cnt} | {ps / 1e9:.2f} | "
                    f"{ps / 1e9 / ROUNDS:.3f} | {100 * ps / total_ps:.1f}% |\n")
        # The per-round counters (COUNTERS registry above): span-count
        # based, so they are robust to tenancy noise in a way the ms
        # numbers are not. One table for all of them; gate a before/after
        # pair with scripts/profile_diff.py --preset <gate>.
        f.write("\n## Per-round counters\n\n")
        f.write("| counter | category | ops/round | ms/round | gate "
                "(profile_diff --preset) | doc |\n")
        f.write("|---|---|---|---|---|---|\n")
        for cat_key, slug, preset, doc in COUNTERS:
            cnt, ps = cats.get(cat_key, (0, 0))
            f.write(f"| {slug} | {cat_key} | {cnt / ROUNDS:.1f} | "
                    f"{ps / 1e9 / ROUNDS:.3f} | {preset} | {doc} |\n")
        f.write("\n## Top 40 ops\n\n")
        f.write("| op | count | total ms | ms/round | % busy |\n")
        f.write("|---|---|---|---|---|\n")
        for name, (cnt, ps) in rows[:40]:
            safe = name.replace("|", "\\|")[:90]
            f.write(f"| `{safe}` | {cnt} | {ps / 1e9:.2f} | "
                    f"{ps / 1e9 / ROUNDS:.3f} | {100 * ps / total_ps:.1f}% |\n")
        f.write(f"\nRaw trace: runs/tpu_profile_trace_{TARGET}/ "
                "(not committed).\n")
    print(f"wrote {out_md}", flush=True)


def main() -> int:
    import jax
    import jax.numpy as jnp

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    if not on_tpu and not os.environ.get("TPU_PROFILE_ALLOW_CPU"):
        print("backend is not a TPU; set TPU_PROFILE_ALLOW_CPU=1 for a "
              "parser self-test on CPU", flush=True)
        return 2

    import bench as B

    tiny = not on_tpu
    if TARGET == "gpt2":
        if not on_tpu:
            print("gpt2 profile target is chip-only (d=124M)", flush=True)
            return 2
        steps, ps, ss, cs, batch, _tokens = B.build_gpt2(
            bf16=True, fused_epilogue=FUSED,
            stream_sketch=STREAM or COALESCE, sketch_coalesce=COALESCE)
    else:
        steps, ps, ss, cs, batch = B.build(tiny=tiny, fused_epilogue=FUSED,
                                           stream_sketch=STREAM or COALESCE,
                                           sketch_coalesce=COALESCE)
    d = int(ps.size)

    def drain(x):
        return float(jnp.asarray(x).ravel()[0])

    state = (ps, ss, cs, {})
    rng = jax.random.key(0)
    print("warmup/compile...", flush=True)
    for _ in range(3):
        out = steps.train_step(*state, batch, 0.1, rng)
        state = out[:4]
        drain(state[0])

    # per-target trace dir, cleared first: the parser takes the newest
    # xplane.pb, and a failed trace must NOT silently re-report an older
    # target's data under this target's filename
    trace_dir = os.path.join(_REPO, "runs",
                             f"tpu_profile_trace_{TARGET}{_SUFFIX}")
    import shutil

    shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"tracing {ROUNDS} rounds -> {trace_dir}", flush=True)
    t0 = time.perf_counter()
    with jax.profiler.trace(trace_dir):
        for _ in range(ROUNDS):
            out = steps.train_step(*state, batch, 0.1, rng)
            state = out[:4]
        drain(state[0])
    wall_ms = (time.perf_counter() - t0) * 1e3 / ROUNDS

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime)
    if not paths:
        print("no xplane.pb produced by the trace", flush=True)
        return 1
    parsed = aggregate_xplane(paths[-1])
    if parsed is None:
        print("xplane parse found no event lines", flush=True)
        return 1
    plane, line, agg = parsed
    # the committed docs path is reserved for real on-chip profiles; the
    # CPU parser self-test writes to a scratch path so it can never
    # clobber (or masquerade as) an on-chip report
    out_md = OUT_MD if on_tpu else os.path.join(
        _REPO, "runs", "tpu_profile_selftest.md")
    write_report(plane, line, agg, wall_ms, backend, d, tiny, out_md)
    return 0


if __name__ == "__main__":
    sys.exit(main())
