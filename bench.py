"""Benchmark: CIFAR10 federated rounds/sec on one chip.

Runs the fused federated train step (ResNet9, 8 simulated clients per round,
count-sketch compression 5x500k/k=50k — the FetchSGD headline CIFAR10 config,
reference utils.py:142-162) on synthetic CIFAR-shaped data and reports
steady-state rounds/sec. Prints ONE JSON line to stdout:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

The same line carries an ``extra`` object with the GPT-2 PersonaChat
sketched-round throughput: tokens/sec/chip over the fused federated train
step on the full GPT-2 (124M) double-heads geometry. The headline
metric/value stay the CIFAR10 number so driver history remains comparable
across rounds.

``vs_baseline`` is measured against BASELINE_ROUNDS_PER_SEC below — the
reference publishes no numbers, so the constant encodes an A100-class
estimate for the same config: 8 sequential ResNet9 fwd+bwd on batches of 8
plus CUDA CSVec sketching at ~180 ms/round ≈ 5.5 rounds/s.

A device metric needs the device: without a TPU this exits non-zero and
prints no number (no CPU fallback), and utilization divides by the published
peak of the chip JAX reports (``PEAK_BF16_FLOPS``, keyed by ``device_kind``;
an unknown kind is an error).

- the parent process never imports jax (a chip belongs to one process at a
  time). It first runs a fail-fast backend *probe* subprocess (default
  120 s, ``BENCH_PROBE_TIMEOUT``); only if the probe finds a TPU does it
  launch the measurement subprocess (``BENCH_RUN_TIMEOUT``, default 2400 s —
  first compile can be slow);
- the measurement child logs timestamped progress to stderr (build, compile,
  per-phase timings) and runs the Pallas kernel self-checks before timing.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import NamedTuple

BASELINE_ROUNDS_PER_SEC = 5.5

# A100-class estimate for config 5 (GPT-2 124M PersonaChat
# sketched round, 4 workers x 2 cand x 256 tok) — the reference publishes no
# numbers, so as with the CIFAR constant this documents an estimate for the
# reference's own stack: HF GPT-2-124M fp32 (TF32 matmuls) trains at
# ~25-40k tokens/sec on one A100; per round the reference runs 4 sequential
# 1024-token fwd+bwd (~7.7e11 FLOPs each, ~16 ms at a generous 47 TFLOP/s
# sustained), 4 CSVec scatter-add sketches of the 124M-coord gradient
# (~8 ms each), server top-k over 2.5M cells + unsketch (~10 ms), plus
# Python dispatch — ~125 ms/round, 4096 tokens/round ~= 33k tokens/sec.
# Rounded down to 30k to stay favorable to the reference.
BASELINE_GPT2_TOKENS_PER_SEC = 30_000.0

# Config 4 (CIFAR100/FEMNIST non-IID sketched) uses the same A100-class
# derivation as config 3 — per-round compute differs only by the 100-wide
# head (<0.01% of FLOPs) and the non-IID client_ids, which change which
# client rows are gathered, not how much work a round does.
BASELINE_CIFAR100_ROUNDS_PER_SEC = BASELINE_ROUNDS_PER_SEC

# Config 1 (1-worker uncompressed round, the cv_train smoke shape): one
# ResNet9 fwd+bwd on a batch of 8 is ~0.6 ms of pure compute at a generous
# 50 TFLOP/s sustained; on the reference's stack the round is dominated by
# Python dispatch + the dense d=6.5M optimizer step (~6-8 ms/round for
# comparable torch loops) → ~150 rounds/s, rounded in the reference's favor.
BASELINE_C1_ROUNDS_PER_SEC = 150.0

# Config 2 (8-worker true_topk): 8 sequential fwd/bwd (~19 ms at the same
# effective rate), a CUDA top-k over the 6.5M-coordinate summed gradient
# (~2 ms), dense momentum/error masking (~2 ms), Python dispatch →
# ~25-30 ms/round ≈ 35-40 r/s; anchored at 40 in the reference's favor.
BASELINE_C2_ROUNDS_PER_SEC = 40.0

# Published single-chip bf16 peaks, keyed by jax's ``device_kind`` (Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s). A device that is not in the
# table is an error, not a default. MFU below is model-FLOPs (fwd+bwd
# matmul/conv work) over wall-clock x peak — sketch/top-k/optimizer FLOPs
# are excluded, per the usual MFU convention, so the metric is comparable
# to published LLM MFU numbers.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_bf16_flops() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_FLOPS:
        sys.exit(f"bench: no published bf16 peak for device_kind {kind!r}; "
                 "add it to PEAK_BF16_FLOPS with its source")
    return PEAK_BF16_FLOPS[kind]


def _require_tpu() -> None:
    """A measurement child without a TPU exits non-zero and prints no
    number: a CPU timing is never written under a device metric's name."""
    import jax

    if jax.default_backend() != "tpu":
        sys.exit(f"bench: backend is {jax.default_backend()!r}, not a TPU "
                 "— no number without a chip")


def resnet9_train_flops_per_image(channels, hw=32, in_ch=3,
                                  num_classes=10) -> float:
    """Analytic fwd+bwd model FLOPs for one image through ResNet9.

    Walks the cifar10-fast topology exactly as ``models/resnet9.py`` builds
    it (3x3 same-pad stride-1 convs; pool(2) after layer1/2/3). MACs x2 =
    fwd FLOPs; bwd ~= 2x fwd, so train = 3x fwd (standard accounting).
    """
    ch = dict(channels)
    h = hw
    macs = in_ch * ch["prep"] * 9 * h * h            # prep conv
    macs += ch["prep"] * ch["layer1"] * 9 * h * h    # layer1 conv, then pool
    h //= 2
    macs += 2 * ch["layer1"] ** 2 * 9 * h * h        # res1 (two convs)
    macs += ch["layer1"] * ch["layer2"] * 9 * h * h  # layer2 conv, then pool
    h //= 2
    macs += ch["layer2"] * ch["layer3"] * 9 * h * h  # layer3 conv, then pool
    h //= 2
    macs += 2 * ch["layer3"] ** 2 * 9 * h * h        # res3 (two convs)
    macs += ch["layer3"] * num_classes               # linear head
    return 3.0 * 2.0 * macs


def gpt2_train_flops_per_token(n_embd=768, n_layer=12, seq_len=256,
                               vocab=50262) -> float:
    """Analytic fwd+bwd model FLOPs per token for GPT2DoubleHeads.

    Per layer 12*d^2 MACs (qkv 3d^2 + proj d^2 + mlp 8d^2), attention
    score+value matmuls 2*T*d MACs/token, plus the weight-tied LM head
    d*vocab (computed over every position). The mc head (d x 1 per
    candidate) is negligible. MACs x2 = fwd; train = 3x fwd.
    """
    d = n_embd
    macs = n_layer * 12 * d * d
    macs += n_layer * 2 * seq_len * d
    macs += d * vocab
    return 3.0 * 2.0 * macs

NUM_WORKERS = 8
LOCAL_BS = 8
WARMUP = 3
ITERS = 20

_REPO_DIR = os.path.dirname(os.path.abspath(__file__))

# small ResNet9 geometry for build(tiny=True) (tool self-tests on the CPU)
TINY_CHANNELS = (("prep", 8), ("layer1", 16), ("layer2", 16), ("layer3", 32))


def _log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:8.1f}s] {msg}", file=sys.stderr,
          flush=True)


_T0 = time.monotonic()


# --------------------------------------------------------------------------
# measurement child (--run)
# --------------------------------------------------------------------------

def build(tiny: bool, num_classes: int = 10, non_iid: bool = False,
          mode: str = "sketch", num_workers: int = NUM_WORKERS,
          server_shard: bool = False, fused_epilogue: bool = False,
          guards: bool = False, stream_sketch: bool = False,
          sketch_coalesce: bool = False,
          telemetry: bool = False, telemetry_hist: bool = False,
          collective_plan: str = "",
          participation: float = 1.0, drop_frac: float = 0.0,
          error_type: str = "virtual", shard_devices: int = 1):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from commefficient_tpu import models
    from commefficient_tpu.federated.losses import make_cv_losses
    from commefficient_tpu.federated.rounds import (
        RoundConfig,
        build_round_step,
        init_client_states,
    )
    from commefficient_tpu.federated.server import (
        ServerConfig,
        init_server_state,
    )
    from commefficient_tpu.federated.worker import WorkerConfig
    from commefficient_tpu.ops.flat import ravel_pytree
    from commefficient_tpu.ops.sketch import make_sketch

    if tiny:
        # same code path at a size a CPU handles in seconds — for tool
        # self-tests (scripts/tpu_profile.py), never for a reported number
        model = models.ResNet9(channels=TINY_CHANNELS, num_classes=num_classes)
        k, c, r, blocks = 512, 8192, 3, 2
    else:
        model = models.ResNet9(num_classes=num_classes)
        k, c, r, blocks = 50_000, 500_000, 5, 20

    x0 = jnp.zeros((1, 32, 32, 3), jnp.float32)
    params = model.init(jax.random.key(0), x0, train=False)["params"]
    flat, unravel = ravel_pytree(params)
    d = int(flat.size)
    _log(f"model built: d={d}, sketch {r}x{c} k={k}")

    def ravel(tree):
        return ravel_pytree(tree)[0]

    # ``mode`` selects the config family on the same round
    # machinery: "sketch" (configs 3/4/5), "true_topk" (config 2), or
    # "uncompressed" (config 1); non-sketch modes transmit dense vectors,
    # so no sketch geometry is built
    # local error feedback carries momentum client-side, so the server's
    # virtual momentum must be 0 there (server.ServerConfig's contract) —
    # the clients_sweep leg's per-client-state configuration
    vmom = 0.9 if error_type == "virtual" else 0.0
    wcfg = WorkerConfig(mode=mode, error_type=error_type, k=k,
                        num_workers=num_workers, weight_decay=5e-4)
    scfg = ServerConfig(mode=mode, error_type=error_type, k=k,
                        grad_size=d, virtual_momentum=vmom,
                        fused_epilogue=fused_epilogue)
    sketch = make_sketch(d, c=c, r=r, seed=42, num_blocks=blocks) \
        if mode == "sketch" else None
    # per-leg compressed collectives (--collective_plan,
    # docs/compressed_collectives.md): a plan spec string, parsed here
    # exactly as the entrypoints do; quantized legs require server_shard
    plan = None
    if collective_plan:
        from commefficient_tpu.ops.collectives import parse_collective_plan

        plan = parse_collective_plan(collective_plan)
    cfg = RoundConfig(worker=wcfg, server=scfg, grad_size=d,
                      server_shard=server_shard, guards=guards,
                      stream_sketch=stream_sketch,
                      sketch_coalesce=sketch_coalesce, telemetry=telemetry,
                      telemetry_hist=telemetry_hist,
                      collective_plan=plan)
    loss_train, loss_val = make_cv_losses(model)
    # the entrypoints' real execution path: shard_map+psum over a clients
    # mesh — a 1-device mesh on the single bench chip; --shard_devices > 1
    # adds the second server axis (2D clients x shard plane,
    # docs/multihost.md) and the server reduce runs over the ordered
    # (shard, clients) tuple
    from commefficient_tpu.parallel.mesh import (
        default_client_mesh,
        server_reduce_axes,
    )

    mesh = default_client_mesh(num_workers, shard_devices=shard_devices)
    axes = server_reduce_axes(mesh)
    _log(f"mesh: {dict(mesh.shape)} over {mesh.devices.size} device(s), "
         f"mode={mode}, W={num_workers}, server_shard={server_shard}")
    steps = build_round_step(loss_train, loss_val, unravel, ravel, cfg,
                             sketch=sketch, mesh=mesh, axis=axes)

    # non_iid models the FEMNIST/CIFAR100 federated split (config 4): a large client population with skewed per-round sampling.
    # Which ids participate changes the client-state rows gathered, not how
    # much compute a round does, so the leg is honest about measuring the
    # same round under the non-IID configuration.
    num_clients = 500 if non_iid else 10
    from commefficient_tpu.parallel.mesh import (
        axis_product,
        mesh_axis_placement,
    )

    lowering = None
    if plan is not None and plan.per_axis and server_shard:
        # per-mesh-axis legs (docs/multihost.md): the same resolution
        # build_round_step does, so the carry slots match the lowering
        from commefficient_tpu.ops.collectives import (
            PLAN_LEGS,
            resolve_leg_lowering,
        )

        placement = mesh_axis_placement(mesh)
        lowering = {l: resolve_leg_lowering(getattr(plan, l), axes,
                                            placement)
                    for l in PLAN_LEGS}
    axis_names = (axes,) if isinstance(axes, str) else axes
    server_state = init_server_state(
        scfg, sketch,
        shard_n=axis_product(mesh, axes) if server_shard else 0,
        plan=plan, lowering=lowering,
        axis_sizes={a: int(mesh.shape[a]) for a in axis_names})
    if server_shard:
        # commit the sharded-plane residency up front — the ONE rule
        # FedModel uses (server.place_server_state), so round 1 hits the
        # jit cache and donation is safe
        from commefficient_tpu.federated.server import place_server_state

        server_state = place_server_state(server_state, mesh, mode,
                                          server_shard=True, axis=axes)
    client_states = init_client_states(num_clients, d, wcfg, sketch=sketch,
                                       init_weights=flat)

    rng = np.random.RandomState(0)
    if non_iid:
        client_ids = rng.zipf(1.5, num_workers) % num_clients
    else:
        client_ids = np.arange(num_workers) % num_clients
    # partial-cohort round shape (--participation, the `straggler` leg /
    # tpu_measure participation A/B): the first ceil(p*W) worker slots
    # are live, then drop_frac of THOSE are zero-masked too (the injected
    # drops). The round math's data-weighted mean makes the missing
    # clients an exact reweighting (docs/fault_tolerance.md), so the leg
    # measures the same round under the partial-participation mask shape.
    # Guarded so the legacy legs draw no extra RNG and stay bit-stable.
    wm = np.ones(num_workers, np.float32)
    if participation < 1.0 or drop_frac > 0.0:
        live = max(1, int(np.ceil(participation * num_workers)))
        wm[live:] = 0.0
        dropped = (rng.random_sample(num_workers) < drop_frac) & (wm > 0)
        wm[dropped] = 0.0
        if wm.sum() == 0:
            wm[0] = 1.0  # a zero-participant round has no defined mean
        _log(f"participation mask: {int(wm.sum())}/{num_workers} live "
             f"slots (target {live}, {int(dropped.sum())} dropped)")
    batch = {
        "inputs": jnp.asarray(
            rng.randn(num_workers, LOCAL_BS, 32, 32, 3), jnp.float32),
        "targets": jnp.asarray(
            rng.randint(0, num_classes, (num_workers, LOCAL_BS))),
        "mask": jnp.asarray(
            np.ones((num_workers, LOCAL_BS), np.float32) * wm[:, None]),
        "client_ids": jnp.asarray(client_ids, jnp.int32),
        "worker_mask": jnp.asarray(wm),
    }
    return steps, flat, server_state, client_states, batch


def build_gpt2(bf16: bool = False, fused_epilogue: bool = False,
               stream_sketch: bool = False, sketch_coalesce: bool = False):
    """GPT-2 PersonaChat sketched federated round (config 5):
    full 124M double-heads geometry, 4 clients/round, 2 candidates x 256
    tokens per example, sketch 5x500k/k=50k (reference gpt2_train.py:255-313
    run shape). ``bf16`` switches the fwd/bwd compute to bf16 (--bf16);
    ``fused_epilogue`` turns on the one-sweep server epilogue
    (docs/fused_epilogue.md), ``stream_sketch`` the streaming client
    phase (docs/stream_sketch.md), and ``sketch_coalesce`` the coalesced
    multi-leaf accumulate on top of it, for their profiling A/Bs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from commefficient_tpu.federated.losses import make_gpt2_losses
    from commefficient_tpu.federated.rounds import (
        RoundConfig,
        build_round_step,
        init_client_states,
    )
    from commefficient_tpu.federated.server import (
        ServerConfig,
        init_server_state,
    )
    from commefficient_tpu.federated.worker import WorkerConfig
    from commefficient_tpu.models.gpt2 import GPT2DoubleHeads
    from commefficient_tpu.ops.flat import ravel_pytree
    from commefficient_tpu.ops.sketch import make_sketch
    from commefficient_tpu.parallel.mesh import default_client_mesh

    W, B, C, T = 4, 2, 2, 256
    model = GPT2DoubleHeads(vocab_size=50262, n_positions=1024)
    rng = np.random.RandomState(0)
    ids0 = jnp.zeros((1, C, T), jnp.int32)
    params = model.init(jax.random.key(0), ids0, token_type_ids=ids0,
                        mc_token_ids=jnp.zeros((1, C), jnp.int32),
                        train=False)["params"]
    flat, unravel = ravel_pytree(params)
    d = int(flat.size)
    _log(f"gpt2 built: d={d}")

    def ravel(tree):
        return ravel_pytree(tree)[0]

    k, c, r, blocks = 50_000, 500_000, 5, 20
    wcfg = WorkerConfig(mode="sketch", error_type="virtual", k=k,
                        num_workers=W)
    scfg = ServerConfig(mode="sketch", error_type="virtual", k=k,
                        grad_size=d, virtual_momentum=0.9,
                        fused_epilogue=fused_epilogue)
    sketch = make_sketch(d, c=c, r=r, seed=42, num_blocks=blocks)
    cfg = RoundConfig(worker=wcfg, server=scfg, grad_size=d,
                      stream_sketch=stream_sketch,
                      sketch_coalesce=sketch_coalesce)
    loss_train, loss_val = make_gpt2_losses(
        model, compute_dtype=jnp.bfloat16 if bf16 else None)
    mesh = default_client_mesh(W)
    steps = build_round_step(loss_train, loss_val, unravel, ravel, cfg,
                             sketch=sketch, mesh=mesh)
    server_state = init_server_state(scfg, sketch)
    client_states = init_client_states(8, d, wcfg)
    batch = {
        "input_ids": jnp.asarray(rng.randint(0, 50000, (W, B, C, T)),
                                 jnp.int32),
        "token_type_ids": jnp.asarray(rng.randint(0, 50000, (W, B, C, T)),
                                      jnp.int32),
        "lm_labels": jnp.asarray(rng.randint(0, 50000, (W, B, C, T)),
                                 jnp.int32),
        "mc_token_ids": jnp.asarray(rng.randint(0, T, (W, B, C)), jnp.int32),
        "mc_labels": jnp.asarray(rng.randint(0, C, (W, B)), jnp.int32),
        "mask": jnp.ones((W, B), jnp.float32),
        "client_ids": jnp.arange(W, dtype=jnp.int32),
        "worker_mask": jnp.ones(W, jnp.float32),
    }
    tokens_per_round = W * B * C * T
    return steps, flat, server_state, client_states, batch, tokens_per_round


def _time_rounds(steps, ps, server_state, client_states, batch, warmup,
                 iters, tag, reps=3):
    """Shared warmup + timed-loop harness for the fused train_step.

    Every timed rep ends with a SCALAR materialization of the new weights
    (fetching one element forces full completion); the settled scalar-fetch
    round trip, measured in situ below, is subtracted; the loop runs
    ``reps`` times and the BEST rep is reported. (Both conventions date
    from the 2026-08 record and go with the benchmark rewrite, ROADMAP
    Queue 1 item 0.)
    """
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.profiling import host_sync_monitor

    def drain(x):
        # force completion of everything x depends on; tiny D2H transfer
        return float(jnp.asarray(x).ravel()[0])

    layout = getattr(steps, "layout", None)
    if layout is not None and ps.ndim == 1:
        # chunked-resident data plane (docs/round_engine.md): convert ONCE
        # before the loop so the steady state runs with zero per-round
        # flat<->chunk layout churn — the state the real training loops
        # (FedModel) keep across rounds
        ps = layout.chunk(ps)
        _log(f"{tag}: ps resident in chunk layout {tuple(ps.shape)}")
    state = (ps, server_state, client_states, {})
    rng = jax.random.key(0)
    _log(f"{tag}: compiling + warmup (first jit is the slow part)")
    for i in range(warmup):
        out = steps.train_step(state[0], state[1], state[2], state[3], batch,
                               0.1, rng)
        state = out[:4]
        drain(state[0])
        _log(f"{tag} warmup iter {i + 1}/{warmup} done")
    # settled-queue scalar-fetch round trip, the transport constant to
    # subtract from each rep
    rtt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        drain(state[0])
        rtt = min(rtt, time.perf_counter() - t0)
    _log(f"{tag}: timing {iters} rounds x {reps} reps "
         f"(scalar-drain rtt {rtt * 1e3:.1f} ms)")
    best = float("inf")
    syncs = 0
    for rep in range(reps):
        t0 = time.perf_counter()
        # the sync audit (profiling.host_sync_monitor, docs/round_engine.md)
        # covers the dispatch loop only — the one drain after it is the
        # deliberate batched fetch
        with host_sync_monitor() as sync_counter:
            for _ in range(iters):
                out = steps.train_step(state[0], state[1], state[2], state[3],
                                       batch, 0.1, rng)
                state = out[:4]
        syncs = sync_counter.count
        drain(state[0])
        dt = max(time.perf_counter() - t0 - rtt, 1e-9)
        _log(f"{tag} rep {rep + 1}/{reps}: {dt:.3f}s for {iters} rounds "
             f"({syncs} host syncs in dispatch loop)")
        best = min(best, dt)
    _log(f"{tag} done: best rep {best:.3f}s for {iters} rounds")
    return best, syncs


def run_gpt2_measurement(legs=(False, True)) -> None:
    """Child-process entry (--run-gpt2 [f32|bf16]): prints its own JSON line
    with the f32 number (comparable to the reference's f32 training) and/or
    the bf16 number (--bf16 mixed precision, the TPU-native mode).

    ``legs`` selects which to run, so that each d=124M compile can have a
    child of its own."""
    import jax

    # own process — the --run child's kernel checks don't reach here
    _check_pallas_kernel()
    out = {
        "gpt2_metric": "GPT-2 PersonaChat tokens/sec/chip "
                       "(124M double-heads, 4 workers, sketch 5x500k k=50k)",
        "platform": jax.default_backend(),
    }
    n = 10

    def one_leg(bf16):
        # loop-scoped so each leg's 124M-param state (weights, momentum and
        # error tables, compiled executables) is dropped before the next
        # leg builds — both legs live at once would ~double peak HBM
        steps, ps, server_state, client_states, batch, tokens = \
            build_gpt2(bf16=bf16)
        tag = "gpt2-bf16" if bf16 else "gpt2-f32"
        # warmup=1: iter 1 pays the compile; the timed loop subtracts the
        # settled rtt, and best-of-3 reps already absorbs residual warmth.
        # A second warmup iter cost window time the d=124M legs don't have.
        dt, syncs = _time_rounds(steps, ps, server_state, client_states,
                                 batch, warmup=1, iters=n, tag=tag)
        return tokens, dt, syncs

    flops_per_token = gpt2_train_flops_per_token()
    for bf16 in legs:
        tokens, dt, syncs = one_leg(bf16)
        key = "gpt2_bf16" if bf16 else "gpt2"
        out[f"{key}_host_syncs_per_round"] = round(syncs / n, 3)
        tok_per_sec = tokens * n / dt
        tflops = flops_per_token * tok_per_sec / 1e12
        out[f"{key}_tokens_per_sec"] = round(tok_per_sec, 1)
        out[f"{key}_rounds_per_sec"] = round(n / dt, 3)
        out[f"{key}_vs_baseline"] = round(
            tok_per_sec / BASELINE_GPT2_TOKENS_PER_SEC, 4)
        out[f"{key}_tflops"] = round(tflops, 2)
        out[f"{key}_mfu_bf16"] = round(
            tflops * 1e12 / peak_bf16_flops(), 4)
        # emit after each leg so a crash in the bf16 leg still leaves the
        # f32 number on stdout (the parent salvages the last JSON line
        # even from a failed child)
        print(json.dumps(out), flush=True)


def _check_pallas_kernel() -> None:
    """Require a TPU, then run the library's one-time kernel self-checks
    eagerly so their outcome is in the bench log before any timing. A
    kernel that fails them raises (ops/sketch.py): there is no XLA
    fallback to time by accident."""
    _require_tpu()
    from commefficient_tpu.ops.sketch import (
        _check_estimates_kernel_once,
        _check_sketch_kernel_once,
        _use_pallas_estimates,
        _use_pallas_sketch,
    )

    _check_sketch_kernel_once(eager=True)
    _check_estimates_kernel_once(eager=True)
    for name, on in (("sketch accumulate", _use_pallas_sketch()),
                     ("estimates", _use_pallas_estimates())):
        _log(f"pallas {name} kernels: "
             + ("passed self-check (bit-exact)" if on
                else "disabled by env; pure XLA path"))


def run_measurement() -> None:
    _log(f"importing jax (platform pref: "
         f"{os.environ.get('JAX_PLATFORMS', '<default>')})")
    import jax

    _log(f"backend: {jax.default_backend()}, devices: {jax.devices()}")
    _check_pallas_kernel()

    steps, ps, server_state, client_states, batch = build(tiny=False)
    dt, syncs = _time_rounds(steps, ps, server_state, client_states, batch,
                             warmup=WARMUP, iters=ITERS, tag="cifar10")

    rounds_per_sec = ITERS / dt
    geom = "ResNet9, 8 workers, sketch 5x500k k=50k"
    from commefficient_tpu.models.resnet9 import DEFAULT_CHANNELS

    flops_per_round = resnet9_train_flops_per_image(
        DEFAULT_CHANNELS) * LOCAL_BS * NUM_WORKERS
    tflops = flops_per_round * rounds_per_sec / 1e12
    print(json.dumps({
        "metric": f"CIFAR10 fed rounds/sec/chip ({geom})",
        "value": round(rounds_per_sec, 4),
        "unit": "rounds/sec",
        "vs_baseline": round(rounds_per_sec / BASELINE_ROUNDS_PER_SEC, 4),
        "platform": jax.default_backend(),
        "tflops": round(tflops, 4),
        "mfu_bf16": round(tflops * 1e12 / peak_bf16_flops(), 4),
    }), flush=True)


class CfgLeg(NamedTuple):
    """One measure-and-emit CIFAR-family config leg. Feature flags are
    keyword defaults so a new RoundConfig flag is one new field here, not
    a positional False appended to every leg (a miscounted positional
    silently flips the wrong feature while the label still reads right).

    ``k_rounds`` multi-rounds per dispatch via lax.scan: the cheap c1/c2
    rounds are small next to the per-dispatch host cost, so 20
    single-round dispatches would measure dispatch noise; K rounds inside
    ONE dispatch keep the queue shallow while the timed region grows
    K x."""

    mode: str
    workers: int
    baseline: str  # baseline r/s constant name
    label: str
    num_classes: int = 10
    non_iid: bool = False
    k_rounds: int = 1
    server_shard: bool = False
    fused_epilogue: bool = False
    guards: bool = False
    stream_sketch: bool = False
    sketch_coalesce: bool = False
    telemetry: bool = False
    telemetry_hist: bool = False
    collective_plan: str = ""
    participation: float = 1.0
    drop_frac: float = 0.0
    shard_devices: int = 1


_CFG_LEGS = {
    "c1": CfgLeg("uncompressed", 1, "BASELINE_C1",
                 "1-worker uncompressed rounds/sec/chip (ResNet9)",
                 k_rounds=20),
    "c2": CfgLeg("true_topk", 8, "BASELINE_C2",
                 "8-worker true-topk rounds/sec/chip (ResNet9, k=50k)",
                 k_rounds=10),
    "cifar100": CfgLeg("sketch", 8, "BASELINE_CIFAR100",
                       "CIFAR100/FEMNIST-style non-IID sketched "
                       "rounds/sec/chip (ResNet9-100, 500 clients, "
                       "8 workers, sketch 5x500k k=50k)",
                       num_classes=100, non_iid=True),
    # the headline sketch leg with the sharded server data plane
    # (--server_shard, docs/sharded_server.md); its baseline anchor is the
    # headline config-3 estimate so BENCH readers can compare the two legs
    # directly. Per-shard server work only drops on a multi-chip mesh, so
    # on the 1-chip bench this leg pins NO-regression with the plane on;
    # on a multi-chip mesh it measures the win.
    "shard": CfgLeg("sketch", 8, "BASELINE",
                    "8-worker sketched rounds/sec/chip with --server_shard "
                    "(ResNet9, sketch 5x500k k=50k, sharded server data "
                    "plane)",
                    server_shard=True),
    # the headline sketch leg with the fused server epilogue
    # (--fused_epilogue, docs/fused_epilogue.md); same config-3 baseline
    # anchor so the fused-vs-composed delta reads straight off the two
    # legs (mfu_attack_r5.md projects ~2.3 ms/round ≈ 32% MFU if the
    # fusion fully lands).
    "fused": CfgLeg("sketch", 8, "BASELINE",
                    "8-worker sketched rounds/sec/chip with "
                    "--fused_epilogue (ResNet9, sketch 5x500k k=50k, "
                    "one-sweep server epilogue)",
                    fused_epilogue=True),
    # the headline sketch leg with on-device health guards (--guards,
    # docs/fault_tolerance.md); same config-3 baseline anchor, so
    # guarded-vs-unguarded steady-state overhead reads straight off this
    # leg vs the headline (the guard is two scalar isfinite reductions +
    # a handful of d-plane selects riding the existing epilogue sweeps —
    # expected low single-digit %).
    "guards": CfgLeg("sketch", 8, "BASELINE",
                     "8-worker sketched rounds/sec/chip with --guards "
                     "(ResNet9, sketch 5x500k k=50k, on-device health "
                     "guards)",
                     guards=True),
    # the headline sketch leg with the streaming client-phase sketch
    # (--stream_sketch, docs/stream_sketch.md); same config-3 baseline
    # anchor so the stream-vs-composed delta reads straight off the two
    # legs. NOTE the leg includes the wd segment-sketch (bench wd=5e-4),
    # so it measures the honest production shape, not the wd=0 best case.
    "stream": CfgLeg("sketch", 8, "BASELINE",
                     "8-worker sketched rounds/sec/chip with "
                     "--stream_sketch (ResNet9, sketch 5x500k k=50k, "
                     "streaming client-phase sketch)",
                     stream_sketch=True),
    # the `stream` leg with the coalesced client-phase megakernel
    # (--sketch_coalesce, docs/stream_sketch.md); same config-3 baseline
    # anchor, so the coalesce-vs-per-leaf delta reads straight off this
    # leg vs `stream` — the per-leaf table row-block RMW (2·r·c_pad·4
    # bytes × ~leaf count per microbatch) drops to once per coalesced
    # group, and the per-leaf kernel-launch overhead goes with it.
    "coalesce": CfgLeg("sketch", 8, "BASELINE",
                       "8-worker sketched rounds/sec/chip with "
                       "--stream_sketch --sketch_coalesce (ResNet9, "
                       "sketch 5x500k k=50k, coalesced client-phase "
                       "sketch megakernel)",
                       stream_sketch=True, sketch_coalesce=True),
    # the headline sketch leg with the telemetry plane's on-device round
    # metrics (--telemetry, docs/observability.md); same config-3 baseline
    # anchor so the telemetry-on overhead reads straight off this leg vs
    # the headline. The metrics are ~a dozen scalar reductions over planes
    # the epilogue already reads — the documented overhead gate is <= 2%
    # rounds/sec (docs/observability.md overhead ledger; number pending a
    # chip window).
    "telemetry": CfgLeg("sketch", 8, "BASELINE",
                        "8-worker sketched rounds/sec/chip with "
                        "--telemetry (ResNet9, sketch 5x500k k=50k, "
                        "on-device round metrics)",
                        telemetry=True),
    # the `shard` leg with the FULL-compressed collective plan
    # (--collective_plan int8: table exchange AND downlink all-gather
    # quantized, docs/compressed_collectives.md) — vs the fp32 `shard`
    # leg this A/B reads the quantize/dequantize + EF-carry step-time
    # cost of compressing every wire leg (~4x fewer ledger bytes; the
    # EQuARX result, arXiv:2506.17615, predicts negligible). On the
    # 1-chip bench mesh it pins NO-regression; a multi-chip mesh adds
    # the actual ICI-byte win.
    "downlink": CfgLeg("sketch", 8, "BASELINE",
                       "8-worker sketched rounds/sec/chip with "
                       "--server_shard --collective_plan int8 (ResNet9, "
                       "sketch 5x500k k=50k, full-compressed wire legs "
                       "incl. quantized downlink + dres carry)",
                       server_shard=True, collective_plan="int8"),
    # the `telemetry` leg plus the schema-v3 histogram block + watch
    # plane (--telemetry_hist, docs/observability.md §watch plane); same
    # config-3 baseline anchor so the continuous-observability overhead
    # reads straight off this leg vs the headline (gate <= 2% rounds/sec
    # WITH histograms + watch enabled — the histogram adds two
    # log/scatter passes over the update + the table-sized error carry;
    # the watch rules are host arithmetic on drained values and cost the
    # device nothing, so this leg times the device half and
    # tpu_measure.py `watch` times both halves).
    "watch": CfgLeg("sketch", 8, "BASELINE",
                    "8-worker sketched rounds/sec/chip with --telemetry "
                    "--telemetry_hist (ResNet9, sketch 5x500k k=50k, "
                    "schema-v3 histogram metrics + watch plane)",
                    telemetry=True, telemetry_hist=True),
    # the headline sketch leg at a PARTIAL cohort (--participation 0.5
    # with 10% injected client drops — the straggler/dropout regime of
    # docs/fault_tolerance.md §client faults); same config-3 baseline
    # anchor so the partial-vs-full delta reads straight off this leg vs
    # the headline. The masked slots still run their (zeroed) compute —
    # XLA's static shapes don't shrink with the cohort — so the leg pins
    # that a partial cohort costs no MORE than full participation; the
    # three-way 1.0/0.5/0.1 sweep is `tpu_measure.py participation`.
    "straggler": CfgLeg("sketch", 8, "BASELINE",
                        "8-worker sketched rounds/sec/chip at "
                        "--participation 0.5 with 10% injected client "
                        "drops (ResNet9, sketch 5x500k k=50k, "
                        "partial-cohort round)",
                        participation=0.5, drop_frac=0.1),
    # the `shard` leg on the 2D (clients x shard) server plane with the
    # per-MESH-AXIS collective plan (--shard_devices 2 --collective_plan
    # table=shard:fp32/clients:int8,..., docs/multihost.md): the shard
    # hop (ICI on a pod) stays fp32 while the clients hop (the
    # DCN-spanning axis on a multi-host mesh) is int8-quantized with its
    # per-level EF carry. On a single-host multi-chip mesh both hops ride
    # ICI, so the leg reads the hierarchical-lowering + per-level
    # quantize step-time cost vs the flat `shard`/`downlink` legs; the
    # cross-host DCN-byte win itself is static (ledger: ~4x fewer
    # DCN bytes/round) and needs a real multi-host window to time.
    # Needs >= 2x2 devices — the leg aborts cleanly on the 1-chip bench.
    "multihost": CfgLeg("sketch", 8, "BASELINE",
                        "8-worker sketched rounds/sec/chip with "
                        "--server_shard --shard_devices 2 and the "
                        "per-axis plan table/downlink=shard:fp32+"
                        "clients:int8 (ResNet9, sketch 5x500k k=50k, "
                        "hierarchical quantized collectives)",
                        server_shard=True, shard_devices=2,
                        collective_plan="table=shard:fp32/clients:int8,"
                                        "downlink=shard:fp32/"
                                        "clients:int8"),
}


def run_config_measurement(name: str) -> None:
    """Child-process entry (--run-c4 / --run-cfg c1|c2): the
    CIFAR-family config legs — c1 = 1-worker uncompressed (reference
    cv_train smoke shape), c2 = 8-worker true_topk (k=50k over the summed
    d=6.5M gradient, reference fed_aggregator.py:525-533 semantics),
    cifar100 = config 4's non-IID sketched round."""
    import jax
    from jax import lax

    _check_pallas_kernel()
    leg = _CFG_LEGS[name]
    W, K, label = leg.workers, leg.k_rounds, leg.label
    num_classes = leg.num_classes
    base = {"BASELINE": BASELINE_ROUNDS_PER_SEC,
            "BASELINE_C1": BASELINE_C1_ROUNDS_PER_SEC,
            "BASELINE_C2": BASELINE_C2_ROUNDS_PER_SEC,
            "BASELINE_CIFAR100":
                BASELINE_CIFAR100_ROUNDS_PER_SEC}[leg.baseline]
    if leg.shard_devices > 1 and jax.device_count() < 2 * leg.shard_devices:
        # the 2D leg needs a real (clients >= 2) x shard mesh; on fewer
        # devices default_client_mesh would degrade to 1D and the
        # per-axis plan would (correctly) refuse to resolve — abort with
        # the actionable message instead
        sys.exit(f"--run-cfg {name}: needs >= {2 * leg.shard_devices} "
                 f"devices for the 2D (clients x shard={leg.shard_devices}) "
                 f"mesh; found {jax.device_count()}")
    steps, ps, server_state, client_states, batch = build(
        tiny=False, num_classes=num_classes, non_iid=leg.non_iid,
        mode=leg.mode, num_workers=W, server_shard=leg.server_shard,
        fused_epilogue=leg.fused_epilogue, guards=leg.guards,
        stream_sketch=leg.stream_sketch,
        sketch_coalesce=leg.sketch_coalesce, telemetry=leg.telemetry,
        telemetry_hist=leg.telemetry_hist,
        collective_plan=leg.collective_plan,
        participation=leg.participation, drop_frac=leg.drop_frac,
        shard_devices=leg.shard_devices)
    if K > 1:
        inner = steps.train_step

        @jax.jit
        def k_step(ps, ss, cs, ms, b, lr, rng):
            def body(carry, _):
                ps, ss, cs, ms = carry
                out = inner(ps, ss, cs, ms, b, lr, rng)
                return out[:4], None

            carry, _ = lax.scan(body, (ps, ss, cs, ms), None, length=K)
            return carry + ((),)

        steps = steps._replace(train_step=k_step)
    best = _time_rounds(steps, ps, server_state, client_states, batch,
                        warmup=WARMUP, iters=ITERS, tag=name)
    rounds_per_sec = ITERS * K / best
    from commefficient_tpu.models.resnet9 import DEFAULT_CHANNELS

    flops_per_round = resnet9_train_flops_per_image(
        DEFAULT_CHANNELS, num_classes=num_classes) * LOCAL_BS * W
    tflops = flops_per_round * rounds_per_sec / 1e12
    out = {
        f"{name}_metric": label,
        f"{name}_rounds_per_sec": round(rounds_per_sec, 4),
        f"{name}_vs_baseline": round(rounds_per_sec / base, 4),
        f"{name}_tflops": round(tflops, 2),
        f"{name}_mfu_bf16": round(tflops * 1e12 / peak_bf16_flops(),
                                  4),
        "platform": jax.default_backend(),
    }
    if leg.baseline in ("BASELINE", "BASELINE_C1", "BASELINE_C2"):
        # these anchors are analytic estimates of the reference's A100
        # throughput (derived FLOP/dispatch arithmetic above), never
        # measured; flag it so a BENCH artifact reader can tell these
        # ratios apart from ones against measured baselines
        out[f"{name}_baseline_estimated"] = True
    print(json.dumps(out), flush=True)


def run_clients_sweep_measurement() -> None:
    """Child-process entry (--run-cfg clients_sweep): rounds/sec vs client
    POPULATION size with disk-tier client state (docs/host_offload.md) —
    the million-client scale leg of ROADMAP item 1.

    Synthetic populations of 10^4 / 10^5 / 10^6 clients back the headline
    sketched round's per-client error state with a sparse
    ``MemmapRowStore`` (rows materialize disk blocks only when touched, so
    the 10^6 x 10 MB logical state costs ~W rows/round of real I/O).
    Each timed round runs the full gather -> jitted round -> scatter
    cycle through the ``CohortPrefetcher`` exactly as the aggregator
    does, with round t+1's row read overlapping round t's compute. The
    expected shape is a FLAT sweep — per-round work is W rows regardless
    of population — so a rising curve is an out-of-core-path regression,
    not a law of nature."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    from commefficient_tpu.federated.host_state import (
        CohortPrefetcher,
        MemmapRowStore,
    )
    from commefficient_tpu.federated.rounds import ClientStates
    from commefficient_tpu.parallel.mesh import default_client_mesh

    _check_pallas_kernel()
    steps, ps, server_state, client_states, batch = build(
        tiny=False, error_type="local")
    import jax.numpy as jnp

    # train_step donates its client_states argument, so the pre-round
    # proxy rows must be copied for the delta (the aggregator reads them
    # from the undonated round ctx; the fused step has no ctx)
    _copy_rows = jax.jit(jnp.copy)
    W = NUM_WORKERS
    mesh = default_client_mesh(W)
    row_shape = tuple(int(x) for x in client_states.errors.shape[1:])
    batch = dict(batch)
    batch["client_ids"] = jnp.arange(W, dtype=jnp.int32)  # proxy remap
    iters, reps = 20, 3
    out = {
        "clients_sweep_metric": (
            "8-worker sketched rounds/sec vs client-population size, "
            "disk-tier (sparse memmap) per-client error state streamed "
            f"{W} rows/round through the cohort prefetcher "
            "(flat sweep expected; docs/host_offload.md)"),
        "clients_sweep_row_bytes": int(np.prod(row_shape)) * 4,
        "platform": jax.default_backend(),
    }
    for n in (10_000, 100_000, 1_000_000):
        tag = f"1e{len(str(n)) - 1}"
        store_dir = tempfile.mkdtemp(prefix=f"clients_sweep_{tag}_")
        store = MemmapRowStore(store_dir, n, {"errors": row_shape},
                               mesh=mesh)
        pf = CohortPrefetcher(store.gather_async)
        rng = np.random.RandomState(7)
        cohorts = [rng.choice(n, W, replace=False) for _ in range(iters + 2)]
        # per-leg copies: train_step donates ps/client-state buffers, and
        # the originals must survive for the next population leg
        ps_leg = _copy_rows(ps)
        ss_leg = jax.tree_util.tree_map(_copy_rows, server_state)

        def run_rounds(k, ps, ss, ms):
            pf.prefetch(cohorts[0])
            for i in range(k):
                stream, _ = pf.take(cohorts[i])
                old = ClientStates(None, _copy_rows(stream.proxy.errors),
                                   None)
                o = steps.train_step(ps, ss, stream.proxy, ms, batch,
                                     0.1, jax.random.key(i))
                ps, ss, new_proxy, ms = o[:4]
                store.scatter(stream, old, new_proxy)
                pf.prefetch(cohorts[i + 1])
            store.drain()
            jax.block_until_ready(ps)
            return ps, ss, ms

        state = run_rounds(1, ps_leg, ss_leg, {})  # compile + warm
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            state = run_rounds(iters, *state)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        rps = iters / best
        out[f"clients_sweep_rounds_per_sec_{tag}"] = round(rps, 4)
        out[f"clients_sweep_prefetch_hits_{tag}"] = pf.hits
        _log(f"clients_sweep n={n}: {rps:.2f} rounds/s "
             f"({pf.hits} prefetch hits / {pf.misses} misses)")
        store.close()
        shutil.rmtree(store_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)


def run_io_faults_measurement() -> None:
    """Child-process entry (--run-cfg io_faults): storage-fault-plane
    overhead A/B (docs/fault_tolerance.md §storage faults).

    Three legs over the disk-tier gather -> round -> scatter cycle at a
    10^5-row population (the clients_sweep loop shape): (a) CLEAN — no
    injection schedule compiled in; (b) IDLE — an all-zero
    ``--inject_io_fault`` schedule, i.e. the injection seam + retry
    ladder + watchdog armed but never firing (gate: <= 2% rounds/sec vs
    clean — the shim must be free when healthy); (c) TRANSIENT — seeded
    eio/short/torn/stall faults below the retry budget, whose retries
    must leave the final row state BIT-identical to the clean leg
    (``io_faults_bit_identical``) while the throughput delta prices what
    a flaky disk actually costs."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from commefficient_tpu.federated.host_state import (
        CohortPrefetcher,
        MemmapRowStore,
        parse_io_fault,
    )
    from commefficient_tpu.federated.rounds import ClientStates
    from commefficient_tpu.parallel.mesh import default_client_mesh

    _check_pallas_kernel()
    _copy_rows = jax.jit(jnp.copy)
    W = NUM_WORKERS
    mesh = default_client_mesh(W)
    n = 100_000
    iters, reps = 20, 3
    legs = (
        ("clean", None),
        ("idle", "eio=0,short=0,torn=0,stall=0,seed=0"),
        ("transient",
         "eio=0.02,short=0.01,torn=0.01,stall=0.01,stall_ms=2,seed=11"),
    )
    out = {
        "io_faults_metric": (
            "8-worker sketched disk-tier rounds/sec: clean vs injection-"
            "idle (gate <= 2%) vs seeded transient faults below the "
            "retry budget (bit-identical rows pinned; "
            "docs/fault_tolerance.md §storage faults)"),
        "platform": jax.default_backend(),
    }
    finals = {}
    for tag, spec in legs:
        # per-leg rebuild: train_step donates the state buffers; the
        # COMPILE is shared through the jit cache
        steps, ps, server_state, client_states, batch = build(
            tiny=False, error_type="local")
        row_shape = tuple(int(x) for x in client_states.errors.shape[1:])
        batch = dict(batch)
        batch["client_ids"] = jnp.arange(W, dtype=jnp.int32)
        store_dir = tempfile.mkdtemp(prefix=f"io_faults_{tag}_")
        store = MemmapRowStore(
            store_dir, n, {"errors": row_shape}, mesh=mesh,
            inject=parse_io_fault(spec) if spec else None,
            io_backoff_ms=0.5)
        pf = CohortPrefetcher(store.gather_async)
        rng = np.random.RandomState(7)
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
            jax.block_until_ready(ps_)
            return ps_, ss_, ms

        state = run_rounds(1, ps, server_state, {})  # compile + warm
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            state = run_rounds(iters, *state)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        rps = iters / best
        counts = store.io_counters()
        out[f"io_faults_rounds_per_sec_{tag}"] = round(rps, 4)
        out[f"io_faults_retries_{tag}"] = counts["retries"]
        # the final row state, for the bit-identity pin across legs (the
        # same seeded cohorts + jitted round => identical trajectories)
        finals[tag] = store.read_full("errors")
        _log(f"io_faults {tag}: {rps:.2f} rounds/s "
             f"({counts['retries']} retries, {counts['errors']} "
             f"exhausted, {counts['quarantined']} quarantined)")
        store.close()
        shutil.rmtree(store_dir, ignore_errors=True)
    clean_rps = out["io_faults_rounds_per_sec_clean"]
    out["io_faults_idle_vs_clean"] = round(
        out["io_faults_rounds_per_sec_idle"] / clean_rps, 4)
    out["io_faults_transient_vs_clean"] = round(
        out["io_faults_rounds_per_sec_transient"] / clean_rps, 4)
    out["io_faults_bit_identical"] = bool(
        np.array_equal(finals["clean"], finals["idle"])
        and np.array_equal(finals["clean"], finals["transient"]))
    assert out["io_faults_bit_identical"], (
        "transient-fault rows diverged from the clean leg — the retry "
        "ladder is NOT invisible to the trajectory")
    print(json.dumps(out), flush=True)


def run_integrity_measurement() -> None:
    """Child-process entry (--run-cfg integrity): integrity-plane
    overhead A/B (docs/fault_tolerance.md §silent corruption).

    Three legs over the disk-tier gather -> round -> scatter cycle at a
    10^5-row population (the io_faults loop shape), no injection: (a)
    OFF — per-row checksums disabled; (b) ON-IDLE — checksums verified
    on every row read/write (gate: <= 2% rounds/sec vs off — one CRC32
    pass per row against MB-scale row I/O); (c) SCRUB — checksums plus
    a 32-row background scrub per round on the ordered worker
    (overlapped; prices the full audit cadence). Verification only
    reads, so the final rows are pinned BIT-identical across all three
    legs (``integrity_bit_identical``)."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from commefficient_tpu.federated.host_state import (
        CohortPrefetcher,
        MemmapRowStore,
    )
    from commefficient_tpu.federated.rounds import ClientStates
    from commefficient_tpu.parallel.mesh import default_client_mesh

    _check_pallas_kernel()
    _copy_rows = jax.jit(jnp.copy)
    W = NUM_WORKERS
    mesh = default_client_mesh(W)
    n = 100_000
    iters, reps = 20, 3
    legs = (
        ("off", False, 0),
        ("on_idle", True, 0),
        ("scrub", True, 32),
    )
    out = {
        "integrity_metric": (
            "8-worker sketched disk-tier rounds/sec: per-row checksums "
            "off vs on-idle (gate <= 2%) vs on + 32-row/round background "
            "scrub (rows pinned bit-identical across legs; "
            "docs/fault_tolerance.md §silent corruption)"),
        "platform": jax.default_backend(),
    }
    finals = {}
    for tag, checksums, scrub in legs:
        # per-leg rebuild: train_step donates the state buffers; the
        # COMPILE is shared through the jit cache
        steps, ps, server_state, client_states, batch = build(
            tiny=False, error_type="local")
        row_shape = tuple(int(x) for x in client_states.errors.shape[1:])
        batch = dict(batch)
        batch["client_ids"] = jnp.arange(W, dtype=jnp.int32)
        store_dir = tempfile.mkdtemp(prefix=f"integrity_{tag}_")
        store = MemmapRowStore(store_dir, n, {"errors": row_shape},
                               mesh=mesh, checksums=checksums,
                               scrub_rows=scrub)
        pf = CohortPrefetcher(store.gather_async)
        rng = np.random.RandomState(7)
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
            jax.block_until_ready(ps_)
            return ps_, ss_, ms

        state = run_rounds(1, ps, server_state, {})  # compile + warm
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            state = run_rounds(iters, *state)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        rps = iters / best
        counts = store.io_counters()
        out[f"integrity_rounds_per_sec_{tag}"] = round(rps, 4)
        out[f"integrity_scrub_checked_{tag}"] = counts["scrub_checked"]
        assert counts["corrupt"] == 0, (
            f"integrity {tag}: clean leg detected corruption — the "
            f"sidecar bookkeeping is wrong")
        finals[tag] = store.read_full("errors")
        _log(f"integrity {tag}: {rps:.2f} rounds/s "
             f"({counts['scrub_checked']} rows scrubbed, "
             f"{counts['corrupt']} corrupt)")
        store.close()
        shutil.rmtree(store_dir, ignore_errors=True)
    off_rps = out["integrity_rounds_per_sec_off"]
    out["integrity_on_idle_vs_off"] = round(
        out["integrity_rounds_per_sec_on_idle"] / off_rps, 4)
    out["integrity_scrub_vs_off"] = round(
        out["integrity_rounds_per_sec_scrub"] / off_rps, 4)
    out["integrity_bit_identical"] = bool(
        np.array_equal(finals["off"], finals["on_idle"])
        and np.array_equal(finals["off"], finals["scrub"]))
    assert out["integrity_bit_identical"], (
        "checksum-on rows diverged from the checksums-off leg — "
        "verification must only READ")
    print(json.dumps(out), flush=True)


def run_async_measurement() -> None:
    """Child-process entry (--run-cfg async): the round-barrier A/B of
    docs/async.md — synchronous vs buffered-async (--async_buffer K)
    server throughput under injected slow clients.

    Six legs: {sync, async K=4} x injected slow probability
    P in {0, 0.1, 0.3}. Client latency is SIMULATED (fast 3 ms, slow
    40 ms per cohort member — the ~13x straggler regime of the FL
    practicality survey, arXiv:2405.20431) because this bench prices the
    server's SCHEDULING semantics, not client compute: the sync plane
    cannot fold round t until its slowest member returns (it sleeps
    max(latency) — the classic barrier), while the async plane folds
    whenever K contributions have landed, so a straggler parks in the
    real ParticipationController pending/buffer machinery
    (hold -> land -> staleness-weighted masked fold, the exact jitted
    helpers cv_train runs) and the server only ever waits for the
    on-time members. Gates (asserted): at P=0.3 the async plane holds
    >= 80% of its own fault-free rate while the sync plane degrades
    >= 2x — plus the conservation invariant contributions == folded +
    async_expired + expired (nothing silently dropped)."""
    from typing import NamedTuple as _NT

    import jax
    import jax.numpy as jnp
    import numpy as np

    from commefficient_tpu.federated import participation as P

    FAST_S, SLOW_S = 0.003, 0.040
    W, K, D, ROUNDS = 8, 4, 500_000, 80
    DELAY = 2  # straggler landing delay (rounds) on both planes

    class SimCtx(_NT):
        gradient: object
        count: object

    @jax.jit
    def _client(model, i):
        # a cohort's already-normalized mean transmit: cheap but real
        # device arithmetic so the fold path runs on-device, not on a
        # python scalar stand-in
        return jnp.sin(model + jnp.float32(i) * 1e-3) * 1e-2

    @jax.jit
    def _apply(model, grad):
        return model - 0.1 * grad

    def run_plane(plane: str, p_slow: float):
        rng = np.random.RandomState(1000 + int(p_slow * 100))
        sched = P.FaultSchedule(slow=p_slow, delay=DELAY, seed=7)
        pc = P.ParticipationController(schedule=sched, decay=0.5,
                                       async_k=(K if plane == "async"
                                                else 0))
        model = jnp.zeros((D,), jnp.float32)
        # warm the jit cache outside the timed region — including the
        # controller's fold helpers (hold -> land -> masked fold), else
        # their compiles land inside the first async leg's timing
        jax.block_until_ready(_apply(model, _client(model, 0)))
        if plane == "async":
            warm = P.ParticipationController(schedule=sched, decay=0.5,
                                             async_k=2)
            warm.hold(P._transmit_sum(_client(model, 0), np.float32(1)),
                      1.0, np.arange(1), 0)
            for j in range(2):
                wctx, wfold, _ = warm.async_step(
                    SimCtx(gradient=_client(model, j), count=None),
                    j + DELAY, sharded=False, count=float(W),
                    ids=np.arange(W))
                if wfold:
                    jax.block_until_ready(wctx.gradient)
        t0 = time.perf_counter()
        for i in range(ROUNDS):
            lat = np.where(rng.random_sample(W) < p_slow, SLOW_S, FAST_S)
            transmit = _client(model, i)
            if plane == "sync":
                # BARRIER: the fold waits for the slowest cohort member
                time.sleep(float(lat.max()))
                model = _apply(model, transmit)
                continue
            # ASYNC: the server waits only for the on-time members; a
            # slow slot's contribution is held (version-tagged) and
            # lands into the buffer DELAY rounds later
            time.sleep(FAST_S)
            n_slow = int((lat > FAST_S).sum())
            if n_slow:
                pc.hold(P._transmit_sum(transmit, np.float32(n_slow)),
                        float(n_slow), np.arange(n_slow), i)
            ctx = SimCtx(gradient=transmit, count=None)
            ctx, fold, _info = pc.async_step(
                ctx, i, sharded=False, count=float(max(W - n_slow, 1)),
                ids=np.arange(W))
            if fold:
                model = _apply(model, ctx.gradient)
        jax.block_until_ready(model)
        dt = time.perf_counter() - t0
        if plane == "async":
            # end-of-run audit, exactly the entrypoints' finally block
            pc.expire_buffer()
            pc.expire_pending()
            assert pc.contributions == (pc.folded + pc.async_expired
                                        + pc.expired), (
                f"async P={p_slow}: conservation violated — "
                f"{pc.contributions} contributions vs {pc.folded} folded "
                f"+ {pc.async_expired} + {pc.expired} expired")
        return ROUNDS / dt, pc

    out = {
        "async_metric": (
            f"dispatches/sec sync vs --async_buffer {K} under injected "
            f"slow clients (P in 0/0.1/0.3; fast {FAST_S * 1e3:g} ms, "
            f"slow {SLOW_S * 1e3:g} ms, {W} members, {ROUNDS} rounds; "
            "docs/async.md)"),
        "platform": jax.default_backend(),
    }
    rates = {}
    for plane in ("sync", "async"):
        for p_slow in (0.0, 0.1, 0.3):
            rps, pc = run_plane(plane, p_slow)
            rates[(plane, p_slow)] = rps
            tag = f"{plane}_slow{p_slow:g}".replace(".", "p")
            out[f"async_rounds_per_sec_{tag}"] = round(rps, 2)
            if plane == "async":
                out[f"async_folds_{tag}"] = pc.folds
                out[f"async_folded_{tag}"] = pc.folded
                out[f"async_expired_{tag}"] = (pc.async_expired
                                               + pc.expired)
            _log(f"async cfg {plane} P={p_slow}: {rps:.1f} rounds/s")
    sync_deg = rates[("sync", 0.0)] / rates[("sync", 0.3)]
    async_keep = rates[("async", 0.3)] / rates[("async", 0.0)]
    out["async_sync_degradation_0p3"] = round(sync_deg, 3)
    out["async_async_retention_0p3"] = round(async_keep, 3)
    # THE acceptance gates (ISSUE 17): the barrier is the bottleneck,
    # removing it is the win
    assert sync_deg >= 2.0, (
        f"sync plane degraded only {sync_deg:.2f}x at P=0.3 — the "
        f"simulated barrier is not binding; raise SLOW_S or ROUNDS")
    assert async_keep >= 0.8, (
        f"async plane kept only {async_keep:.1%} of its fault-free rate "
        f"at P=0.3 — buffered folds are stalling on stragglers")
    print(json.dumps(out), flush=True)


def run_packing_measurement(n_tenants: int = 3, workdir: str = "",
                            gate: float = 1.10):
    """Child-process entry (--run-cfg packing): the multi-tenant
    run-packing A/B of docs/packing.md — N tiny cv_train runs executed
    the way fleets run today (sequentially, each process paying its own
    cold compile against its own fresh cache) vs packed under
    scripts/orchestrate.py (one shared fresh compile cache + cache-warmup
    admission: the first tenant compiles cold and populates the cache,
    the followers are admitted on its first heartbeat and load the same
    executables from disk).

    This leg runs on the CPU backend BY DESIGN (the crash_matrix child
    env): a chip belongs to one process at a time, so tenants cannot
    share one — while the mechanism the speedup comes from (shared-cache
    warm compiles) is identical on both backends; chip_smoke.py reports
    the cold/warm compile pair of the real trainer on the chip.

    Concurrency is host-aware: ``max_concurrent = min(n_tenants,
    cpu_count)``. On a 1-core host the fleet therefore packs
    back-to-back (concurrent tenants on one core pay pure
    context-switch overhead with zero overlap win — measured 0.93x),
    and the ENTIRE speedup is cross-tenant compile-cache sharing:
    follower tenants load the leader's executables from disk instead
    of recompiling. Both legs run with the persistent-cache
    min-compile-time floor at 0 — the tiny geometry's individual jits
    compile in under a second each, so the default 1 s floor would
    cache (and share) almost nothing.

    Gates (asserted in-leg, the ISSUE 18 acceptance criteria):
    aggregate wall-clock speedup >= ``gate`` AND each tenant's final
    fp32 weights bit-identical to its solo sequential baseline."""
    import shutil
    import tempfile

    sys.path.insert(0, os.path.join(_REPO_DIR, "scripts"))
    import crash_matrix as cm
    import orchestrate as orch

    own_workdir = not workdir
    workdir = workdir or tempfile.mkdtemp(prefix="commefficient_packing_")
    data = os.path.join(workdir, "data")
    os.makedirs(data, exist_ok=True)

    def tenant_argv(i: int, ckpt: str) -> list:
        # the crash_matrix tiny geometry (synthetic CIFAR10), trimmed
        # to 1 epoch and differentiated by seed so the fleet is N
        # distinct runs, not N copies of one
        argv = cm.train_argv(data, ckpt, shard=False)
        argv += ["--num_epochs", "1", "--seed", str(i)]  # last flag wins
        return argv

    # --- leg A: today's fleet — N sequential solo runs, fresh cache each
    solo_walls = []
    for i in range(n_tenants):
        ckpt = os.path.join(workdir, f"solo{i}", "ckpt")
        cache = os.path.join(workdir, f"solo{i}", "cache")
        os.makedirs(cache, exist_ok=True)
        # floor 0 in BOTH legs (see docstring): cache-write overhead is
        # paid symmetrically; only the fleet gets to READ across runs
        env = {"JAX_COMPILATION_CACHE_DIR": cache,
               "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
        t0 = time.perf_counter()
        cm.run_to_completion(tenant_argv(i, ckpt), timeout=1800,
                             env_extra=env)
        solo_walls.append(time.perf_counter() - t0)
        _log(f"packing solo tenant {i}: {solo_walls[-1]:.1f}s")

    # --- leg B: the packed fleet (shared fresh cache + warm admission)
    # the orchestrator spawns from ITS process env: force the same
    # sanitized crash_matrix child env the solo legs ran under
    os.environ.update(cm.child_env())
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    # orchestrate() only setdefaults the floor — pin it to match leg A
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    fleet_dir = os.path.join(workdir, "fleet")
    tenants = [tenant_argv(i, os.path.join(fleet_dir, f"t{i}", "ckpt"))
               for i in range(n_tenants)]
    max_concurrent = min(n_tenants, os.cpu_count() or 1)
    t0 = time.perf_counter()
    rc = orch.orchestrate(
        tenants, fleet_dir=fleet_dir, max_concurrent=max_concurrent,
        warm_admission=True, share_cache=True,
        heartbeat_timeout=600.0, startup_grace=1800.0,
        # a restart would silently absorb a crash into the timing — a
        # bench tenant that dies must fail the leg loudly instead.
        # poll tight (50 ms): on a back-to-back 1-core pack every
        # finish->admit transition costs up to 2 poll ticks, and at
        # 0.2 s that overhead ate half the measured cache win
        max_restarts=0, poll=0.05, out=open(os.devnull, "w"))
    packed_wall = time.perf_counter() - t0
    assert rc == 0, f"packed fleet degraded (rc {rc}) — see {fleet_dir}"
    _log(f"packing packed fleet ({n_tenants} tenants): {packed_wall:.1f}s"
         f" vs sequential {sum(solo_walls):.1f}s")

    # --- per-tenant bit-identity: packing must not perturb the math
    for i in range(n_tenants):
        cm.assert_identical(
            cm.final_weights(os.path.join(workdir, f"solo{i}", "ckpt")),
            cm.final_weights(os.path.join(fleet_dir, f"t{i}", "ckpt")),
            f"packing tenant {i} (seed {i}) vs solo baseline")

    speedup = sum(solo_walls) / packed_wall
    out = {
        "packing_metric": (
            f"{n_tenants}-tenant tiny-cv_train fleet: sequential "
            "solo runs (fresh cache each) vs packed under "
            "scripts/orchestrate.py (shared fresh cache, warm "
            "admission, host-aware concurrency; docs/packing.md)"),
        "packing_tenants": n_tenants,
        "packing_max_concurrent": max_concurrent,
        "packing_cpu_count": os.cpu_count() or 1,
        "packing_sequential_s": round(sum(solo_walls), 2),
        "packing_sequential_per_run_s": [round(w, 2) for w in solo_walls],
        "packing_packed_s": round(packed_wall, 2),
        "packing_speedup": round(speedup, 3),
        "packing_bit_identical": True,  # assert_identical above raised
        "platform": "cpu",  # by design; see docstring
    }
    # THE acceptance gate (ISSUE 18): packing the fleet must beat
    # running it sequentially even on one core — the shared-cache warm
    # compiles are the win the admission policy exists to harvest
    assert speedup >= gate, (
        f"packed fleet speedup {speedup:.2f}x < gate {gate:g}x — "
        f"warm admission is not harvesting the shared compile cache "
        f"(sequential {sum(solo_walls):.1f}s, packed {packed_wall:.1f}s)")
    if own_workdir:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return out


def run_serving_measurement(workdir: str = "", gate: float = 1.50,
                            load_interval: float = 0.2):
    """Child-process entry (--run-cfg serving): the serving-interference
    A/B of docs/service.md — one tiny cv_train run solo vs the SAME run
    (same seed) with a live serving replica (scripts/serve.py) tracking
    its checkpoint dir and a query load loop hammering the file queue
    the whole time. The replica is read-only by construction (weights
    loaded from drained snapshots, pin lease instead of file moves), so
    the training trajectory must stay bit-identical; the wall-clock
    ratio prices what the replica's polling + request traffic cost the
    trainer on a shared host.

    CPU by design (the crash_matrix child env, same reasoning as the
    packing leg): the mechanism measured — snapshot-handoff polling,
    pin-lease I/O, request/response file traffic — is identical on both
    backends; tpu_measure.py's ``serving`` leg prices it on silicon.

    Gates (asserted in-leg): final weights bit-identical solo vs
    served; wall-clock ratio <= ``gate``; the replica answered at least
    one query, hot-swapped at least once, and its model_version stream
    (rebuilt from serving.jsonl by obs_report — the report path IS the
    verifier) is monotone."""
    import shutil
    import tempfile
    import threading

    sys.path.insert(0, os.path.join(_REPO_DIR, "scripts"))
    import crash_matrix as cm
    import obs_report

    own_workdir = not workdir
    workdir = workdir or tempfile.mkdtemp(prefix="commefficient_serving_")
    data = os.path.join(workdir, "data")
    os.makedirs(data, exist_ok=True)

    def leg_argv(ckpt: str) -> list:
        argv = cm.train_argv(data, ckpt, shard=False)
        argv += ["--num_epochs", "1"]  # last flag wins
        return argv

    # --- leg A: solo baseline
    solo_ckpt = os.path.join(workdir, "solo", "ckpt")
    t0 = time.perf_counter()
    cm.run_to_completion(leg_argv(solo_ckpt), timeout=1800)
    solo_wall = time.perf_counter() - t0
    _log(f"serving solo leg: {solo_wall:.1f}s")

    # --- leg B: same run with a live replica + query load
    live_ckpt = os.path.join(workdir, "live", "ckpt")
    serve_dir = os.path.join(workdir, "serve")
    stop_file = os.path.join(workdir, "serve.stop")
    os.makedirs(live_ckpt, exist_ok=True)
    replica = subprocess.Popen(
        [sys.executable, os.path.join(_REPO_DIR, "scripts", "serve.py"),
         "--checkpoint_path", live_ckpt, "--serve_dir", serve_dir,
         "--owner", "bench", "--poll_interval", "0.05",
         "--stop_file", stop_file, "--deadline_s", "1800"],
        env=cm.child_env(), cwd=_REPO_DIR, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True)

    from commefficient_tpu.federated.serving import (
        read_response,
        submit_request,
    )

    queries = {"sent": 0, "answered": 0}
    done = threading.Event()

    def load_loop():
        # steady query load for the whole training run — every answer
        # carries the model_version the replica served it from
        seed = 0
        while not done.is_set():
            rid = submit_request(serve_dir, op="query", probe_seed=seed)
            queries["sent"] += 1
            seed += 1
            resp = read_response(serve_dir, rid, timeout=10, poll=0.02)
            if "error" not in resp:
                queries["answered"] += 1
            done.wait(load_interval)

    loader = threading.Thread(target=load_loop, daemon=True)
    loader.start()
    try:
        t0 = time.perf_counter()
        cm.run_to_completion(leg_argv(live_ckpt), timeout=1800)
        live_wall = time.perf_counter() - t0
    finally:
        done.set()
        loader.join(timeout=30)
        with open(stop_file, "w") as f:
            f.write("done")
        try:
            replica.wait(timeout=60)
        except subprocess.TimeoutExpired:
            replica.kill()
    _log(f"serving live leg: {live_wall:.1f}s "
         f"({queries['answered']}/{queries['sent']} queries answered)")

    # the report path IS the verifier: rebuild the replica's story from
    # serving.jsonl alone (docs/service.md acceptance)
    sv = obs_report.summarize(obs_report.load_events(
        os.path.join(serve_dir, "serving.jsonl")))["serving"]
    assert sv is not None, "replica wrote no serving.jsonl events"
    assert sv["answers"] > 0 and queries["answered"] > 0, (
        f"replica answered nothing (log {sv['answers']}, "
        f"client-side {queries['answered']}) — queue or snapshot "
        f"discovery is wedged")
    # error answers are legitimate pre-first-snapshot ("no model yet"),
    # but at least one query must have been served FROM a model
    assert sv["answers"] > sv["errors"], (
        f"every answer was an error ({sv['errors']}/{sv['answers']}) — "
        "the replica never served from a loaded snapshot")
    assert sv["swaps"] >= 1, (
        "replica never hot-swapped a snapshot — checkpoint discovery "
        "is wedged (run saved every 3 rounds)")
    assert sv["versions_monotone"], (
        f"served model_version stream is not monotone: "
        f"swaps {sv['swap_versions']}")

    # serving is read-only: the trained trajectory must not move
    cm.assert_identical(cm.final_weights(solo_ckpt),
                        cm.final_weights(live_ckpt),
                        "serving leg (live replica) vs solo baseline")

    ratio = live_wall / solo_wall
    out = {
        "serving_metric": (
            "tiny cv_train wall-clock solo vs with a live serving "
            "replica (scripts/serve.py: snapshot handoff + pin lease + "
            "file-queue query load every "
            f"{load_interval:g}s; docs/service.md)"),
        "serving_solo_s": round(solo_wall, 2),
        "serving_live_s": round(live_wall, 2),
        "serving_overhead_ratio": round(ratio, 3),
        "serving_queries_sent": queries["sent"],
        "serving_answers": sv["answers"],
        "serving_errors": sv["errors"],
        "serving_qps": sv["qps"],
        "serving_latency_ms_p50": sv["latency_ms_p50"],
        "serving_swaps": sv["swaps"],
        "serving_final_version": sv["final_version"],
        "serving_versions_monotone": True,   # asserted above
        "serving_bit_identical": True,       # assert_identical raised
        "platform": "cpu",  # by design; see docstring
    }
    assert ratio <= gate, (
        f"serving interference {ratio:.2f}x > gate {gate:g}x — the "
        f"replica's polling/IO is stealing too much from the trainer "
        f"(solo {solo_wall:.1f}s, live {live_wall:.1f}s)")
    if own_workdir:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return out


# --------------------------------------------------------------------------
# parent orchestration
# --------------------------------------------------------------------------

def _tpu_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO_DIR + os.pathsep + env.get("PYTHONPATH", "")
    return env


# Tracked in git: the last full headline result, kept as a record.
_TPU_CACHE = os.path.join(_REPO_DIR, ".bench_last_tpu.json")


def _save_tpu_cache(result: dict) -> None:
    """Record a successful TPU measurement. Partial/salvaged results (a
    child that died after printing) must not clobber a clean one."""
    if "partial" in result:
        _log("not caching partial TPU result")
        return
    try:
        with open(_TPU_CACHE, "w") as f:
            json.dump({"measured_at": time.strftime("%Y-%m-%d %H:%M:%S"),
                       "result": result}, f)
    except OSError as e:
        _log(f"could not write TPU result cache: {e}")


# Per-leg result cache for the secondary (extra) measurements (goes with
# the benchmark rewrite, ROADMAP Queue 1 item 0). Tracked in git like
# _TPU_CACHE.
_EXTRAS_CACHE = os.path.join(_REPO_DIR, ".bench_extras.json")

# leg name -> (child argv, env var for its timeout, default timeout s,
#              result key that proves the leg produced its number)
_EXTRA_LEGS = {
    "gpt2_bf16": (["--run-gpt2", "bf16"], "BENCH_GPT2_TIMEOUT", 1500,
                  "gpt2_bf16_tokens_per_sec"),
    "gpt2_f32": (["--run-gpt2", "f32"], "BENCH_GPT2_TIMEOUT", 1500,
                 "gpt2_tokens_per_sec"),
    "c4": (["--run-c4"], "BENCH_C4_TIMEOUT", 900,
           "cifar100_rounds_per_sec"),
    "c1": (["--run-cfg", "c1"], "BENCH_C12_TIMEOUT", 900,
           "c1_rounds_per_sec"),
    "c2": (["--run-cfg", "c2"], "BENCH_C12_TIMEOUT", 900,
           "c2_rounds_per_sec"),
    "shard": (["--run-cfg", "shard"], "BENCH_C12_TIMEOUT", 900,
              "shard_rounds_per_sec"),
    "fused": (["--run-cfg", "fused"], "BENCH_C12_TIMEOUT", 900,
              "fused_rounds_per_sec"),
    "guards": (["--run-cfg", "guards"], "BENCH_C12_TIMEOUT", 900,
               "guards_rounds_per_sec"),
    "stream": (["--run-cfg", "stream"], "BENCH_C12_TIMEOUT", 900,
               "stream_rounds_per_sec"),
    "coalesce": (["--run-cfg", "coalesce"], "BENCH_C12_TIMEOUT", 900,
                 "coalesce_rounds_per_sec"),
    "telemetry": (["--run-cfg", "telemetry"], "BENCH_C12_TIMEOUT", 900,
                  "telemetry_rounds_per_sec"),
    "watch": (["--run-cfg", "watch"], "BENCH_C12_TIMEOUT", 900,
              "watch_rounds_per_sec"),
    "downlink": (["--run-cfg", "downlink"], "BENCH_C12_TIMEOUT", 900,
                 "downlink_rounds_per_sec"),
    "straggler": (["--run-cfg", "straggler"], "BENCH_C12_TIMEOUT", 900,
                  "straggler_rounds_per_sec"),
    # 2D (clients x shard) server plane + per-mesh-axis quantized
    # collectives (docs/multihost.md): needs >= 4 devices
    "multihost": (["--run-cfg", "multihost"], "BENCH_C12_TIMEOUT", 900,
                  "multihost_rounds_per_sec"),
    # million-client host-offload data plane (docs/host_offload.md):
    # rounds/sec vs synthetic population 10^4/10^5/10^6 with disk-tier
    # (sparse memmap) client state streamed through the cohort prefetcher
    "clients_sweep": (["--run-cfg", "clients_sweep"],
                      "BENCH_CLIENTS_TIMEOUT", 1800,
                      "clients_sweep_rounds_per_sec_1e6"),
    # storage-fault plane (docs/fault_tolerance.md §storage faults):
    # disk-tier rounds/sec clean vs injection-idle (gate <= 2%) vs
    # seeded transient faults (bit-identical rows pinned in-leg)
    "io_faults": (["--run-cfg", "io_faults"], "BENCH_C12_TIMEOUT", 900,
                  "io_faults_rounds_per_sec_idle"),
    # integrity plane (docs/fault_tolerance.md §silent corruption):
    # disk-tier rounds/sec checksums-off vs on-idle (gate <= 2%) vs
    # on + background scrub (bit-identical rows pinned in-leg)
    "integrity": (["--run-cfg", "integrity"], "BENCH_C12_TIMEOUT", 900,
                  "integrity_rounds_per_sec_on_idle"),
    # async buffered federation (docs/async.md): sync vs --async_buffer 4
    # dispatches/sec under injected slow clients (P = 0/0.1/0.3) — the
    # round-barrier A/B, gates asserted in-leg (sync degrades >= 2x at
    # P=0.3 while async keeps >= 80% of its fault-free rate)
    "async": (["--run-cfg", "async"], "BENCH_C12_TIMEOUT", 900,
              "async_rounds_per_sec_async_slow0p3"),
}


def _git_head() -> str:
    try:
        out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             cwd=_REPO_DIR, capture_output=True, text=True,
                             timeout=10)
        return out.stdout.strip() or "unknown"
    except (OSError, subprocess.TimeoutExpired):
        return "unknown"


def _load_extras() -> dict:
    try:
        with open(_EXTRAS_CACHE) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}


def _save_extra(leg: str, result: dict) -> None:
    if "partial" in result:
        _log(f"not caching partial {leg} result")
        return
    extras = _load_extras()
    extras[leg] = {"measured_at": time.strftime("%Y-%m-%d %H:%M:%S"),
                   "head": _git_head(), "result": result}
    try:
        with open(_EXTRAS_CACHE, "w") as f:
            json.dump(extras, f, indent=1)
    except OSError as e:
        _log(f"could not write extras cache: {e}")


def _capture_extra(leg: str) -> int:
    """Parent-side one-leg capture (--capture LEG): run the leg's child on
    the TPU env and merge a success into the extras cache. Exit 0 only when
    the leg's defining key landed."""
    result, err = _run_leg(leg)
    if result is None:
        _log(f"leg {leg} failed: {err}")
        return 1
    print(json.dumps({leg: result}), flush=True)
    return 0 if "partial" not in result else 1


def _fresh_or_cached_extras(result: dict,
                            allow_stale: bool = False) -> None:
    """Populate result['extra'] from the per-leg children, falling back to
    the extras cache for any leg that fails. A cache hit younger than
    BENCH_EXTRAS_MAX_AGE (default 12h) AND measured at the current HEAD
    skips the fresh run. A cached leg from a DIFFERENT head is re-run by
    default — a stale number silently mixed two code generations into one
    artifact (BENCH_r05 c2/gpt2 legs); it is only used as the fallback
    when the fresh run fails, clearly marked ``stale_head`` (and listed in
    the artifact's top-level ``"stale"`` list — see below).
    ``allow_stale`` (--allow_stale_cache / BENCH_ALLOW_STALE_CACHE=1) uses
    it without re-running. The cache stamp (measured_at @ head) is copied
    into the artifact so provenance stays explicit. Set
    BENCH_EXTRAS_MAX_AGE=0 to force fresh runs. (The whole result cache
    goes with the benchmark rewrite, ROADMAP Queue 1 item 0.)"""
    max_age = float(os.environ.get("BENCH_EXTRAS_MAX_AGE", 12 * 3600))
    extras_out = {}
    stale_legs = []
    cache = _load_extras()
    head_now = _git_head()

    def _is_stale(cached):
        return cached.get("head") not in (head_now, "unknown", None)

    def _mark_stale(leg, cached):
        # a cached leg measured at a different commit can silently mix two
        # code generations into one artifact — make that explicit, BOTH
        # as the per-leg key and in the artifact's top-level "stale" list
        # (a reader scanning the summary must not mistake a stale leg for
        # a fresh number; the buried extra key alone proved too easy to
        # miss — BENCH_r05's gpt2/c2 legs)
        if _is_stale(cached):
            _log(f"extra leg {leg}: cached head {cached.get('head')} != "
                 f"current {head_now} — marking stale_head")
            extras_out[f"{leg}_stale_head"] = (f"{cached.get('head')} != "
                                               f"{head_now}")
            stale_legs.append(leg)

    for leg in _EXTRA_LEGS:
        cached = cache.get(leg)
        cache_ok = cached is not None and "result" in cached
        if cache_ok and max_age > 0:
            try:
                age = time.time() - time.mktime(
                    time.strptime(cached["measured_at"], "%Y-%m-%d %H:%M:%S"))
            except (ValueError, KeyError):
                age = float("inf")
            if age < max_age and (allow_stale or not _is_stale(cached)):
                _log(f"extra leg {leg}: cache hit ({age / 60:.0f} min old, "
                     f"head {cached.get('head')}) — skipping fresh run")
                extras_out.update(cached["result"])
                extras_out[f"{leg}_cached"] = (f"{cached['measured_at']} @ "
                                               f"{cached.get('head')}")
                _mark_stale(leg, cached)
                continue
            if age < max_age:
                _log(f"extra leg {leg}: cache fresh by age but measured at "
                     f"head {cached.get('head')} != {head_now} — re-running "
                     f"(--allow_stale_cache to use it anyway)")
        fresh, err = _run_leg(leg)
        if fresh is not None:
            extras_out.update(fresh)
        elif cache_ok:
            stamp = (f"{cached.get('measured_at')} @ {cached.get('head')}")
            _log(f"extra leg {leg} failed ({err}); using cached result "
                 f"from {stamp}")
            extras_out.update(cached["result"])
            extras_out[f"{leg}_cached"] = f"{stamp} (fresh: {err})"
            _mark_stale(leg, cached)
        else:
            extras_out[f"{leg}_error"] = err
    result["extra"] = extras_out
    # top-level staleness summary: always present (empty = every reported
    # leg was measured at the current HEAD), so artifact consumers check
    # ONE key instead of grepping extra for *_stale_head suffixes
    result["stale"] = sorted(stale_legs)


def _run_leg(leg: str):
    """The ONE path that runs an extra-leg child, validates it, and banks a
    success in the extras cache. Returns (result, None) or (None, err)."""
    argv, tmo_var, tmo_default, key = _EXTRA_LEGS[leg]
    timeout = float(os.environ.get(tmo_var, tmo_default))
    _log(f"running extra leg {leg} (timeout {timeout:.0f}s)")
    fresh, err = _run_child(argv, _tpu_env(), timeout)
    if fresh is None or key not in fresh:
        return None, err or f"no {key} in child output"
    if fresh.get("platform") != "tpu":
        # the child reports its own backend; a CPU run must never be
        # cached and published as an on-chip number
        return None, f"ran on backend {fresh.get('platform')!r}, not a TPU"
    _save_extra(leg, fresh)
    return fresh, None


def _last_json_line(text):
    for line in reversed((text or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                pass
    return None


def _run_child(argv, env, timeout):
    """Run a child, teeing stderr through, capturing the last stdout JSON
    line. A crash or timeout AFTER the child printed a JSON line still
    salvages that line (children emit incrementally for exactly this), with
    the failure noted alongside."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + argv,
            env=env, cwd=_REPO_DIR, stdout=subprocess.PIPE, stderr=None,
            text=True, timeout=timeout)
        out, failure = proc.stdout, (None if proc.returncode == 0
                                     else f"rc={proc.returncode}")
    except subprocess.TimeoutExpired as e:
        out = e.stdout.decode(errors="replace") \
            if isinstance(e.stdout, bytes) else (e.stdout or "")
        failure = f"timeout after {timeout}s"
    result = _last_json_line(out)
    if result is None:
        return None, failure or "no JSON line in child stdout"
    if failure is not None:
        result["partial"] = failure
    return result, None


def main() -> int:
    probe_timeout = float(os.environ.get("BENCH_PROBE_TIMEOUT", 120))
    run_timeout = float(os.environ.get("BENCH_RUN_TIMEOUT", 2400))
    # escape hatch for the HEAD-mismatch re-run policy (see
    # _fresh_or_cached_extras): accept cached extra legs measured at a
    # different commit instead of re-running them
    allow_stale = ("--allow_stale_cache" in sys.argv[1:]
                   or os.environ.get("BENCH_ALLOW_STALE_CACHE") == "1")

    _log(f"probing TPU backend (timeout {probe_timeout:.0f}s)")
    probe = ("import jax, sys; d = jax.devices(); b = jax.default_backend(); "
             "print('probe', b, d[0].device_kind, len(d), file=sys.stderr); "
             "assert b == 'tpu', f'backend is {b}, not a TPU'")
    tpu_error = None
    try:
        p = subprocess.run([sys.executable, "-c", probe], env=_tpu_env(),
                           cwd=_REPO_DIR, timeout=probe_timeout,
                           capture_output=True, text=True)
        if p.returncode != 0:
            tpu_error = f"probe rc={p.returncode}: {p.stderr.strip()[-500:]}"
    except subprocess.TimeoutExpired:
        tpu_error = f"probe timeout after {probe_timeout:.0f}s (backend init hang)"
    if tpu_error is not None:
        # no chip, no number: nothing on stdout, non-zero exit
        _log(f"TPU unavailable: {tpu_error}")
        return 3

    _log(f"TPU probe OK; running measurement (timeout {run_timeout:.0f}s)")
    result, err = _run_child(["--run"], _tpu_env(), run_timeout)
    if result is None:
        _log(f"tpu run failed: {err}")
        return 1

    # secondary workloads (GPT-2 bf16/f32 = config 5, and the config-4
    # non-IID CIFAR100 round), each in its OWN child with its own timeout so
    # a compile hang, HBM OOM, or hard libtpu abort there can never cost the
    # already-captured headline number; each leg falls back to the per-leg
    # result cache (see _EXTRAS_CACHE).
    _fresh_or_cached_extras(result, allow_stale=allow_stale)
    _save_tpu_cache(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1].startswith("--run"):
        # a measurement child: place the compile cache like the entry
        # points do (the parent never imports jax)
        from commefficient_tpu.utils import configure_compile_cache

        configure_compile_cache()
    if len(sys.argv) >= 2 and sys.argv[1] == "--run":
        run_measurement()
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--run-gpt2":
        sel = sys.argv[2] if len(sys.argv) >= 3 else "both"
        table = {"f32": (False,), "bf16": (True,), "both": (False, True)}
        if sel not in table:
            # a typo silently running BOTH legs would reinstate the exact
            # two-compiles-one-child failure mode the split exists to avoid
            sys.exit(f"--run-gpt2: unknown leg {sel!r}; use f32|bf16|both")
        run_gpt2_measurement(table[sel])
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--run-c4":
        run_config_measurement("cifar100")
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--run-cfg":
        sel = sys.argv[2] if len(sys.argv) >= 3 else "<missing>"
        if sel == "clients_sweep":
            # the disk-tier population sweep has its own round loop (the
            # gather->round->scatter cycle), not a CfgLeg timing
            run_clients_sweep_measurement()
            sys.exit(0)
        if sel == "io_faults":
            # storage-fault-plane overhead A/B (same custom round loop)
            run_io_faults_measurement()
            sys.exit(0)
        if sel == "integrity":
            # integrity-plane overhead A/B: checksums off / on-idle /
            # scrub-active (same custom round loop)
            run_integrity_measurement()
            sys.exit(0)
        if sel == "async":
            # round-barrier A/B: sync vs buffered-async dispatches/sec
            # under injected slow clients (its own simulated-latency
            # loop over the real ParticipationController fold machinery)
            run_async_measurement()
            sys.exit(0)
        if sel == "packing":
            # multi-tenant run-packing A/B: sequential solo runs vs the
            # packed fleet (orchestrate.py shared-cache + warm
            # admission); its own wall-clock loop over real cv_train
            # children, CPU by design (one process per chip claim)
            run_packing_measurement()
            sys.exit(0)
        if sel == "serving":
            # serving-interference A/B: tiny cv_train solo vs with a
            # live serving replica + query load (snapshot handoff, pin
            # lease, file queue); wall-clock over real children, CPU by
            # design (docs/service.md)
            run_serving_measurement()
            sys.exit(0)
        # the allowlist IS the leg table — a hand-maintained copy here
        # silently orphaned the coalesce/straggler captures (their
        # children exited "unknown config" while the parent reported a
        # failed leg)
        if sel not in _CFG_LEGS:
            # a missing/typo'd operand must never fall through to the full
            # parent orchestration and claim the chip for a headline bench
            sys.exit(f"--run-cfg: unknown config {sel!r}; use "
                     + "|".join(sorted(_CFG_LEGS))
                     + "|clients_sweep|io_faults|integrity|async|packing"
                       "|serving")
        run_config_measurement(sel)
        sys.exit(0)
    if len(sys.argv) >= 3 and sys.argv[1] == "--capture":
        sys.exit(_capture_extra(sys.argv[2]))
    sys.exit(main())
