"""CLI / config surface.

Flag-for-flag parity with the reference CLI (reference utils.py:102-230): same
names, dests, choices and defaults, so recipes written against the reference
drive this framework unchanged. TPU-specific deviations, all documented here:

- ``--device`` accepts ``{tpu, cpu}`` (auto-detected default) instead of
  ``{cuda, cpu}``.
- ``--num_devices`` means the size of the JAX device mesh the round is
  shard_map'ed over (default: all visible devices), not "number of GPUs"; there
  is no parameter-server device, so ``--share_ps_gpu`` is accepted and ignored.
- ``--port`` is accepted for compatibility but unused: there is no NCCL
  process group to rendezvous (the collective is an XLA ``psum`` over ICI).

``parse_args`` also enforces the reference's fedavg invariants
(reference utils.py:225-228).
"""

from __future__ import annotations

import argparse
import os

MODES = ["sketch", "true_topk", "local_topk", "fedavg", "uncompressed"]
ERROR_TYPES = ["none", "local", "virtual"]
DP_MODES = ["worker", "server"]


def parse_inject_fault(spec: str):
    """``--inject_fault`` spec → {round_index: poison_value}. The spec is
    'ROUND:KIND[,ROUND:KIND...]' with KIND in {nan, inf}; a malformed spec
    fails here at parse time, not rounds into a run."""
    values = {"nan": float("nan"), "inf": float("inf")}
    out = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            rnd, kind = part.split(":")
            rnd = int(rnd)
        except ValueError:
            raise ValueError(
                f"--inject_fault: bad entry {part!r}; expected ROUND:KIND "
                f"(e.g. '5:nan' or '2:nan,7:inf')") from None
        assert kind in values, (
            f"--inject_fault: unknown kind {kind!r}; use nan|inf")
        assert rnd >= 0, f"--inject_fault: round {rnd} must be >= 0"
        out[rnd] = values[kind]
    return out


def _model_names():
    from commefficient_tpu import models

    return [m for m in dir(models) if not m.startswith("__") and m[0].isupper()]


def _dataset_names():
    from commefficient_tpu.data_utils import fed_datasets

    return list(fed_datasets.keys())


def build_parser(default_lr=None) -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser()

    # meta-args
    parser.add_argument("--test", action="store_true", dest="do_test")
    # TPU mixed precision (no reference equivalent — the reference trains
    # f32): bf16 forward/backward on the MXU, f32 master weights and
    # compression/server math (federated/losses.py compute_dtype).
    parser.add_argument("--bf16", action="store_true", dest="do_bf16")
    parser.add_argument("--mode", choices=MODES, default="sketch")
    parser.add_argument("--tensorboard", dest="use_tensorboard", action="store_true")
    # jax.profiler trace window (replaces the reference's commented cProfile
    # scaffolding, fed_aggregator.py:32-52): a profiling.RoundTracer window
    # over rounds 2 … 2+profile_steps-1, written to profile_dir
    parser.add_argument("--profile", action="store_true", dest="do_profile")
    parser.add_argument("--profile_dir", type=str, default="profiles")
    parser.add_argument("--profile_steps", type=int, default=3)
    parser.add_argument("--seed", type=int, default=21)

    # data/model args
    parser.add_argument("--model", default="ResNet9", choices=_model_names(),
                        help="Name of the model.")
    parser.add_argument("--finetune", action="store_true", dest="do_finetune")
    parser.add_argument("--checkpoint", action="store_true", dest="do_checkpoint")
    parser.add_argument("--checkpoint_path", type=str, default="./checkpoint")
    # mid-run resume (no reference equivalent — its checkpointing is
    # save-only, reference cv_train.py:418-421; SURVEY.md §5): save the FULL
    # run state every N epochs, restart from it bit-exactly
    parser.add_argument("--checkpoint_every", type=int, default=0,
                        help="Save full run state every N epochs (0 = off).")
    # Preemption-safe round-granular resume (docs/fault_tolerance.md): save
    # the full run state — including the FedSampler position and partial
    # epoch metrics — every N rounds mid-epoch, so a SIGKILL'd run resumed
    # with --resume auto loses at most N rounds and reproduces the
    # uninterrupted fp32 trajectory bit-exactly.
    parser.add_argument("--checkpoint_every_rounds", type=int, default=0,
                        help="Save full run state every N rounds mid-epoch "
                             "(0 = off; engine in-flight window is drained "
                             "before each save).")
    parser.add_argument("--resume", type=str, default="",
                        help="Path of a run-state checkpoint to resume "
                             "from, or 'auto' to pick the newest VALID "
                             "run_state*.npz under --checkpoint_path "
                             "(corrupt/truncated files are skipped).")
    parser.add_argument("--keep_checkpoints", type=int, default=0,
                        help="Retain only the newest N run_state*.npz under "
                             "--checkpoint_path, pruning older ones after "
                             "each save (0 = keep all; existing workflows "
                             "unchanged).")
    parser.add_argument("--state_dir", type=str, default="",
                        help="Backing directory for disk-tier per-client "
                             "state (the sparse memory-mapped row store, "
                             "docs/host_offload.md). Default: a "
                             "client_state/ directory under "
                             "--checkpoint_path. Only used when the "
                             "memory plan resolves the disk placement "
                             "tier.")
    parser.add_argument("--finetune_path", type=str, default="./finetune")
    parser.add_argument("--finetuned_from", type=str, choices=_dataset_names(),
                        help="Name of the dataset you pretrained on.")
    parser.add_argument("--num_results_train", type=int, default=2)
    parser.add_argument("--num_results_val", type=int, default=2)
    parser.add_argument("--dataset_name", type=str, default="",
                        choices=_dataset_names() + [""])
    parser.add_argument("--dataset_dir", type=str, default="./dataset")
    parser.add_argument("--batchnorm", action="store_true", dest="do_batchnorm")
    parser.add_argument("--nan_threshold", type=float, default=999)

    # compression args
    parser.add_argument("--k", type=int, default=50000)
    parser.add_argument("--num_cols", type=int, default=500000)
    parser.add_argument("--num_rows", type=int, default=5)
    parser.add_argument("--num_blocks", type=int, default=20)
    parser.add_argument("--topk_down", action="store_true", dest="do_topk_down")

    # optimization args
    parser.add_argument("--local_momentum", type=float, default=0.9)
    parser.add_argument("--virtual_momentum", type=float, default=0)
    parser.add_argument("--weight_decay", type=float, default=5e-4)
    parser.add_argument("--num_epochs", type=float, default=24)
    parser.add_argument("--num_fedavg_epochs", type=int, default=1)
    parser.add_argument("--fedavg_batch_size", type=int, default=-1)
    parser.add_argument("--fedavg_lr_decay", type=float, default=1)
    parser.add_argument("--error_type", choices=ERROR_TYPES, default="none")
    parser.add_argument("--lr_scale", type=float, default=default_lr)
    parser.add_argument("--pivot_epoch", type=float, default=5)

    # parallelization args
    parser.add_argument("--port", type=int, default=5315,
                        help="Unused on TPU (kept for CLI compatibility).")
    parser.add_argument("--num_clients", type=int)
    parser.add_argument("--num_workers", type=int, default=1,
                        help="Clients sampled per round (reference semantics).")
    parser.add_argument("--device", type=str, choices=["cpu", "tpu"], default=None,
                        help="Platform; default = whatever JAX auto-detects.")
    parser.add_argument("--num_devices", type=int, default=-1,
                        help="Mesh size; -1 = all visible JAX devices.")
    parser.add_argument("--share_ps_gpu", action="store_true",
                        help="Unused on TPU (no separate PS device).")
    # Pipelined round engine (federated/engine.py, docs/round_engine.md):
    # the training loops dispatch rounds without blocking host transfers,
    # bound host run-ahead to --round_window dispatched-but-incomplete
    # rounds, and fetch metrics in batches of --metrics_drain_every.
    parser.add_argument("--round_window", type=int, default=2,
                        help="Max rounds dispatched ahead of device "
                             "completion (pipelined round engine).")
    # Sharded server data plane (docs/sharded_server.md): reduce-scatter
    # the round transmit over the worker mesh axis, run the server update
    # per-shard (velocity/error/top-k on the local slice, threshold via a
    # psum'd count exchange), all-gather only the result. fp32
    # trajectories are bit-identical to the replicated path; per-chip
    # server FLOPs/HBM drop ~n_devices.
    parser.add_argument("--server_shard", action="store_true",
                        dest="server_shard",
                        help="Shard the server aggregation/update over the "
                             "worker mesh axis (reduce-scatter -> per-"
                             "shard update -> all-gather).")
    # 2D server plane (docs/multihost.md): factor the worker axis into
    # (clients, shard) so the server reduce composes per mesh level — on a
    # multi-host DCN x ICI mesh the leading 'clients' axis spans processes
    # and 'shard' stays intra-host, letting --collective_plan pick a wire
    # dtype per axis (cheap ICI leg exact, expensive DCN leg quantized).
    parser.add_argument("--shard_devices", type=int, default=1,
                        help="Devices on the intra-host 'shard' server "
                             "axis of the 2D (clients x shard) mesh; 1 = "
                             "the flat 1D worker axis. Requires "
                             "--server_shard (the shard axis only carries "
                             "the sharded server plane).")
    parser.add_argument("--reduce_dtype", choices=["float32", "int8"],
                        default="float32",
                        help="LEGACY alias of --collective_plan: int8 sets "
                             "EVERY wire leg to the block-scaled "
                             "stochastic-rounding quantized collectives "
                             "(the full-compressed round, ~4x fewer ICI "
                             "bytes) with residuals carried in server "
                             "error feedback; requires --server_shard.")
    # Per-leg collective plan (docs/compressed_collectives.md): choose the
    # wire dtype of each collective leg independently — uplink (dense
    # transmit reduce), table (sketch-table exchange), downlink (update
    # all-gather) — from {fp32, int8, fp8_e4m3, int4}. Quantized legs run
    # the block-scaled stochastic-rounding error-feedback collectives
    # (ops/collectives.py) with the un-transmitted remainder carried in
    # ServerState.qres (uplink/table) / ServerState.dres (downlink) and
    # folded into the next round — compensated, not lossy. 'auto' runs a
    # one-time on-chip probe at startup that times each {leg x dtype}
    # candidate and picks the cheapest within an error budget.
    parser.add_argument("--collective_plan", type=str, default="",
                        help="Per-leg wire dtypes: 'leg=dtype,...' over "
                             "legs {uplink,table,downlink} and dtypes "
                             "{fp32,int8,fp8_e4m3,int4} (unnamed legs stay "
                             "fp32), one bare dtype for every leg, or "
                             "'auto' (one-time on-chip probe picks the "
                             "cheapest dtype per leg within "
                             "--plan_error_budget). A leg value may also "
                             "pick a dtype PER MESH AXIS as slash-joined "
                             "'axis:dtype' pairs — axis is a mesh axis "
                             "name or the placement alias ici/dcn (e.g. "
                             "table=ici:fp32/dcn:int8 quantizes only the "
                             "cross-host level; docs/multihost.md). Empty "
                             "= derive from --reduce_dtype. Quantized "
                             "legs require --server_shard.")
    parser.add_argument("--plan_error_budget", type=float, default=0.05,
                        help="Relative L2 round-trip error budget per leg "
                             "for --collective_plan auto (a candidate "
                             "dtype is admissible iff its calibration "
                             "error is within this).")
    # Fused server epilogue (docs/fused_epilogue.md): one Pallas megakernel
    # replaces the composed threshold-mask + re-sketch d-plane sweeps of
    # sketch mode's server step (both the replicated and --server_shard
    # planes). fp32 bit-identical to the composed path; env kill-switch
    # COMMEFFICIENT_FUSED_EPILOGUE=0 restores composed without a restartable
    # flag change.
    parser.add_argument("--fused_epilogue", action="store_true",
                        dest="fused_epilogue",
                        help="Fuse sketch mode's server epilogue "
                             "(estimates->threshold mask->update emit->"
                             "re-sketch) into one kernel pass over the "
                             "d-plane (sketch mode only; composed path "
                             "stays the default and the reference).")
    parser.add_argument("--metrics_drain_every", type=int, default=8,
                        help="Fetch per-round metrics in batches of N "
                             "rounds; 1 restores per-round (blocking) "
                             "metric fetching.")
    parser.add_argument("--iid", action="store_true", dest="do_iid")
    parser.add_argument("--train_dataloader_workers", type=int, default=0)
    parser.add_argument("--val_dataloader_workers", type=int, default=0)
    # Sequence/context parallelism (TPU-first extension; the reference's only
    # sequence-scaling lever is microbatching, SURVEY.md §5). The mesh gains a
    # second `seq` axis of size --seq_devices; activations are sharded over it
    # and attention runs exactly over the global sequence (parallel/ring.py,
    # parallel/ulysses.py).
    parser.add_argument("--seq_parallel", choices=["none", "ring", "ulysses"],
                        default="none",
                        help="Sequence-parallel attention over a `seq` mesh "
                             "axis (GPT-2 only).")
    parser.add_argument("--seq_devices", type=int, default=2,
                        help="Size of the seq mesh axis when --seq_parallel "
                             "is enabled.")
    # Tensor parallelism (TPU-first extension, GPT-2 only): Megatron-style
    # head/hidden sharding over a third `model` mesh axis with two psums
    # per block; composes with the clients axis (not with --seq_parallel
    # yet). Parameters stay full-shape/replicated, so the federated flat
    # vector, compression, and checkpoints are unchanged.
    parser.add_argument("--model_devices", type=int, default=1,
                        help="Size of the `model` (tensor-parallel) mesh "
                             "axis for GPT-2 (1 disables).")
    # Pipeline parallelism (TPU-first extension, GPT-2 only): GPipe-style
    # contiguous layer ranges over a `stage` mesh axis, microbatched clock
    # schedule with ppermute activation hops (parallel/pipeline.py).
    # Parameters stay full-shape/replicated, like --model_devices.
    parser.add_argument("--pipeline_devices", type=int, default=1,
                        help="Size of the `stage` (pipeline-parallel) mesh "
                             "axis for GPT-2 (1 disables).")
    parser.add_argument("--pp_microbatches", type=int, default=4,
                        help="GPipe microbatches per client batch when "
                             "--pipeline_devices > 1 (auto-reduced to a "
                             "divisor of the batch).")
    # Mixture-of-Experts + expert parallelism (TPU-first extension, GPT-2
    # only; parallel/moe.py): --n_experts > 0 gives every other transformer
    # block a top-1-routed (Switch-style) MoE MLP; --expert_devices shards
    # the experts over an `expert` mesh axis. Parameters stay full-shape/
    # replicated like --model_devices, so compression and checkpoints are
    # unchanged.
    parser.add_argument("--n_experts", type=int, default=0,
                        help="Experts per MoE MLP for GPT-2 (0 = dense "
                             "MLPs, the reference architecture). NOTE: "
                             "dispatch is dense for parity/static shapes — "
                             "each MoE block computes all n_experts/"
                             "expert_devices local experts per token, so an "
                             "MoE block costs that many full MLP passes; "
                             "there is no sparse-MoE FLOP saving unless "
                             "expert_devices == n_experts.")
    parser.add_argument("--expert_devices", type=int, default=1,
                        help="Size of the `expert` (expert-parallel) mesh "
                             "axis for GPT-2 MoE (1 disables).")
    parser.add_argument("--moe_dispatch", choices=["dense", "sparse"],
                        default="dense",
                        help="MoE token dispatch: 'dense' evaluates every "
                             "expert on every token (no drops, max FLOPs); "
                             "'sparse' is GShard/Switch capacity-factor "
                             "dispatch — each expert processes at most "
                             "round(capacity_factor*N/E) tokens, overflow "
                             "tokens skip the MoE layer (residual "
                             "passthrough).")
    parser.add_argument("--moe_capacity_factor", type=float, default=1.25,
                        help="Per-expert token capacity multiplier for "
                             "--moe_dispatch sparse.")
    parser.add_argument("--moe_aux_coef", type=float, default=0.01,
                        help="Switch load-balancing auxiliary loss "
                             "coefficient for MoE GPT-2 (0 disables; only "
                             "meaningful with --n_experts > 0). The aux is "
                             "the mean over MoE layers of the per-token "
                             "Switch balance term, weighted per example. "
                             "Note the Switch paper SUMS per-layer auxes; "
                             "the mean here (a deliberate deviation) makes "
                             "the effective per-layer weight "
                             "coef/n_moe_layers, so retune rather than "
                             "assuming published values transfer.")
    # The decoder gpt2_train.py builds (models/joyai.py for the DeepSeek-V3
    # family, models/laguna.py, models/ouro.py, models/kimi_linear.py) and
    # the cut of it held here: one chip's share of a deployment in which
    # --layer_chips chips share each layer.
    parser.add_argument("--arch",
                        choices=["gpt2", "joyai_llm_flash", "laguna_xs2",
                                 "ouro_2p6b", "kimi_linear"],
                        default="gpt2",
                        help="gpt2_train.py's model: GPT-2 double heads, "
                             "JoyAI-LLM-Flash (MLA, sigmoid top-8 routed "
                             "experts with a shared expert, causal-LM "
                             "loss), Laguna-XS.2 (window-512 and full "
                             "grouped-query layers with 64 / 48 heads over "
                             "8, two RoPEs, gated head outputs, the same "
                             "kind of expert layer without its selection "
                             "bias), or Ouro-2.6B (a dense stack of "
                             "sandwich-norm layers run four times on shared "
                             "weights, a head and an exit gate after every "
                             "pass, the expected-exit loss; no routed "
                             "experts: --layer_chips stays 1), or "
                             "Kimi-Linear (Kimi Delta Attention, a gated "
                             "delta rule with a decay per channel, in three "
                             "layers of four and NoPE latent attention in "
                             "the fourth; the routed experts of "
                             "JoyAI-LLM-Flash's kind).")
    parser.add_argument("--arch_layers", type=int, default=0,
                        help="Layers held, leading dense layer included "
                             "(0 = all the architecture has).")
    parser.add_argument("--layer_chips", type=int, default=1,
                        help="Chips that share each layer in the deployment "
                             "this chip stands for: it holds n_routed_experts"
                             "/layer_chips experts of every expert layer.")
    parser.add_argument("--expert_offset", type=int, default=0,
                        help="First routed expert held here.")
    parser.add_argument("--vocab_rows", type=int, default=0,
                        help="Rows of the vocabulary held here: ids, logits "
                             "and loss are over the slice (0 = all).")
    # TPU-first extension: dropout/DP mask PRNG. threefry (JAX default) is
    # counter-based ALU work; rbg uses the TPU hardware RNG and is much
    # cheaper at GPT-2 mask volumes. unsafe_rbg additionally relaxes
    # fold_in/split guarantees (fastest; fine for dropout).
    parser.add_argument("--rng_impl",
                        choices=["threefry2x32", "rbg", "unsafe_rbg"],
                        default="threefry2x32",
                        help="PRNG implementation for training randomness "
                             "(dropout/DP noise).")
    # Failure-simulation extension (SURVEY §5: the reference has no client
    # dropout/elasticity): each sampled client independently misses the
    # round with this probability; deterministic in --seed, resume-safe.
    parser.add_argument("--client_dropout", type=float, default=0.0,
                        help="Per-round probability that a sampled client "
                             "drops out (0 disables).")
    # Straggler- and dropout-tolerant participation layer
    # (federated/participation.py, docs/fault_tolerance.md §client
    # faults): partial per-round cohorts through FedSampler, seeded
    # client-level drop/slow/corrupt fault injection with graceful
    # degradation (requeue / staleness-weighted late landing /
    # client-level quarantine). Full participation with no faults is
    # bit-identical to the pre-participation trajectories.
    parser.add_argument("--participation", type=str, default="",
                        help="Per-round cohort as a fraction of "
                             "--num_workers in (0,1] or an absolute client "
                             "count; unused worker slots are zero-masked "
                             "and the data-weighted round mean makes the "
                             "missing clients an exact reweighting. Empty "
                             "= full participation (bit-identical legacy "
                             "path).")
    parser.add_argument("--participation_sampling",
                        choices=["uniform", "weighted", "stratified"],
                        default="uniform",
                        help="Cohort draw for --participation: uniform "
                             "(legacy np.random.choice), weighted "
                             "(probability ~ remaining items), or "
                             "stratified (one pick per remaining-size "
                             "stratum).")
    parser.add_argument("--inject_client_fault", type=str, default="",
                        help="Debug: seeded per-client fault schedule "
                             "'drop=P,slow=P,corrupt=P,delay=N,seed=N,"
                             "quarantine_after=N' — per round each live "
                             "slot independently drops (items requeued "
                             "with bounded retries), straggles (transmit "
                             "lands delay rounds late with the staleness "
                             "decay), or is corrupted (masked out BEFORE "
                             "the round sum — the guard never trips; "
                             "repeat offenders are client-quarantined).")
    parser.add_argument("--staleness_decay", type=float, default=0.5,
                        help="Late-landing weight w(delta) = decay**delta "
                             "for straggler cohorts landing delta rounds "
                             "late (1.0 = undecayed).")
    parser.add_argument("--client_retry_limit", type=int, default=3,
                        help="Max requeues per client per epoch for "
                             "dropped-client data before the drop is "
                             "abandoned (participation layer).")
    # Open-world population churn (federated/participation.py,
    # docs/service.md): clients register and depart mid-run; the sampler
    # draws from the LIVE population only, and on the disk state tier the
    # row store allocates/retires/compacts rows to track it. Off =
    # closed population, bit-identical legacy path (parity row A22).
    parser.add_argument("--churn", type=str, default="",
                        help="Seeded population-churn schedule "
                             "'join=R,depart=R,init=F,seed=N,compact=N': "
                             "R = expected clients per round (Poisson "
                             "draws), init = fraction registered at "
                             "round 0, compact = disk-tier hole count "
                             "that triggers checkpoint-time row-store "
                             "compaction. Empty = closed population "
                             "(docs/service.md).")
    # Asynchronous buffered federation (docs/async.md): remove the round
    # barrier — cohorts dispatch continuously and the server folds a
    # buffered update whenever K contributions have landed (FedBuff,
    # arXiv:2106.06639), each contribution staleness-weighted by the
    # EXACT number of server folds it missed (w(Δ) = --staleness_decay**Δ
    # with Δ = server_version_at_fold - version_read). Off (0) keeps the
    # synchronous path bit-identical.
    parser.add_argument("--async_buffer", type=int, default=0,
                        help="Buffered-asynchronous federation: fold a "
                             "server update whenever K contributions have "
                             "landed instead of once per dispatch; "
                             "contributions carry exact model-version "
                             "staleness and fold with w(delta) = "
                             "--staleness_decay**delta. 0 (default) = "
                             "synchronous rounds (bit-identical legacy "
                             "path).")
    # Zero-sync telemetry plane (docs/observability.md): on-device round
    # metrics computed inside the jitted server phase (norms of the
    # transmit / update / error-feedback carries, resolved top-k
    # threshold, guard detail) ride the batched metric drain into a
    # structured per-run JSONL event log with round-lifecycle spans
    # (dispatch -> window wait -> drain, in-flight occupancy). ON by
    # default. On the device it costs, histograms included, 0.26 ms of a
    # 72 ms round in resnet9_sketch_1c, 7.7 of 79 ms in gpt2_sketch_1c and
    # 9.1 of 49 ms in gpt2_uncompressed_1c, where a dozen reductions sweep
    # d = 124M (my chip run, PR 27; PERF.md section 5 — the per-layer
    # metric telemetry_device_ms reads it). The fp32 trajectory is
    # bit-identical either way (tests/test_telemetry.py). Render the log
    # with scripts/obs_report.py.
    parser.add_argument("--telemetry", action="store_true", dest="telemetry",
                        default=True,
                        help="Per-round on-device metrics + JSONL run "
                             "event log (docs/observability.md; the "
                             "default).")
    parser.add_argument("--no_telemetry", action="store_false",
                        dest="telemetry",
                        help="Disable the telemetry plane (bit-identical "
                             "trajectories either way).")
    # Schema-v3 distribution telemetry (docs/observability.md): fixed-K
    # log-magnitude histograms of the emitted update and the error carry
    # appended to the on-device metrics vector — online threshold-drift /
    # sketch-estimation-fidelity visibility scalar norms cannot give.
    # Same non-perturbation contract (bit-identical trajectories on/off).
    parser.add_argument("--telemetry_hist", action="store_true",
                        dest="telemetry_hist", default=True,
                        help="Append the schema-v3 log-magnitude "
                             "histogram block (emitted update + error "
                             "carry) to the on-device round metrics "
                             "(the default with telemetry on).")
    parser.add_argument("--no_telemetry_hist", action="store_false",
                        dest="telemetry_hist",
                        help="Drop the histogram block (12-field v2 "
                             "metric schema; bit-identical trajectories "
                             "either way).")
    # Watch/alert rule engine (docs/observability.md §watch plane):
    # declarative threshold + EWMA-drift rules evaluated over the drained
    # metric stream at zero extra host syncs, emitting immediate
    # watch_alert JSONL events with a reaction ladder (log / windowed
    # trace capture of the next N rounds / forced run-state checkpoint).
    parser.add_argument("--watch", action="store_true", dest="watch",
                        default=True,
                        help="Evaluate watch rules over the drained "
                             "metric stream (the default with telemetry "
                             "on; alerts land as watch_alert events).")
    parser.add_argument("--no_watch", action="store_false", dest="watch",
                        help="Disable the watch/alert plane.")
    parser.add_argument("--watch_rules", type=str, default="",
                        help="Watch rules 'METRIC{>|<}BOUND[@N]"
                             "[->log|trace[:R]|checkpoint]' joined by "
                             "','; BOUND a float or ewma*F (drift vs the "
                             "metric's own EWMA). Empty = the default "
                             "rule set (loss divergence, carry blowups, "
                             "resolved-k collapse, occupancy drop, "
                             "prefetch miss storm, rounds/sec "
                             "regression).")
    # Round-scoped trace capture (docs/observability.md §trace capture):
    # windowed jax.profiler captures addressed by GLOBAL round_no —
    # aimable at an absolute round instead of a loop index, landing in
    # <run_dir>/trace_round_<N> with a trace_captured JSONL event.
    parser.add_argument("--trace_rounds", type=str, default="",
                        help="Windowed round-aligned profiler capture(s) "
                             "'START:COUNT[,START:COUNT...]' over global "
                             "round_no; traces land in the run dir named "
                             "by the start round.")
    # On-device health guards + quarantine (docs/fault_tolerance.md): a
    # scalar finiteness/magnitude verdict per round, riding the batched
    # metric drain (zero extra host syncs). A tripped round's contribution
    # — INCLUDING its error-feedback carry — is discarded on device the
    # same round; repeated trips roll back to a device-resident snapshot
    # and eventually abort with a clear error.
    parser.add_argument("--guards", action="store_true", dest="guards",
                        help="Enable per-round on-device health guards: "
                             "non-finite (or over-magnitude) rounds are "
                             "quarantined without touching (velocity, "
                             "error) and training continues.")
    parser.add_argument("--guard_max_abs", type=float, default=0.0,
                        help="Magnitude guard: trip when any updated PS "
                             "weight exceeds this absolute value "
                             "(0 = finiteness-only).")
    parser.add_argument("--snapshot_every", type=int, default=64,
                        help="Refresh the device-resident last-good server "
                             "snapshot every N healthy drained rounds "
                             "(guards only; 0 disables rollback).")
    parser.add_argument("--max_guard_trips", type=int, default=3,
                        help="Consecutive guard trips before aborting with "
                             "a fatal error (guards only).")
    # Storage-fault tolerance (docs/fault_tolerance.md §storage faults):
    # the disk-tier row store's I/O plane — seeded fault injection at the
    # pread/pwrite seam, a bounded retry/backoff ladder, a per-op
    # watchdog deadline, row-level quarantine, and a bounded work queue.
    # Transient faults below the retry/deadline budget are invisible to
    # the fp32 trajectory (retried I/O lands identical bytes).
    parser.add_argument("--inject_io_fault", type=str, default="",
                        help="Debug: seeded storage-fault schedule "
                             "'eio=P,short=P,torn=P,stall=P,stall_ms=N,"
                             "seed=N,persist_after=N' injected at the "
                             "disk-tier row store's pread/pwrite seam — "
                             "transient EIO / short reads / torn writes "
                             "are retried (bit-invisible below the "
                             "budget), stalls exercise the watchdog, and "
                             "a row failing persist_after consecutive "
                             "attempts is quarantined (re-initialized "
                             "from its base row).")
    parser.add_argument("--io_retries", type=int, default=3,
                        help="Bounded retries per row-store I/O op "
                             "(exponential backoff + jitter) before the "
                             "ladder degrades to row quarantine.")
    parser.add_argument("--io_backoff_ms", type=float, default=5.0,
                        help="Base backoff between row-store I/O retries "
                             "(doubles per attempt, jittered).")
    parser.add_argument("--io_deadline_ms", type=float, default=30000.0,
                        help="Per-op watchdog deadline for row-store I/O: "
                             "a pread/pwrite in flight longer than this "
                             "declares the store unusable with one "
                             "actionable timeout error instead of "
                             "wedging the worker silently (0 disables "
                             "the watchdog).")
    parser.add_argument("--io_queue_bound", type=int, default=0,
                        help="Row-store work-queue bound (ops): a slow "
                             "disk applies backpressure to the dispatch "
                             "path instead of accumulating unbounded "
                             "pending scatter deltas in host RAM. 0 = "
                             "auto (max(8, 4 x --round_window)).")
    # Integrity plane (docs/fault_tolerance.md §silent corruption): one
    # CRC32 per (member, row) in a sidecar array, recorded on every row
    # write and verified on every row read — the fault class the retry
    # ladder cannot see (corruption that never errors: bit rot, a
    # silently-lying torn write, --inject_io_fault flip/storn) becomes a
    # detected, counted, repaired-or-quarantined event. Verification
    # only reads, so the clean-path fp32 trajectory is bit-identical
    # checksums on/off (tests/test_integrity.py); overhead on the chip
    # not measured (no cell runs the disk tier).
    parser.add_argument("--io_checksums", action="store_true",
                        dest="io_checksums", default=True,
                        help="Per-row CRC32 verification of the disk-"
                             "tier row store: every row read checks a "
                             "write-time sidecar checksum; mismatches "
                             "repair from the CRC'd .rows snapshot or "
                             "quarantine (the default for the disk "
                             "tier).")
    parser.add_argument("--no_io_checksums", action="store_false",
                        dest="io_checksums",
                        help="Disable per-row checksums (bit-identical "
                             "trajectories on the clean path either "
                             "way; COMMEFFICIENT_IO_CHECKSUMS=0 is the "
                             "no-restart kill-switch).")
    parser.add_argument("--io_scrub_rows", type=int, default=0,
                        help="Background scrub budget: verify this many "
                             "cold rows per round against the checksum "
                             "sidecar on the store's ordered I/O worker "
                             "(rolling cursor over the population), so "
                             "corruption in rows no cohort touches is "
                             "found and repaired before the next "
                             "snapshot inherits it (0 = off; requires "
                             "--io_checksums).")
    # Fault-injection debug hook (tests/test_fault_tolerance.py): poison
    # the aggregated transmit of the given dispatch round(s) so guard
    # detection/quarantine is testable end-to-end.
    parser.add_argument("--inject_fault", type=str, default="",
                        help="Debug: 'ROUND:KIND[,ROUND:KIND...]' with KIND "
                             "in {nan,inf} — overwrite one element of that "
                             "round's aggregated transmit with the value "
                             "before the server phase.")

    # GPT2 args
    parser.add_argument("--model_checkpoint", type=str, default="gpt2")
    parser.add_argument("--num_candidates", type=int, default=2)
    parser.add_argument("--max_history", type=int, default=2)
    parser.add_argument("--local_batch_size", type=int, default=8)
    parser.add_argument("--valid_batch_size", type=int, default=8)
    parser.add_argument("--microbatch_size", type=int, default=-1)
    parser.add_argument("--lm_coef", type=float, default=1.0)
    parser.add_argument("--mc_coef", type=float, default=1.0)
    parser.add_argument("--max_grad_norm", type=float)
    parser.add_argument("--personality_permutations", type=int, default=1)
    # TPU deviation: the reference pads each batch to the model max on the
    # fly (fed_persona.py:360-392); XLA wants static shapes, so the pad
    # length is a flag. COMMEFFICIENT_GPT2_SEQ_LEN is the deprecated
    # round-1/2 env spelling, kept as the default's fallback.
    parser.add_argument("--max_seq_len", type=int,
                        default=int(os.environ.get(
                            "COMMEFFICIENT_GPT2_SEQ_LEN", 256)),
                        help="GPT-2 static sequence length (pad/left-"
                             "truncate PersonaChat examples to this).")
    parser.add_argument("--eval_before_start", action="store_true")

    # Differential Privacy args
    parser.add_argument("--dp", action="store_true", dest="do_dp")
    parser.add_argument("--dp_mode", choices=DP_MODES, default="worker")
    parser.add_argument("--l2_norm_clip", type=float, default=1.0)
    parser.add_argument("--noise_multiplier", type=float, default=0.0)

    return parser


def validate_args(args):
    if args.mode == "fedavg":
        assert args.local_batch_size == -1, "fedavg requires local_batch_size == -1"
        assert args.local_momentum == 0, "fedavg requires local_momentum == 0"
        assert args.error_type == "none", "fedavg requires error_type == none"
    if args.seq_parallel != "none":
        assert args.max_seq_len % args.seq_devices == 0, (
            f"--max_seq_len {args.max_seq_len} must divide by "
            f"--seq_devices {args.seq_devices}")
    assert 0.0 <= args.client_dropout < 1.0, (
        f"--client_dropout {args.client_dropout} must be in [0, 1)")
    if args.checkpoint_every_rounds:
        assert args.train_dataloader_workers == 0, (
            "--checkpoint_every_rounds needs --train_dataloader_workers 0: "
            "a prefetch thread draws batches (and augmentation randomness) "
            "ahead of the training loop, so the saved sampler/RNG position "
            "would not match the rounds actually applied")
    assert args.max_guard_trips >= 1, "--max_guard_trips must be >= 1"
    assert args.snapshot_every >= 0, "--snapshot_every must be >= 0"
    # participation layer (federated/participation.py): fail fast on a
    # malformed spec — not rounds into a run
    assert 0.0 < args.staleness_decay <= 1.0, (
        f"--staleness_decay {args.staleness_decay} must be in (0, 1]")
    assert args.client_retry_limit >= 0, (
        "--client_retry_limit must be >= 0")
    # async buffered federation (docs/async.md): fail fast on a malformed
    # buffer size, and document the interactions that change meaning
    assert getattr(args, "async_buffer", 0) >= 0, (
        f"--async_buffer {args.async_buffer} must be >= 0 (0 = "
        f"synchronous rounds)")
    if getattr(args, "async_buffer", 0):
        print(f"async buffered federation: fold every "
              f"{args.async_buffer} landed contribution(s), "
              f"w(Δ)={args.staleness_decay:g}**Δ exact-version staleness "
              f"(docs/async.md); buffered dispatches fold the TRANSMIT "
              f"only — client carries advance on fold dispatches")
    if getattr(args, "participation", ""):
        from commefficient_tpu.federated.participation import (
            parse_participation,
        )

        parse_participation(args.participation, args.num_workers)
    fault_spec = (getattr(args, "inject_client_fault", "") or "").strip()
    if fault_spec:
        from commefficient_tpu.federated.participation import (
            parse_client_fault,
        )

        sched = parse_client_fault(fault_spec)
        assert args.train_dataloader_workers == 0, (
            "--inject_client_fault needs --train_dataloader_workers 0: "
            "dropped clients requeue into the live sampler epoch, and a "
            "prefetch thread would have drawn rounds past the requeue "
            "point (same constraint as --checkpoint_every_rounds)")
        if sched.slow and (args.local_momentum > 0
                           or args.error_type == "local"
                           or args.do_topk_down):
            print("NOTE: straggler late landings fold the TRANSMIT only — "
                  "per-client velocity/error/stale-weight state does not "
                  "advance for a straggler cohort "
                  "(docs/fault_tolerance.md)")
    churn_spec = (getattr(args, "churn", "") or "").strip()
    if churn_spec:
        from commefficient_tpu.federated.participation import parse_churn

        parse_churn(churn_spec)
        assert args.train_dataloader_workers == 0, (
            "--churn needs --train_dataloader_workers 0: the sampler "
            "steps the churn clock in-order on the main thread, and a "
            "prefetch thread would have drawn rounds past the churn "
            "point (same constraint as --inject_client_fault)")
    # continuous-observability surface (docs/observability.md): fail fast
    # on malformed watch-rule / trace-window specs, not rounds into a run
    if getattr(args, "watch_rules", ""):
        from commefficient_tpu.telemetry import parse_watch_rules

        rules = parse_watch_rules(args.watch_rules)
        if any(r.action == "checkpoint" for r in rules) \
                and args.train_dataloader_workers > 0:
            print("NOTE: a watch 'checkpoint' reaction needs "
                  "--train_dataloader_workers 0 for a resumable save "
                  "(same constraint as --checkpoint_every_rounds); the "
                  "reaction will be skipped with a message")
    if getattr(args, "trace_rounds", ""):
        from commefficient_tpu.profiling import parse_trace_rounds

        parse_trace_rounds(args.trace_rounds)
    # storage-fault plane (host_state.MemmapRowStore,
    # docs/fault_tolerance.md §storage faults): fail fast on a malformed
    # spec or a nonsensical ladder, not rounds into a run
    io_spec = (getattr(args, "inject_io_fault", "") or "").strip()
    if io_spec:
        from commefficient_tpu.federated.host_state import parse_io_fault

        parse_io_fault(io_spec)
    assert args.io_retries >= 0, "--io_retries must be >= 0"
    assert args.io_backoff_ms >= 0, "--io_backoff_ms must be >= 0"
    assert args.io_deadline_ms >= 0, "--io_deadline_ms must be >= 0"
    assert args.io_queue_bound >= 0, "--io_queue_bound must be >= 0"
    assert args.io_scrub_rows >= 0, "--io_scrub_rows must be >= 0"
    if args.io_scrub_rows and not args.io_checksums:
        print("NOTE: --io_scrub_rows verifies rows against the per-row "
              "checksum sidecar; with --no_io_checksums there is nothing "
              "to verify and the scrub is inert")
    if args.inject_fault:
        parse_inject_fault(args.inject_fault)  # fail fast on a bad spec
        if not args.guards:
            print("NOTE: --inject_fault without --guards will poison the "
                  "run with nothing to catch it (intentional only for "
                  "demonstrating the failure mode)")
    if args.reduce_dtype == "int8":
        assert args.server_shard, (
            "--reduce_dtype int8 quantizes the transmit reduce of the "
            "sharded server plane; it requires --server_shard")
    plan_spec = (getattr(args, "collective_plan", "") or "").strip()
    if plan_spec:
        assert args.reduce_dtype == "float32", (
            "--collective_plan and --reduce_dtype int8 both name wire "
            "dtypes; use --collective_plan alone (the int8 alias equals "
            "--collective_plan int8)")
        if plan_spec == "auto":
            assert args.server_shard, (
                "--collective_plan auto probes the quantized collectives "
                "of the sharded server plane; it requires --server_shard")
        else:
            from commefficient_tpu.ops.collectives import (
                parse_collective_plan,
            )

            # fail at parse time, not rounds into a run
            plan = parse_collective_plan(plan_spec)
            if plan.quantized:
                assert args.server_shard, (
                    "quantized --collective_plan legs require "
                    "--server_shard (the block-scaled collectives live on "
                    "the sharded server plane)")
    assert args.plan_error_budget > 0, (
        "--plan_error_budget must be > 0")
    assert getattr(args, "shard_devices", 1) >= 1, (
        "--shard_devices must be >= 1")
    if getattr(args, "shard_devices", 1) > 1:
        assert args.server_shard, (
            "--shard_devices factors the server reduce into the 2D "
            "(clients x shard) mesh; the shard axis only carries the "
            "sharded server plane, so it requires --server_shard")
    if args.server_shard:
        assert not args.do_topk_down, (
            "--server_shard is incompatible with --topk_down (stale-"
            "weight reconstruction lives on dense per-client rows)")
    assert args.model_devices >= 1, "--model_devices must be >= 1"
    if args.model_devices > 1:
        assert args.seq_parallel in ("none", "ring"), (
            "--model_devices > 1 composes only with --seq_parallel ring "
            "(ring attention is per-head; ulysses all-to-alls the head "
            "dim over the seq axis, conflicting with model-axis head "
            "slicing)")
    assert args.pipeline_devices >= 1, "--pipeline_devices must be >= 1"
    assert args.pp_microbatches >= 1, "--pp_microbatches must be >= 1"
    assert args.n_experts >= 0, "--n_experts must be >= 0"
    assert args.expert_devices >= 1, "--expert_devices must be >= 1"
    if args.expert_devices > 1:
        assert args.n_experts > 0, "--expert_devices > 1 requires --n_experts"
        assert args.n_experts % args.expert_devices == 0, (
            f"--n_experts {args.n_experts} must divide by "
            f"--expert_devices {args.expert_devices}")
    if args.arch != "gpt2":
        assert args.layer_chips >= 1 and args.expert_offset >= 0 \
            and args.arch_layers >= 0 and args.vocab_rows >= 0
        # its expert layer routes all clients' tokens of a microbatch as one
        # axis (losses.make_causal_lm_losses over_clients): the round's
        # fused-gradient client phase, not a vmap of per-client gradients
        assert (args.mode in ("sketch", "uncompressed", "true_topk")
                and args.local_momentum == 0 and args.error_type != "local"
                and not args.do_dp and not args.do_topk_down
                and args.max_grad_norm is None and not args.do_test), (
            f"--arch {args.arch} runs in the fused-gradient client phase "
            "only: --mode sketch|uncompressed|true_topk without per-client "
            "momentum, error, clipping, DP or --topk_down")
        assert (args.seq_parallel == "none" and args.model_devices == 1
                and args.pipeline_devices == 1 and not args.n_experts
                and not args.do_bf16), (
            f"--arch {args.arch} has no seq/model/stage/expert axis, no "
            "--n_experts and no --bf16 path yet")
    if args.device:
        # --device X sets jax_platforms to X before the backend
        # initializes. After initialization the update has no effect, so
        # say so instead of running on the wrong device without a word
        # (FedModel additionally refuses --device tpu on a non-TPU
        # backend). The private probe is allowed to raise if jax moves it.
        import jax
        from jax._src import xla_bridge

        if not xla_bridge.backends_are_initialized():
            jax.config.update("jax_platforms", args.device)
        elif jax.default_backend() != args.device:
            print(f"--device {args.device} ignored: JAX backend already "
                  f"initialized on {jax.default_backend()!r}")
    return args


def parse_args(default_lr=None, argv=None):
    args = build_parser(default_lr).parse_args(argv)
    return validate_args(args)
