"""GPT-2 PersonaChat federated training entrypoint.

Loop parity with reference gpt2_train.py:115-365: special-token surgery with
embedding resize, per-batch TableLogger rows, download tracking in epoch 1
only, final ``save_pretrained`` + validation pass reporting NLL / MC accuracy
/ perplexity. The model is the flax ``GPT2DoubleHeads``
(commefficient_tpu/models/gpt2.py); pretrained HF weights load when present
locally, else training starts from scratch (zero-egress environment).
"""

from __future__ import annotations

import dataclasses
import math
import os

import numpy as np
import jax
import jax.numpy as jnp

from commefficient_tpu.config import parse_args
from commefficient_tpu.data_utils import FedLoader, PrefetchLoader
from commefficient_tpu.data_utils.fed_persona import (
    FedPERSONA,
    make_personachat_collate_fn,
)
from commefficient_tpu.data_utils.tokenization import (
    ATTR_TO_SPECIAL_TOKEN,
    get_tokenizer,
)
from commefficient_tpu.federated import (
    FedModel,
    FedOptimizer,
    LambdaLR,
    PipelinedRoundEngine,
)
from commefficient_tpu.federated.checkpoint import (
    load_checkpoint,
    load_matching,
    maybe_save_run_state,
    restore_mid_epoch,
)
from commefficient_tpu.federated.losses import (
    make_causal_lm_losses,
    make_gpt2_losses,
)
from commefficient_tpu.federated.run import (
    attach_planes,
    close_run,
    finish_setup,
    population_emptied,
    run_rounds,
    val_pass,
)
from commefficient_tpu.models.gpt2 import (
    GPT2DoubleHeads,
    load_hf_gpt2,
    resize_token_embeddings,
)
from commefficient_tpu.models.joyai import JoyAIConfig, JoyAIFlash
from commefficient_tpu.models.laguna import LagunaConfig, LagunaXS2
from commefficient_tpu.models.ouro import Ouro, OuroConfig
from commefficient_tpu.ops.attention import GQA_PLAN, PATH_CALLS
from commefficient_tpu.profiling import begin_setup, phase
from commefficient_tpu.utils import (
    PiecewiseLinear,
    TableLogger,
    Timer,
    announce_devices,
    configure_compile_cache,
    is_tpu_backend,
    make_logdir,
    union,
)


def get_data_loaders(args, tokenizer, emit_shifted=False):
    train_dataset = FedPERSONA(
        tokenizer, args.num_candidates, args.max_history,
        args.personality_permutations,
        args.dataset_dir, args.dataset_name, None, args.do_iid,
        args.num_clients, train=True, download=True,
        max_seq_len=args.max_seq_len)
    val_dataset = FedPERSONA(
        tokenizer, -1, args.max_history, 1,
        args.dataset_dir, args.dataset_name, None, train=False,
        download=False, max_seq_len=args.max_seq_len)
    # val candidates vary; collate pads to the train candidate count for
    # static shapes
    n_cand_val = max(args.num_candidates, 3)
    train_loader = FedLoader(
        train_dataset, args.num_workers, args.local_batch_size,
        collate_fn=_wrap(make_personachat_collate_fn(
            args.max_seq_len, args.num_candidates,
            emit_shifted=emit_shifted)))
    val_loader = FedLoader(
        val_dataset,
        val_batch_size=args.valid_batch_size * args.num_workers,
        collate_fn=_wrap(make_personachat_collate_fn(
            args.max_seq_len, n_cand_val, emit_shifted=emit_shifted)))
    if args.train_dataloader_workers > 0:
        train_loader = PrefetchLoader(train_loader)
    if args.val_dataloader_workers > 0:
        val_loader = PrefetchLoader(val_loader)
    return train_loader, val_loader


def _wrap(collate):
    # FedLoader hands items as tuples of the post-client-id columns
    return lambda items: collate(items)


def report_attention_core(model):
    """Which path the attention's core (latent or grouped-query) was traced
    on in this process and how many calls took it (ops/attention.py
    ``PATH_CALLS``), and by kind of grouped-query layer the kernels' tile
    walk and where q and k were turned and the heads gated (``GQA_PLAN``):
    printed, and a ``model`` event in the run's log; a recurrent stack's
    passes, layers and block applications a step beside it (``loop``).
    Said once a run, when its first round has been dispatched, so the
    round's own programs are among the traces counted, not the
    initialisation's alone."""
    if getattr(model, "attention_core_reported", False):
        return
    model.attention_core_reported = True
    attn_path = "fused" if PATH_CALLS["fused"] else "einsum"
    print(f"attention core: {attn_path} path, "
          f"{PATH_CALLS[attn_path]} calls traced (ops/attention.py)"
          + "".join(
              f"; {kind} layers"
              + (f" visit {p['key_tiles_visited']} of "
                 f"{p['key_tiles_causal']} causal key tiles of {p['tile']}, "
                 if "tile" in p else " ")
              + (f"turn and gate: {p['turn_and_gate']}"
                 if "turn_and_gate" in p
                 else f"turn: {p['turn']}, gate: {p['gate']}")
              for kind, p in sorted(GQA_PLAN.items())))
    # a decoder whose stack is a recurrence says how it was traced
    # (models/ouro.py ``loop_plan``): its own event, ``loop``
    loop = getattr(model.model.cfg, "loop_plan", None)
    if loop:
        print("recurrence: " + ", ".join(f"{k} {v}" for k, v in loop.items()))
    rt = getattr(model, "telemetry", None)
    if rt is not None:
        rt.event("model", attn_path=attn_path,
                 attn_calls=PATH_CALLS[attn_path],
                 **({"attn_plan": dict(GQA_PLAN)} if GQA_PLAN else {}))
        if loop:
            rt.event("loop", **loop)


def run_batches(model, opt, lr_scheduler, loader, args, timer, training,
                epoch=None, epoch_fraction=1, logger=None, writer=None,
                resume_mid=None, totals=(0.0, 0.0)):
    model.train(training)
    if training:
        spe = loader.steps_per_epoch()
        num_clients = loader.dataset.num_clients
        client_download = np.zeros(num_clients)
        client_upload = np.zeros(num_clients)
        losses = []
        # round-granular resume (docs/fault_tolerance.md): sampler position
        # replayed, partial epoch accumulators reloaded, loop indices offset
        # by the rounds done
        i0, ex = restore_mid_epoch(resume_mid, loader, client_download,
                                   client_upload)
        losses.extend(np.asarray(ex.get("losses", [])).tolist())
        # Pipelined round engine (federated/engine.py): rounds are
        # dispatched sync-free and metrics arrive in batches of
        # --metrics_drain_every, so logger rows are appended at drain time.
        # Per-row train_time is the drain interval divided over its rounds
        # (the per-round value no longer exists — fetching it every round
        # is exactly the blocking sync the engine removes); loss and byte
        # values are identical to per-round fetching (tests/test_engine.py).
        engine = PipelinedRoundEngine(
            model, opt, lr_scheduler,
            window=getattr(args, "round_window", 2),
            drain_every=getattr(args, "metrics_drain_every", 8))
        meta_by_round = {}

        def consume(results):
            nonlocal client_download, client_upload
            if not results:
                return
            interval = timer()
            for res in results:
                # a loss's named metric sums ride between the loss and the
                # byte counts; the event log keeps them (telemetry "model")
                loss, *_, download, upload = res.values
                client_download += download
                client_upload += upload
                loss = float(np.mean(loss))
                losses.append(loss)
                row_batch_idx, row_lr = meta_by_round.pop(res.index)
                batch_stats = {
                    "train_time": interval / len(results),
                    "train_loss": loss,
                    "total_time": timer.total_time,
                    "down (MiB)": round(download.sum() / (1024 * 1024)),
                    "up (MiB)": round(upload.sum() / (1024 * 1024)),
                }
                if logger is not None:
                    logger.append(
                        union({"batch_idx": row_batch_idx, "lr": row_lr},
                              batch_stats))

        def submitted(rounds_done):
            if engine.rounds_submitted == 1 and args.arch in DECODERS:
                report_attention_core(model)
            # the scheduler stepped inside submit(); record this round's
            # batch index and LR so its drained row logs what it ran with
            meta_by_round[engine.rounds_submitted - 1] = (
                rounds_done, lr_scheduler.get_last_lr()[0])

        run_rounds(
            engine, loader, args, epoch=epoch or 0, i0=i0, spe=spe,
            epoch_fraction=epoch_fraction, totals=totals, consume=consume,
            extras=lambda: {"download": client_download,
                            "upload": client_upload,
                            "losses": np.asarray(losses, np.float64)},
            skip=(lambda i: 2 < i < spe - 10) if args.do_test else None,
            submitted=submitted)
        if population_emptied(model, losses):
            return None, client_download, client_upload
        return np.mean(losses), client_download, client_upload

    nlls, accs = [], []
    spe = len(loader)
    with val_pass(model):
        for batch_idx, batch in enumerate(loader):
            if batch_idx > 5 and args.do_test and batch_idx < spe - 5:
                continue
            nll, acc = model(batch)
            nlls.append(float(np.mean(nll)))
            accs.append(float(np.mean(acc)))
    return np.mean(nlls), np.mean(accs), np.exp(np.mean(nlls))


def test_gpt2(model, val_loader, args, logger=None, timer=None, writer=None):
    timer = timer or Timer()
    nll, acc, ppl = run_batches(model, None, None, val_loader, args, timer,
                                training=False, logger=TableLogger())
    stats = {"val_nll": nll, "val_acc": acc, "val_ppl": ppl,
             "val_time": timer(), "total_time": timer.total_time}
    (logger or TableLogger()).append(stats)
    return stats


def train_gpt2(model, opt, scheduler, train_loader, val_loader, args,
               log_dir, writer=None, logger=None, timer=None, start_epoch=0,
               totals=(0.0, 0.0), resume_mid=None):
    timer = timer or Timer()
    total_download, total_upload = totals
    for epoch in range(start_epoch, math.ceil(args.num_epochs)):
        if epoch == math.ceil(args.num_epochs) - 1:
            epoch_fraction = args.num_epochs - epoch
        else:
            epoch_fraction = 1
        train_loss, download, upload = run_batches(
            model, opt, scheduler, train_loader, args, timer, training=True,
            epoch=epoch, epoch_fraction=epoch_fraction, logger=logger,
            writer=writer,
            resume_mid=(resume_mid if epoch == start_epoch else None),
            totals=(total_download, total_upload))
        if train_loss is None:
            print("ending training: live population is empty with no "
                  "pending joiners (--churn open-world end state)")
            break
        if epoch == 0:
            # download tracking valid in epoch 1 only (reference
            # gpt2_train.py:132-145)
            total_download += download.sum() / (1024 * 1024)
            total_upload += upload.sum() / (1024 * 1024)
        maybe_save_run_state(args, epoch, model, opt, scheduler,
                             (total_download, total_upload))
    print(f"Total Download (MiB): {total_download:0.2f} (only epoch 1)")
    print(f"Total Upload (MiB): {total_upload:0.2f} (only epoch 1)")
    n = train_loader.dataset.num_clients
    print(f"Avg Download Per Client: {total_download / n:0.2f} (only epoch 1)")
    print(f"Avg Upload Per Client: {total_upload / n:0.2f} (only epoch 1)")
    model.save_pretrained(log_dir)
    return test_gpt2(model, val_loader, args, timer=timer, writer=writer)


# --arch: the configuration and its decoder (models/joyai.py,
# models/laguna.py, models/ouro.py)
DECODERS = {"joyai_llm_flash": (JoyAIConfig, JoyAIFlash),
            "laguna_xs2": (LagunaConfig, LagunaXS2),
            "ouro_2p6b": (OuroConfig, Ouro)}


def build_decoder(args, tiny):
    """The --arch decoder and its causal-LM losses, cut as the flags say:
    the layers held, the vocabulary's rows and, where the configuration has
    routed experts, this chip's of --layer_chips that share a layer."""
    config, decoder = DECODERS[args.arch]
    full = config.tiny() if tiny else config()
    cut = dict(layers=args.arch_layers or full.layers,
               vocab_rows=args.vocab_rows or max(full.vocab_rows,
                                                 args.len_tokenizer))
    if full.routed:
        assert full.routed % args.layer_chips == 0, \
            f"--layer_chips must divide {full.routed} routed experts"
        # the unit rounds a float32 product's multiplicands to bfloat16 at
        # the default precision; XLA:TPU does not do so to a grouped
        # product, so the expert layer is told to (parallel/moe.py
        # _grouped_dot)
        rounds = (is_tpu_backend()
                  and jax.config.jax_default_matmul_precision is None)
        cut.update(expert_operand_dtype=jnp.bfloat16 if rounds else None,
                   experts_held=full.routed // args.layer_chips,
                   expert_offset=args.expert_offset)
    else:
        assert args.layer_chips == 1 and args.expert_offset == 0, (
            f"--arch {args.arch} has no routed experts to share out: its "
            "cut is in depth alone (--layer_chips 1, --expert_offset 0)")
    cfg = dataclasses.replace(full, **cut)
    assert args.len_tokenizer <= cfg.vocab_rows, (
        f"the tokenizer's {args.len_tokenizer} ids do not fit the "
        f"{cfg.vocab_rows} rows of the vocabulary held")
    model = decoder(cfg)
    return (model,) + make_causal_lm_losses(model)


def train(argv=None):
    from commefficient_tpu.parallel.mesh import maybe_init_distributed

    # join a multi-process cohort (supervise.py --procs N env seam) BEFORE
    # the first jax.devices() call, so the mesh sees the global device set
    maybe_init_distributed()
    args = parse_args(default_lr=4e-2, argv=argv)
    configure_compile_cache()
    announce_devices()
    if not args.dataset_name:
        args.dataset_name = "PERSONA"
    print(args)
    timer = Timer()

    # sequence parallelism (--seq_parallel ring|ulysses): attention runs
    # over the global sequence sharded across the mesh's `seq` axis.
    # Tensor parallelism (--model_devices N): heads/hidden sharded over a
    # `model` axis. The two COMPOSE for ring attention (a clients x seq x
    # model mesh: heads over `model`, tokens over `seq`); ulysses is
    # excluded (validate_args). Both derive from the REALIZED mesh: the
    # policy warns and degrades to fewer axes on small hosts, and the
    # model must not reference an axis the mesh lacks.
    from commefficient_tpu.parallel.mesh import default_client_mesh

    mesh = default_client_mesh(
        args.num_workers, args.num_devices,
        seq_devices=(args.seq_devices if args.seq_parallel != "none" else 1),
        model_devices=args.model_devices,
        pipeline_devices=args.pipeline_devices,
        expert_devices=(args.expert_devices if args.n_experts else 1),
        n_experts=args.n_experts)
    sp = args.seq_parallel != "none" and "seq" in mesh.axis_names
    tp = "model" in mesh.axis_names
    pp = "stage" in mesh.axis_names
    ep = "expert" in mesh.axis_names
    if args.seq_parallel != "none" and not sp:
        print(f"--seq_parallel {args.seq_parallel} disabled: "
              f"mesh has no seq axis ({dict(mesh.shape)})")
        args.seq_parallel = "none"
    if args.expert_devices > 1 and not ep:
        print(f"--expert_devices {args.expert_devices} disabled: "
              f"mesh has no expert axis ({dict(mesh.shape)})")
        args.expert_devices = 1
    # start-up's phases (profiling.py): `import` ends here, process start
    # to the devices announced and laid out as a mesh
    begin_setup()

    with phase("data"):
        tokenizer = get_tokenizer(args.model_checkpoint)
        print(f"tokenizer: {type(tokenizer).__name__} "
              f"(vocab {len(tokenizer)})")
        tokenizer.add_special_tokens(ATTR_TO_SPECIAL_TOKEN)
        args.len_tokenizer = len(tokenizer)
        train_loader, val_loader = get_data_loaders(args, tokenizer,
                                                    emit_shifted=sp)

    # --finetune points the MODEL load at a previously saved run dir while
    # the tokenizer stays that of the base checkpoint (reference
    # gpt2_train.py:270-273); the run itself is then eval-only (see below)
    if args.do_finetune and not args.do_test:
        args.model_checkpoint = args.finetune_path

    with phase("model"):
        geometry = dict(attn_impl=args.seq_parallel) if sp else {}
        if tp:
            geometry["model_axis"] = "model"
        if args.n_experts:
            # MoE GPT-2 (--n_experts N): every other block gets a
            # Switch-style MoE MLP; with --expert_devices the experts shard
            # over the `expert` mesh axis (parallel/moe.py)
            geometry["n_experts"] = args.n_experts
            geometry["moe_dispatch"] = args.moe_dispatch
            geometry["moe_capacity_factor"] = args.moe_capacity_factor
            if ep:
                geometry["expert_axis"] = "expert"

        # model geometry: tiny when smoke-testing or using the byte fallback
        tiny = args.do_test or os.environ.get("COMMEFFICIENT_TINY_MODEL")
        if args.arch in DECODERS:
            model, compute_loss_train, compute_loss_val = build_decoder(
                args, tiny)
        elif tiny:
            # COMMEFFICIENT_TINY_LAYERS: tests exercising layer-pattern
            # constraints (e.g. MoE pipeline stage alignment) need more
            # depth
            model = GPT2DoubleHeads(vocab_size=max(512, args.len_tokenizer),
                                    n_positions=args.max_seq_len, n_embd=64,
                                    n_layer=int(os.environ.get(
                                        "COMMEFFICIENT_TINY_LAYERS", 2)),
                                    n_head=2, **geometry)
        else:
            model = GPT2DoubleHeads(vocab_size=max(50257 + 5,
                                                   args.len_tokenizer),
                                    n_positions=1024, **geometry)
        if sp and args.seq_parallel == "ulysses":
            assert model.n_head % args.seq_devices == 0, \
                "ulysses needs n_head divisible by --seq_devices"
        if tp:
            nm = mesh.shape["model"]  # realized size, possibly reduced
            assert model.n_head % nm == 0, \
                f"--model_devices (realized {nm}) must divide n_head"
            assert (4 * model.n_embd) % nm == 0, (
                f"--model_devices (realized {nm}) must divide the MLP "
                "hidden dim")
        if ep:
            ne = mesh.shape["expert"]  # realized size, possibly reduced
            assert args.n_experts % ne == 0, \
                f"--expert_devices (realized {ne}) must divide --n_experts"
        if args.arch == "gpt2" and pp:
            # pipeline parallelism (--pipeline_devices): the loss callbacks
            # carry the GPipe schedule (parallel/pipeline.py); the model
            # object itself stays the plain dense one
            n_stages = mesh.shape["stage"]  # realized size, possibly reduced
            assert model.n_layer >= n_stages, (
                f"--pipeline_devices (realized {n_stages}) must be <= "
                "n_layer")
            from commefficient_tpu.parallel.pipeline import (
                make_gpt2_pp_losses,
            )

            compute_loss_train, compute_loss_val = make_gpt2_pp_losses(
                model, n_stages, n_micro=args.pp_microbatches,
                lm_coef=args.lm_coef, mc_coef=args.mc_coef,
                compute_dtype=jnp.bfloat16 if args.do_bf16 else None,
                moe_aux_coef=args.moe_aux_coef if args.n_experts else 0.0)
        elif args.arch == "gpt2":
            compute_loss_train, compute_loss_val = make_gpt2_losses(
                model, args.lm_coef, args.mc_coef,
                seq_axis="seq" if sp else None,
                compute_dtype=jnp.bfloat16 if args.do_bf16 else None,
                moe_aux_coef=args.moe_aux_coef if args.n_experts else 0.0)

        # try local pretrained weights (reference loads from the hub,
        # gpt2_train.py:262-273)
        x0 = {
            "input_ids": jnp.zeros(
                (1, args.num_candidates, args.max_seq_len), jnp.int32),
        }
        # init with a non-parallel twin: same parameter structure, but
        # usable outside shard_map (ring/ulysses need the `seq` axis bound;
        # TPDense needs the `model` axis bound)
        init_model = model
        if sp:
            init_model = init_model.copy(attn_impl="dense")
        if tp:
            init_model = init_model.copy(model_axis=None)
        if ep:
            init_model = init_model.copy(expert_axis=None)
        if args.arch == "gpt2":
            variables = init_model.init(
                jax.random.key(args.seed), x0["input_ids"],
                token_type_ids=x0["input_ids"],
                mc_token_ids=jnp.zeros((1, args.num_candidates), jnp.int32),
                train=False)
        else:
            variables = jax.jit(init_model.init)(jax.random.key(args.seed),
                                                 x0["input_ids"][0])
        init_params = variables["params"]
        pretrained = (load_hf_gpt2(init_params, args.model_checkpoint)
                      if args.arch == "gpt2" else None)
        if pretrained is not None:
            init_params = resize_token_embeddings(pretrained,
                                                  args.len_tokenizer)
            print("loaded local pretrained GPT-2 weights")
        elif os.path.exists(os.path.join(args.model_checkpoint,
                                         "model.npz")):
            # a run dir this framework saved (save_pretrained → model.npz):
            # the finetune round trip, since HF-format checkpoints are
            # rarely present in the zero-egress environment
            ckpt_params, _ = load_checkpoint(
                os.path.join(args.model_checkpoint, "model"))
            init_params, loaded, skipped = load_matching(init_params,
                                                         ckpt_params)
            assert loaded > 0, (
                f"--finetune checkpoint {args.model_checkpoint} shares no "
                f"tensor shapes with the current model geometry "
                f"(COMMEFFICIENT_TINY_MODEL / --max_seq_len "
                f"mismatch?) — refusing to silently train from scratch")
            print(f"loaded saved run dir: {loaded} tensors, "
                  f"fresh: {len(skipped)}")

        args.num_results_train = 1
        args.num_results_val = 2
        # hand the seed's weights over and keep no name on them: from here
        # on they live in fed_model's flat vector alone, and a caller that
        # puts its own weights in their place (the benchmark) does not hold
        # both trees
        handover = [init_params]
        del variables, init_params, pretrained

    with phase("fed"):
        fed_model = FedModel(model, compute_loss_train, args,
                             compute_loss_val,
                             num_clients=train_loader.dataset.num_clients,
                             init_params=handover.pop(), mesh=mesh)
        opt = FedOptimizer(fed_model, args)

    planes = None
    with phase("planes"):
        spe = train_loader.steps_per_epoch()
        print("Steps per epoch", spe)
        lr_schedule = PiecewiseLinear([0, args.num_epochs * spe],
                                      [args.lr_scale, 0.0])
        scheduler = LambdaLR(opt, lr_lambda=lambda s: lr_schedule(s))

        log_dir = make_logdir(args)
        if os.environ.get("COMMEFFICIENT_RUN_DIR"):
            # orchestrated tenant (scripts/orchestrate.py,
            # docs/packing.md): the run dir — and with it telemetry.jsonl
            # + trace_round_* captures — is pinned per tenant so fleet
            # neighbors never collide
            print(f"run dir pinned by orchestrator: {log_dir} (tenant "
                  f"{os.environ.get('COMMEFFICIENT_TENANT_ID', '?')})",
                  flush=True)
        os.makedirs(log_dir, exist_ok=True)
        tokenizer.save_pretrained(log_dir)
        if not args.do_finetune:
            planes, start_epoch, totals, resume_mid = attach_planes(
                args, fed_model, opt, scheduler, train_loader, log_dir,
                "gpt2_train")
    finish_setup(planes)

    if args.do_finetune:
        # --finetune is the reference's eval-only path: load the saved run
        # (above) and run validation, no training (reference
        # gpt2_train.py:308-309)
        stats = test_gpt2(fed_model, val_loader, args, logger=TableLogger(),
                          timer=timer)
    else:
        try:
            stats = train_gpt2(fed_model, opt, scheduler, train_loader,
                               val_loader, args, log_dir,
                               logger=TableLogger(), timer=timer,
                               start_epoch=start_epoch, totals=totals,
                               resume_mid=resume_mid)
        finally:
            close_run(planes)
    if args.do_finetune:
        fed_model.finalize()
    return stats


if __name__ == "__main__":
    train()
