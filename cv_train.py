"""CV federated training entrypoint (CIFAR10/100, EMNIST, ImageNet).

CLI- and loop-parity with the reference cv_train.py:85-421: same flags, same
epoch structure (PiecewiseLinear LR peaking at ``--pivot_epoch``, NaN abort,
per-epoch TableLogger rows, byte totals), same model_config construction
(1-channel EMNIST stems, ``--test`` shrinkage, Fixup per-group LRs, finetune
head swap). The execution engine underneath is the jitted SPMD round of
``commefficient_tpu.federated`` instead of worker processes.
"""

from __future__ import annotations

import math
import os

import numpy as np
import jax
import jax.numpy as jnp

from commefficient_tpu import models
from commefficient_tpu.config import parse_args
from commefficient_tpu.data_utils import (
    FedCIFAR10,
    FedCIFAR100,
    FedEMNIST,
    FedImageNet,
    FedLoader,
    PrefetchLoader,
    num_classes_of_dataset,
    transforms,
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
    save_checkpoint,
)
from commefficient_tpu.federated.losses import make_cv_losses
from commefficient_tpu.federated.run import (
    attach_planes,
    close_run,
    finish_setup,
    population_emptied,
    run_rounds,
    val_pass,
)
from commefficient_tpu.ops.flat import ravel_pytree
from commefficient_tpu.profiling import begin_setup, phase
from commefficient_tpu.utils import (
    PiecewiseLinear,
    TableLogger,
    Timer,
    announce_devices,
    configure_compile_cache,
    make_logdir,
    union,
)


def get_data_loaders(args):
    train_transforms, val_transforms = {
        "ImageNet": (transforms.imagenet_train_transforms,
                     transforms.imagenet_val_transforms),
        "CIFAR10": (transforms.cifar10_train_transforms,
                    transforms.cifar10_test_transforms),
        "CIFAR100": (transforms.cifar100_train_transforms,
                     transforms.cifar100_test_transforms),
        "EMNIST": (transforms.femnist_train_transforms,
                   transforms.femnist_test_transforms),
    }[args.dataset_name]

    dataset_class = {"CIFAR10": FedCIFAR10, "CIFAR100": FedCIFAR100,
                     "EMNIST": FedEMNIST, "ImageNet": FedImageNet}[
        args.dataset_name]
    train_dataset = dataset_class(args.dataset_dir, args.dataset_name,
                                  train_transforms, args.do_iid,
                                  args.num_clients, train=True, download=True)
    test_dataset = dataset_class(args.dataset_dir, args.dataset_name,
                                 val_transforms, train=False, download=False)

    train_loader = FedLoader(train_dataset, args.num_workers,
                             args.local_batch_size)
    test_loader = FedLoader(test_dataset,
                            val_batch_size=args.valid_batch_size
                            * args.num_workers)
    # background prefetch (the reference's DataLoader worker knob,
    # utils.py:178-182); assembly runs in GIL-released native calls
    if args.train_dataloader_workers > 0:
        train_loader = PrefetchLoader(train_loader)
    if args.val_dataloader_workers > 0:
        test_loader = PrefetchLoader(test_loader)
    return train_loader, test_loader


def run_batches(model, opt, lr_scheduler, loader, training, epoch_fraction,
                args, epoch=0, resume_mid=None, totals=(0.0, 0.0)):
    if not training and epoch_fraction != 1:
        raise ValueError("Must do full epochs for val")
    model.train(training)
    losses, accs = [], []
    if training:
        num_clients = loader.dataset.num_clients
        client_download = np.zeros(num_clients)
        client_upload = np.zeros(num_clients)
        spe = loader.steps_per_epoch()
        # Preemption-safe round-granular resume (docs/fault_tolerance.md):
        # re-enter a half-finished epoch at the saved round — the sampler
        # replays its saved position (the global np RNG was restored by
        # load_run_state) and the partial epoch accumulators reload, so the
        # remaining rounds reproduce the uninterrupted run bit-for-bit.
        i0, ex = restore_mid_epoch(resume_mid, loader, client_download,
                                   client_upload)
        losses.extend(np.asarray(ex.get("losses", [])).tolist())
        accs.extend(np.asarray(ex.get("accs", [])).tolist())
        # the engine owns the liveness heartbeat (global telemetry round
        # index, scripts/crash_matrix.py) and the telemetry spans (the
        # recorder attached to the model by main)
        engine = PipelinedRoundEngine(
            model, opt, lr_scheduler,
            window=getattr(args, "round_window", 2),
            drain_every=getattr(args, "metrics_drain_every", 8))

        def consume(results):
            """Fold drained rounds in; true on a NaN loss (the abort)."""
            nonlocal client_download, client_upload
            for res in results:
                loss, acc, download, upload = res.values
                if np.any(np.isnan(loss)):
                    print(f"LOSS OF {np.mean(loss)} IS NAN, "
                          "TERMINATING TRAINING")
                    return True
                client_download += download
                client_upload += upload
                losses.extend(loss.tolist())
                accs.extend(acc.tolist())
            return False

        finished = run_rounds(
            engine, loader, args, epoch=epoch, i0=i0, spe=spe,
            epoch_fraction=epoch_fraction, totals=totals, consume=consume,
            extras=lambda: {"download": client_download,
                            "upload": client_upload,
                            "losses": np.asarray(losses, np.float64),
                            "accs": np.asarray(accs, np.float64)},
            stop_after_first=args.do_test)
        if not finished:
            return np.nan, np.nan, np.nan, np.nan
        if population_emptied(model, losses):
            return None, None, client_download, client_upload
        return (np.mean(losses), np.mean(accs), client_download,
                client_upload)
    with val_pass(model):
        for batch in loader:
            loss, acc = model(batch)
            losses.extend(loss.tolist())
            accs.extend(acc.tolist())
            if args.do_test:
                break
    return np.mean(losses), np.mean(accs), None, None


def train(model, opt, lr_scheduler, train_loader, test_loader, args, writer,
          loggers=(), timer=None, start_epoch=0, totals=(0.0, 0.0),
          resume_mid=None):
    timer = timer or Timer()
    total_download, total_upload = totals
    if args.eval_before_start and start_epoch == 0:
        _, test_acc, _, _ = run_batches(model, None, None, test_loader,
                                        False, 1, args)
        timer()
        print(f"Test acc at epoch 0: {test_acc:0.4f}")
    summary = {}
    for epoch in range(start_epoch, math.ceil(args.num_epochs)):
        if epoch == math.ceil(args.num_epochs) - 1:
            epoch_fraction = args.num_epochs - epoch
        else:
            epoch_fraction = 1
        train_loss, train_acc, download, upload = run_batches(
            model, opt, lr_scheduler, train_loader, True, epoch_fraction,
            args, epoch=epoch,
            resume_mid=(resume_mid if epoch == start_epoch else None),
            totals=(total_download, total_upload))
        if train_loss is None:
            print("ending training: live population is empty with no "
                  "pending joiners (--churn open-world end state)")
            break
        if np.isnan(train_loss):
            print("TERMINATING TRAINING DUE TO NAN LOSS")
            return
        train_time = timer()
        download_mb = download.sum() / (1024 * 1024)
        upload_mb = upload.sum() / (1024 * 1024)
        total_download += download_mb
        total_upload += upload_mb

        test_loss, test_acc, _, _ = run_batches(model, None, None,
                                                test_loader, False, 1, args)
        test_time = timer()
        epoch_stats = {
            "train_time": train_time,
            "train_loss": train_loss,
            "train_acc": train_acc,
            "test_loss": test_loss,
            "test_acc": test_acc,
            "down (MiB)": round(download_mb),
            "up (MiB)": round(upload_mb),
            "total_time": timer.total_time,
        }
        lr = lr_scheduler.get_last_lr()[0]
        summary = union({"epoch": epoch + 1, "lr": lr}, epoch_stats)
        for logger in loggers:
            logger.append(summary)
        if getattr(model, "telemetry", None) is not None:
            model.telemetry.event(
                "epoch", epoch=epoch + 1, lr=float(lr),
                **{k.split(" ")[0]: float(v)
                   for k, v in epoch_stats.items()})
        maybe_save_run_state(args, epoch, model, opt, lr_scheduler,
                             (total_download, total_upload))
        if writer is not None:
            for key, val in (("Loss/train", train_loss),
                             ("Loss/test", test_loss),
                             ("Acc/train", train_acc),
                             ("Acc/test", test_acc),
                             ("Time/train", train_time),
                             ("Time/test", test_time),
                             ("Time/total", timer.total_time),
                             ("Lr", lr)):
                writer.add_scalar(key, val, epoch)

    print(f"Total Download (MiB): {total_download:0.2f}")
    print(f"Total Upload (MiB): {total_upload:0.2f}")
    n = train_loader.dataset.num_clients
    print(f"Avg Download Per Client: {total_download / n:0.2f}")
    print(f"Avg Upload Per Client: {total_upload / n:0.2f}")
    return summary


def build_model_and_config(args):
    """model_config construction (reference cv_train.py:328-364)."""
    if args.do_test:
        model_config = {"channels": (("prep", 1), ("layer1", 1),
                                     ("layer2", 1), ("layer3", 1))}
        args.num_cols = 10
        args.num_rows = 1
        args.k = 10
    elif os.environ.get("COMMEFFICIENT_MODEL_CHANNELS"):
        # explicit ResNet9 widths "prep,l1,l2,l3" — the golden-trajectory
        # test uses 12,24,48,96 (d = 232,812: honest geometry where sketch
        # 5x16384 is a genuine 2.84x compression, not a capacity probe)
        pre, l1, l2, l3 = (int(x) for x in os.environ[
            "COMMEFFICIENT_MODEL_CHANNELS"].split(","))
        model_config = {"channels": (("prep", pre), ("layer1", l1),
                                     ("layer2", l2), ("layer3", l3))}
    elif os.environ.get("COMMEFFICIENT_TINY_MODEL"):
        # CPU-test scale: keeps e2e runs fast where conv throughput is low
        model_config = {"channels": (("prep", 8), ("layer1", 16),
                                     ("layer2", 16), ("layer3", 32))}
    else:
        model_config = {}

    if args.do_finetune:
        num_classes = num_classes_of_dataset(args.finetuned_from)
        num_new_classes = num_classes_of_dataset(args.dataset_name)
    else:
        num_classes = num_classes_of_dataset(args.dataset_name)
        num_new_classes = None
    model_config.update({"num_classes": num_classes,
                         "new_num_classes": num_new_classes})
    input_channels = 1 if args.dataset_name == "EMNIST" else 3
    if input_channels == 1:
        model_config["initial_channels"] = 1

    model_cls = getattr(models, args.model)
    import inspect

    accepted = inspect.signature(model_cls).parameters
    if "do_batchnorm" in accepted:
        model_config["do_batchnorm"] = args.do_batchnorm
    model_config = {k: v for k, v in model_config.items() if k in accepted}
    model = model_cls(**model_config)
    input_hw = {"CIFAR10": 32, "CIFAR100": 32, "EMNIST": 28,
                "ImageNet": 224}[args.dataset_name]
    input_shape = (input_hw, input_hw, input_channels)
    return model, input_shape


def build_param_groups(args, params):
    """Fixup per-group LRs (reference cv_train.py:366-376) and finetune
    freezing (reference cv_train.py:377-384) as flat-vector masks."""
    flat, _ = ravel_pytree(params)
    d = int(flat.size)

    def mask_for(pred):
        leaves = jax.tree_util.tree_leaves_with_path(params)
        mask = np.zeros(d, bool)
        start = 0
        for path, leaf in leaves:
            n = int(np.prod(leaf.shape)) if leaf.shape else 1
            keys = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path).lower()
            if pred(keys):
                mask[start:start + n] = True
            start += n
        return mask

    if args.model.startswith("Fixup"):
        bias = mask_for(lambda k: "bias" in k)
        scale = mask_for(lambda k: "scale" in k or "mul" in k)
        other = ~(bias | scale)
        return [(bias, 0.1), (scale & ~bias, 0.1), (other, 1.0)]
    if args.do_finetune:
        head = mask_for(lambda k: "linear" in k or "classifier" in k
                        or k.endswith("fc"))
        return [(head, 1.0), (~head, 0.0)]
    return None


def main(argv=None):
    from commefficient_tpu.parallel.mesh import maybe_init_distributed

    # join a multi-process cohort (supervise.py --procs N env seam) BEFORE
    # the first jax.devices() call, so the mesh sees the global device set
    maybe_init_distributed()
    args = parse_args(argv=argv)
    configure_compile_cache()
    announce_devices()
    # start-up's phases (profiling.py): `import` ends here, process start
    # to devices announced
    begin_setup()
    assert args.model_devices == 1, (
        "--model_devices (tensor parallelism) is GPT-2 only; the CV models "
        "have no model axis — use gpt2_train.py")
    assert args.pipeline_devices == 1, (
        "--pipeline_devices (pipeline parallelism) is GPT-2 only; the CV "
        "models have no stage axis — use gpt2_train.py")
    assert args.n_experts == 0, (
        "--n_experts (MoE / expert parallelism) is GPT-2 only; the CV "
        "models have no expert axis — use gpt2_train.py")
    if args.lr_scale is None:
        args.lr_scale = 0.4  # cifar10-fast default peak LR
    print(args)
    timer = Timer()
    np.random.seed(args.seed)

    with phase("data"):
        train_loader, test_loader = get_data_loaders(args)

    with phase("model"):
        model, input_shape = build_model_and_config(args)
        has_bn = args.do_batchnorm and hasattr(model, "do_batchnorm")
        compute_loss_train, compute_loss_val = make_cv_losses(
            model, has_batch_stats=has_bn,
            compute_dtype=jnp.bfloat16 if args.do_bf16 else None)

        init_params = None
        model_state = None
        if args.do_finetune:
            x = jnp.zeros((1,) + input_shape, jnp.float32)
            variables = model.init(jax.random.key(args.seed), x,
                                   train=False)
            ckpt_params, ckpt_state = load_checkpoint(
                os.path.join(args.finetune_path, args.model))
            init_params, loaded, skipped = load_matching(
                variables["params"], ckpt_params)
            print(f"finetune: loaded {loaded} tensors, fresh: {skipped}")
            model_state = variables.get("batch_stats", {})

    with phase("fed"):
        fed_model = FedModel(model, compute_loss_train, args,
                             compute_loss_val, input_shape=input_shape,
                             num_clients=train_loader.dataset.num_clients,
                             init_params=init_params,
                             model_state=model_state)
        param_groups = build_param_groups(args, fed_model.params)
        opt = FedOptimizer(fed_model, args, param_groups=param_groups)

    with phase("planes"):
        lr_schedule = PiecewiseLinear(
            [0, args.pivot_epoch, args.num_epochs], [0, args.lr_scale, 0])
        spe = train_loader.steps_per_epoch()
        lr_scheduler = LambdaLR(
            opt, lr_lambda=lambda step: lr_schedule(step / spe))

        log_dir = make_logdir(args)
        if os.environ.get("COMMEFFICIENT_RUN_DIR"):
            # orchestrated tenant (scripts/orchestrate.py,
            # docs/packing.md): the run dir — and with it telemetry.jsonl
            # + trace_round_* captures — is pinned per tenant so fleet
            # neighbors never collide
            print(f"run dir pinned by orchestrator: {log_dir} (tenant "
                  f"{os.environ.get('COMMEFFICIENT_TENANT_ID', '?')})",
                  flush=True)
        writer = None
        if args.use_tensorboard:
            try:
                from torch.utils.tensorboard import SummaryWriter

                writer = SummaryWriter(log_dir=log_dir)
            except ImportError:
                print("tensorboard unavailable; console logging only")
        planes, start_epoch, totals, resume_mid = attach_planes(
            args, fed_model, opt, lr_scheduler, train_loader, log_dir,
            "cv_train")
    finish_setup(planes)
    timer()  # the epochs' clock starts here; set-up stays in total_time

    try:
        summary = train(fed_model, opt, lr_scheduler, train_loader,
                        test_loader, args, writer, loggers=(TableLogger(),),
                        timer=timer, start_epoch=start_epoch, totals=totals,
                        resume_mid=resume_mid)
    finally:
        close_run(planes)
    if args.do_checkpoint:
        os.makedirs(args.checkpoint_path, exist_ok=True)
        save_checkpoint(os.path.join(args.checkpoint_path, args.model),
                        fed_model.params, fed_model._model_state)
    return summary


if __name__ == "__main__":
    main()
