"""Full-scale learning evidence on the real chip.

Round-2 verdict gap: every learning trajectory recorded so far is tiny
geometry (d≈32k, CPU mesh), where sketch capacity arguments apply. This run
trains the REAL FetchSGD CIFAR geometry — full ResNet9 (d=6,568,640),
8 workers, sketch 5x500k / k=50k, virtual momentum 0.9 — sketched vs
uncompressed on the same synthetic data and seed, and records both
trajectories (reference recipe utils.py:142-162, fed_aggregator.py:568-613).

Run on the TPU (the process owns the chip):
  python scripts/learning_fullscale.py
Writes docs/learning_fullscale.json and prints per-epoch rows.
"""

from __future__ import annotations

import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# 512 images/class -> 5,120 train images, 10 rounds/epoch at the FetchSGD
# batch of 512 (8 workers x 64). Test split stays at the fallback default.
os.environ.setdefault("COMMEFFICIENT_SYNTHETIC_PER_CLASS", "512")
# LEARN_TINY=1: harness smoke mode (CPU-sized model+sketch, same script
# mechanics) used by the test suite; the real run uses the full geometry.
TINY = os.environ.get("LEARN_TINY") == "1"
if TINY:
    os.environ["COMMEFFICIENT_TINY_MODEL"] = "1"
else:
    os.environ.pop("COMMEFFICIENT_TINY_MODEL", None)  # full-size ResNet9

EPOCHS = os.environ.get("LEARN_EPOCHS", "24")

COMMON = [
    "--dataset_name", "CIFAR10",
    "--dataset_dir", os.path.join(_REPO, "runs", "learn_fullscale_data"),
    "--model", "ResNet9",
    "--batchnorm",
    "--iid", "--num_clients", "8",
    "--num_workers", "8",
    "--local_batch_size", "64",
    "--valid_batch_size", "64",
    "--num_epochs", EPOCHS,
    "--pivot_epoch", os.environ.get("LEARN_PIVOT", "5"),
    "--weight_decay", "5e-4",
    "--lr_scale", "0.4",
    "--seed", "0",
    # overlap host-side augmentation/assembly with device compute
    "--train_dataloader_workers", "1",
]

SKETCH = [
    "--mode", "sketch", "--error_type", "virtual",
    "--k", "2000" if TINY else "50000",
    "--num_cols", "16384" if TINY else "500000",
    "--num_rows", "5",
    "--num_blocks", "2" if TINY else "20",
    "--virtual_momentum", "0.9", "--local_momentum", "0",
]

UNCOMPRESSED = [
    "--mode", "uncompressed", "--error_type", "virtual",
    "--virtual_momentum", "0.9", "--local_momentum", "0",
]


def run(tag, mode_args):
    from commefficient_tpu.utils import run_cv_recorded

    return run_cv_recorded(COMMON + mode_args, tag)


def main():
    import jax

    print("backend:", jax.default_backend(), flush=True)
    if jax.default_backend() != "tpu" and not os.environ.get(
            "COMMEFFICIENT_LEARNING_ALLOW_CPU"):
        # chip-only: at d=6.5M a CPU epoch takes hours
        # (set COMMEFFICIENT_LEARNING_ALLOW_CPU=1 to override)
        sys.exit("learning_fullscale: backend is not a TPU; refusing "
                 "the full-scale run on CPU")
    path = os.path.join(_REPO, "docs", "learning_fullscale.json")
    geometry = {"epochs": EPOCHS, "tiny": TINY,
                "per_class": os.environ["COMMEFFICIENT_SYNTHETIC_PER_CLASS"]}
    out = dict(geometry, backend=jax.default_backend())
    # per-leg resume: a kill mid-leg keeps every completed leg. Sketch
    # runs FIRST: it is the leg the evidence needs; uncompressed is the
    # anchor. Legs resume only from a run of the SAME geometry (a
    # LEARN_TINY smoke artifact must never be kept as full-scale evidence).
    prev = None
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
        except json.JSONDecodeError:
            print("previous artifact unreadable; re-running all legs",
                  flush=True)
    if prev is not None:
        if all(prev.get(k) == v for k, v in geometry.items()):
            for tag in ("sketch", "uncompressed"):
                if prev.get(tag):
                    out[tag] = prev[tag]
                    print(f"leg {tag}: kept from previous run "
                          f"({len(prev[tag])} rows)", flush=True)
        else:
            prev_geo = {k: prev.get(k) for k in geometry}
            print(f"previous artifact geometry {prev_geo} != current "
                  f"{geometry}; re-running all legs", flush=True)
    for tag, mode_args in (("sketch", SKETCH),
                           ("uncompressed", UNCOMPRESSED)):
        if out.get(tag):
            continue
        out[tag] = run(tag, mode_args)
        # atomic: a window kill during the write must not destroy the
        # completed legs the resume exists to keep
        with open(path + ".tmp", "w") as f:
            json.dump(out, f, indent=1)
        os.replace(path + ".tmp", path)
        print(f"wrote {path} after {tag}", flush=True)


if __name__ == "__main__":
    main()
