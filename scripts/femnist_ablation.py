"""FEMNIST sketched-generalization sample-count ablation (VERDICT r3 #3).

Round-3 evidence showed the sketched synthetic-FEMNIST run overfitting
(test acc 0.08 vs 0.18 uncompressed at ~40 samples/client), explained as a
small-data artifact of the zero-egress fallback (real FEMNIST has 800k
images; reference data_utils/fed_emnist.py:36-138). This script PROVES the
explanation by sweeping samples/client (COMMEFFICIENT_SYNTHETIC_SAMPLES)
for the sketched config with uncompressed anchors: if the explanation is
right, the sketched test accuracy must close on (or pass) the uncompressed
one as data grows, producing the healthy sketched FEMNIST curve the
verdict asks for.

Run on CPU (tiny model geometry, the documented learning-curve harness):
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python scripts/femnist_ablation.py
Writes docs/femnist_ablation.json and prints per-epoch rows.
"""

from __future__ import annotations

import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

os.environ.setdefault("COMMEFFICIENT_TINY_MODEL", "1")
os.environ.setdefault("COMMEFFICIENT_SYNTHETIC_CLIENTS", "50")

SAMPLE_GRID = [int(s) for s in
               os.environ.get("FEMNIST_SAMPLES", "40,160,640").split(",")]


def epochs_for(samples: int) -> int:
    """16 epochs up to s=160, 12 at larger settings. A constant-rounds
    budget was tried first and undertrained BOTH modes at s=160 (4 epochs:
    uncompressed fell 0.24 -> 0.09 test acc vs its own 16-epoch s=40 run)
    — epoch count matters independently of rounds here, so the sweep keeps
    near-equal epochs and pays the single-core wall time at s=640."""
    if os.environ.get("FEMNIST_EPOCHS"):
        return int(os.environ["FEMNIST_EPOCHS"])
    return 16 if samples <= 160 else 12

# FEMNIST_SKETCH_LR: lr sweep hook (non-default values get lr-tagged
# artifact keys). Diagnosis history for the round-3 "sketched FEMNIST
# overfits" finding: lr (0.25 vs 0.1) did NOT explain it — the root cause
# was the old noise-prototype synthetic data decorrelating under the
# reference's resampling augmentation (see fed_emnist._smooth_protos);
# with augmentation disabled the same sketched config hit test acc 1.00.
SKETCH_LR = os.environ.get("FEMNIST_SKETCH_LR", "0.25")
SKETCH = [
    "--mode", "sketch", "--error_type", "virtual",
    "--k", "4000", "--num_cols", "16384", "--num_rows", "5",
    "--num_blocks", "2",
    "--virtual_momentum", "0.9", "--local_momentum", "0",
    "--lr_scale", SKETCH_LR,
]
UNCOMPRESSED = [
    "--mode", "uncompressed", "--error_type", "virtual",
    "--virtual_momentum", "0.9", "--local_momentum", "0",
    "--lr_scale", "0.1",
]


def run(tag, samples, mode_args):
    from commefficient_tpu.data_utils.fed_emnist import SYNTHETIC_GEN_VERSION
    from commefficient_tpu.utils import run_cv_recorded

    os.environ["COMMEFFICIENT_SYNTHETIC_SAMPLES"] = str(samples)
    ep = epochs_for(samples)
    argv = [
        "--dataset_name", "EMNIST",
        # samples env is read at dataset PREPARE time: one dir per setting,
        # fingerprinted by the generator version — FedDataset caches
        # prepared data, so without the version a resumed sweep after a
        # generator change would silently train on stale data
        "--dataset_dir", os.path.join(
            _REPO, "runs",
            f"femnist_ablation_g{SYNTHETIC_GEN_VERSION}_s{samples}"),
        "--model", "ResNet9", "--batchnorm",
        "--num_workers", "8",
        "--local_batch_size", "16",
        "--valid_batch_size", "64",
        "--num_epochs", str(ep),
        "--pivot_epoch", str(max(1, ep // 4)),
        "--seed", "0",
        # overlap host-side augmentation/assembly with device compute
        "--train_dataloader_workers", "1",
    ] + mode_args
    rows = run_cv_recorded(argv, f"{tag} s={samples}")
    # provenance lives WITH each run, so a resumed sweep under different
    # env settings cannot silently mislabel earlier entries
    return {"rows": rows, "samples": samples, "epochs": ep,
            "clients": int(os.environ["COMMEFFICIENT_SYNTHETIC_CLIENTS"])}


def main():
    path = os.path.join(_REPO, "docs", "femnist_ablation.json")
    out = {}
    if os.path.exists(path):
        # resumable: an interrupted sweep keeps its completed settings
        with open(path) as f:
            out.update(json.load(f))
    for samples in SAMPLE_GRID:
        for tag, mode_args in (("sketch", SKETCH),
                               ("uncompressed", UNCOMPRESSED)):
            key = f"{tag}_s{samples}"
            if tag == "sketch" and SKETCH_LR != "0.25":
                key = f"sketch_lr{SKETCH_LR}_s{samples}"
            if out.get(key):
                print(f"skip {key}: already recorded", flush=True)
                continue
            out[key] = run(tag, samples, mode_args)
            with open(path, "w") as f:
                json.dump(out, f, indent=1)
            print(f"wrote {path} after {tag} s={samples}", flush=True)


if __name__ == "__main__":
    main()
