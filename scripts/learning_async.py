"""Async-buffered learning rung: accuracy vs --async_buffer K x decay.

The bench `async` cfg prices the THROUGHPUT side of removing the round
barrier (sync degrades ~12x under 30% slow clients while buffered-async
holds its rate); this rung prices the LEARNING side — what buffered folds
with exact-staleness decay w(D) = --staleness_decay**D cost in accuracy
at the golden in-suite geometry (ResNet9 12/24/48/96, d = 232,812, the
learning-ladder anchor of docs/learning_curves.md). Sweep:

- ``sync``          — the K=0 anchor (identical recipe, no async plane);
- ``sync_slow``     — the anchor under 20% injected stragglers, i.e.
  what the synchronous late-landing path already tolerates;
- ``k2_d5 k2_d8 k4_d5 k4_d8`` — --async_buffer {2,4} x
  --staleness_decay {0.5, 0.8} under the SAME 20% straggler schedule,
  so every buffered fold carries genuinely stale contributions and the
  decay knob is actually load-bearing (FedBuff, arXiv:2106.06639,
  reports K~10 matching synchronous accuracy; docs/async.md).

Run:  JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python scripts/learning_async.py [legs...]
Appends each completed leg to docs/learning_async.json (atomic, resume
by re-running with the remaining legs), the learning_midscale.py shape.
"""

from __future__ import annotations

import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

os.environ.setdefault("COMMEFFICIENT_SYNTHETIC_PER_CLASS", "64")

from script_env import force_cpu_mesh  # noqa: E402

force_cpu_mesh(8)

OUT = os.path.join(_REPO, "docs", "learning_async.json")

GOLDEN_CHANNELS = "12,24,48,96"  # d = 232,812 (the learning-ladder anchor)
# 20% slow slots, landing 2 rounds late: every ~K-th fold then carries a
# version-tagged stale contribution, so the decay sweep measures a real
# effect, not w(0) = 1 no-ops
SLOW = ["--inject_client_fault", "slow=0.2,delay=2,seed=7"]


def common(epochs, seed):
    os.environ["COMMEFFICIENT_MODEL_CHANNELS"] = GOLDEN_CHANNELS
    return [
        "--dataset_name", "CIFAR10",
        "--dataset_dir", os.path.join(_REPO, "runs", "learn_async_data"),
        "--model", "ResNet9", "--batchnorm",
        "--num_workers", "8", "--num_devices", "8",
        "--local_batch_size", "16",
        "--valid_batch_size", "50",
        "--num_epochs", str(epochs), "--pivot_epoch", "2",
        "--lr_scale", "0.3",
        "--local_momentum", "0",
        "--seed", str(seed),
        "--iid", "--num_clients", "16",
    ]


SKETCH = ["--mode", "sketch", "--error_type", "virtual",
          "--k", "2000", "--num_cols", "8192", "--num_rows", "5",
          "--num_blocks", "2", "--virtual_momentum", "0.9"]


def _async(k, decay):
    return SLOW + ["--async_buffer", str(k),
                   "--staleness_decay", str(decay)]


# leg -> (epochs, seed, extra argv)
LEGS = {
    "sync": (12, 0, []),
    "sync_slow": (12, 0, SLOW),
    "k2_d5": (12, 0, _async(2, 0.5)),
    "k2_d8": (12, 0, _async(2, 0.8)),
    "k4_d5": (12, 0, _async(4, 0.5)),
    "k4_d8": (12, 0, _async(4, 0.8)),
}


def main():
    from commefficient_tpu.utils import run_cv_recorded

    legs = sys.argv[1:] or list(LEGS)
    results = {}
    if os.path.exists(OUT):
        try:
            with open(OUT) as f:
                results = json.load(f)
        except json.JSONDecodeError:
            print("previous artifact unreadable; starting fresh", flush=True)
    for leg in legs:
        epochs, seed, extra = LEGS[leg]
        argv = common(epochs, seed) + SKETCH + extra
        print(f"=== {leg}: channels {GOLDEN_CHANNELS} epochs {epochs} "
              f"seed {seed} ===", flush=True)
        rows = run_cv_recorded(argv, leg)
        results[leg] = {"channels": GOLDEN_CHANNELS, "epochs": epochs,
                        "seed": seed, "argv": argv, "rows": rows}
        # atomic: an interrupt during the write must not destroy
        # previously completed legs
        with open(OUT + ".tmp", "w") as f:
            json.dump(results, f, indent=1)
        os.replace(OUT + ".tmp", OUT)
        print(f"leg {leg} done -> {OUT}", flush=True)


if __name__ == "__main__":
    main()
