"""``kimi_linear_ep32_ref``'s lean ``follow`` against reference.py's at the
configuration's rehearsal size, on the CPU: the same losses, first transmit,
change and kept leaves in sketch and dense modes; no more than four d-sized
float32 vectors alive at any of its stage boundaries (reference.py's holds
seven); and any other configuration's model still takes reference.py's."""

import json
import os
import sys

import numpy as np
import pytest

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for sub in (BENCH, os.path.join(BENCH, "configs")):
    sys.path.insert(0, sub)

import kimi_linear_ep32_ref as ref_file  # noqa: E402
import reference  # noqa: E402

W, B, T = 4, 2, 32


@pytest.fixture(scope="module")
def model():
    with open(os.path.join(BENCH, "configs", "kimi_linear_ep32.json")) as f:
        config = json.load(f)
    config.update(config["rehearse"])
    return ref_file.Model(config)


def batches(model, n=3):
    rng = np.random.RandomState(0)
    out = []
    for _ in range(n):
        ids = rng.randint(0, model.V, (W, B, 1, T))
        mask = np.ones((W, B), np.float32)
        mask[-1, -1] = 0.0
        out.append({"input_ids": ids,
                    "lm_labels": np.where(rng.rand(*ids.shape) < 0.8, ids,
                                          -1),
                    "mask": mask, "worker_mask": np.ones(W, np.float32),
                    "client_ids": np.arange(W, dtype=np.int32)})
    return out


def traffic(mode):
    return dict(mode=mode, num_cols=2048, num_rows=3, k=500,
                virtual_momentum=0.9, program_seed=21, weight_decay=5e-4,
                num_workers=W,
                schedule={"kind": "linear_decay", "lr_scale": 0.004,
                          "pivot_epoch": 0.0, "num_epochs": 1.0})


@pytest.mark.parametrize("mode", ["sketch", "uncompressed"])
def test_lean_follow_is_reference_follow(model, mode):
    assert reference.follow is ref_file.follow
    args = (model, traffic(mode), 2147484005, batches(model), 50, 1)
    lean = ref_file.lean_follow(*args)
    plain = ref_file._plain_follow(*args)
    for got, want in zip(lean["client_losses"], plain["client_losses"]):
        np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(lean["first"], plain["first"], rtol=1e-6)
    # the change of the weights: the same operations on a tree instead of
    # on the flat vector, which the compiler may fuse into one rounding where
    # reference.py's eager steps round twice: a last bit of a weight near 1
    # (A_log) is 1.2e-7, a tenth of a percent of that leaf's change of 1e-4
    np.testing.assert_allclose(lean["change"], plain["change"], rtol=1e-4,
                               atol=2e-7)
    assert (lean["keep"] == plain["keep"]).all()


def test_at_most_four_d_sized_vectors_at_every_stage(model, monkeypatch):
    def f32_elements():
        return sum(a.size for a in jax.live_arrays()
                   if a.dtype == np.float32)

    d = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        model.shapes, is_leaf=lambda x: isinstance(x, tuple)))
    base = f32_elements()
    seen = {}
    monkeypatch.setattr(ref_file, "observe", lambda stage: seen.setdefault(
        stage, []).append(f32_elements() - base))
    ref_file.follow(model, traffic("sketch"), 7, batches(model, 2), 50, 1)
    assert set(seen) == {"init", "block gradient", "flat gradient", "update",
                         "step"}
    # the server's tables (three of 3 x 2048: transmit, momentum, error)
    # beside the vectors
    tables = 3 * 3 * 2048
    for stage, counts in seen.items():
        assert max(counts) <= 4 * d + tables, (stage, max(counts) / d)
    assert max(seen["block gradient"]) > 2 * d      # the stage is measured


def test_another_model_takes_reference_follow(monkeypatch):
    called = []
    monkeypatch.setattr(ref_file, "_plain_follow",
                        lambda *a, **k: called.append(a[0]))
    ref_file.follow("another model", None)
    assert called == ["another model"]
