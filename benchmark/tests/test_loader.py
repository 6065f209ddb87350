"""The loader wrapper: it re-iterates the inner loader, counts its waits and
stops on a multiple of metrics_drain_every once the time has passed."""

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import WindowLoader, _drop_half  # noqa: E402

import numpy as np  # noqa: E402


class Inner:
    """An epoch of five batches, 10 ms each."""

    dataset = "the dataset"

    def __iter__(self):
        for i in range(5):
            time.sleep(0.01)
            yield {"i": i}

    def steps_per_epoch(self):
        return 5


def test_fixed_rounds_cross_epochs_and_keep_the_first():
    ld = WindowLoader(Inner(), drain_every=8, rounds=12, keep=3)
    got = [b["i"] for b in ld]
    assert got == [0, 1, 2, 3, 4, 0, 1, 2, 3, 4, 0, 1]
    assert ld.rounds == 12 and ld.epochs == 3
    assert [b["i"] for b in ld.kept] == [0, 1, 2]
    assert ld.wait_s >= 0.1
    assert ld.dataset == "the dataset"          # passes attributes through
    assert ld.steps_per_epoch() > 10**9         # an endless epoch


def test_timed_window_stops_on_a_multiple_of_the_drain():
    for drain_every, seconds in ((8, 0.05), (8, 0.3), (3, 0.12)):
        ld = WindowLoader(Inner(), drain_every=drain_every, seconds=seconds)
        t0 = time.monotonic()
        n = sum(1 for _ in ld)
        assert n == ld.rounds and n > 0
        assert n % drain_every == 0
        assert time.monotonic() - t0 >= seconds
        # not a drain more than needed: the one before was too early
        assert (n - drain_every) * 0.01 < seconds + 0.05


def test_the_planted_fault_drops_the_second_half():
    b = {"worker_mask": np.ones(4, np.float32),
         "mask": np.ones((4, 5), np.float32), "inputs": np.zeros((4, 5, 2))}
    out = _drop_half(b)
    assert out["worker_mask"].tolist() == [1, 1, 0, 0]
    assert out["mask"].sum() == 10 and b["mask"].sum() == 20
