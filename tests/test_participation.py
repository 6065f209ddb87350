"""Straggler- and dropout-tolerant client participation
(federated/participation.py, docs/fault_tolerance.md §client faults).

Pins the participation PR's contracts:

- **Full-participation bit-identity**: a cohort target of ``num_workers``
  with no injected faults leaves the fp32 trajectory BIT-identical to the
  pre-participation path — across replicated/``--server_shard`` ×
  composed/``--fused_epilogue`` — and the sampler's uniform draw consumes
  the RNG byte-for-byte like the legacy code.
- **Exact reweighting**: a partial cohort is the data-weighted mean over
  the live slots — the linearity identity
  ``S_full == S_live + S_complement`` pinned at the transmit-sum level.
- **Client-fault ladder**: a seeded drop+slow+corrupt injected run
  completes WITHOUT a guard quarantine, its trajectory is deterministic
  under rerun, drops requeue into the sampler pool with bounded retries,
  repeat-corrupt clients are quarantined at client granularity.
- **Staleness-weighted late landing**: the straggler fold is pinned
  against a hand-computed reweighting — both the formula (numpy) and the
  full engine trajectory vs a manually-orchestrated twin — on BOTH server
  planes.
- **Zero syncs**: the strict ``host_sync_monitor`` audit holds through
  the engine with partial participation AND late landing in flight.
- **State**: ``FedSampler.get_state``/``set_state`` round-trips the
  retry/quarantine bookkeeping; the controller's fault RNG + pending
  straggler buffer ride ``save_run_state``; a mid-epoch crash→resume of a
  fault-injected cv_train run reproduces the uninterrupted run
  bit-exactly.
- **Observability**: the telemetry ``run_start`` header carries the
  participation config, and a fault-injected run's participation history
  reproduces from the JSONL log ALONE (scripts/obs_report.py).
"""

import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

# the e2e pieces drive cv_train; same import-time setdefault as
# test_fault_tolerance.py (a standalone invocation must not build the
# full d=6.5M ResNet9)
os.environ.setdefault("COMMEFFICIENT_TINY_MODEL", "1")

import jax
import jax.numpy as jnp

import flax.linen as nn

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")
if _SCRIPTS not in sys.path:
    sys.path.insert(0, _SCRIPTS)

from commefficient_tpu.data_utils.fed_sampler import FedSampler  # noqa: E402
from commefficient_tpu.federated import participation as P  # noqa: E402
from commefficient_tpu.federated.aggregator import (  # noqa: E402
    FedModel,
    FedOptimizer,
    LambdaLR,
)
from commefficient_tpu.federated.engine import PipelinedRoundEngine  # noqa: E402
from commefficient_tpu.federated.participation import (  # noqa: E402
    FaultSchedule,
    ParticipationController,
    attach_participation,
    parse_client_fault,
    parse_participation,
    staleness_weight,
)
from commefficient_tpu.profiling import host_sync_monitor  # noqa: E402
from commefficient_tpu.telemetry import (  # noqa: E402
    RunTelemetry,
    collective_ledger,
    read_events,
)

from test_fault_tolerance import fresh_compiles  # noqa: E402,F401


class TinyModel(nn.Module):
    @nn.compact
    def __call__(self, x, train=False):
        return nn.Dense(4, use_bias=False)(x)


def _loss(params, model_state, batch, rng, train):
    pred = TinyModel().apply({"params": params}, batch["inputs"])
    err = pred - batch["targets"]
    mask = batch["mask"]
    return jnp.sum(jnp.square(err).mean(-1) * mask), (), jnp.sum(mask), \
        model_state


def _args(**over):
    base = dict(
        mode="sketch", error_type="virtual", k=2, num_workers=2,
        weight_decay=0.0, local_momentum=0.0, virtual_momentum=0.9,
        microbatch_size=-1, max_grad_norm=None, do_dp=False,
        dp_mode="worker", l2_norm_clip=1.0, noise_multiplier=0.0,
        num_fedavg_epochs=1, fedavg_batch_size=-1, fedavg_lr_decay=1.0,
        do_topk_down=False, num_clients=4, num_devices=1, seed=0,
        do_test=False, dataset_name="CIFAR10", num_epochs=2,
        local_batch_size=2, num_cols=16, num_rows=2, num_blocks=1,
        seq_parallel="none", seq_devices=1,
        guards=False, guard_max_abs=0.0, snapshot_every=0,
        max_guard_trips=3, inject_fault="",
        participation="", participation_sampling="uniform",
        inject_client_fault="", staleness_decay=0.5, client_retry_limit=3,
        telemetry=False,
    )
    base.update(over)
    return SimpleNamespace(**base)


def _host_batch(ids, seed, d_in=3):
    W = len(ids)
    rng = np.random.RandomState(seed)
    return {
        "inputs": rng.randn(W, 2, d_in).astype(np.float32),
        "targets": rng.randn(W, 2, 4).astype(np.float32),
        "mask": np.ones((W, 2), np.float32),
        "client_ids": np.asarray(ids, np.int32),
        "worker_mask": np.ones(W, np.float32),
    }


def _engine(drain_every=1, controller=None, **over):
    fm = FedModel(TinyModel(), _loss, _args(**over), input_shape=(3,))
    opt = FedOptimizer(fm, fm.args)
    sched = LambdaLR(opt, lambda step: 0.5)
    if controller is not None:
        fm._participation = controller
    return fm, opt, PipelinedRoundEngine(fm, opt, sched, window=2,
                                         drain_every=drain_every)


def _flat_weights(fm):
    w = fm.ps_weights
    return np.asarray(fm.layout.unchunk(w) if fm.layout is not None else w)


def _mask_batch(batch, keep):
    """The test-side twin of ParticipationController._masked."""
    out = dict(batch)
    wm = np.where(keep, np.asarray(batch["worker_mask"]),
                  0.0).astype(np.float32)
    mask = np.asarray(batch["mask"])
    out["worker_mask"] = wm
    out["mask"] = (mask * wm[:, None]).astype(mask.dtype)
    return out


def _predict_faults(schedule, rounds, W):
    """Replicate the controller's draw stream: the hand-computed fault
    pattern the pinning tests compare against."""
    rng = np.random.RandomState(schedule.seed)
    out = []
    for _ in range(rounds):
        draws = rng.random_sample(W)
        drop = draws < schedule.drop
        slow = ~drop & (draws < schedule.drop + schedule.slow)
        corrupt = ~drop & ~slow & (
            draws < schedule.drop + schedule.slow + schedule.corrupt)
        if (drop | slow | corrupt).all():
            drop = slow = corrupt = np.zeros(W, bool)
        out.append((drop, slow, corrupt))
    return out


class FakeDataset:
    def __init__(self, data_per_client):
        self.data_per_client = np.asarray(data_per_client, np.int64)
        self.num_clients = len(data_per_client)

    def __len__(self):
        return int(self.data_per_client.sum())


# ---------------------------------------------------------------------------
# parsing
# ---------------------------------------------------------------------------

class TestParsing:
    def test_parse_participation(self):
        assert parse_participation("", 8) is None
        assert parse_participation(None, 8) is None
        assert parse_participation("0.5", 8) == 4
        assert parse_participation("0.1", 8) == 1   # ceil, min 1
        assert parse_participation("1.0", 8) == 8
        assert parse_participation("3", 8) == 3
        assert parse_participation("8", 8) == 8
        with pytest.raises(ValueError, match="fraction"):
            parse_participation("half", 8)
        with pytest.raises(ValueError, match="> 0"):
            parse_participation("0", 8)
        with pytest.raises(ValueError, match="integral"):
            parse_participation("2.5", 8)
        with pytest.raises(ValueError, match="exceeds"):
            parse_participation("9", 8)

    def test_parse_client_fault(self):
        s = parse_client_fault("drop=0.1,slow=0.05,corrupt=0.02,delay=3,"
                               "seed=7,quarantine_after=2")
        assert (s.drop, s.slow, s.corrupt) == (0.1, 0.05, 0.02)
        assert (s.delay, s.seed, s.quarantine_after) == (3, 7, 2)
        assert s.active
        # spec() round-trips through the parser (the telemetry header
        # records spec + seed as the reproducibility contract)
        s2 = parse_client_fault(s.spec())
        assert s2 == s
        with pytest.raises(ValueError, match="bad entry"):
            parse_client_fault("drop:0.1")
        with pytest.raises(ValueError, match="unknown key"):
            parse_client_fault("dropp=0.1")
        with pytest.raises(AssertionError, match="at least one"):
            parse_client_fault("delay=2")
        with pytest.raises(AssertionError, match="< 1"):
            parse_client_fault("drop=0.5,slow=0.5")
        with pytest.raises(AssertionError, match="delay"):
            parse_client_fault("drop=0.1,delay=0")

    def test_staleness_weight(self):
        assert staleness_weight(0, 0.5) == 1.0
        assert staleness_weight(1, 0.5) == 0.5
        assert staleness_weight(3, 0.5) == 0.125
        assert staleness_weight(5, 1.0) == 1.0

    def test_fold_mean_formula_matches_numpy(self):
        """The late-landing weighted data mean, pinned against plain
        numpy arithmetic: (g·C + w·S) / (C + w·C_late)."""
        rng = np.random.RandomState(0)
        g = rng.randn(7).astype(np.float32)
        s = rng.randn(7).astype(np.float32)
        c, cl, w = 12.0, 4.0, 0.25
        got = np.asarray(P._fold_mean(jnp.asarray(g), np.float32(c),
                                      jnp.asarray(s), np.float32(w * cl),
                                      np.float32(w)))
        want = (g * np.float32(c) + np.float32(w) * s) \
            / np.float32(c + w * cl)
        np.testing.assert_allclose(got, want, rtol=1e-6)
        # and the sum-plane fold: g + w·S
        got2 = np.asarray(P._fold_sum(jnp.asarray(g), jnp.asarray(s),
                                      np.float32(w)))
        np.testing.assert_allclose(got2, g + np.float32(w) * s, rtol=1e-6)


# ---------------------------------------------------------------------------
# FedSampler: partial cohorts, requeue, quarantine, state
# ---------------------------------------------------------------------------

class TestSamplerParticipation:
    def test_full_participation_draw_is_bit_identical_to_legacy(self):
        """participation == num_workers, uniform sampling: the cohort
        draw is the SAME np.random.choice call with the same RNG
        consumption — the sequence matches a legacy sampler exactly."""
        ds = FakeDataset([5, 7, 6, 4])
        np.random.seed(3)
        legacy = [(w.copy(), [i.copy() for i in idx]) for w, idx in
                  FedSampler(ds, 2, 3).iter_structured()]
        np.random.seed(3)
        part = [(w.copy(), [i.copy() for i in idx]) for w, idx in
                FedSampler(ds, 2, 3, participation=2,
                           sampling="uniform").iter_structured()]
        assert len(legacy) == len(part)
        for (w1, i1), (w2, i2) in zip(legacy, part):
            np.testing.assert_array_equal(w1, w2)
            for a, b in zip(i1, i2):
                np.testing.assert_array_equal(a, b)

    def test_partial_cohort_size(self):
        ds = FakeDataset([8, 8, 8, 8, 8, 8, 8, 8])
        np.random.seed(0)
        sampler = FedSampler(ds, num_workers=4, local_batch_size=2,
                             participation=2)
        rounds = list(sampler.iter_structured())
        assert all(len(w) <= 2 for w, _ in rounds)
        # the epoch still exhausts every client
        served = np.concatenate([np.hstack(idx) for _, idx in rounds])
        assert len(served) == len(ds)
        assert len(np.unique(served)) == len(ds)

    @pytest.mark.parametrize("sampling", ["weighted", "stratified"])
    def test_nonuniform_sampling_deterministic_and_complete(self, sampling):
        ds = FakeDataset([2, 16, 4, 8, 1, 6])
        def run():
            np.random.seed(11)
            s = FedSampler(ds, num_workers=3, local_batch_size=2,
                           participation=2, sampling=sampling)
            return [(w.copy(), np.hstack(i).copy())
                    for w, i in s.iter_structured()]

        a, b = run(), run()
        assert len(a) == len(b)
        for (w1, i1), (w2, i2) in zip(a, b):
            np.testing.assert_array_equal(w1, w2)
            np.testing.assert_array_equal(i1, i2)
        served = np.concatenate([i for _, i in a])
        assert len(served) == len(ds) and len(np.unique(served)) == len(ds)

    def test_requeue_returns_data_to_pool(self):
        """A dropped client's cursor rolls back, so the SAME permutation
        positions re-serve when it is re-sampled — no item is lost."""
        ds = FakeDataset([4, 4])
        np.random.seed(0)
        sampler = FedSampler(ds, num_workers=2, local_batch_size=2)
        it = sampler.iter_structured()
        workers, idx_lists = next(it)
        victim = int(workers[0])
        batch_idx = np.asarray(idx_lists[0])
        req, aband, attempts = sampler.requeue([victim], [len(batch_idx)])
        assert (req, aband, attempts) == (1, 0, [1])
        assert sampler.requeues == 1
        # the rest of the epoch re-serves the requeued items ...
        rest = np.concatenate([np.hstack(i) for _, i in it])
        for item in batch_idx:
            assert item in rest, "requeued item must be re-served"
        # ... so across the whole epoch the victim's items appear twice
        # (once dropped, once re-served) and everything else exactly once
        counts = np.bincount(np.concatenate([np.hstack(idx_lists), rest]),
                             minlength=len(ds))
        assert (counts[batch_idx] == 2).all()
        others = np.setdiff1d(np.arange(len(ds)), batch_idx)
        assert (counts[others] == 1).all()

    def test_retry_limit_abandons(self):
        ds = FakeDataset([4, 4])
        np.random.seed(0)
        sampler = FedSampler(ds, num_workers=2, local_batch_size=2,
                             retry_limit=1)
        next(sampler.iter_structured())
        assert sampler.requeue([0], [2])[0] == 1
        req, aband, attempts = sampler.requeue([0], [2])
        assert (req, aband) == (0, 1)
        assert sampler.abandoned == 1

    def test_quarantine_excludes_client(self):
        ds = FakeDataset([4, 4, 4])
        np.random.seed(0)
        sampler = FedSampler(ds, num_workers=1, local_batch_size=4)
        sampler.quarantine(1)
        served_clients = {int(w[0]) for w, _ in sampler.iter_structured()}
        assert 1 not in served_clients
        assert served_clients == {0, 2}
        np.testing.assert_array_equal(sampler.quarantined_clients, [1])

    def test_state_roundtrip_includes_participation_bookkeeping(self):
        """get_state/set_state round-trip retry + quarantine AND still
        replay the remainder of the epoch exactly — including a requeue
        taken before the capture point."""
        ds = FakeDataset([5, 7, 6, 4])
        np.random.seed(7)
        sampler = FedSampler(ds, num_workers=2, local_batch_size=3,
                             retry_limit=2)
        it = sampler.iter_structured()
        w0, idx0 = next(it)
        sampler.requeue([int(w0[0])], [len(idx0[0])])
        sampler.quarantine(3)
        next(it)
        state = sampler.get_state()
        rng_state = np.random.get_state()
        rest = [(w.copy(), np.hstack(i).copy()) for w, i in it]

        sampler2 = FedSampler(ds, num_workers=2, local_batch_size=3,
                              retry_limit=2)
        sampler2.set_state(state)
        np.testing.assert_array_equal(sampler2._retry, sampler._retry)
        np.testing.assert_array_equal(sampler2._quarantined,
                                      sampler._quarantined)
        np.random.set_state(rng_state)
        rest2 = [(w.copy(), np.hstack(i).copy())
                 for w, i in sampler2.iter_structured()]
        assert len(rest) == len(rest2)
        for (w1, i1), (w2, i2) in zip(rest, rest2):
            np.testing.assert_array_equal(w1, w2)
            np.testing.assert_array_equal(i1, i2)

    def test_legacy_state_without_new_keys_restores(self):
        """A pre-participation checkpoint's sampler state (permuted +
        cursor only) still restores — the new bookkeeping keeps its zero
        init."""
        ds = FakeDataset([4, 4])
        np.random.seed(0)
        sampler = FedSampler(ds, num_workers=2, local_batch_size=2)
        next(sampler.iter_structured())
        state = sampler.get_state()
        legacy = {"permuted": state["permuted"], "cursor": state["cursor"]}
        sampler2 = FedSampler(ds, num_workers=2, local_batch_size=2)
        sampler2.set_state(legacy)
        assert sampler2._retry.sum() == 0
        assert not sampler2._quarantined.any()


# ---------------------------------------------------------------------------
# controller: fault classification + ladder
# ---------------------------------------------------------------------------

class TestController:
    def test_apply_faults_matches_predicted_schedule(self):
        sched = FaultSchedule(drop=0.25, slow=0.25, corrupt=0.2, delay=1,
                              seed=13)
        ctl = ParticipationController(schedule=sched)
        W, rounds = 4, 12
        predicted = _predict_faults(sched, rounds, W)
        for rnd in range(rounds):
            batch = _host_batch(list(range(W)), seed=rnd)
            primary, late, info = ctl.apply_faults(batch, rnd)
            drop, slow, corrupt = predicted[rnd]
            if info.get("fault_skip"):
                assert primary is batch and late is None
                continue
            ontime = ~(drop | slow | corrupt)
            np.testing.assert_array_equal(
                primary["worker_mask"], ontime.astype(np.float32),
                err_msg=f"round {rnd} primary mask")
            # the per-datum mask is zeroed with the slot
            np.testing.assert_array_equal(
                primary["mask"], np.ones((W, 2), np.float32)
                * ontime.astype(np.float32)[:, None])
            if slow.any():
                assert late is not None
                np.testing.assert_array_equal(
                    late["worker_mask"], slow.astype(np.float32))
            else:
                assert late is None
            assert info.get("dropped", 0) == int(drop.sum())
            assert info.get("slow", 0) == int(slow.sum())
            assert info.get("corrupt", 0) == int(corrupt.sum())
        assert ctl.drops == sum(int(d.sum()) for d, _, _ in predicted)
        assert ctl.slows == sum(int(s.sum()) for _, s, _ in predicted)
        assert ctl.corrupts == sum(int(c.sum()) for _, _, c in predicted)

    def test_drop_requeues_into_sampler_and_corrupt_quarantines(self):
        """The ladder's data paths: a drop's items return to the epoch
        pool (cursor rollback via FedSampler.requeue); a repeat-corrupt
        client leaves the sampling pool (FedSampler.quarantine)."""
        ds = FakeDataset([32, 32, 32, 32])
        np.random.seed(0)
        sampler = FedSampler(ds, num_workers=4, local_batch_size=2,
                             retry_limit=3)
        it = sampler.iter_structured()

        sched = FaultSchedule(drop=0.4, corrupt=0.3, seed=1,
                              quarantine_after=2)
        ctl = ParticipationController(schedule=sched, sampler=sampler)
        for rnd in range(8):
            # draw a round from the live epoch, then fault it — the real
            # orchestration order (requeue rolls back what was JUST
            # consumed, so cursors never clamp at 0)
            workers, idx_lists = next(it)
            cursor_before = sampler._cursor.copy()
            batch = _host_batch(list(workers), seed=rnd)
            _, _, info = ctl.apply_faults(batch, rnd)
            # every requeued drop rolled its client's cursor back by its
            # batch size (2)
            rolled = (cursor_before - sampler._cursor)
            assert rolled.sum() == 2 * info.get("requeued", 0)
            assert (sampler._cursor >= 0).all()
        assert ctl.drops > 0 and ctl.corrupts > 0, \
            "seed must exercise both fault kinds"
        assert ctl.requeued == sampler.requeues
        assert ctl.requeued > 0
        # clients corrupted quarantine_after times left the pool — the
        # controller's corrupt ledger and the sampler's quarantine set
        # must agree
        assert ctl.quarantined == len(sampler.quarantined_clients)
        for c in sampler.quarantined_clients:
            assert ctl._corrupt_counts[int(c)] >= sched.quarantine_after

    def test_attach_participation(self):
        args = _args(participation="0.5", participation_sampling="weighted",
                     inject_client_fault="drop=0.1,seed=4",
                     client_retry_limit=2)
        fm = FedModel(TinyModel(), _loss, args, input_shape=(3,))
        ds = FakeDataset([4, 4, 4, 4])
        sampler = FedSampler(ds, 2, 2)
        ctl = attach_participation(args, fm, sampler=sampler)
        assert ctl is not None and fm._participation is ctl
        assert sampler.participation == 1  # ceil(0.5 * 2 workers)
        assert sampler.sampling == "weighted"
        assert sampler.retry_limit == 2
        assert ctl.schedule.drop == 0.1 and ctl.schedule.seed == 4
        # neither flag set -> no controller, legacy path untouched
        args2 = _args()
        fm2 = FedModel(TinyModel(), _loss, args2, input_shape=(3,))
        assert attach_participation(args2, fm2, sampler=None) is None
        assert fm2._participation is None


# ---------------------------------------------------------------------------
# round math: bit-identity, exact reweighting, late landing
# ---------------------------------------------------------------------------

class TestFullParticipationBitIdentity:
    @pytest.mark.parametrize("server_shard", [False, True],
                             ids=["replicated", "shard"])
    @pytest.mark.parametrize("fused", [False, True],
                             ids=["composed", "fused"])
    def test_matrix(self, monkeypatch, server_shard, fused):
        """Full participation + no faults through the attached layer is
        BIT-identical to the layer absent — the parity-matrix style pin
        the acceptance requires (replicated/--server_shard ×
        composed/--fused_epilogue)."""
        if fused:
            monkeypatch.setenv("COMMEFFICIENT_FUSED_EPILOGUE", "interpret")
        over = {}
        if server_shard:
            over.update(num_devices=2, server_shard=True)
        if fused:
            over["fused_epilogue"] = True
        runs = {}
        for layered in (False, True):
            ctl = (ParticipationController(schedule=None, target=2)
                   if layered else None)
            fm, opt, engine = _engine(controller=ctl, **over)
            if server_shard:
                assert fm._n_shard == 2
            for rnd in range(4):
                engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4],
                                          seed=rnd))
            runs[layered] = _flat_weights(fm)
        np.testing.assert_array_equal(runs[False], runs[True])


class TestExactReweighting:
    def test_partial_cohort_is_linear_split_of_full(self):
        """A missing client is an EXACT reweighting: the full round's
        transmit SUM equals live-subset sum + complement sum (sketches
        and dense reduces are linear), so the data-weighted mean over a
        partial cohort is exactly the mean over its members."""
        fm, opt, engine = _engine()
        batch = _host_batch([0, 1], seed=0)
        lr = fm._current_lr()
        rng = jax.random.key(0)

        def transmit_sum(keep):
            b = _mask_batch(batch, np.asarray(keep))
            jb = {k: jnp.asarray(v) for k, v in b.items()}
            ctx, _, _ = fm.steps.client_step(
                fm.ps_weights, fm.client_states, fm._model_state, jb, lr,
                rng)
            count = float(max(np.asarray(b["mask"]).sum(), 1.0))
            return np.asarray(ctx.gradient) * np.float32(count)

        s_full = transmit_sum([True, True])
        s_a = transmit_sum([True, False])
        s_b = transmit_sum([False, True])
        np.testing.assert_allclose(s_full, s_a + s_b, rtol=1e-5,
                                   atol=1e-6)


def _find_fault_seed(drop, slow, corrupt, delay, rounds, W):
    """A schedule seed whose predicted pattern exercises EVERY configured
    fault kind and lands at least one straggler inside the run — found by
    replaying the controller's own draw stream (deterministic)."""
    for seed in range(500):
        sched = FaultSchedule(drop=drop, slow=slow, corrupt=corrupt,
                              delay=delay, seed=seed)
        pattern = _predict_faults(sched, rounds, W)
        n_drop = sum(int(d.sum()) for d, _, _ in pattern)
        n_cor = sum(int(c.sum()) for _, _, c in pattern)
        slow_rounds = [r for r, (_, s, _) in enumerate(pattern)
                       if s.any()]
        if (n_drop and n_cor and slow_rounds
                and slow_rounds[0] + delay < rounds):
            return seed
    raise AssertionError("no suitable seed found")


def _find_slow_seed(slow_p, rounds, W, delay):
    """A schedule seed whose predicted pattern has at least one straggler
    cohort landing inside the run and at least one clean round — found by
    replaying the controller's own draw stream (deterministic)."""
    for seed in range(200):
        pattern = _predict_faults(FaultSchedule(slow=slow_p, delay=delay,
                                                seed=seed), rounds, W)
        slow_rounds = [r for r, (_, s, _) in enumerate(pattern) if s.any()]
        if slow_rounds and slow_rounds[0] + delay < rounds \
                and len(slow_rounds) < rounds:
            return seed, pattern
    raise AssertionError("no suitable seed found")


class TestLateLanding:
    @pytest.mark.parametrize("server_shard", [False, True],
                             ids=["replicated", "shard"])
    def test_trajectory_matches_hand_computed_reweighting(self,
                                                          server_shard):
        """The acceptance pin: drive the engine with a seeded slow-only
        schedule, and reproduce the IDENTICAL weight trajectory with a
        manually-orchestrated twin — masks derived by replaying the draw
        stream, the late transmit computed by a direct client_step call
        against the dispatch round's weights, and the fold applied by
        hand as the staleness-weighted data mean
        (S_now + w·S_late) / (C_now + w·C_late), w = decay**Δ."""
        rounds, W, delay, decay = 5, 2, 1, 0.5
        seed, pattern = _find_slow_seed(0.45, rounds, W, delay)
        sched = FaultSchedule(slow=0.45, delay=delay, seed=seed)
        over = {}
        if server_shard:
            over.update(num_devices=2, server_shard=True)

        ctl = ParticipationController(schedule=sched, decay=decay)
        fmA, optA, engineA = _engine(controller=ctl, **over)
        fmB, optB, engineB = _engine(**over)
        schedB = engineB.lr_scheduler

        pending = []  # [transmit_sum, count, dispatch_round]
        for rnd in range(rounds):
            batch = _host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd)
            engineA.submit(dict(batch))

            # ---- the hand-computed twin ----
            schedB.step()
            _, slow, _ = pattern[rnd]
            primary = _mask_batch(batch, ~slow)
            msB = fmB._model_state
            handleB = fmB.begin_round(primary)
            if slow.any():
                late = _mask_batch(batch, slow)
                jlate = {k: jnp.asarray(v) for k, v in late.items()}
                lctx, _, _ = fmB.steps.client_step(
                    fmB.ps_weights, fmB.client_states, msB, jlate,
                    fmB._current_lr(), jax.random.key(0))
                cl = float(np.asarray(late["mask"]).sum())
                s_late = (lctx.gradient if server_shard else
                          P._transmit_sum(lctx.gradient, np.float32(cl)))
                pending.append([s_late, cl, rnd])
            due = [p for p in pending if p[2] + delay <= rnd]
            pending = [p for p in pending if p[2] + delay > rnd]
            ctx = fmB._round_ctx
            c_now = float(max(np.asarray(primary["mask"]).sum(), 1.0))
            for s_late, cl, r0 in due:
                w = staleness_weight(rnd - r0, decay)
                if server_shard:
                    ctx = ctx._replace(
                        gradient=P._fold_sum(ctx.gradient, s_late,
                                             np.float32(w)),
                        count=P._add(ctx.count, np.float32(w * cl)))
                else:
                    ctx = ctx._replace(gradient=P._fold_mean(
                        ctx.gradient, np.float32(c_now), s_late,
                        np.float32(w * cl), np.float32(w)))
                    c_now = c_now + w * cl
            fmB._round_ctx = ctx
            optB.step()
            fmB.finish_round(handleB)

            np.testing.assert_array_equal(
                _flat_weights(fmA), _flat_weights(fmB),
                err_msg=f"round {rnd}: engine fold != hand-computed "
                        f"reweighting")
        assert ctl.slows > 0 and ctl.landed > 0, \
            "the seed must actually exercise a landing"

    def test_decay_one_with_immediate_landing_equals_full(self):
        """decay=1.0 + the landing round's fold reduce the straggler to a
        plain (late) data-mean participant: after the landing, the
        weighted mean over {on-time, late} cohorts with w=1 equals the
        mean the two cohorts would produce jointly. Pinned at the ctx
        level against a jointly-computed round."""
        fm, opt, engine = _engine()
        batch = _host_batch([0, 1], seed=0)
        lr = fm._current_lr()
        rng = jax.random.key(0)

        def ctx_for(b):
            jb = {k: jnp.asarray(v) for k, v in b.items()}
            return fm.steps.client_step(fm.ps_weights, fm.client_states,
                                        fm._model_state, jb, lr, rng)[0]

        full = np.asarray(ctx_for(batch).gradient)
        slow = np.array([False, True])
        primary = _mask_batch(batch, ~slow)
        late = _mask_batch(batch, slow)
        g_now = ctx_for(primary).gradient
        g_late = ctx_for(late).gradient
        c_now = float(np.asarray(primary["mask"]).sum())
        c_late = float(np.asarray(late["mask"]).sum())
        s_late = P._transmit_sum(g_late, np.float32(c_late))
        folded = np.asarray(P._fold_mean(g_now, np.float32(c_now), s_late,
                                         np.float32(1.0 * c_late),
                                         np.float32(1.0)))
        np.testing.assert_allclose(folded, full, rtol=1e-5, atol=1e-6)

    def test_expire_pending_counts(self):
        sched = FaultSchedule(slow=0.45, delay=50, seed=0)
        ctl = ParticipationController(schedule=sched)
        fm, opt, engine = _engine(controller=ctl)
        for rnd in range(6):
            engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd))
        assert ctl.slows > 0, "seed must produce stragglers"
        n_pending = len(ctl.pending)
        assert n_pending > 0, "delay=50 keeps every cohort pending"
        assert ctl.expire_pending() == n_pending
        assert ctl.expired == n_pending and not ctl.pending


class TestFaultLadderE2E:
    SCHED = "drop=0.2,slow=0.2,corrupt=0.15,delay=1,seed=6," \
            "quarantine_after=2"

    def _run(self, **over):
        ctl = ParticipationController(
            schedule=parse_client_fault(self.SCHED), decay=0.5)
        fm, opt, engine = _engine(controller=ctl, guards=True,
                                  snapshot_every=0, **over)
        for rnd in range(12):
            engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd))
        return fm, ctl

    def test_injected_run_completes_without_guard_quarantine(self):
        """The acceptance criterion: a seeded drop+straggler+corrupt run
        completes with ZERO guard trips — corrupt contributions are
        masked out of the within-round sum BEFORE the guard sees them
        (contrast --inject_fault, which trips the guard by design,
        tests/test_fault_tolerance.py), and every fault kind actually
        fired."""
        fm, ctl = self._run()
        assert fm.guard_trips == 0, \
            "client faults must never quarantine a round"
        assert np.all(np.isfinite(_flat_weights(fm)))
        c = ctl.counters()
        assert c["drops"] > 0 and c["slows"] > 0 and c["corrupts"] > 0, c
        assert c["landed"] > 0, "delay=1 stragglers must have landed"

    def test_trajectory_deterministic_under_rerun(self):
        fm1, ctl1 = self._run()
        fm2, ctl2 = self._run()
        np.testing.assert_array_equal(_flat_weights(fm1),
                                      _flat_weights(fm2))
        assert ctl1.counters() == ctl2.counters()


class TestZeroSyncAudit:
    def test_strict_no_syncs_with_participation_and_late_landing(self):
        """The zero-blocking-fetch invariant holds with the participation
        layer active: partial cohorts, fault classification, the
        straggler's extra client-phase dispatch AND the due-cohort fold
        are all dispatch-side work. Warm rounds compile every path
        (incl. the fold) first; then 5 monitored rounds must fetch
        nothing."""
        # a seed whose pattern has stragglers both in the warm-up rounds
        # (so the late dispatch + fold jits compile there) and in the
        # monitored window (so the audit covers live folds)
        rounds, W, delay = 10, 2, 1
        for seed in range(300):
            pattern = _predict_faults(FaultSchedule(slow=0.4, delay=delay,
                                                    seed=seed), rounds, W)
            warm = any(s.any() for _, s, _ in pattern[:3])
            monitored = any(s.any() for _, s, _ in pattern[5:9])
            if warm and monitored:
                break
        else:
            raise AssertionError("no suitable seed")
        sched = FaultSchedule(slow=0.4, delay=delay, seed=seed)
        ctl = ParticipationController(schedule=sched, decay=0.5,
                                      target=2)
        fm, opt, engine = _engine(drain_every=100, controller=ctl)
        for rnd in range(5):
            engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd))
        landed_before = ctl.landed
        with host_sync_monitor(strict=True) as counter:
            for rnd in range(5, 10):
                engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4],
                                          seed=rnd))
                assert counter.count == 0, \
                    f"round {rnd}: {counter.count} blocking host syncs " \
                    "with participation + late landing enabled"
        assert ctl.landed > landed_before, \
            "the monitored window must have folded a late cohort"
        engine.drain()


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------

class TestCheckpointState:
    def test_controller_state_roundtrips_and_run_continues_bit_exact(
            self, tmp_path):
        """save_run_state/load_run_state round-trip the fault RNG, the
        pending straggler buffer (device sums), and the counters; the
        restored run continues bit-identically to the uninterrupted
        one."""
        from commefficient_tpu.federated.checkpoint import (
            load_run_state,
            save_run_state,
        )

        sched = FaultSchedule(drop=0.15, slow=0.3, corrupt=0.1, delay=2,
                              seed=9)

        def fresh(seed_args=0):
            ctl = ParticipationController(schedule=sched, decay=0.5)
            return (*_engine(controller=ctl), ctl)

        fm1, opt1, engine1, ctl1 = fresh()
        for rnd in range(6):
            engine1.submit(_host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd))
        assert ctl1.slows > 0, "seed must produce stragglers"
        path = save_run_state(str(tmp_path / "rs"), fm1, opt1,
                              engine1.lr_scheduler, next_epoch=1)

        fm2, opt2, engine2, ctl2 = fresh()
        load_run_state(path, fm2, opt2, engine2.lr_scheduler)
        assert ctl2.counters() == ctl1.counters()
        assert len(ctl2.pending) == len(ctl1.pending)
        for a, b in zip(ctl1.pending, ctl2.pending):
            np.testing.assert_array_equal(np.asarray(a.transmit_sum),
                                          np.asarray(b.transmit_sum))
            assert (a.count, a.dispatch_round, a.due_round) == \
                (b.count, b.dispatch_round, b.due_round)
            np.testing.assert_array_equal(a.ids, b.ids)
        # the fault RNG stream continues identically: run both 4 more
        # rounds and compare weights bitwise
        for rnd in range(6, 10):
            batch = _host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd)
            engine1.submit(dict(batch))
            engine2.submit(dict(batch))
        np.testing.assert_array_equal(_flat_weights(fm1),
                                      _flat_weights(fm2))
        assert ctl1.counters() == ctl2.counters()

    def test_quarantine_survives_epoch_boundary_resume(self, tmp_path):
        """An epoch-boundary checkpoint carries NO sampler state, so the
        quarantine ledger must ride the controller's part/* meta: a
        known-bad client stays excluded after resume, and a restored
        corrupt count already past the threshold still (re-)quarantines
        on the next offense (>= trigger, not ==)."""
        from commefficient_tpu.federated.checkpoint import (
            load_run_state,
            save_run_state,
        )

        sched = FaultSchedule(corrupt=0.3, seed=0, quarantine_after=2)

        def fresh():
            ds = FakeDataset([8, 8, 8, 8])
            sampler = FedSampler(ds, num_workers=2, local_batch_size=2)
            ctl = ParticipationController(schedule=sched, sampler=sampler)
            fm, opt, engine = _engine(controller=ctl)
            return fm, opt, engine, ctl, sampler

        fm1, opt1, engine1, ctl1, sampler1 = fresh()
        # put the ladder in its post-quarantine state: client 3 corrupted
        # quarantine_after times and was quarantined
        ctl1._corrupt_counts[3] = sched.quarantine_after
        ctl1._quarantined_clients.add(3)
        sampler1.quarantine(3)
        engine1.submit(_host_batch([0, 1], seed=0))
        path = save_run_state(str(tmp_path / "rs"), fm1, opt1,
                              engine1.lr_scheduler, next_epoch=1)

        fm2, opt2, engine2, ctl2, sampler2 = fresh()
        load_run_state(path, fm2, opt2, engine2.lr_scheduler)
        assert ctl2.quarantined == 1
        assert 3 in ctl2._quarantined_clients
        np.testing.assert_array_equal(sampler2.quarantined_clients, [3])

        # >= trigger: a ledger restored WITHOUT the quarantine set (e.g.
        # hand-edited / partial meta) but with the corrupt count past the
        # threshold must still quarantine on the next offense
        ctl3 = ParticipationController(
            schedule=FaultSchedule(corrupt=0.9, slow=0.0, drop=0.0,
                                   seed=1, quarantine_after=2))
        ctl3._corrupt_counts[0] = 5  # past threshold, ledger empty
        batch = _host_batch([0, 0, 1], seed=0)
        for rnd in range(20):
            ctl3.apply_faults(batch, rnd)
            if ctl3.quarantined:
                break
        assert 0 in ctl3._quarantined_clients, \
            "a past-threshold client must still quarantine (== would " \
            "never fire again)"

    def test_inject_fault_resume_warns_about_global_rounds(self, tmp_path):
        """meta_json's rounds_dispatched makes --inject_fault rounds
        GLOBAL dispatch indices across a resume; entries already in the
        past must be called out instead of silently never firing."""
        from commefficient_tpu.federated.checkpoint import (
            load_run_state,
            save_run_state,
        )

        fm1, opt1, engine1 = _engine()
        for rnd in range(3):
            engine1.submit(_host_batch([0, 1], seed=rnd))
        path = save_run_state(str(tmp_path / "rs"), fm1, opt1,
                              engine1.lr_scheduler, next_epoch=1)
        fm2, opt2, engine2 = _engine(inject_fault="1:nan")
        with pytest.warns(UserWarning,
                          match=r"GLOBAL dispatch indices.*\[1\] are "
                                r"already in the past"):
            load_run_state(path, fm2, opt2, engine2.lr_scheduler)
        assert fm2._rounds_dispatched == 3

    def test_checkpoint_without_participation_warns_into_fault_run(
            self, tmp_path):
        from commefficient_tpu.federated.checkpoint import (
            load_run_state,
            save_run_state,
        )

        fm1, opt1, engine1 = _engine()
        engine1.submit(_host_batch([0, 1], seed=0))
        path = save_run_state(str(tmp_path / "rs"), fm1, opt1,
                              engine1.lr_scheduler, next_epoch=1)
        ctl = ParticipationController(
            schedule=FaultSchedule(drop=0.2, seed=1))
        fm2, opt2, engine2 = _engine(controller=ctl)
        with pytest.warns(UserWarning,
                          match="predates the participation layer"):
            load_run_state(path, fm2, opt2, engine2.lr_scheduler)
        # and the mirror image: participation checkpoint into a plain run
        path2 = save_run_state(str(tmp_path / "rs2"), fm2, opt2,
                               engine2.lr_scheduler, next_epoch=1)
        fm3, opt3, engine3 = _engine()
        with pytest.warns(UserWarning,
                          match="no participation layer attached"):
            load_run_state(path2, fm3, opt3, engine3.lr_scheduler)


@pytest.mark.heavy
class TestMidEpochResumeWithFaults:
    CKPT_ARGS = [
        "--dataset_name", "CIFAR10",
        "--num_epochs", "1", "--num_workers", "4",
        "--local_batch_size", "4", "--valid_batch_size", "8",
        "--lr_scale", "0.01", "--pivot_epoch", "0.5", "--seed", "0",
        "--iid", "--num_clients", "8",
        "--mode", "sketch", "--error_type", "virtual",
        "--local_momentum", "0", "--virtual_momentum", "0.9",
        "--k", "200", "--num_cols", "1024", "--num_rows", "3",
        "--num_blocks", "2",
        "--checkpoint", "--train_dataloader_workers", "0",
        # the participation layer under test: a partial weighted cohort
        # (2 of 4 slots live, so faults can fire without emptying the
        # round) plus the full seeded fault ladder, guards armed (they
        # must never trip — client faults are masked before the sum)
        "--participation", "0.5",
        "--participation_sampling", "weighted",
        "--inject_client_fault",
        "drop=0.2,slow=0.2,corrupt=0.1,delay=1,seed=5",
        "--staleness_decay", "0.5", "--client_retry_limit", "2",
        "--guards",
    ]

    def test_fault_injected_mid_epoch_resume_bit_exact(self, tmp_path,
                                                       monkeypatch, capsys,
                                                       fresh_compiles):
        """The satellite acceptance: a fault-injected, partial-cohort
        cv_train run checkpointed mid-epoch and resumed reproduces the
        uninterrupted run bit-for-bit — sampler retry/quarantine state,
        the controller's fault RNG, and the pending straggler buffer all
        ride the run state. And the guard never trips."""
        monkeypatch.setenv("COMMEFFICIENT_SYNTHETIC_PER_CLASS", "16")
        import cv_train
        from commefficient_tpu.federated.checkpoint import load_checkpoint

        common = self.CKPT_ARGS + ["--dataset_dir", str(tmp_path / "data")]
        s_full = cv_train.main(common + [
            "--checkpoint_path", str(tmp_path / "full"),
            "--checkpoint_every_rounds", "3"])
        ckpt = tmp_path / "full" / "run_state_ep1_r3.npz"
        assert ckpt.exists()
        # the scenario must be non-degenerate: the checkpoint's
        # participation ledger shows faults actually fired before the
        # save point (a single-member cohort would fault_skip every
        # faulted round and test nothing)
        with np.load(ckpt) as d:
            meta = json.loads(bytes(d["meta_json"]).decode())
        ctrs = meta["participation"]["counters"]
        assert ctrs["drops"] + ctrs["slows"] + ctrs["corrupts"] > 0, ctrs
        s_res = cv_train.main(common + [
            "--checkpoint_path", str(tmp_path / "res"),
            "--resume", str(tmp_path / "full" / "run_state_ep1_r3")])
        out = capsys.readouterr().out
        assert "HEALTH GUARD tripped" not in out, \
            "client faults must never quarantine a round"
        assert "participation layer:" in out

        p1, m1 = load_checkpoint(str(tmp_path / "full" / "ResNet9"))
        p2, m2 = load_checkpoint(str(tmp_path / "res" / "ResNet9"))
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(a, b), p1, p2)
        assert s_full["train_loss"] == s_res["train_loss"]
        assert s_full["test_acc"] == s_res["test_acc"]
        assert s_full["down (MiB)"] == s_res["down (MiB)"]
        assert s_full["up (MiB)"] == s_res["up (MiB)"]


# ---------------------------------------------------------------------------
# telemetry + obs_report
# ---------------------------------------------------------------------------

class TestTelemetryIntegration:
    @pytest.mark.parametrize("mode,error_type,plan", [
        ("sketch", "virtual", {"path": "xla"}),
        ("true_topk", "virtual", {"path": "xla"}),
        ("uncompressed", "none", None)])
    def test_run_start_records_topk_plan(self, tmp_path, mode, error_type,
                                         plan):
        """The run header says how the run resolves its top-k threshold
        (ops/topk.topk_plan has the rule; tests/test_ops.py holds it to
        the cells' sizes); a mode with no top-k has no plan."""
        from commefficient_tpu.telemetry import attach_run_telemetry

        args = _args(telemetry=True, mode=mode, error_type=error_type)
        fm = FedModel(TinyModel(), _loss, args, input_shape=(3,))
        attach_run_telemetry(args, fm, str(tmp_path), "test").close()
        start = next(read_events(str(tmp_path / "telemetry.jsonl")))
        assert start.get("topk_plan") == plan

    def test_run_start_records_participation_config(self, tmp_path):
        """The satellite bugfix: the run header carries the participation
        config (fraction, sampling, decay, fault schedule incl. seed) so
        a logged run is reproducible from the header alone — like
        --collective_plan already is."""
        from commefficient_tpu.telemetry import attach_run_telemetry

        args = _args(telemetry=True, participation="0.5",
                     participation_sampling="stratified",
                     staleness_decay=0.25,
                     inject_client_fault="drop=0.1,slow=0.2,delay=3,"
                                         "seed=11")
        fm = FedModel(TinyModel(), _loss, args, input_shape=(3,))
        rt = attach_run_telemetry(args, fm, str(tmp_path), "test")
        rt.close()
        events = list(read_events(str(tmp_path / "telemetry.jsonl")))
        start = events[0]
        assert start["ev"] == "run_start"
        assert start["participation"] == "0.5"
        assert start["participation_sampling"] == "stratified"
        assert start["staleness_decay"] == 0.25
        cf = start["client_fault"]
        assert cf["drop"] == 0.1 and cf["slow"] == 0.2
        assert cf["delay"] == 3 and cf["seed"] == 11
        # no participation flags -> explicit full-participation header
        args2 = _args(telemetry=True)
        fm2 = FedModel(TinyModel(), _loss, args2, input_shape=(3,))
        rt2 = attach_run_telemetry(args2, fm2, str(tmp_path / "b"), "test")
        rt2.close()
        start2 = next(read_events(str(tmp_path / "b" / "telemetry.jsonl")))
        assert start2["participation"] == "1.0"
        assert start2["client_fault"] is None

    def test_obs_report_reproduces_participation_history(self, tmp_path,
                                                         capsys):
        """The satellite acceptance (mirrors PR 6's drill): a
        fault-injected run's participation history — cohort sizes, drop/
        straggler/corrupt counts, retry ladder, staleness histogram —
        reproduces from the JSONL log ALONE, matching the live
        controller's counters."""
        ds = FakeDataset([8, 8, 8, 8])
        np.random.seed(0)
        sampler = FedSampler(ds, num_workers=2, local_batch_size=2,
                             retry_limit=1)
        next(sampler.iter_structured())  # arm the epoch for requeues
        seed = _find_fault_seed(0.25, 0.25, 0.15, 1, rounds=14, W=2)
        sched = parse_client_fault(
            f"drop=0.25,slow=0.25,corrupt=0.15,delay=1,seed={seed},"
            "quarantine_after=2")
        ctl = ParticipationController(schedule=sched, decay=0.5,
                                      sampler=sampler, target=2)
        fm, opt, engine = _engine(drain_every=1, controller=ctl,
                                  telemetry=True)
        rt = RunTelemetry(
            str(tmp_path / "telemetry.jsonl"),
            run_info={"mode": fm.args.mode, "grad_size": fm.grad_size,
                      "guards": False,
                      "participation": "1.0",
                      "participation_sampling": "uniform",
                      "staleness_decay": 0.5,
                      "client_fault": {"spec": sched.spec()},
                      "ledger": collective_ledger(fm.args.mode,
                                                  fm.grad_size,
                                                  sketch=fm.sketch)})
        fm.telemetry = rt
        engine.telemetry = rt
        for rnd in range(14):
            engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd))
        engine.drain()
        expired = ctl.expire_pending()
        if expired:
            rt.event("straggler_expired", count=expired)
        rt.close()
        c = ctl.counters()
        assert c["drops"] and c["slows"] and c["corrupts"] and c["landed"]

        import obs_report

        events = obs_report.load_events(str(tmp_path))
        s = obs_report.summarize(events)["participation"]
        assert s["dropped"] == c["drops"]
        assert s["slow"] == c["slows"]
        assert s["corrupt"] == c["corrupts"]
        assert s["landed"] == c["landed"]
        assert s["expired"] == ctl.expired
        assert s["requeued"] == c["requeued"]
        assert s["abandoned"] == c["abandoned"]
        assert s["quarantined"] == c["quarantined"]
        assert s["cohort_target"] == 2
        assert s["client_fault"]["spec"] == sched.spec()
        assert sum(s["staleness_hist"].values()) == c["landed"]
        assert sum(s["retry_ladder"].values()) == c["requeued"]

        rc = obs_report.main([str(tmp_path / "telemetry.jsonl")])
        assert rc == 0
        out = capsys.readouterr().out
        assert "## Participation" in out
        tail = json.loads(out.strip().splitlines()[-1])
        assert tail["participation"]["dropped"] == c["drops"]
