"""Zero-sync telemetry plane (docs/observability.md).

Pins the four contracts of the telemetry PR:

- **Non-perturbation**: fp32 round trajectories are BIT-identical with
  telemetry on vs off, on both the replicated and ``--server_shard``
  planes (the device metrics are pure reductions — nothing feeds back
  into the state transition).
- **Zero syncs**: 5 steady-state rounds through the engine with
  ``--guards`` AND ``--telemetry`` on perform zero blocking device→host
  transfers under ``host_sync_monitor(strict=True)`` — the metrics vector
  rides the round handle to the batched drain exactly like the guard
  verdict.
- **Event log**: every drained round lands one ``round`` JSONL line with
  the fixed METRIC_FIELDS schema and lifecycle spans; guard trips /
  rollbacks land their own immediate events.
- **obs_report**: the guard-trip/rollback history of a fault-injected run
  is reproducible from the JSONL log ALONE (scripts/obs_report.py), and
  its machine-readable tail parses.

Plus the satellite contract: the engine-owned heartbeat carries the
global telemetry round index.
"""

import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flax.linen as nn

from commefficient_tpu.federated.aggregator import (
    FedModel,
    FedOptimizer,
    LambdaLR,
)
from commefficient_tpu.federated.engine import PipelinedRoundEngine
from commefficient_tpu.federated.rounds import (
    RoundConfig,
    build_round_step,
    init_client_states,
)
from commefficient_tpu.federated.server import ServerConfig, init_server_state
from commefficient_tpu.federated.worker import WorkerConfig
from commefficient_tpu.ops.flat import ravel_pytree
from commefficient_tpu.ops.sketch import make_sketch
from commefficient_tpu.profiling import Heartbeat, host_sync_monitor
from commefficient_tpu.telemetry import (
    METRIC_FIELDS,
    RunTelemetry,
    collective_ledger,
    metric_schema,
    read_events,
)

# this suite pins the v2 SCALAR contracts (the schema-v3 histogram block
# is tests/test_watch.py's); the steps here build with telemetry_hist off
SCALAR_FIELDS = metric_schema(False)

_SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")
if _SCRIPTS not in sys.path:
    sys.path.insert(0, _SCRIPTS)

D = 4
# 6 worker slots, NOT test_engine's 8: the donation-aliasing test over
# there is only meaningful on a FRESH compile (jax 0.4.37 drops the
# aliasing metadata on a compile-cache hit — see test_engine's
# fresh_compiles fixture), so this suite must never compile the identical
# HLO first and seed the shared persistent cache with it
W = 6


def _linear_loss(params, model_state, batch, rng, train):
    w = params["w"]
    pred = batch["inputs"] @ w
    err = pred - batch["targets"]
    mask = batch["mask"]
    return jnp.sum(0.5 * err ** 2 * mask), (jnp.sum(jnp.abs(err) * mask),), \
        jnp.sum(mask), model_state


def _vec_batch(num_workers=W, bs=2, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "inputs": jnp.asarray(rng.randn(num_workers, bs, D), jnp.float32),
        "targets": jnp.asarray(rng.randn(num_workers, bs), jnp.float32),
        "mask": jnp.ones((num_workers, bs), jnp.float32),
        "client_ids": jnp.arange(num_workers, dtype=jnp.int32),
        "worker_mask": jnp.ones(num_workers, jnp.float32),
    }


def _sketch_steps(telemetry: bool, server_shard: bool = False,
                  guards: bool = False, mesh=None):
    params = {"w": jnp.zeros(D)}
    flat, unravel = ravel_pytree(params)

    def ravel(tree):
        return ravel_pytree(tree)[0]

    n_workers = 8 if server_shard else W  # shard plane: divisible by mesh
    wcfg = WorkerConfig(mode="sketch", error_type="virtual", k=2,
                        num_workers=n_workers)
    scfg = ServerConfig(mode="sketch", error_type="virtual", k=2,
                        grad_size=D, virtual_momentum=0.9,
                        local_momentum=0.0)
    sketch = make_sketch(D, 16, 3, seed=0, num_blocks=1)
    cfg = RoundConfig(worker=wcfg, server=scfg, grad_size=D,
                      telemetry=telemetry, server_shard=server_shard,
                      guards=guards)
    steps = build_round_step(_linear_loss, _linear_loss, unravel, ravel,
                             cfg, sketch=sketch, mesh=mesh)
    ps = steps.layout.chunk(flat)
    n_shard = mesh.shape["clients"] if (server_shard and mesh) else 0
    server_state = init_server_state(scfg, sketch, shard_n=n_shard)
    if mesh is not None:
        from commefficient_tpu.federated.server import place_server_state

        server_state = place_server_state(server_state, mesh, "sketch",
                                          server_shard)
    client_states = init_client_states(16, D, wcfg, init_weights=flat,
                                       sketch=sketch)
    return steps, ps, server_state, client_states


def _run_trajectory(steps, ps, ss, cs, rounds=4, telemetry=False,
                    guards=False, num_workers=W):
    state = (ps, ss, cs, {})
    traj, metrics = [], []
    for rnd in range(rounds):
        out = steps.train_step(state[0], state[1], state[2], state[3],
                               _vec_batch(num_workers, seed=rnd), 0.1,
                               jax.random.key(rnd))
        state = out[:4]
        traj.append(np.asarray(steps.layout.unchunk(state[0])))
        if telemetry:
            tel = out[5 + (1 if guards else 0)]
            assert tel.shape == (len(SCALAR_FIELDS),)
            metrics.append(np.asarray(tel))
    return traj, metrics


class TestNonPerturbation:
    def test_trajectory_bit_identical_replicated(self):
        """fp32 trajectories with telemetry on are BIT-identical to
        telemetry off on the replicated plane (and the guard+telemetry
        combination unpacks in the documented order)."""
        runs = {}
        for tel in (False, True):
            steps, ps, ss, cs = _sketch_steps(telemetry=tel)
            runs[tel], ms = _run_trajectory(steps, ps, ss, cs,
                                            telemetry=tel)
        for rnd, (a, b) in enumerate(zip(runs[False], runs[True])):
            np.testing.assert_array_equal(a, b, err_msg=f"round {rnd}")

        steps, ps, ss, cs = _sketch_steps(telemetry=True, guards=True)
        traj, ms = _run_trajectory(steps, ps, ss, cs, telemetry=True,
                                   guards=True)
        for rnd, (a, b) in enumerate(zip(runs[False], traj)):
            np.testing.assert_array_equal(a, b,
                                          err_msg=f"guarded round {rnd}")
        fields = dict(zip(SCALAR_FIELDS, ms[-1]))
        assert fields["guard_ok"] == 1.0
        assert fields["update_nnz"] >= 1
        assert fields["ps_norm"] > 0

    @pytest.mark.skipif(jax.device_count() < 8,
                        reason="needs the forced-8-device CPU mesh")
    def test_trajectory_bit_identical_server_shard(self):
        """Same bit-identity on the sharded server plane: the telemetry
        reductions over the stacked pre-reduce transmit and the sharded
        state slices must not perturb the sharded update either."""
        from commefficient_tpu.parallel.mesh import default_client_mesh

        runs = {}
        for tel in (False, True):
            mesh = default_client_mesh(8, 8)
            steps, ps, ss, cs = _sketch_steps(telemetry=tel,
                                              server_shard=True, mesh=mesh)
            runs[tel], _ = _run_trajectory(steps, ps, ss, cs, telemetry=tel,
                                           num_workers=8)
        for rnd, (a, b) in enumerate(zip(runs[False], runs[True])):
            np.testing.assert_array_equal(a, b, err_msg=f"round {rnd}")
        # (sharded-vs-replicated plane identity itself is
        # tests/test_sharded_server.py's contract — this test pins only
        # that telemetry does not perturb the sharded plane)


# ---- FedModel/engine-level fixtures (mirrors test_engine.py) -------------

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
        seq_parallel="none", seq_devices=1, telemetry=True,
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


def _engine(tmp_path, window=2, drain_every=8, heartbeat=None, **over):
    fm = FedModel(TinyModel(), _loss, _args(**over), input_shape=(3,))
    opt = FedOptimizer(fm, fm.args)
    sched = LambdaLR(opt, lambda step: 0.5)
    rt = RunTelemetry(str(tmp_path / "telemetry.jsonl"),
                      run_info={"mode": fm.args.mode,
                                "grad_size": fm.grad_size,
                                "guards": bool(getattr(fm.args, "guards",
                                                       False)),
                                "ledger": collective_ledger(
                                    fm.args.mode, fm.grad_size,
                                    sketch=fm.sketch)})
    fm.telemetry = rt
    engine = PipelinedRoundEngine(fm, opt, sched, window=window,
                                  drain_every=drain_every,
                                  heartbeat=heartbeat)
    return fm, engine, rt


class TestSyncAudit:
    def test_zero_syncs_strict_with_guards_and_telemetry(self, tmp_path):
        """The acceptance audit: guards AND telemetry on, strict monitor —
        5 steady-state engine rounds perform ZERO blocking device→host
        transfers; the batched drain is the one counted fetch and every
        drained round lands a schema-complete event line."""
        fm, engine, rt = _engine(tmp_path, drain_every=10, guards=True,
                                 snapshot_every=4, max_guard_trips=3,
                                 guard_max_abs=0.0)
        engine.submit(_host_batch([0, 1], seed=0))  # compile round
        with host_sync_monitor(strict=True) as counter:
            for rnd in range(1, 6):
                done = engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4],
                                                 seed=rnd))
                assert done == [], "must not drain before drain_every"
                assert counter.count == 0, \
                    f"round {rnd}: {counter.count} blocking host syncs " \
                    "with guards+telemetry enabled"
            results = engine.drain()
            assert len(results) == 6
            assert counter.count > 0, \
                "drain must go through the counted materialize seam"
        rt.close()
        assert fm.guard_trips == 0

        events = list(read_events(str(tmp_path / "telemetry.jsonl")))
        rounds = [e for e in events if e["ev"] == "round"]
        assert [e["round"] for e in rounds] == list(range(6))
        for e in rounds:
            assert set(e["metrics"]) == set(SCALAR_FIELDS)
            assert e["guard_ok"] is True
            assert e["metrics"]["guard_ok"] == 1.0
            assert "dispatch_ms" in e and "drain_fetch_ms" in e
            assert "dispatch_to_drain_ms" in e and "occupancy" in e
            assert isinstance(e.get("loss"), float)
            # cohort staleness hook: the multi-epoch accounting regime
            # tracks per-client participation, so every round event
            # carries the participation/staleness summary
            assert e["cohort"]["participants"] == 2
            assert "staleness_mean" in e["cohort"]
        # rounds past the window carry the completion stamp from the
        # engine's window wait
        assert any("compute_ms" in e for e in rounds)
        kinds = [e["ev"] for e in events]
        assert kinds[0] == "run_start" and kinds[-1] == "run_end"
        assert "drain" in kinds

    def test_engine_heartbeat_carries_global_round_index(self, tmp_path,
                                                         capfd):
        """The engine-owned heartbeat (scripts/crash_matrix.py's kill
        anchor) emits the model's GLOBAL dispatch index — monotonic across
        engine instances, 0-based — not a per-engine counter."""
        fm, engine, rt = _engine(tmp_path, drain_every=1,
                                 heartbeat=Heartbeat(enabled=True))
        for rnd in range(3):
            engine.submit(_host_batch([0, 1], seed=rnd))
        # a SECOND engine over the same model (the per-epoch pattern of
        # cv_train.run_batches) continues the same index space
        opt = engine.opt
        engine2 = PipelinedRoundEngine(fm, opt, engine.lr_scheduler,
                                       drain_every=1,
                                       heartbeat=Heartbeat(enabled=True))
        engine2.submit(_host_batch([0, 1], seed=3))
        rt.close()
        err = capfd.readouterr().err
        lines = [ln for ln in err.splitlines()
                 if ln.startswith("HEARTBEAT")]
        # the leading round=N field is the supervisor contract
        # (crash_matrix parses it); the mean-loss extra appends after it
        # (guard verdict absent — guards are off here) so a heartbeat
        # tail is a minimal live monitor even with telemetry off
        assert [ln.split()[1] for ln in lines] == \
            [f"round={i}" for i in range(4)], lines
        assert all(ln.split()[2].startswith("loss=") for ln in lines), \
            lines


class TestEventLog:
    def test_drain_parity_with_telemetry(self, tmp_path):
        """Telemetry must not disturb the drained training values:
        batched drains return the same losses/bytes as drain_every=1."""
        def run(drain_every, sub):
            fm, engine, rt = _engine(tmp_path / sub,
                                     drain_every=drain_every)
            results = []
            for rnd in range(6):
                results.extend(engine.submit(
                    _host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd)))
            results.extend(engine.drain())
            rt.close()
            return results

        (tmp_path / "a").mkdir()
        (tmp_path / "b").mkdir()
        per_round = run(1, "a")
        batched = run(4, "b")
        for ref, got in zip(per_round, batched):
            for r, g in zip(ref.values, got.values):
                np.testing.assert_array_equal(r, g)

    def test_collective_ledger(self):
        sketch = make_sketch(1000, 128, 3, seed=0, num_blocks=1)
        led = collective_ledger("sketch", 1000, sketch=sketch)
        assert led["client_uplink"]["bytes_per_round"] == \
            4 * sketch.r * sketch.c_pad
        assert led["transmit_reduce"]["collective"] == "psum"
        # int8 transmit: strictly fewer bytes than f32, more than 1 B/elem
        led8 = collective_ledger("sketch", 1000, sketch=sketch, n_shard=8,
                                 reduce_dtype="int8")
        f32b = led["transmit_reduce"]["bytes_per_round"]
        i8b = led8["transmit_reduce"]["bytes_per_round"]
        assert sketch.r * sketch.c_pad < i8b < f32b / 3
        assert "update_all_gather" in led8 and "threshold_exchange" in led8
        # dense sharded plane pads d to the shard multiple
        ledd = collective_ledger("true_topk", 1000, n_shard=8)
        assert ledd["transmit_reduce"]["elements"] == 1000
        assert ledd["update_all_gather"]["elements"] == 1000
        ledd = collective_ledger("true_topk", 1001, n_shard=8)
        assert ledd["update_all_gather"]["elements"] == 1008

    def test_torn_tail_is_skipped(self, tmp_path):
        path = tmp_path / "t.jsonl"
        path.write_text(json.dumps({"ev": "run_start"}) + "\n"
                        + json.dumps({"ev": "round", "round": 0}) + "\n"
                        + '{"ev": "round", "rou')
        events = list(read_events(str(path)))
        assert [e["ev"] for e in events] == ["run_start", "round"]


class TestObsReport:
    def test_reproduces_fault_history_from_log_alone(self, tmp_path,
                                                     capsys):
        """The acceptance drill: a fault-injected run's guard-trip history
        must be reconstructible by scripts/obs_report.py from the JSONL
        log ALONE, and the machine-readable tail must parse as strict
        JSON."""
        fm, engine, rt = _engine(tmp_path, drain_every=10, guards=True,
                                 snapshot_every=4, max_guard_trips=5,
                                 inject_fault="2:nan,4:inf")
        for rnd in range(7):
            engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd))
        engine.drain()
        rt.close()
        assert fm.guard_trips == 2  # rounds 2 and 4 were poisoned

        import obs_report

        events = obs_report.load_events(str(tmp_path))
        summary = obs_report.summarize(events)
        assert summary["guard_trips"] == fm.guard_trips
        assert summary["tripped_rounds"] == [2, 4]
        assert summary["rollbacks"] == 0 and summary["fatal"] is False
        assert summary["log_rounds"] == 7

        # quarantined rounds carry the poisoned transmit detail; the
        # non-finite norm is string-encoded ('nan'/'inf') so every log
        # line stays strict RFC-8259 JSON — float() round-trips it
        rounds = {e["round"]: e for e in events if e["ev"] == "round"}
        assert rounds[2]["guard_ok"] is False
        poisoned = rounds[2]["metrics"]["transmit_norm"]
        assert isinstance(poisoned, str)
        assert not np.isfinite(float(poisoned))
        assert rounds[3]["guard_ok"] is True

        # the CLI renders and its LAST stdout line is strict JSON
        rc = obs_report.main([str(tmp_path / "telemetry.jsonl")])
        assert rc == 0
        out = capsys.readouterr().out
        tail = json.loads(out.strip().splitlines()[-1])
        assert tail["guard_trips"] == 2
        assert tail["tripped_rounds"] == [2, 4]
        assert "guard TRIP at round 2" in out
        assert "guard TRIP at round 4" in out
