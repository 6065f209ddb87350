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
from commefficient_tpu import profiling
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


class _MemoryDevice:
    """A device whose ``memory_stats()`` reads like a TPU's (the CPU's is
    None): the drain's memory sample with something to read."""

    calls = 0

    def memory_stats(self):
        type(self).calls += 1
        return {"bytes_in_use": 1 << 20, "peak_bytes_in_use": 2 << 20,
                "bytes_reserved": 0, "peak_bytes_reserved": 3 << 20,
                "largest_free_block_bytes": 1 << 30, "num_allocs": 7}


class TestSyncAudit:
    @pytest.mark.parametrize("memory", ["cpu_none", "device_sample"])
    def test_zero_syncs_strict_with_guards_and_telemetry(self, tmp_path,
                                                         monkeypatch,
                                                         memory):
        """The acceptance audit: guards AND telemetry on, strict monitor —
        5 steady-state engine rounds perform ZERO blocking device→host
        transfers; the batched drain is the one counted fetch and every
        drained round lands a schema-complete event line. With the
        drain's memory sample on a device that reports (``memory_stats()``
        is a host call into the runtime, no fetch) as without."""
        if memory == "device_sample":
            monkeypatch.setattr(profiling, "_local_devices",
                                lambda: [_MemoryDevice()])
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
        (drain,) = [e for e in events if e["ev"] == "drain"]
        assert drain["round"] == 5 and drain["inflight"] == 0
        if memory == "device_sample":
            assert drain["memory"]["peak_bytes_reserved"] == 3 << 20
            assert events[-1]["memory"]["at"] == "run_end"
        else:
            assert drain["memory"] is None and events[-1]["memory"] is None

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

    def test_memory_samples_feed_nothing_back(self, tmp_path, monkeypatch):
        """fp32 trajectory bit-identical with the event log, its drain
        samples and the program listener on, or with no recorder at all."""
        def run(sub, recorded):
            if recorded:
                monkeypatch.setattr(profiling, "_local_devices",
                                    lambda: [_MemoryDevice()])
                profiling.install_program_listener()
            fm, engine, rt = _engine(tmp_path / sub, drain_every=3)
            if recorded:
                rt.setup(profiling.PHASES, profiling.PROCESS_START_T)
            else:
                rt.close()
                fm.telemetry = engine.telemetry = None
            for rnd in range(7):
                engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4],
                                          seed=rnd))
            engine.drain()
            rt.close()
            return np.asarray(fm.ps_weights)

        (tmp_path / "on").mkdir()
        (tmp_path / "off").mkdir()
        calls = _MemoryDevice.calls
        on = run("on", True)
        assert _MemoryDevice.calls >= calls + 3     # 3 drains + run_end
        monkeypatch.undo()
        np.testing.assert_array_equal(on, run("off", False))

    def test_setup_event_then_the_programs_built(self, tmp_path,
                                                 monkeypatch):
        """``setup`` once, right after ``run_start``; then every program
        built before it, then each build as it closes: a line of its own
        from ``PROGRAM_LOG_S`` up, the small ones summed under ``other``
        before the next line of any kind; ``run_end.programs`` by name."""
        from commefficient_tpu import telemetry

        profiling.install_program_listener()
        profiling.begin_setup()
        with profiling.phase("model"):
            jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(3))   # `<lambda>`
        rt = RunTelemetry(str(tmp_path / "telemetry.jsonl"))
        rt._dispatched = 41
        rt.setup(profiling.PHASES, profiling.PROCESS_START_T)
        # (the line between small and logged, out of a loaded machine's way)
        monkeypatch.setattr(telemetry, "PROGRAM_LOG_S", 1e9)

        @jax.jit
        def lifecycle_small(x):
            return x - 2.0

        lifecycle_small(jnp.ones(3))
        rt.event("checkpoint", round=41)
        monkeypatch.setattr(telemetry, "PROGRAM_LOG_S", 0.0)

        @jax.jit
        def lifecycle_logged(x):
            return x / 3.0

        lifecycle_logged(jnp.ones(3))
        rt.close()
        events = list(read_events(str(tmp_path / "telemetry.jsonl")))
        kinds = [e["ev"] for e in events]
        assert kinds[:2] == ["run_start", "setup"]
        assert kinds.count("setup") == 1 and kinds[-1] == "run_end"
        setup = events[1]
        assert setup["t0"] == profiling.PROCESS_START_T
        assert [p["phase"] for p in setup["phases"]][-1] == "model"
        assert setup["phases"][-1]["programs"] >= 1
        progs = [e for e in events if e["ev"] == "program"]
        # the backlog: built before the log opened, no round
        past = [e for e in progs if e["round"] is None]
        assert past and any(e["phase"] == "model" for e in past)
        # a small build after it: summed, written before the checkpoint
        live = [e for e in progs if e["round"] == 41]
        small = [e for e in live if e["name"] == "other"]
        assert small and small[0]["builds"] >= 1
        assert kinds.index("checkpoint") > events.index(small[0])
        assert not [e for e in progs if "lifecycle_small" in e["name"]]
        (logged,) = [e for e in live
                     if e["name"] == "jit(lifecycle_logged)"]
        assert logged["cache"] in ("hit", "miss", "off")
        assert logged["backend_s"] > 0 and logged["phase"] is None
        assert logged["t"] <= events[-1]["t"]
        end = events[-1]
        assert "jit(lifecycle_logged)" in profiling.program_totals()
        assert sum(t["builds"] for t in end["programs"].values()) == \
            sum(t["builds"] for t in profiling.program_totals().values())
        # closed: a later build goes nowhere and raises nothing
        jax.jit(lambda x: x + 7.0)(jnp.ones(2))

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

    @staticmethod
    def _lifecycle_log():
        """A run's record of its own start-up and memory, as the program
        writes it (profiling.py): hand-made, small."""
        def mem(at, in_use, peak, reserved_peak):
            return {"at": at, "bytes_in_use": in_use,
                    "peak_bytes_in_use": peak, "bytes_reserved": 0,
                    "peak_bytes_reserved": reserved_peak,
                    "largest_free_block_bytes": 1 << 33, "num_allocs": 9}

        gib = 1 << 30
        return [
            {"ev": "run_start", "t": 100.0, "mode": "sketch"},
            {"ev": "setup", "t": 100.1, "t0": 80.0, "phases": [
                {"phase": "import", "start_s": 0.0, "seconds": 6.0,
                 "programs": 0, "build_s": 0.0, "memory": None},
                {"phase": "data", "start_s": 6.0, "seconds": 9.0,
                 "programs": 0, "build_s": 0.0,
                 "memory": mem("phase:data", 0, 0, 0)},
                {"phase": "fed", "start_s": 15.0, "seconds": 5.0,
                 "programs": 40, "build_s": 3.5,
                 "memory": mem("phase:fed", gib, gib + 5, 0)}]},
            {"ev": "program", "t": 96.0, "round": None, "phase": "fed",
             "name": "jit(init)", "trace_s": 1.0, "lower_s": 0.5,
             "backend_s": 1.5, "cache": "hit", "load_s": 1.2},
            {"ev": "program", "t": 97.0, "round": None, "phase": "fed",
             "name": "other", "builds": 39, "hits": 39, "trace_s": 0.1,
             "lower_s": 0.2, "backend_s": 0.2},
            {"ev": "program", "t": 103.0, "round": 0, "phase": None,
             "name": "jit(client_step)", "trace_s": 4.0, "lower_s": 2.0,
             "backend_s": 60.0, "cache": "miss", "stored": True},
            {"ev": "round", "t": 104.0, "round": 0, "t_dispatch": 100.2},
            {"ev": "round", "t": 104.0, "round": 1, "t_dispatch": 103.5},
            {"ev": "drain", "t": 104.1, "round": 1, "rounds": 2,
             "inflight": 0,
             "memory": mem("drain", 2 * gib, 3 * gib, 4 * gib)},
            {"ev": "val", "t": 106.0, "round": 2, "seconds": 1.5,
             "memory_start": mem("val_start", 2 * gib, 3 * gib, 4 * gib),
             "memory_end": mem("val_end", 2 * gib, 3 * gib, 5 * gib)},
            {"ev": "program", "t": 106.5, "round": 2, "phase": None,
             "name": "jit(val_step)", "trace_s": 0.2, "lower_s": 0.1,
             "backend_s": 0.4, "cache": "hit", "load_s": 0.3},
            {"ev": "program", "t": 108.0, "round": 3, "phase": None,
             "name": "jit(client_step)", "trace_s": 4.0, "lower_s": 2.0,
             "backend_s": 0.9, "cache": "hit", "load_s": 0.8},
            {"ev": "round", "t": 109.0, "round": 2, "t_dispatch": 107.0},
            {"ev": "round", "t": 109.0, "round": 3, "t_dispatch": 108.5},
            {"ev": "drain", "t": 109.1, "round": 3, "rounds": 2,
             "inflight": 0,
             "memory": mem("drain", 2 * gib + 64, 3 * gib, 5 * gib)},
            {"ev": "run_end", "t": 110.0, "rounds": 4, "spans": {},
             "programs": {},
             "memory": mem("run_end", 2 * gib + 64, 3 * gib, 5 * gib)},
        ]

    def test_start_up_and_memory_sections_from_the_log_alone(self):
        import io

        import obs_report

        out = io.StringIO()
        s = obs_report.render(self._lifecycle_log(), out=out)
        st, mem = s["startup"], s["memory"]
        assert [p["phase"] for p in st["phases"]] == ["import", "data",
                                                      "fed"]
        assert st["setup_s"] == 20.0
        assert st["programs"]["jit(client_step)"] == {
            "builds": 2, "trace_s": 8.0, "lower_s": 4.0, "backend_s": 60.9,
            "hits": 1, "misses": 1}
        assert st["programs"]["other"]["builds"] == 39
        assert st["trace_lower_s"] == 14.1 and st["backend_s"] == 63.0
        assert (st["cache_hits"], st["cache_misses"]) == (42, 1)
        # past the first drain (round 1): the first validation pass's
        # program is a late first build, client_step built again a
        # recompile
        assert [(b["round"], b["name"], b["recompile"])
                for b in st["steady_state_builds"]] == [
            (2, "jit(val_step)", False), (3, "jit(client_step)", True)]
        assert st["steady_state_recompiles"] == 1
        gib = 1 << 30
        assert mem["at_rest_bytes"] == 2 * gib + 64
        assert mem["peak_bytes_in_use"] == 3 * gib
        assert mem["peak_bytes_in_use_last_rose"] == "drain at round 1"
        assert mem["peak_bytes_reserved"] == 5 * gib
        assert mem["peak_bytes_reserved_last_rose"] == \
            "validation end (round 2)"
        text = out.getvalue()
        assert "## Start-up" in text and "## Memory" in text
        assert "STEADY-STATE RECOMPILE at round 3: jit(client_step)" in text
        assert "late first build at round 2: jit(val_step)" in text
        assert "| jit(client_step) | 2 | 8.0 | 4.0 | 60.9 | 1 hit / 1 miss" \
            in text
        assert "last rose at validation end (round 2)" in text
        json.dumps(s, allow_nan=False)      # the machine tail stays strict

    def test_a_log_from_before_the_record_renders_as_before(self):
        import io

        import obs_report

        old = [e for e in self._lifecycle_log()
               if e["ev"] in ("run_start", "round", "run_end")]
        for e in old:
            e.pop("memory", None)
            e.pop("programs", None)
        old.insert(2, {"ev": "drain", "t": 104.1, "rounds": 2, "ms": 3.0})
        old.append({"ev": "from_the_future", "t": 111.0})
        out = io.StringIO()
        s = obs_report.render(old, out=out)
        assert s["startup"] is None and s["memory"] is None
        assert s["drains"] == 1 and s["log_rounds"] == 4
        text = out.getvalue()
        assert "## Start-up" not in text and "## Memory" not in text
        assert "## Guard / rollback history" in text
