"""Continuous observability plane (docs/observability.md): schema-v3
histograms, the watch/alert rule engine, and round-scoped trace capture.

Pins the acceptance contracts of the continuous-observability PR:

- **Histogram correctness**: ``log_magnitude_histogram`` matches a numpy
  reference over the fixed log10 bin edges, incl. the zero / underflow /
  overflow / NaN / Inf conventions.
- **Non-perturbation**: fp32 round trajectories are BIT-identical with
  the v3 histogram metrics on vs off, on both the replicated and
  ``--server_shard`` planes (the v2 contract, extended to v3).
- **Zero syncs**: 5 steady-state engine rounds with guards + telemetry +
  histograms + watch ALL enabled perform zero blocking device→host
  transfers under ``host_sync_monitor(strict=True)``.
- **Watch rules**: grammar, EWMA warmup/drift, consecutive streaks,
  cooldown, non-finite violation, and the reaction ladder (log / trace /
  checkpoint).
- **Injected-fault drill**: an ``--inject_fault`` poisoned round fires a
  watch alert that is reproducible from the JSONL ALONE, and its
  triggered trace capture lands a round-aligned trace directory named by
  the global round_no.
- **Schema cross-parse**: synthesized v1 (11-field), v2 (12-field), and
  v3 logs render identically for the shared fields.
- **Live reader**: ``obs_report --follow``'s incremental reader survives
  torn tails on a concurrently-appended file and the follow loop renders
  a live run.
"""

import json
import os
import re
import sys
import threading
import time
from io import StringIO
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
from commefficient_tpu.federated.rounds import RoundConfig, build_round_step
from commefficient_tpu.federated.rounds import init_client_states
from commefficient_tpu.federated.server import ServerConfig, init_server_state
from commefficient_tpu.federated.worker import WorkerConfig
from commefficient_tpu.ops.flat import ravel_pytree
from commefficient_tpu.ops.sketch import make_sketch
from commefficient_tpu.profiling import (
    Heartbeat,
    RoundTracer,
    host_sync_monitor,
    parse_trace_rounds,
)
from commefficient_tpu.telemetry import (
    DEFAULT_WATCH_RULES,
    HIST_BINS,
    HIST_LO,
    HIST_STEP,
    METRIC_FIELDS,
    N_SCALAR_FIELDS,
    RunTelemetry,
    WatchEngine,
    log_magnitude_histogram,
    metric_schema,
    parse_watch_rules,
    read_events,
)

_SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")
if _SCRIPTS not in sys.path:
    sys.path.insert(0, _SCRIPTS)

D = 4
# 6 worker slots for the steps-level fixtures (the test_telemetry
# precedent: never compile test_engine's 8-slot geometry first — its
# donation-aliasing test needs a fresh compile on jax 0.4.37)
W = 6


def _np_hist(x):
    """Numpy reference of the fixed log-magnitude binning contract."""
    ax = np.abs(np.asarray(x, np.float32)).ravel()
    counts = np.zeros(HIST_BINS, np.float32)
    for v in ax:
        if v == 0.0:
            continue
        if not np.isfinite(v):
            counts[HIST_BINS - 1] += 1
            continue
        b = int(np.clip(np.floor((np.log10(v) - HIST_LO) / HIST_STEP),
                        0, HIST_BINS - 1))
        counts[b] += 1
    return counts


def _np_hist_int(x):
    """The same contract vectorised, counted in integers: what a bin past
    float32's 2**24 must hold."""
    ax = np.abs(np.asarray(x, np.float32)).ravel()
    nz = ax != 0
    e = (np.log10(np.where(nz, ax, np.float32(1))) - HIST_LO) / HIST_STEP
    with np.errstate(invalid="ignore"):
        idx = np.clip(np.floor(e), 0, HIST_BINS - 1)
    idx = np.where(np.isfinite(ax), idx, HIST_BINS - 1).astype(np.int64)
    return np.bincount(idx[nz], minlength=HIST_BINS)


def _scatter_hist(x):
    """The body this function had before it counted with masked sums
    (float32 scatter-add): the reference for WHERE an element lands. At an
    edge the bin is log10's last float32 bit, which numpy's log10 rounds
    differently (-10.000001 for 1e-10 where XLA reads -10.0), so there
    ``_np_hist`` is no reference for either body."""
    ax = jnp.abs(x.astype(jnp.float32)).reshape(-1)
    nz = ax != 0
    e = (jnp.log10(jnp.where(nz, ax, 1.0)) - HIST_LO) / HIST_STEP
    idx = jnp.clip(jnp.floor(e), 0, HIST_BINS - 1).astype(jnp.int32)
    idx = jnp.where(jnp.isfinite(ax), idx, HIST_BINS - 1)
    return jnp.zeros(HIST_BINS, jnp.float32).at[idx].add(
        nz.astype(jnp.float32))


def _inner_edge_points():
    """Each of the seven inner bin edges as float32, and the float32 one
    ulp below and above it."""
    edges = np.array([10.0 ** (HIST_LO + i * HIST_STEP)
                      for i in range(1, HIST_BINS)], np.float32)
    return np.concatenate([np.nextafter(edges, np.float32(0)), edges,
                           np.nextafter(edges, np.float32(np.inf))])


def _mixed_2d(dtype):
    rng = np.random.RandomState(3)
    x = rng.randn(37, 129).astype(np.float32) * 10 ** rng.uniform(
        -14, 6, (37, 129)).astype(np.float32)
    x[::5, ::3] = 0.0
    return jnp.asarray(x).astype(dtype)


class TestHistogram:
    @pytest.mark.parametrize("make,ref", [
        # 20M elements in ONE bin: a float32 accumulator stops at 2**24
        pytest.param(lambda: np.full(20_000_000, 1e-3, np.float32),
                     _np_hist_int, id="past_f32_exact_range"),
        pytest.param(lambda: _mixed_2d(jnp.bfloat16), _np_hist, id="bf16_2d"),
        pytest.param(lambda: _mixed_2d(jnp.float32), _np_hist, id="f32_2d"),
    ])
    def test_counts_equal_reference(self, make, ref):
        x = make()
        got = np.asarray(jax.jit(log_magnitude_histogram)(jnp.asarray(x)))
        assert got.shape == (HIST_BINS,) and got.dtype == np.float32
        np.testing.assert_array_equal(got, ref(x).astype(np.float32))
        assert got.sum() == np.count_nonzero(np.asarray(x, np.float32))

    @pytest.mark.parametrize("jit", [True, False], ids=["jit", "eager"])
    def test_inner_edges_land_where_they_did(self, jit):
        """On and one ulp either side of every inner edge: the bin is the
        scatter-add body's, element for element, and always one of the two
        bins that meet at that edge (``_np_hist``'s is one of those too)."""
        wrap = jax.jit if jit else (lambda f: f)
        new, old = wrap(log_magnitude_histogram), wrap(_scatter_hist)
        pts = _inner_edge_points()
        np.testing.assert_array_equal(np.asarray(new(jnp.asarray(pts))),
                                      np.asarray(old(jnp.asarray(pts))))
        for j, v in enumerate(pts):
            one = jnp.full((1,), v)
            got = np.asarray(new(one))
            np.testing.assert_array_equal(got, np.asarray(old(one)))
            upper = j % (HIST_BINS - 1) + 1   # the bin above this edge
            assert got[upper - 1] + got[upper] == 1, (v, got)
            assert _np_hist(pts[j:j + 1])[upper - 1:upper + 1].sum() == 1

    def test_one_pass_no_scatter(self):
        """The counts are one fused reduction: no scatter in the optimised
        HLO, and no temporary of the operand's size (the old body wrote the
        bin index AND the weights out, two operands' worth)."""
        n = 1 << 20
        compiled = jax.jit(log_magnitude_histogram).lower(
            jax.ShapeDtypeStruct((n,), jnp.float32)).compile()
        assert not re.search(r"\bscatter\(", compiled.as_text())
        assert compiled.memory_analysis().temp_size_in_bytes < 4 * n

    def test_matches_numpy_reference(self):
        rng = np.random.RandomState(0)
        x = rng.randn(257).astype(np.float32) * 10 ** rng.uniform(
            -14, 6, 257).astype(np.float32)
        x[::17] = 0.0
        got = np.asarray(log_magnitude_histogram(jnp.asarray(x)))
        np.testing.assert_array_equal(got, _np_hist(x))
        # every nonzero element lands in exactly one bin
        assert got.sum() == np.count_nonzero(x)

    def test_edge_conventions(self):
        x = np.array([0.0, 1e-13, 1e-11, 0.5, 3.0, 1e5, np.inf, np.nan],
                     np.float32)
        h = np.asarray(log_magnitude_histogram(jnp.asarray(x)))
        # zero excluded; 1e-13 underflows into bin 0; 1e-11 is bin 0
        # proper; 0.5/3.0 land in bins 5/6; 1e5 overflows into the last
        # bin; Inf AND NaN are pinned into the last bin (never dropped)
        np.testing.assert_array_equal(h, [2, 0, 0, 0, 0, 1, 1, 3])

    def test_schema_versions(self):
        assert len(METRIC_FIELDS) == N_SCALAR_FIELDS + 2 * HIST_BINS
        assert metric_schema(False) == METRIC_FIELDS[:N_SCALAR_FIELDS]
        assert metric_schema(True) == METRIC_FIELDS
        assert METRIC_FIELDS[N_SCALAR_FIELDS] == "update_hist_0"
        assert METRIC_FIELDS[-1] == f"error_hist_{HIST_BINS - 1}"


# ---- steps-level fixtures (the test_telemetry pattern) -------------------

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


def _sketch_steps(telemetry: bool, hists: bool = False,
                  server_shard: bool = False, mesh=None):
    params = {"w": jnp.zeros(D)}
    flat, unravel = ravel_pytree(params)

    def ravel(tree):
        return ravel_pytree(tree)[0]

    n_workers = 8 if server_shard else W
    wcfg = WorkerConfig(mode="sketch", error_type="virtual", k=2,
                        num_workers=n_workers)
    scfg = ServerConfig(mode="sketch", error_type="virtual", k=2,
                        grad_size=D, virtual_momentum=0.9,
                        local_momentum=0.0)
    sketch = make_sketch(D, 16, 3, seed=0, num_blocks=1)
    cfg = RoundConfig(worker=wcfg, server=scfg, grad_size=D,
                      telemetry=telemetry, telemetry_hist=hists,
                      server_shard=server_shard)
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
                    num_workers=W):
    state = (ps, ss, cs, {})
    traj, metrics = [], []
    for rnd in range(rounds):
        out = steps.train_step(state[0], state[1], state[2], state[3],
                               _vec_batch(num_workers, seed=rnd), 0.1,
                               jax.random.key(rnd))
        state = out[:4]
        traj.append(np.asarray(steps.layout.unchunk(state[0])))
        if telemetry:
            metrics.append(np.asarray(out[5]))
    return traj, metrics


class TestHistNonPerturbation:
    def test_v3_bit_identical_replicated(self):
        """fp32 trajectories with the v3 histogram metrics on are
        BIT-identical to v2 and to telemetry-off on the replicated plane,
        and the histogram block is consistent with the scalar slots."""
        runs = {}
        for key, (tel, hi) in {"off": (False, False), "v2": (True, False),
                               "v3": (True, True)}.items():
            steps, ps, ss, cs = _sketch_steps(telemetry=tel, hists=hi)
            runs[key], ms = _run_trajectory(steps, ps, ss, cs,
                                            telemetry=tel)
        for rnd, (a, b) in enumerate(zip(runs["off"], runs["v3"])):
            np.testing.assert_array_equal(a, b, err_msg=f"round {rnd}")
        for rnd, (a, b) in enumerate(zip(runs["v2"], runs["v3"])):
            np.testing.assert_array_equal(a, b, err_msg=f"round {rnd}")

        steps, ps, ss, cs = _sketch_steps(telemetry=True, hists=True)
        _, ms = _run_trajectory(steps, ps, ss, cs, telemetry=True)
        vec = ms[-1]
        assert vec.shape == (len(METRIC_FIELDS),)
        fields = dict(zip(METRIC_FIELDS, vec))
        up_hist = vec[N_SCALAR_FIELDS:N_SCALAR_FIELDS + HIST_BINS]
        # the update histogram's total count == the resolved nnz slot
        assert up_hist.sum() == fields["update_nnz"]
        # v3 scalars == the v2 vector bit for bit
        steps2, ps2, ss2, cs2 = _sketch_steps(telemetry=True, hists=False)
        _, ms2 = _run_trajectory(steps2, ps2, ss2, cs2, telemetry=True)
        np.testing.assert_array_equal(vec[:N_SCALAR_FIELDS], ms2[-1])

    @pytest.mark.skipif(jax.device_count() < 8,
                        reason="needs the forced-8-device CPU mesh")
    def test_v3_bit_identical_server_shard(self):
        """Same bit-identity on the sharded server plane: the histogram
        reductions must not perturb the sharded update either."""
        from commefficient_tpu.parallel.mesh import default_client_mesh

        runs = {}
        for hi in (False, True):
            mesh = default_client_mesh(8, 8)
            steps, ps, ss, cs = _sketch_steps(telemetry=True, hists=hi,
                                              server_shard=True, mesh=mesh)
            runs[hi], _ = _run_trajectory(steps, ps, ss, cs,
                                          telemetry=True, num_workers=8)
        for rnd, (a, b) in enumerate(zip(runs[False], runs[True])):
            np.testing.assert_array_equal(a, b, err_msg=f"round {rnd}")


# ---- watch rules ---------------------------------------------------------

class TestWatchRules:
    def test_grammar(self):
        rules = parse_watch_rules(
            "loss>ewma*4@2->trace:5, error_norm>1e3, "
            "update_nnz<ewma*0.25->checkpoint, occupancy<1.5@3->log")
        assert [r.metric for r in rules] == [
            "loss", "error_norm", "update_nnz", "occupancy"]
        assert rules[0].op == ">" and rules[0].ewma_factor == 4.0
        assert rules[0].consecutive == 2 and rules[0].action == "trace"
        assert rules[0].trace_rounds == 5
        assert rules[1].bound == 1e3 and rules[1].ewma_factor == 0.0
        assert rules[2].op == "<" and rules[2].action == "checkpoint"
        assert rules[3].bound == 1.5 and rules[3].consecutive == 3

    def test_defaults_parse(self):
        rules = parse_watch_rules(",".join(DEFAULT_WATCH_RULES))
        assert len(rules) == len(DEFAULT_WATCH_RULES)
        metrics = {r.metric for r in rules}
        # the issue's named signals are all covered
        for name in ("loss", "error_norm", "qres_norm", "dres_norm",
                     "update_nnz", "occupancy", "prefetch_miss",
                     "rounds_per_sec"):
            assert name in metrics

    def test_bad_specs_raise(self):
        for bad in ("loss=4", "loss>ewma*0", "loss>x",
                    "loss>1->explode", ">1"):
            with pytest.raises((ValueError, AssertionError)):
                parse_watch_rules(bad)

    def test_unknown_metric_fails_at_parse_time(self):
        """A typo'd metric name must fail AT STARTUP, not silently never
        fire for the whole run (the fail-fast contract)."""
        with pytest.raises(ValueError, match="unknown metric"):
            parse_watch_rules("eror_norm>ewma*8@3")
        # every schema field, span key, and derived quantity parses
        parse_watch_rules("update_hist_7>10, compute_ms>1e4, "
                          "dispatch_to_drain_ms>1e5")


class _FakeRT:
    def __init__(self):
        self.events = []

    def event(self, ev, **fields):
        self.events.append(dict(fields, ev=ev))


class TestWatchEngine:
    def test_threshold_consecutive_and_cooldown(self):
        rt = _FakeRT()
        w = WatchEngine(parse_watch_rules("error_norm>1.0@2"), telemetry=rt)
        vals = [0.5, 2.0, 2.0, 2.0, 2.0, 2.0]
        for rnd, v in enumerate(vals):
            w.observe({"round": rnd, "metrics": {"error_norm": v}})
        # @2: first violation at round 1 does not fire, round 2 does;
        # cooldown (8 rounds) silences the rest of the streak
        assert w.fired == [(2, "error_norm>1.0@2")]
        assert rt.events[0]["ev"] == "watch_alert"
        assert rt.events[0]["round"] == 2
        assert rt.events[0]["value"] == 2.0

    def test_ewma_warmup_and_drift(self):
        w = WatchEngine(parse_watch_rules("loss>ewma*3"),
                        telemetry=_FakeRT())
        # a big value DURING warmup must not fire (no armed baseline yet)
        w.observe({"round": 0, "loss": 100.0})
        for rnd in range(1, 8):
            w.observe({"round": rnd, "loss": 1.0})
        assert w.alerts == 0
        w.observe({"round": 8, "loss": 50.0})
        assert w.alerts == 1

    def test_nonfinite_violates(self):
        w = WatchEngine(parse_watch_rules("transmit_norm>ewma*10"),
                        telemetry=_FakeRT())
        for rnd in range(6):
            w.observe({"round": rnd, "metrics": {"transmit_norm": 1.0}})
        w.observe({"round": 6,
                   "metrics": {"transmit_norm": float("nan")}})
        assert w.alerts == 1
        # the non-finite value did not poison the EWMA baseline
        w.observe({"round": 20, "metrics": {"transmit_norm": 1.0}})
        assert w.alerts == 1

    def test_checkpoint_reaction_pending(self):
        w = WatchEngine(parse_watch_rules("loss>2->checkpoint"),
                        telemetry=_FakeRT())
        w.observe({"round": 0, "loss": 5.0})
        assert w.checkpoint_pending
        assert w.pop_checkpoint() and not w.pop_checkpoint()

    def test_derived_metrics(self):
        # prefetch_miss: per-round indicator from the offload span
        w = WatchEngine(parse_watch_rules("prefetch_miss>0.5@3"),
                        telemetry=_FakeRT())
        for rnd in range(3):
            w.observe({"round": rnd,
                       "offload": {"prefetch": "miss"}})
        assert w.alerts == 1
        # rounds_per_sec: from successive dispatch stamps; a 10x slower
        # dispatch cadence under the EWMA floor fires
        w2 = WatchEngine(parse_watch_rules("rounds_per_sec<ewma*0.5"),
                         telemetry=_FakeRT())
        t = 0.0
        for rnd in range(8):
            w2.observe({"round": rnd, "t_dispatch": t})
            t += 0.01
        assert w2.alerts == 0
        w2.observe({"round": 8, "t_dispatch": t + 1.0})
        assert w2.alerts == 1

    def test_trace_reaction_requests_tracer(self, tmp_path):
        tracer = RoundTracer(str(tmp_path))
        w = WatchEngine(parse_watch_rules("loss>2->trace:2"),
                        telemetry=_FakeRT(), tracer=tracer)
        w.observe({"round": 3, "loss": 9.0})
        assert tracer._requests == 2


# ---- engine-level fixtures (the test_telemetry pattern) ------------------

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
        telemetry_hist=True,
    )
    base.update(over)
    return SimpleNamespace(**base)


def _host_batch(ids, seed, d_in=3):
    n = len(ids)
    rng = np.random.RandomState(seed)
    return {
        "inputs": rng.randn(n, 2, d_in).astype(np.float32),
        "targets": rng.randn(n, 2, 4).astype(np.float32),
        "mask": np.ones((n, 2), np.float32),
        "client_ids": np.asarray(ids, np.int32),
        "worker_mask": np.ones(n, np.float32),
    }


def _engine(tmp_path, window=2, drain_every=8, rules=None, tracer=None,
            **over):
    fm = FedModel(TinyModel(), _loss, _args(**over), input_shape=(3,))
    opt = FedOptimizer(fm, fm.args)
    sched = LambdaLR(opt, lambda step: 0.5)
    hists = bool(getattr(fm.args, "telemetry_hist", False))
    rt = RunTelemetry(str(tmp_path / "telemetry.jsonl"),
                      run_info={"mode": fm.args.mode,
                                "grad_size": fm.grad_size,
                                "guards": bool(getattr(fm.args, "guards",
                                                       False)),
                                "watch": [r.spec for r in (rules or [])]},
                      schema=metric_schema(hists))
    if rules is not None:
        rt.watch = WatchEngine(rules, telemetry=rt, tracer=tracer)
    fm.telemetry = rt
    fm.tracer = tracer
    engine = PipelinedRoundEngine(fm, opt, sched, window=window,
                                  drain_every=drain_every)
    return fm, engine, rt


class TestSyncAudit:
    def test_zero_syncs_with_hists_and_watch(self, tmp_path):
        """The acceptance audit: guards + telemetry + HISTOGRAMS + WATCH
        all enabled, strict monitor — 5 steady-state engine rounds
        perform ZERO blocking device→host transfers, and every drained
        round lands a schema-v3-complete event line."""
        rules = parse_watch_rules(",".join(DEFAULT_WATCH_RULES))
        fm, engine, rt = _engine(tmp_path, drain_every=10, rules=rules,
                                 guards=True, snapshot_every=4,
                                 max_guard_trips=3, guard_max_abs=0.0)
        engine.submit(_host_batch([0, 1], seed=0))  # compile round
        with host_sync_monitor(strict=True) as counter:
            for rnd in range(1, 6):
                done = engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4],
                                                 seed=rnd))
                assert done == [], "must not drain before drain_every"
                assert counter.count == 0, \
                    f"round {rnd}: {counter.count} blocking host syncs " \
                    "with guards+telemetry+hists+watch enabled"
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
            assert set(e["metrics"]) == set(METRIC_FIELDS)
        start = next(e for e in events if e["ev"] == "run_start")
        assert start["schema"] == list(METRIC_FIELDS)


class TestInjectedFaultAlert:
    def test_alert_and_trace_reproducible_from_log(self, tmp_path):
        """THE acceptance drill: a watch alert fired by an injected fault
        is reproducible from the JSONL alone, and its triggered trace
        capture lands a round-aligned trace directory named by the
        global round_no."""
        rules = parse_watch_rules(",".join(DEFAULT_WATCH_RULES))
        tracer = RoundTracer(str(tmp_path))
        fm, engine, rt = _engine(tmp_path, drain_every=2, rules=rules,
                                 tracer=tracer, guards=True,
                                 snapshot_every=4, max_guard_trips=5,
                                 inject_fault="7:nan")
        for rnd in range(12):
            engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd))
        engine.drain()
        cap = tracer.close()
        if cap is not None:
            rt.event("trace_captured", **cap)
        rt.close()
        assert fm.guard_trips == 1
        live_alerts = rt.watch.alerts
        assert live_alerts >= 1

        # --- everything below reads the JSONL ALONE -------------------
        import obs_report

        events = obs_report.load_events(str(tmp_path))
        s = obs_report.summarize(events)
        assert s["alerts"]["count"] == live_alerts
        assert 7 in s["alerts"]["rounds"]
        alert = next(e for e in events if e.get("ev") == "watch_alert"
                     and e["round"] == 7)
        # the poisoned transmit fired the what-tripped blowup rule, and
        # its reaction requested a trace
        assert alert["metric"] == "transmit_norm"
        assert alert["action"] == "trace" and alert["trace_requested"]
        # the triggered capture landed, round-aligned: the dir is named
        # by the global round_no the capture started at (the first
        # dispatch after the alert, = 8 + the 2-round in-flight window)
        caps = [e for e in events if e.get("ev") == "trace_captured"]
        assert caps, "trace_captured event missing"
        cap = caps[0]
        start = cap["round_start"]
        assert start > 7
        assert cap["dir"].endswith(f"trace_round_{start:06d}")
        assert os.path.isdir(cap["dir"])
        # a real profiler capture was written into the round-named dir
        files = [os.path.join(r, f) for r, _, fs in os.walk(cap["dir"])
                 for f in fs]
        assert files, f"no trace artifacts under {cap['dir']}"
        # the poisoned round itself is quarantined + string-encoded
        rounds = {e["round"]: e for e in events if e.get("ev") == "round"}
        assert rounds[7]["guard_ok"] is False
        assert isinstance(rounds[7]["metrics"]["transmit_norm"], str)
        # obs_report renders and its machine tail carries the alert keys
        buf = StringIO()
        obs_report.render(events, out=buf)
        out = buf.getvalue()
        assert "ALERT at round 7" in out
        assert "trace captured" in out


class TestTraceRounds:
    def test_static_window_round_aligned(self, tmp_path):
        """--trace_rounds START:COUNT: the capture starts at the window's
        start round, the dir is named by it, and the trace_captured event
        carries the exact round range."""
        tracer = RoundTracer(str(tmp_path),
                             windows=parse_trace_rounds("2:2"))
        fm, engine, rt = _engine(tmp_path, drain_every=1, tracer=tracer)
        for rnd in range(5):
            engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd))
        engine.drain()
        rt.close()
        events = list(read_events(str(tmp_path / "telemetry.jsonl")))
        caps = [e for e in events if e["ev"] == "trace_captured"]
        assert len(caps) == 1
        assert caps[0]["round_start"] == 2
        assert caps[0]["round_until"] == 3
        assert caps[0]["dir"].endswith("trace_round_000002")
        assert os.path.isdir(caps[0]["dir"])
        assert tracer.captures and tracer.close() is None

    def test_open_window_stops_at_close(self, tmp_path):
        """A window still open at run end is stopped by close() and its
        partial record is still reportable."""
        tracer = RoundTracer(str(tmp_path),
                             windows=parse_trace_rounds("1:100"))
        fm, engine, rt = _engine(tmp_path, drain_every=1, tracer=tracer)
        for rnd in range(3):
            engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd))
        engine.drain()
        cap = tracer.close()
        assert cap is not None and cap["round_start"] == 1
        rt.close()

    def test_parse_trace_rounds(self):
        assert parse_trace_rounds("10:3,2:5") == [(2, 5), (10, 3)]
        with pytest.raises(ValueError):
            parse_trace_rounds("x:y")
        with pytest.raises(AssertionError):
            parse_trace_rounds("3:0")


class TestHeartbeatExtras:
    def test_line_carries_loss_and_guard(self, tmp_path, capfd):
        """Satellite: the heartbeat line carries the drained round's mean
        loss and guard verdict next to the round index, keyed fields
        appended after the supervisor-parsed round=N."""
        fm, engine, rt = _engine(tmp_path, drain_every=1, guards=True,
                                 snapshot_every=4, max_guard_trips=3)
        engine.heartbeat = Heartbeat(enabled=True)
        for rnd in range(3):
            engine.submit(_host_batch([0, 1], seed=rnd))
        rt.close()
        err = capfd.readouterr().err
        lines = [ln for ln in err.splitlines()
                 if ln.startswith("HEARTBEAT")]
        assert len(lines) == 3
        for i, ln in enumerate(lines):
            parts = ln.split()
            assert parts[1] == f"round={i}"
            assert parts[2].startswith("loss=")
            assert float(parts[2].split("=")[1]) > 0
            assert parts[3] == "guard=ok"


# ---- schema cross-parse (satellite) --------------------------------------

def _synth_log(path, n_fields, rounds=4):
    """Synthesize a run log at a given metric schema width: 11 = v1,
    12 = v2, 28 = v3 — same shared values in every version."""
    schema = list(METRIC_FIELDS[:n_fields])
    with open(path, "w") as f:
        f.write(json.dumps({"ev": "run_start", "mode": "sketch",
                            "grad_size": 64, "guards": True,
                            "backend": "cpu", "schema": schema}) + "\n")
        for r in range(rounds):
            metrics = {k: float(i + 1) for i, k in enumerate(schema)}
            f.write(json.dumps({
                "ev": "round", "round": r, "t": 100.0 + r,
                "t_dispatch": 100.0 + r, "dispatch_ms": 1.5,
                "drain_fetch_ms": 0.25, "dispatch_to_drain_ms": 4.0,
                "occupancy": 2, "loss": 0.5, "guard_ok": True,
                "metrics": metrics}) + "\n")
        f.write(json.dumps({"ev": "run_end", "rounds": rounds}) + "\n")


class TestSchemaCrossParse:
    # the machine-tail keys every schema version must agree on
    SHARED = ("log_rounds", "run_complete", "mode", "grad_size",
              "guards", "backend", "dispatch_ms_p50", "drain_fetch_ms_p50",
              "occupancy_mean", "mean_loss", "mean_update_nnz",
              "mean_topk_threshold", "mean_error_norm", "guard_trips",
              "mean_qres_norm")

    def test_v1_v2_v3_render_identically_for_shared_fields(self, tmp_path):
        import obs_report

        sums = {}
        for tag, n in (("v1", 11), ("v2", 12), ("v3", len(METRIC_FIELDS))):
            p = tmp_path / f"{tag}.jsonl"
            _synth_log(str(p), n)
            sums[tag] = obs_report.summarize(obs_report.load_events(str(p)))
            # every version renders without error
            buf = StringIO()
            obs_report.render(obs_report.load_events(str(p)), out=buf)
            assert "Run summary" in buf.getvalue()
        for key in self.SHARED:
            assert sums["v1"][key] == sums["v2"][key] == sums["v3"][key], \
                key
        # version-specific tails degrade to None/absent, never crash
        assert sums["v1"]["mean_dres_norm"] is None
        assert sums["v2"]["mean_dres_norm"] is not None
        assert sums["v1"]["histograms"]["update"] is None
        assert sums["v2"]["histograms"]["update"] is None
        assert sums["v3"]["histograms"]["update"]["bins"] == HIST_BINS
        assert sums["v1"]["metric_schema_len"] == 11
        assert sums["v3"]["metric_schema_len"] == len(METRIC_FIELDS)

    def test_unknown_event_kinds_are_skipped(self, tmp_path):
        """Satellite (consumer audit): unknown `ev` values — and records
        with no `ev` at all — must be skipped, never crash a report."""
        import obs_report

        p = tmp_path / "t.jsonl"
        _synth_log(str(p), 12, rounds=2)
        with open(p, "a") as f:
            f.write(json.dumps({"ev": "watch_alert", "round": 1,
                                "rule": "loss>1", "metric": "loss",
                                "value": 2.0, "bound": 1.0,
                                "action": "log"}) + "\n")
            f.write(json.dumps({"ev": "some_future_event_kind",
                                "round": 1}) + "\n")
            f.write(json.dumps({"no_ev_at_all": True}) + "\n")
        events = obs_report.load_events(str(p))
        s = obs_report.summarize(events)
        assert s["log_rounds"] == 2
        assert s["alerts"]["count"] == 1
        buf = StringIO()
        obs_report.render(events, out=buf)
        assert "ALERT at round 1" in buf.getvalue()


# ---- live follow reader + compare (satellites) ---------------------------

class TestFollow:
    def test_live_reader_resumes_across_torn_tail(self, tmp_path):
        """The incremental reader buffers a torn trailing line and parses
        it once the newline lands — where read_events (correctly) stops
        at the tear forever."""
        import obs_report

        p = tmp_path / "t.jsonl"
        line = json.dumps({"ev": "round", "round": 0, "t": 1.0}) + "\n"
        p.write_text(json.dumps({"ev": "run_start"}) + "\n" + line[:9])
        reader = obs_report.LiveReader(str(p))
        first = reader.poll()
        assert [e["ev"] for e in first] == ["run_start"]
        with open(p, "a") as f:
            f.write(line[9:])
        second = reader.poll()
        assert [e["ev"] for e in second] == ["round"]
        # a COMPLETE but corrupt line is skipped, not fatal
        with open(p, "a") as f:
            f.write('{"ev": "round", broken\n')
            f.write(json.dumps({"ev": "run_end"}) + "\n")
        third = reader.poll()
        assert [e["ev"] for e in third] == ["run_end"]

    def test_follow_renders_concurrently_appended_run(self, tmp_path):
        """--follow live-tails a run in progress: rounds written (with
        torn-tail flushes) by a concurrent writer appear in the rendered
        table, and the loop exits at run_end with the machine tail."""
        import obs_report

        p = str(tmp_path / "live.jsonl")

        def writer():
            with open(p, "w") as f:
                f.write(json.dumps({"ev": "run_start",
                                    "mode": "sketch"}) + "\n")
                f.flush()
                for r in range(4):
                    time.sleep(0.03)
                    line = json.dumps(
                        {"ev": "round", "round": r, "t": 1.0 + r,
                         "loss": 0.5, "guard_ok": True,
                         "metrics": {"update_nnz": 2.0,
                                     "topk_threshold": 0.1,
                                     "error_norm": 0.5}}) + "\n"
                    # torn write: half the line, flush, then the rest
                    f.write(line[:11])
                    f.flush()
                    time.sleep(0.02)
                    f.write(line[11:])
                    f.flush()
                f.write(json.dumps({"ev": "run_end", "rounds": 4}) + "\n")
                f.flush()

        t = threading.Thread(target=writer)
        t.start()
        buf = StringIO()
        rc = obs_report.follow(p, out=buf, interval=0.02, max_iters=500,
                               clear=False)
        t.join()
        out = buf.getvalue()
        assert rc == 0
        assert "rounds drained: 4" in out
        assert "| 3 |" in out  # the last round's table row
        tail = json.loads(out.strip().splitlines()[-1])
        assert tail["log_rounds"] == 4 and tail["run_complete"]


class TestCompare:
    def test_delta_table_between_two_runs(self, tmp_path):
        import obs_report

        a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
        _synth_log(a, len(METRIC_FIELDS), rounds=4)
        _synth_log(b, len(METRIC_FIELDS), rounds=8)
        buf = StringIO()
        out = obs_report.compare(a, b, out=buf)
        text = buf.getvalue()
        assert "| metric | A | B | delta | B/A |" in text
        assert out["delta"]["log_rounds"] == 4
        assert out["a"]["log_rounds"] == 4 and out["b"]["log_rounds"] == 8
        # the CLI wires it: exactly two paths + --compare, strict tail
        import contextlib
        import io

        cap = io.StringIO()
        with contextlib.redirect_stdout(cap):
            rc = obs_report.main(["--compare", a, b])
        assert rc == 0
        tail = json.loads(cap.getvalue().strip().splitlines()[-1])
        assert tail["delta"]["log_rounds"] == 4
        with contextlib.redirect_stdout(io.StringIO()):
            assert obs_report.main(["--compare", a]) == 2
