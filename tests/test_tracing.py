"""The names the program gives its own work (docs/observability.md §names).

- **Device stages**: every ``jax.named_scope`` of ``profiling.DEVICE_STAGES``
  appears in the lowered text of ``client_step`` / ``server_step`` /
  ``val_step`` in the modes that run that stage, and no compiled operation's
  scope path holds two stages (the per-layer stage metrics are disjoint).
- **Kernel names**: every ``pallas_call`` equation carries its ``name``
  (``profiling.KERNEL_NAMES``).
- **Host spans**: ``profiling.annotate`` feeds ``SPAN_TOTALS``; N submits
  through ``PipelinedRoundEngine`` count ``fed_round`` N times,
  ``fed_window_wait`` N - window times, ``fed_h2d`` N times, ``fed_drain``
  once a drained round; ``PrefetchLoader`` counts its producer and consumer
  sides; the ``round`` records carry ``window_wait_ms`` / ``h2d_ms`` /
  ``input_wait_ms`` from those spans and ``run_end`` the totals; the dispatch
  path still performs zero blocking fetches.
- **One profiler starter**: ``--profile --profile_steps 1`` is a
  ``RoundTracer`` window over round 2, written to ``--profile_dir``.
"""

import re
import threading
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flax.linen as nn

from commefficient_tpu import profiling
from commefficient_tpu.data_utils import PrefetchLoader
from commefficient_tpu.federated.aggregator import (
    FedModel,
    FedOptimizer,
    LambdaLR,
)
from commefficient_tpu.federated.engine import PipelinedRoundEngine
from commefficient_tpu.profiling import (
    DEVICE_STAGES,
    KERNEL_NAMES,
    SPAN_TOTALS,
    RoundTracer,
    annotate,
    host_sync_monitor,
    span_totals,
)
from commefficient_tpu.telemetry import (
    RunTelemetry,
    attach_run_telemetry,
    metric_schema,
    read_events,
)

MODES = {
    "sketch": dict(mode="sketch", error_type="virtual"),
    "true_topk": dict(mode="true_topk", error_type="virtual"),
    "uncompressed": dict(mode="uncompressed", error_type="none"),
}
# the stages each mode's two-phase round runs (fed_accounting lives in the
# aggregator's own jitted programs, fed_val in val_step)
CLIENT = {"fed_client_grad", "fed_client_compress"}
SERVER = {
    "sketch": {"fed_server_estimate", "fed_server_topk",
               "fed_server_resketch", "fed_server_apply",
               "fed_telemetry_metrics"},
    "true_topk": {"fed_server_topk", "fed_server_apply",
                  "fed_telemetry_metrics"},
    "uncompressed": {"fed_server_apply", "fed_telemetry_metrics"},
}


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


def _model(mode, **over):
    fm = FedModel(TinyModel(), _loss, _args(**MODES[mode], **over),
                  input_shape=(3,))
    return fm, FedOptimizer(fm, fm.args)


def _stages_in(path):
    return [n for n in re.findall(r"fed_[a-z_]+", path)
            if n in DEVICE_STAGES]


def _lowered_steps(mode):
    """(name, Lowered) of the three jitted steps on a tiny round."""
    fm, opt = _model(mode)
    batch = {k: jnp.asarray(v) for k, v in _host_batch([0, 1], 0).items()}
    rng = jax.random.key(0)
    cargs = (fm.ps_weights, fm.client_states, fm._model_state, batch, 0.5,
             rng)
    ctx, _, _ = fm.steps.client_step(*cargs)
    vbatch = {k: batch[k][0] for k in ("inputs", "targets", "mask")}
    return {
        "client": fm.steps.client_step.lower(*cargs),
        "server": fm.steps.server_step.lower(
            fm.ps_weights, opt.server_state, fm.client_states, ctx, 0.5,
            rng),
        "val": fm.steps.val_step.lower(fm.ps_weights, fm._model_state,
                                       vbatch),
    }


@pytest.fixture(scope="module", params=sorted(MODES))
def lowered(request):
    return request.param, _lowered_steps(request.param)


class TestDeviceStages:
    def test_every_stage_is_in_the_lowered_text(self, lowered):
        mode, steps = lowered
        want = {"client": CLIENT, "server": SERVER[mode], "val": {"fed_val"}}
        for step, low in steps.items():
            text = low.as_text(debug_info=True)
            found = {s for s in DEVICE_STAGES if s in text}
            assert found == want[step], \
                f"{mode} {step}_step: stages {sorted(found)}, " \
                f"expected {sorted(want[step])}"

    def test_no_operation_carries_two_stages(self, lowered):
        """The compiled programs' ``op_name`` metadata is the scope path the
        profiler reports: one stage per operation, under whatever transform
        (``transpose(jvp(...))``) wrapped it."""
        mode, steps = lowered
        for step, low in steps.items():
            names = set(re.findall(r'op_name="([^"]*)"',
                                   low.compile().as_text()))
            staged = [n for n in names if _stages_in(n)]
            assert staged, f"{mode} {step}_step: no staged operation"
            two = [n for n in staged if len(set(_stages_in(n))) > 1]
            assert not two, f"{mode} {step}_step: two stages in {two[:3]}"

    def test_backward_pass_keeps_its_stage(self, lowered):
        _, steps = lowered
        names = re.findall(r'op_name="([^"]*)"',
                           steps["client"].compile().as_text())
        back = [n for n in names if "transpose(" in n]
        assert back and all(_stages_in(n) == ["fed_client_grad"]
                            for n in back), back[:3]

    def test_leaf_group_sketch_lies_under_fed_client_compress(self,
                                                              monkeypatch):
        """Sketch mode's client phase (docs/stream_sketch.md): every
        operation of the groups' staging (cast, weight decay, concatenate,
        pad) and of every ``fed_sketch_accum`` launch carries
        ``fed_client_compress`` — ``compress_ms`` reads them — and the
        route leaves no operation without a stage that the flat route's
        program does not leave too."""
        import functools

        from commefficient_tpu.federated import aggregator

        monkeypatch.setenv("COMMEFFICIENT_PALLAS_SKETCH", "interpret")

        def op_names(want):
            fm, _ = _model("sketch", weight_decay=5e-4, microbatch_size=1)
            assert fm.steps.client_sketch_path == want
            batch = {k: jnp.asarray(v)
                     for k, v in _host_batch([0, 1], 0).items()}
            text = fm.steps.client_step.lower(
                fm.ps_weights, fm.client_states, fm._model_state, batch,
                0.5, jax.random.key(0)).compile().as_text()
            return set(re.findall(r'op_name="([^"]*)"', text))

        leaf = op_names("leaf_groups")
        with monkeypatch.context() as m:
            m.setattr(aggregator, "RoundConfig", functools.partial(
                aggregator.RoundConfig, sketch_leaf_groups=False))
            flat = op_names("flat")
        kernel = [n for n in leaf if "fed_sketch_accum" in n]
        staging = [n for n in leaf if "fed_sketch_accum" not in n
                   and re.search(r"/(pad|concatenate|convert_element_type)$",
                                 n) and "fed_client_grad" not in n]
        assert kernel and any(n.endswith("/pad") for n in staging)
        for n in kernel + staging:
            assert _stages_in(n) == ["fed_client_compress"], n
        assert not any("fed_sketch_accum" in n for n in flat)
        bare = {n for n in leaf if not _stages_in(n)}
        assert bare <= {n for n in flat if not _stages_in(n)}, \
            sorted(bare - flat)

    def test_accounting_programs_are_scoped(self):
        from commefficient_tpu.federated import aggregator as agg

        last = jnp.zeros(8, jnp.int32)
        w = jnp.ones(8)
        for low in (
                agg._mark_changed.lower(last, w, w * 2, 1),
                agg._changed_since_counts.lower(last,
                                                jnp.zeros(2, jnp.int32)),
                agg._fold_updated.lower(jnp.zeros(8, bool), w, w * 2)):
            assert "fed_accounting" in low.as_text(debug_info=True)


class TestInnerScopes:
    def test_inner_scopes_nest_under_one_stage(self):
        """A model's own scopes (``INNER_SCOPES``: the expert layer's
        routing and grouped products, the attention cores, a recurrent
        stack's body and head) are no stages: an operation under one of
        them, forward, recomputed or backward, still carries
        ``fed_client_grad`` as its one stage, so ``stage_of`` finds one
        stage an operation."""
        from commefficient_tpu.federated.losses import make_causal_lm_losses
        from commefficient_tpu.models.joyai import JoyAIConfig, JoyAIFlash
        from commefficient_tpu.models.laguna import LagunaConfig, LagunaXS2
        from commefficient_tpu.models.ouro import Ouro, OuroConfig
        from commefficient_tpu.profiling import INNER_SCOPES

        assert not set(INNER_SCOPES) & set(DEVICE_STAGES)
        cut = dict(layers=2, experts_held=4, expert_offset=0, vocab_rows=64)
        # (a full and a sliding layer: LagunaConfig.tiny's first two; the
        # recurrence's body and head: Ouro's)
        names = {}
        for model in (JoyAIFlash(JoyAIConfig.tiny(**cut)),
                      LagunaXS2(LagunaConfig.tiny(**cut)),
                      Ouro(OuroConfig.tiny(layers=2, vocab_rows=64))):
            ids = jnp.zeros((2, 1, 8), jnp.int32)
            params = model.init(jax.random.key(0), ids[:, 0])["params"]
            train, _ = make_causal_lm_losses(model)
            batch = {"input_ids": ids, "lm_labels": ids,
                     "mask": jnp.ones(2, jnp.float32)}

            @jax.jit
            def step(p):
                with jax.named_scope("fed_client_grad"):
                    return jax.grad(
                        lambda p: train(p, {}, batch, None, True)[0])(p)

            names[type(model).__name__] = set(re.findall(
                r'op_name="([^"]*)"', step.lower(params).compile().as_text()))
        looped = names.pop("Ouro")
        plain = set().union(*names.values())
        for inner in INNER_SCOPES:
            under = [n for n in plain if inner in n]
            loop_under = [n for n in looped if inner in n]
            assert loop_under if inner.startswith("fed_loop_") else under, \
                f"no operation under {inner}"
            for found in filter(None, (under, loop_under)):
                assert any("transpose(" in n for n in found), \
                    f"no backward operation under {inner}"
            # (a custom_vjp's backward repeats the stack: the one stage twice)
            assert all(set(_stages_in(n)) == {"fed_client_grad"}
                       for n in under), under[:3]
            # a stack run as a ``scan`` over its passes, and that alone:
            # differentiating the loop moves what does not depend on the
            # pass (the turn's table, the oracle's mask, the labels' index)
            # out of it, and the CPU's reducers inside a loop are named by
            # their own path: those carry no stage. Never a product, never a
            # kernel, and no other stage
            stray = [n for n in loop_under if not _stages_in(n)]
            assert not any("dot_general" in n or "pallas_call" in n
                           for n in stray), stray[:3]
            assert all(set(_stages_in(n)) == {"fed_client_grad"}
                       for n in loop_under if n not in stray), loop_under[:3]
            assert len(stray) <= len(loop_under) / 2


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            info = eqn.params.get("name_and_src_info")
            out.append(getattr(info, "name", None) or eqn.params.get("name"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_names(sub, out)
    return out


class TestKernelNames:
    @pytest.mark.parametrize("kernel", sorted(KERNEL_NAMES))
    def test_pallas_call_carries_its_name(self, kernel):
        import importlib

        from commefficient_tpu.ops import attention as at
        from commefficient_tpu.ops import sketch as sk

        # (``ops.topk`` the attribute is the function, not the module)
        tk = importlib.import_module("commefficient_tpu.ops.topk")

        cs = sk.make_sketch(3000, 2048, 3, seed=0, num_blocks=1)
        S, T = cs.sublanes, cs.T
        v3 = jnp.zeros((T, S, 128), jnp.float32)
        tbl3 = jnp.zeros((cs.r, S, 128), jnp.float32)
        kw = dict(S=S, T=T, interpret=True)
        hashes = (cs.shift_q, cs.shift_w, cs.sign_keys, sk._T0)
        raw = jnp.zeros((4, 8, 128), jnp.int32)
        qkv = [jnp.zeros((1, 128) + w) for w in (
            (2, 192), (2, 64), (2, 256), (64,))]
        # q, k, v flat; the gates; the rope's (cos, sin) and its lane table
        gqa = [jnp.zeros((1, 32, w)) for w in (32, 16, 16, 2)]
        rope = (jnp.ones((32, 8)), jnp.zeros((32, 8)))
        calls = {
            "fed_sketch_vec": lambda: sk._sketch_vec_pallas(v3, *hashes,
                                                            **kw),
            "fed_sketch_accum": lambda: sk._sketch_segments_pallas(
                tbl3, v3, *hashes, **kw),
            "fed_estimates": lambda: sk._estimates_pallas(
                sk._doubled_table(cs, jnp.zeros(cs.table_shape)), *hashes,
                c_pad=cs.c_pad, **kw),
            "fed_epilogue": lambda: sk._fused_epilogue_pallas(
                v3, *hashes, jnp.zeros(1, jnp.int32), **kw),
            "fed_topk_count": lambda: tk._count_ge_pallas(
                raw, jnp.zeros(16, jnp.int32), T=4, sub=8, interpret=True),
            "fed_topk_descent": lambda: tk._descent_pallas(
                raw, jnp.ones(1, jnp.int32), T=4, sub=8, interpret=True),
            "fed_mla_attn_fwd": lambda: at.mla_attention_fused(
                *qkv, interpret=True),
            # the backward pass alone, on residuals (output, log-sum-exp)
            "fed_mla_attn_bwd": lambda: at._fused_bwd(
                jnp.float32, True,
                (*qkv, jnp.zeros((1, 128, 2, 128)), jnp.zeros((1, 1, 2, 128))),
                jnp.zeros((1, 128, 2, 128))),
            "fed_gqa_attn_fwd": lambda: at.gqa_attention_fused(
                *gqa, rope, window=16, interpret=True, tile=16),
            # residuals: the operands, the table, the output, log-sum-exp
            "fed_gqa_attn_bwd": lambda: at._gqa_fused_bwd(
                (8, 16, 16, jnp.float32, True, None),
                (*gqa, at._rope_table(*rope, 16), gqa[0],
                 jnp.zeros((1, 1, 32, 2))), gqa[0]),
        }
        names = _pallas_names(jax.make_jaxpr(calls[kernel])().jaxpr, [])
        assert names == [kernel]

    def test_sketch_words_tell_the_kernels_apart(self):
        """benchmark/metrics/sketch_kernel_roofline.py finds the sketch's
        kernels by these words in the operation's head; the top-k and the
        attention kernels must not match."""
        word = re.compile(r"sketch|estimates|epilogue")
        assert [bool(word.search(k)) for k in KERNEL_NAMES] \
            == [True] * 4 + [False] * 6


def _engine(tmp_path, mode="sketch", window=2, drain_every=4, tracer=None):
    fm, opt = _model(mode)
    rt = RunTelemetry(str(tmp_path / "telemetry.jsonl"),
                      run_info={"mode": mode},
                      schema=metric_schema(True))
    fm.telemetry, fm.tracer = rt, tracer
    engine = PipelinedRoundEngine(fm, opt, LambdaLR(opt, lambda step: 0.5),
                                  window=window, drain_every=drain_every)
    return fm, engine, rt


class TestStartEvent:
    @pytest.mark.parametrize("mode,over,want", [
        ("sketch", {}, ("leaf_groups", 1)),
        ("sketch", dict(microbatch_size=1, weight_decay=5e-4),
         ("leaf_groups", 1)),
        ("sketch", dict(error_type="local", virtual_momentum=0.0),
         ("flat", 0)),
        ("true_topk", {}, None),
        ("uncompressed", {}, None)],
        ids=["sketch", "sketch-scan2-wd", "sketch-per_client_state",
             "true_topk", "uncompressed"])
    def test_start_event_says_the_client_sketch_route(self, tmp_path, mode,
                                                      over, want):
        """``client_sketch_path`` / ``client_sketch_launches`` (docs/
        observability.md §Names): sketch mode's run header says which
        route the gradient takes to the table and how many accumulate
        launches its group plan makes a round (TinyModel's one leaf: one);
        a mode with no client sketch has neither."""
        args = _args(**{**MODES[mode], **over})
        fm = FedModel(TinyModel(), _loss, args, input_shape=(3,))
        attach_run_telemetry(args, fm, str(tmp_path), "test").close()
        start = next(read_events(str(tmp_path / "telemetry.jsonl")))
        assert start["ev"] == "run_start"
        got = (start.get("client_sketch_path"),
               start.get("client_sketch_launches"))
        assert got == (want or (None, None))
        if want:
            assert got == (fm.steps.client_sketch_path,
                           fm.steps.client_sketch_launches)


def _counts(before):
    return {k: v["count"] - before.get(k, {"count": 0})["count"]
            for k, v in span_totals().items()}


class TestHostSpans:
    def test_annotate_feeds_the_totals(self):
        before = span_totals()
        with annotate("fed_test_span", round=7) as span:
            pass
        assert span.end_ns >= span.start_ns and span.ms >= 0.0
        assert _counts(before)["fed_test_span"] == 1
        count, ns = SPAN_TOTALS["fed_test_span"]
        assert ns == span.end_ns - span.start_ns or count > 1

    def test_totals_survive_threads(self):
        before = span_totals()

        def spin():
            for _ in range(200):
                with annotate("fed_test_threads"):
                    pass

        threads = [threading.Thread(target=spin) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
        assert _counts(before)["fed_test_threads"] == 1600

    @pytest.mark.parametrize("mode", sorted(MODES))
    def test_engine_counts_its_spans(self, tmp_path, mode):
        N, window = 6, 2
        fm, engine, rt = _engine(tmp_path, mode, window=window,
                                 drain_every=N + 2)
        before = span_totals()
        drained = []
        for rnd in range(N):
            drained += engine.submit(
                _host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd))
        counts = _counts(before)
        assert counts["fed_round"] == N
        assert counts["fed_h2d"] == N
        assert counts["fed_client_phase"] == N
        assert counts["fed_server_phase"] == N
        assert counts["fed_window_wait"] == N - window
        assert counts.get("fed_drain", 0) == len(drained) == 0
        drained += engine.drain()
        assert _counts(before)["fed_drain"] == len(drained) == N
        rt.close()

        events = list(read_events(str(tmp_path / "telemetry.jsonl")))
        rounds = [e for e in events if e["ev"] == "round"]
        assert [e["round"] for e in rounds] == list(range(N))
        for e in rounds:
            for key in ("dispatch_ms", "h2d_ms", "input_wait_ms",
                        "drain_fetch_ms", "dispatch_to_drain_ms"):
                assert e[key] >= 0.0, (key, e)
            assert e["h2d_ms"] <= e["dispatch_ms"]
        waited = [e for e in rounds if "window_wait_ms" in e]
        assert [e["round"] for e in waited] == list(range(N - window))
        for e in waited:
            assert 0.0 <= e["window_wait_ms"] <= e["compute_ms"]
        end = next(e for e in events if e["ev"] == "run_end")
        for name in ("fed_round", "fed_window_wait", "fed_h2d", "fed_drain",
                     "fed_telemetry_host"):
            assert end["spans"][name]["count"] >= 1
            assert end["spans"][name]["ms"] >= 0.0

    def test_dispatch_path_still_fetches_nothing(self, tmp_path):
        fm, engine, rt = _engine(tmp_path, drain_every=10)
        engine.submit(_host_batch([0, 1], seed=0))  # compile round
        with host_sync_monitor(strict=True) as counter:
            for rnd in range(1, 6):
                assert engine.submit(
                    _host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd)) == []
            assert counter.count == 0
            engine.drain()
            assert counter.count > 0
        rt.close()

    def test_prefetch_loader_counts_both_sides(self):
        before = span_totals()
        assert list(PrefetchLoader(list(range(5)))) == list(range(5))
        counts = _counts(before)
        # five batches and the StopIteration / end sentinel on each side
        assert counts["fed_input_produce"] == 6
        assert counts["fed_input_wait"] == 6

    def test_input_wait_lands_on_the_next_round(self, tmp_path):
        fm, engine, rt = _engine(tmp_path, drain_every=1)
        for rnd, _ in enumerate(PrefetchLoader([0, 1, 2])):
            engine.submit(_host_batch([rnd, rnd + 1], seed=rnd))
        engine.drain()
        rt.close()
        rounds = [e for e in read_events(str(tmp_path / "telemetry.jsonl"))
                  if e["ev"] == "round"]
        assert len(rounds) == 3
        assert all(e["input_wait_ms"] > 0.0 for e in rounds)


class TestOneProfilerStarter:
    def test_step_profiler_is_gone(self):
        assert not hasattr(profiling, "StepProfiler")
        assert not hasattr(profiling, "_profiler_busy")

    def test_profile_flag_is_a_round_tracer_window(self, tmp_path):
        """--profile --profile_steps 1: the attached RoundTracer captures
        round 2 into --profile_dir."""
        fm, engine, rt = _engine(tmp_path, drain_every=1)
        rt.close()
        fm.telemetry = engine.telemetry = None
        args = _args(do_profile=True, profile_steps=1,
                     profile_dir=str(tmp_path / "prof"), telemetry=False,
                     watch=False, trace_rounds="")
        assert attach_run_telemetry(args, fm, str(tmp_path), "test") is None
        assert isinstance(fm.tracer, RoundTracer)
        engine.tracer = fm.tracer
        for rnd in range(4):
            engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd))
        engine.drain()
        assert fm.tracer.captures == [{
            "round_start": 2, "round_until": 2,
            "dir": str(tmp_path / "prof")}]
        assert fm.tracer.close() is None
        assert list((tmp_path / "prof").rglob("*.xplane.pb"))

    def test_windows_queue_behind_an_open_one(self, tmp_path):
        """One class starts the profiler, so two windows cannot collide: a
        window due while another is open starts when that one has closed."""
        tracer = RoundTracer(str(tmp_path),
                             windows=[(0, 2), (1, 1, str(tmp_path / "own"))])
        tracer.on_submit(0)
        tracer.on_submit(1)
        assert tracer._active["start"] == 0 and len(tracer._pending) == 1
        assert tracer.on_drained(1)["round_until"] == 1
        tracer.on_submit(2)
        assert tracer._active["dir"] == str(tmp_path / "own")
        assert tracer.close()["round_start"] == 2


# ---------------------------------------------------------------------------
# the run's record of itself outside the round loop (profiling.phase /
# PHASES, the program listener, memory_sample)
# ---------------------------------------------------------------------------

class StubDevice:
    """A device whose ``memory_stats()`` reads like a TPU's."""

    def __init__(self, in_use):
        self.stats = {
            "bytes_in_use": in_use, "peak_bytes_in_use": in_use + 7,
            "bytes_reserved": 11, "peak_bytes_reserved": 13,
            "largest_free_block_bytes": 17, "num_allocs": 19,
            "bytes_limit": 23, "pool_bytes": 29}
        self.calls = 0

    def memory_stats(self):
        self.calls += 1
        return dict(self.stats)


@pytest.fixture
def listener():
    profiling.install_program_listener()
    profiling.program_totals()          # closes a build another test left


def _built_since(n):
    profiling.program_totals()
    return list(profiling.PROGRAMS)[n:]


@pytest.fixture
def own_cache(tmp_path):
    """A persistent compile cache of this test's own, with no floor: every
    executable is stored."""
    from jax._src import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path / "cache"))
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], -1)
    cc.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


class TestRunRecord:
    def test_phases_are_ordered_and_do_not_overlap(self):
        before = span_totals()
        profiling.begin_setup()
        for name in ("data", "model", "data"):
            with profiling.phase(name):
                sum(range(20000))
        phases = [p for p in profiling.PHASES if p["phase"] != "import"]
        assert [p["phase"] for p in phases] == ["data", "model", "data"]
        for a, b in zip(profiling.PHASES, profiling.PHASES[1:]):
            assert a["start_s"] + a["seconds"] <= b["start_s"] + 2e-3, (a, b)
        for p in phases:
            assert p["seconds"] >= 0.0 and p["memory"] is None  # the CPU
            assert p["programs"] == 0 and p["build_s"] == 0.0
        counts = _counts(before)
        assert counts["fed_setup_data"] == 2
        assert counts["fed_setup_model"] == 1
        # an entry point's second run in one process: its own phases, and
        # no `import` (process start to now would be the first run too)
        profiling.begin_setup()
        assert profiling.PHASES == []
        table = profiling.phase_table()
        assert table.startswith("set-up: 0.00 s in 0 phases")

    def test_import_phase_is_the_os_start_or_left_out(self, monkeypatch):
        monkeypatch.setattr(profiling, "_import_recorded", [False])
        before = span_totals()
        profiling.begin_setup()
        if profiling.PROCESS_START_T is None:
            assert profiling.PHASES == []
        else:
            (imp,) = profiling.PHASES
            assert imp["phase"] == "import" and imp["start_s"] == 0.0
            # this process has lived at least as long as this module
            assert 0.0 < imp["seconds"] < 86400.0
            assert _counts(before)["fed_setup_import"] == 1
        monkeypatch.setattr(profiling, "_import_recorded", [False])
        monkeypatch.setattr(profiling, "PROCESS_START_T", None)
        profiling.begin_setup()
        assert profiling.PHASES == [], "never guessed"
        # a later run of the process keeps no earlier run's builds
        profiling.begin_setup()
        assert not profiling.PROGRAMS

    def test_phases_do_not_nest(self):
        with profiling.phase("data"):
            with pytest.raises(AssertionError):
                with profiling.phase("model"):
                    pass
        assert profiling._open_phase[0] is None

    def test_a_named_program_is_recorded_once(self, listener):
        @jax.jit
        def lifecycle_probe(x):
            return jnp.sin(x) * 2.0 + jnp.cos(x)    # inner jits nest

        n = len(_built_since(0))
        with profiling.phase("fed"):
            lifecycle_probe(jnp.ones(5))
        mine = [b for b in _built_since(n)
                if b["name"] == "jit(lifecycle_probe)"]
        assert len(mine) == 1, _built_since(n)
        (b,) = mine
        assert b["trace_s"] > 0 and b["lower_s"] > 0 and b["backend_s"] > 0
        assert b["cache"] in ("hit", "miss", "off") and b["phase"] == "fed"
        assert profiling.PHASES[-1]["programs"] >= 1
        assert profiling.PHASES[-1]["build_s"] >= b["backend_s"]
        n = len(_built_since(0))
        lifecycle_probe(jnp.ones(5))            # its second call: no build
        assert not [b for b in _built_since(n)
                    if "lifecycle_probe" in b["name"]]
        tot = profiling.program_totals()["jit(lifecycle_probe)"]
        assert tot["builds"] == 1 and tot["hits"] + tot["misses"] <= 1
        # traced but never compiled: a build of its own, no backend
        n = len(_built_since(0))
        jax.eval_shape(lifecycle_probe, jnp.ones(7))
        (b,) = [b for b in _built_since(n) if "lifecycle_probe" in b["name"]]
        assert b["backend_s"] == 0.0 and "cache" not in b

    def test_second_build_reads_cache_hit(self, listener, own_cache):
        salt = float(np.random.RandomState().randint(1, 1 << 30))

        def make():
            @jax.jit
            def lifecycle_cached(x):
                return jnp.tanh(x) * salt
            return lifecycle_cached

        # (a second function object of the same text is a second build
        # with the same cache key: what ``jax.clear_caches()`` would get,
        # without making the suite's other programs build again; called
        # from one line, since with ``configure_compile_cache``'s metadata
        # in the key the caller's line is part of it)
        n = len(_built_since(0))
        for _ in range(2):
            make()(jnp.ones(3))
        first, second = [b for b in _built_since(n)
                         if b["name"] == "jit(lifecycle_cached)"]
        assert first["cache"] == "miss" and first.get("stored") is True
        assert "load_s" not in first
        assert second["cache"] == "hit"
        assert 0.0 < second["load_s"] <= second["backend_s"]
        tot = profiling.program_totals()["jit(lifecycle_cached)"]
        assert (tot["builds"], tot["hits"], tot["misses"],
                tot["stored"]) == (2, 1, 1, 1)

    def test_summary_sums_the_small_programs(self, listener):
        @jax.jit
        def lifecycle_small(x):
            return x + 1

        lifecycle_small(jnp.ones(2))
        whole = profiling.program_summary(floor_s=0.0)
        assert "jit(lifecycle_small)" in whole and "other" not in whole
        few = profiling.program_summary(floor_s=1e9)
        assert set(few) - {"other"} == {
            name for name, t in profiling.program_totals().items()
            if t["misses"]}
        assert sum(t["builds"] for t in few.values()) == \
            sum(t["builds"] for t in whole.values())
        # the listener's own cost is a span total like any other
        assert span_totals()[profiling.LISTENER_SPAN]["count"] > 0

    def test_memory_sample(self, monkeypatch):
        assert profiling.memory_sample("here") is None       # the CPU
        small, full = StubDevice(100), StubDevice(500)
        monkeypatch.setattr(profiling, "_local_devices",
                            lambda: [small, full])
        got = profiling.memory_sample("drain")
        assert got == {"at": "drain", "bytes_in_use": 500,
                       "peak_bytes_in_use": 507, "bytes_reserved": 11,
                       "peak_bytes_reserved": 13,
                       "largest_free_block_bytes": 17, "num_allocs": 19}
        assert small.calls == full.calls == 1

    def test_drain_carries_one_sample(self, tmp_path, monkeypatch):
        """Once a drain, not a round, under its own span; the dispatch path
        and the drain's sample fetch nothing."""
        dev = StubDevice(4096)
        monkeypatch.setattr(profiling, "_local_devices", lambda: [dev])
        fm, engine, rt = _engine(tmp_path, drain_every=3)
        before = span_totals()
        engine.submit(_host_batch([0, 1], seed=0))  # compile round
        with host_sync_monitor(strict=True) as counter:
            for rnd in range(1, 3):
                engine.submit(_host_batch([rnd, rnd + 1], seed=rnd))
        for rnd in range(3, 7):
            engine.submit(_host_batch([rnd % 4, (rnd + 1) % 4], seed=rnd))
        calls = dev.calls
        with host_sync_monitor(strict=True) as counter:
            with annotate("fed_memory_sample"):
                profiling.memory_sample("drain")
            assert counter.count == 0
        engine.drain()
        rt.close()
        events = list(read_events(str(tmp_path / "telemetry.jsonl")))
        drains = [e for e in events if e["ev"] == "drain"]
        assert [(d["round"], d["rounds"], d["inflight"]) for d in drains] \
            == [(2, 3, 0), (5, 3, 0), (6, 1, 0)]
        assert all(d["memory"]["bytes_in_use"] == 4096
                   and d["memory"]["at"] == "drain" for d in drains)
        assert calls == 2 and dev.calls == 5      # + mine, the last drain,
        # run_end's
        assert _counts(before)["fed_memory_sample"] == 4
        end = events[-1]
        assert end["ev"] == "run_end"
        assert end["memory"]["at"] == "run_end"
        assert "fed_memory_sample" in end["spans"]
        assert isinstance(end["programs"], dict)
