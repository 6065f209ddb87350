"""Real multi-process (DCN-path) round execution + the virtual 2D plane.

The reference cannot do multi-host at all (MASTER_ADDR hard-coded to
127.0.0.1, reference fed_aggregator.py:161-162). This framework's multihost
branch (parallel/mesh.py hybrid DCN x ICI meshes) is unit-tested with
monkeypatched fakes in test_parallel.py; the gated tests here run the REAL
thing: scripts/multihost_demo.py spawns two jax.distributed processes (4
virtual CPU devices each), builds the hybrid 8-device mesh, executes one
fused round (or the full engine path with a coordinated checkpoint +
elastic resume) with the transmit reduce crossing the process boundary,
and asserts the result equals the single-process run — parametrized over
{dense, sketch} x {fp32, per-axis int8} (docs/multihost.md).

The NON-gated tests verify the same data plane without a pod: the
single-process VIRTUAL 2D (clients x shard) mesh (--shard_devices) must be
bit-identical to the 1D mesh under the fp32 plan (round step, engine
dispatch, and checkpoint restore across mesh shapes), per-axis plans must
resolve/carry/restore through the FedModel surface, and the telemetry
ledger's per-axis byte split must show the DCN acceptance ratio. The
hierarchical collectives' per-level conservation pins live in
tests/test_compressed_collectives.py §7.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

N = 8


def _run_demo(*argv):
    # bounded by the subprocess timeout below (no pytest-timeout plugin)
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "multihost_demo.py")]
        + list(argv),
        cwd=_REPO, env=dict(os.environ), capture_output=True, text=True,
        timeout=580)
    assert proc.returncode == 0, \
        f"multihost demo failed:\n{proc.stdout[-3000:]}\n{proc.stderr[-2000:]}"
    assert "MULTIHOST OK" in proc.stdout
    return proc.stdout


@pytest.mark.heavy
@pytest.mark.parametrize("mode,plan", [
    ("sketch", ""),
    ("uncompressed", ""),
    ("sketch", "table=dcn:int8,downlink=dcn:int8"),
    ("uncompressed", "uplink=dcn:int8,downlink=dcn:int8"),
], ids=["sketch-fp32", "dense-fp32", "sketch-dcn-int8", "dense-dcn-int8"])
def test_two_process_round_matches_single_process(mode, plan):
    args = ["--mode", mode]
    if plan:
        args += ["--plan", plan]
    _run_demo(*args)


@pytest.mark.heavy
def test_two_process_engine_checkpoint_elastic_resume():
    """The FULL engine path across two processes: pipelined dispatch on
    the 2D (clients x shard) hybrid mesh, a coordinated mid-run
    checkpoint (process 0 writes, cohort barriers), and the parent's
    elastic resume of that checkpoint onto a single-process mesh."""
    out = _run_demo("--engine")
    assert "ELASTIC RESUME OK" in out


# --------------------------------------------------------------------------
# virtual 2D (clients x shard) plane — no pod, no version gate
# --------------------------------------------------------------------------

# explicit axis names (placement-independent on the single-process mesh);
# quantizes the would-be-DCN clients hop of the table and downlink legs
PER_AXIS_PLAN = "table=shard:fp32/clients:int8," \
                "downlink=shard:fp32/clients:int8"


def _fed_model(**over):
    """test_sharded_server's Dense(4) FedModel harness, 2D-mesh-ready
    (shard_devices rides through _fed_args overrides)."""
    import flax.linen as nn

    from commefficient_tpu.federated.aggregator import (
        FedModel,
        FedOptimizer,
        LambdaLR,
    )
    from tests.test_sharded_server import _fed_args

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(4, use_bias=False)(x)

    def loss(params, model_state, batch, rng, train):
        pred = Tiny().apply({"params": params}, batch["inputs"])
        err = pred - batch["targets"]
        mask = batch["mask"]
        return jnp.sum(jnp.square(err).mean(-1) * mask), (), \
            jnp.sum(mask), model_state

    args = _fed_args(**over)
    fm = FedModel(Tiny(), loss, args, input_shape=(3,))
    opt = FedOptimizer(fm, args)
    sched = LambdaLR(opt, lambda step: 0.5)
    return fm, opt, sched


def _fed_batch(seed=1):
    rng = np.random.RandomState(seed)
    return {
        "inputs": jnp.asarray(rng.randn(N, 2, 3), jnp.float32),
        "targets": jnp.asarray(rng.randn(N, 2, 4), jnp.float32),
        "mask": jnp.ones((N, 2), jnp.float32),
        "client_ids": jnp.arange(N, dtype=jnp.int32),
        "worker_mask": jnp.ones(N, jnp.float32),
    }


class TestVirtual2DMesh:
    def test_mesh_and_axes_resolve(self):
        """--shard_devices 2 builds the (clients=4, shard=2) mesh; the
        server plane reduces over the ordered (shard, clients) tuple and
        client state shards over the full 8-device product."""
        fm, _, _ = _fed_model(shard_devices=2)
        assert dict(fm.mesh.shape) == {"clients": 4, "shard": 2}
        assert fm._server_axes == ("shard", "clients")
        assert fm._n_shard == 8
        assert fm._axis_sizes == {"shard": 2, "clients": 4}

    @pytest.mark.parametrize("mode", ["sketch", "uncompressed"])
    def test_2d_fp32_bit_identical_to_1d(self, mode):
        """THE transparency pin: the same rounds on the 2D (clients x
        shard) mesh and the 1D clients mesh produce bit-identical weights
        and server state under the fp32 plan — the flat tuple collectives
        tile exactly like the 1D ones (docs/multihost.md)."""
        et = "virtual" if mode == "sketch" else "none"
        vm = 0.9 if mode == "sketch" else 0.5
        runs = {}
        for sd in (1, 2):
            fm, opt, _ = _fed_model(mode=mode, error_type=et,
                                    virtual_momentum=vm, shard_devices=sd)
            for r in range(2):
                fm(_fed_batch(seed=r))
                opt.step()
            runs[sd] = (np.asarray(fm.ps_weights),
                        np.asarray(opt.server_state.velocity))
        np.testing.assert_array_equal(runs[1][0], runs[2][0])
        np.testing.assert_array_equal(runs[1][1], runs[2][1])

    def test_per_axis_plan_round_and_carries(self):
        """A per-axis plan on the 2D mesh: the legs lower hierarchically,
        the carries come back as per-level slot TUPLES (None at fp32
        levels, live at the quantized clients level), and the round stays
        finite and near the fp32 trajectory."""
        fm, opt, _ = _fed_model(shard_devices=2,
                                collective_plan=PER_AXIS_PLAN)
        assert fm._plan_lowering == {
            "uplink": "float32",
            "table": (("shard", "float32"), ("clients", "int8")),
            "downlink": (("shard", "float32"), ("clients", "int8")),
        }
        assert isinstance(opt.server_state.qres, tuple)
        assert isinstance(opt.server_state.dres, tuple)
        assert opt.server_state.qres[0] is None
        assert opt.server_state.dres[0] is None
        fmf, optf, _ = _fed_model(shard_devices=2)
        for r in range(2):
            fm(_fed_batch(seed=r))
            opt.step()
            fmf(_fed_batch(seed=r))
            optf.step()
        w = np.asarray(fm.ps_weights)
        wf = np.asarray(fmf.ps_weights)
        assert np.isfinite(w).all()
        assert np.abs(w - wf).max() / max(np.abs(wf).max(), 1e-12) < 0.05
        assert float(np.abs(np.asarray(
            opt.server_state.qres[1])).max()) > 0
        assert float(np.abs(np.asarray(
            opt.server_state.dres[1])).max()) > 0

    def test_elastic_restore_across_mesh_shapes(self, tmp_path):
        """A 2D-mesh run's checkpoint restores onto the 1D mesh (and back)
        through the canonical flat view: weights and server state match
        exactly, and the continued rounds agree bit for bit."""
        from commefficient_tpu.federated.checkpoint import (
            load_run_state,
            save_run_state,
        )

        fm, opt, sched = _fed_model(shard_devices=2)
        for r in range(2):
            fm(_fed_batch(seed=r))
            opt.step()
        path = save_run_state(str(tmp_path / "rs"), fm, opt, sched,
                              next_epoch=1)
        fm1, opt1, sched1 = _fed_model(shard_devices=1)
        load_run_state(path, fm1, opt1, sched1)
        np.testing.assert_array_equal(np.asarray(fm.ps_weights),
                                      np.asarray(fm1.ps_weights))
        np.testing.assert_array_equal(np.asarray(opt.server_state.velocity),
                                      np.asarray(opt1.server_state.velocity))
        # both continue and stay in lockstep
        fm(_fed_batch(seed=2))
        opt.step()
        fm1(_fed_batch(seed=2))
        opt1.step()
        np.testing.assert_array_equal(np.asarray(fm.ps_weights),
                                      np.asarray(fm1.ps_weights))

    def test_per_axis_checkpoint_roundtrip(self, tmp_path):
        """Per-axis carry slots save per-slot (server/qres.j) and restore
        exactly into a same-plan run; a plan CHANGE re-inits them
        cleanly."""
        import warnings

        from commefficient_tpu.federated.checkpoint import (
            load_run_state,
            save_run_state,
        )

        fm, opt, sched = _fed_model(shard_devices=2,
                                    collective_plan=PER_AXIS_PLAN)
        for r in range(2):
            fm(_fed_batch(seed=r))
            opt.step()
        path = save_run_state(str(tmp_path / "rs"), fm, opt, sched,
                              next_epoch=1)
        fm2, opt2, sched2 = _fed_model(shard_devices=2,
                                       collective_plan=PER_AXIS_PLAN)
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # exact restore must not warn
            load_run_state(path, fm2, opt2, sched2)
        for name in ("qres", "dres"):
            a, b = getattr(opt.server_state, name), \
                getattr(opt2.server_state, name)
            assert a[0] is None and b[0] is None
            np.testing.assert_array_equal(np.asarray(a[1]),
                                          np.asarray(b[1]), err_msg=name)
        fm(_fed_batch(seed=2))
        opt.step()
        fm2(_fed_batch(seed=2))
        opt2.step()
        np.testing.assert_array_equal(np.asarray(fm.ps_weights),
                                      np.asarray(fm2.ps_weights))
        # flat-plan restore of the per-axis checkpoint re-inits carries
        fm3, opt3, sched3 = _fed_model(shard_devices=2,
                                       collective_plan="int8")
        with pytest.warns(UserWarning):
            load_run_state(path, fm3, opt3, sched3)
        assert not isinstance(opt3.server_state.qres, tuple)

    def test_per_axis_plan_validates_at_startup(self):
        """Satellite 6: an entry naming a missing mesh axis fails at
        FedModel construction with the resolved axis list — and a dcn:
        alias on an all-ICI single-process mesh is the same startup
        error (no silent fp32 fallback)."""
        with pytest.raises(ValueError) as ei:
            _fed_model(shard_devices=2, collective_plan="table=bogus:int8")
        assert "shard=" in str(ei.value) and "clients=" in str(ei.value)
        with pytest.raises(ValueError, match="no server reduce axis"):
            _fed_model(shard_devices=2, collective_plan="table=dcn:int8")

    def test_forced_dcn_alias_resolves(self, monkeypatch):
        """COMMEFFICIENT_FORCE_DCN_AXIS lets the dcn: alias resolve on
        the single-process harness — the no-pod seam for the per-axis
        plan's DCN legs."""
        monkeypatch.setenv("COMMEFFICIENT_FORCE_DCN_AXIS", "clients")
        fm, _, _ = _fed_model(shard_devices=2,
                              collective_plan="table=ici:fp32/dcn:int8")
        assert fm._plan_lowering["table"] \
            == (("shard", "float32"), ("clients", "int8"))


class TestEngine2D:
    def test_engine_2d_fp32_bit_identical_and_elastic_resume(self,
                                                             tmp_path):
        """The tiny-engine harness (the multihost demo's --engine leg,
        __graft_entry__.run_tiny_engine): PipelinedRoundEngine dispatch
        on the 2D mesh is bit-identical to the 1D mesh, a mid-run
        checkpoint resumes bit-exactly on the SAME shape, and elastically
        onto the 1D shape."""
        from __graft_entry__ import run_tiny_engine

        w2, ck = run_tiny_engine(W=N, rounds=3, shard_devices=2,
                                 save_path=str(tmp_path / "rs"), save_at=2)
        w1, _ = run_tiny_engine(W=N, rounds=3, shard_devices=1)
        np.testing.assert_array_equal(w1, w2)
        assert ck is not None
        wr, _ = run_tiny_engine(W=N, rounds=3, shard_devices=2,
                                resume_path=ck)
        np.testing.assert_array_equal(wr, w2)
        we, _ = run_tiny_engine(W=N, rounds=3, shard_devices=1,
                                resume_path=ck)
        np.testing.assert_array_equal(we, w2)


# --------------------------------------------------------------------------
# ledger + run_start topology (satellite 3 acceptance)
# --------------------------------------------------------------------------


class TestPerAxisLedger:
    def _geom(self, d=6_568_640, c=500_000, r=5):
        from types import SimpleNamespace

        c_pad = -(-c // 128) * 128
        return SimpleNamespace(r=r, c_pad=c_pad, T=max(1, -(-d // c_pad)),
                               sublanes=c_pad // 128, d=d)

    def test_dcn_byte_ratio_at_cifar10_sketch_geometry(self):
        """THE multihost acceptance ratio: under the per-axis plan that
        keeps ICI hops fp32 and quantizes only the DCN (clients) hop, the
        ledger's DCN wire bytes/round drop >= 3.99x vs the fp32 plan at
        the CIFAR10 sketch geometry — with the ICI bytes UNCHANGED."""
        from commefficient_tpu.ops import collectives as C
        from commefficient_tpu.telemetry import collective_ledger

        geo = self._geom()
        axes = ("shard", "clients")
        sizes = {"shard": 4, "clients": 2}
        placement = {"shard": "ici", "clients": "dcn"}
        low_fp32 = {leg: "float32" for leg in C.PLAN_LEGS}

        def split(lowering, plan):
            led = collective_ledger("sketch", geo.d, sketch=geo, n_shard=N,
                                    plan=plan, lowering=lowering,
                                    axis_sizes=sizes,
                                    axis_placement=placement)
            out = {"ici": 0, "dcn": 0}
            for name, row in led.items():
                if name == "client_uplink":
                    continue
                per_axis = row.get("bytes_per_axis")
                if per_axis:
                    for ax, leg in per_axis.items():
                        out[leg["placement"]] += leg["bytes_per_round"]
                else:
                    # flat rows price every level at the row's dtype
                    for ax in axes:
                        out[placement[ax]] += row["bytes_per_round"]
            return out

        # fp32 reference, spelled per-axis so both runs split identically
        fp32_low = {"table": (("shard", "float32"), ("clients", "float32")),
                    "downlink": (("shard", "float32"),
                                 ("clients", "float32")),
                    "uplink": "float32"}
        plan_fp32 = C.parse_collective_plan("")
        plan_q = C.parse_collective_plan(
            "table=shard:fp32/clients:int8,downlink=shard:fp32/clients:int8")
        q_low = {"table": (("shard", "float32"), ("clients", "int8")),
                 "downlink": (("shard", "float32"), ("clients", "int8")),
                 "uplink": "float32"}
        base = split(fp32_low, plan_fp32)
        quant = split(q_low, plan_q)
        assert base["ici"] == quant["ici"], "ICI bytes must not change"
        ratio = base["dcn"] / quant["dcn"]
        assert ratio >= 3.99, ratio

    def test_run_start_records_mesh_topology(self, tmp_path):
        """attach_run_telemetry's run_start carries the mesh axes with
        sizes and placements plus the per-axis ledger split — obs_report
        renders the ICI-vs-DCN split from the JSONL alone."""
        from types import SimpleNamespace

        from commefficient_tpu.telemetry import attach_run_telemetry

        fm, _, _ = _fed_model(shard_devices=2,
                              collective_plan=PER_AXIS_PLAN,
                              telemetry=True)
        args = SimpleNamespace(mode="sketch", num_workers=N, k=2, seed=0,
                               server_shard=True, reduce_dtype="float32",
                               telemetry=True, telemetry_hist=False,
                               watch=False, trace_rounds="", guards=False,
                               collective_plan=PER_AXIS_PLAN)
        rt = attach_run_telemetry(args, fm, str(tmp_path), "test")
        assert rt is not None
        rt.close()
        events = [json.loads(line) for line in
                  open(os.path.join(str(tmp_path), "telemetry.jsonl"))]
        start = next(e for e in events if e["ev"] == "run_start")
        mesh = start["mesh"]
        assert mesh["process_count"] == 1
        assert {a["name"]: a["size"] for a in mesh["axes"]} \
            == {"clients": 4, "shard": 2}
        assert all(a["placement"] in ("ici", "dcn") for a in mesh["axes"])
        led = start["ledger"]
        row = led["transmit_reduce"]
        assert "per-axis" in row["collective"]
        per_axis = row["bytes_per_axis"]
        assert set(per_axis) == {"shard", "clients"}
        assert per_axis["shard"]["dtype"] == "float32"
        assert per_axis["clients"]["dtype"] == "int8"
        assert row["bytes_per_round"] \
            == sum(v["bytes_per_round"] for v in per_axis.values())

    def test_obs_report_renders_per_axis_split(self, tmp_path, capsys):
        """scripts/obs_report.py renders the ICI/DCN wire split and mesh
        topology from the run's JSONL."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "obs_report", os.path.join(_REPO, "scripts", "obs_report.py"))
        obs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(obs)

        from types import SimpleNamespace

        from commefficient_tpu.telemetry import attach_run_telemetry

        fm, _, _ = _fed_model(shard_devices=2,
                              collective_plan=PER_AXIS_PLAN,
                              telemetry=True)
        args = SimpleNamespace(mode="sketch", num_workers=N, k=2, seed=0,
                               server_shard=True, reduce_dtype="float32",
                               telemetry=True, telemetry_hist=False,
                               watch=False, trace_rounds="", guards=False,
                               collective_plan=PER_AXIS_PLAN)
        rt = attach_run_telemetry(args, fm, str(tmp_path), "test")
        rt.close()
        path = os.path.join(str(tmp_path), "telemetry.jsonl")
        obs.render(obs.load_events(path))
        out = capsys.readouterr().out
        assert "per-axis wire split" in out
        assert "DCN" in out and "ICI" in out
