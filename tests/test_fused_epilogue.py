"""Fused server epilogue (--fused_epilogue, docs/fused_epilogue.md).

Contracts pinned on the forced-8-device CPU mesh, with the megakernel run
through the Pallas interpreter (COMMEFFICIENT_FUSED_EPILOGUE=interpret —
bit-identical math to the TPU kernel, no Mosaic):

1. op level: ``fused_epilogue_chunks`` == the composed
   ``topk_dense_nd`` + ``sketch_chunks`` pair bit-for-bit (update AND
   re-sketch table), full-range and the sharded ``t0``-offset ``_local``
   variant against the composed local pair;
2. round level: fp32 trajectories and server/client state of a
   ``--fused_epilogue`` round are BIT-IDENTICAL to the composed path's, on
   both the replicated and ``--server_shard`` planes, across the sketch
   mode families (the same pinning style as tests/test_sharded_server.py);
3. error feedback: the fused path retains error/velocity cells exactly
   outside the re-sketched update's nonzero cells — the EF telescoping
   invariant tracked explicitly across rounds;
4. the d-scalable count kernel (ops/topk.py adaptive blocking) bit-equals
   the XLA descent at a >32M synthetic d — the large-d blocking path the
   armed topk_ab A/B measures on-chip.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from commefficient_tpu.federated.rounds import (
    RoundConfig,
    build_round_step,
    init_client_states,
)
from commefficient_tpu.federated.server import (
    ServerConfig,
    init_server_state,
    server_update,
)
from commefficient_tpu.federated.worker import WorkerConfig
from commefficient_tpu.ops.flat import ravel_pytree
from commefficient_tpu.ops.sketch import (
    estimates_chunks,
    fused_epilogue_chunks,
    fused_epilogue_chunks_local,
    make_sketch,
    sketch_chunks,
    sketch_chunks_local,
)
from commefficient_tpu.ops.topk import topk_dense_nd
from tests.test_rounds import _batch, _linear_loss, D
from tests.test_sharded_server import N, _mesh


@pytest.fixture(autouse=True)
def _interpret_kernels(monkeypatch):
    """Run the fused epilogue megakernel through the Pallas interpreter for
    every test here — the CPU suite's only way to execute the kernel path
    (the env is read at trace time; each build below traces fresh)."""
    monkeypatch.setenv("COMMEFFICIENT_FUSED_EPILOGUE", "interpret")


# ---- 1. op-level bit-equality -------------------------------------------

class TestFusedOps:
    GEOMETRIES = [
        (5000, 512, 3, 64),        # tiny: SB > S, multi-strip wrap fold
        (200_000, 80_000, 3, 500),  # S > SB: the sub-blocked (G > 1) path
        (45_000, 1024, 5, 300),    # r = 5 (the FetchSGD row count)
    ]

    @pytest.mark.parametrize("d,c,r,k", GEOMETRIES,
                             ids=[f"d{d}" for d, c, r, k in GEOMETRIES])
    def test_matches_composed_pair(self, d, c, r, k):
        cs = make_sketch(d, c, r, seed=7, num_blocks=2)
        tbl = jnp.asarray(
            np.random.RandomState(5).randn(*cs.table_shape), jnp.float32)
        est = estimates_chunks(cs, tbl)
        upd_c = topk_dense_nd(est, k)
        tbl_c = sketch_chunks(cs, upd_c)
        upd_f, tbl_f = fused_epilogue_chunks(cs, est, k, interpret=True)
        np.testing.assert_array_equal(np.asarray(upd_f), np.asarray(upd_c))
        np.testing.assert_array_equal(np.asarray(tbl_f), np.asarray(tbl_c))

    def test_nan_passthrough(self):
        """Diverged estimates must stay visible in the update (the NaN-abort
        contract of ops/topk's threshold mask), and poison the re-sketch
        exactly like the composed path."""
        cs = make_sketch(5000, 512, 3, seed=7, num_blocks=2)
        tbl = jnp.asarray(
            np.random.RandomState(5).randn(*cs.table_shape), jnp.float32)
        est = estimates_chunks(cs, tbl)
        est = est.at[0, 0, 3].set(jnp.nan)
        upd_f, tbl_f = fused_epilogue_chunks(cs, est, 64, interpret=True)
        upd_c = topk_dense_nd(est, 64)
        np.testing.assert_array_equal(np.asarray(upd_f), np.asarray(upd_c))
        assert np.isnan(np.asarray(upd_f)[0, 0, 3])
        assert np.isnan(np.asarray(tbl_f)).any()

    def test_local_matches_composed_local(self):
        """The t0-offset shard variant == the composed local pair
        (slice-local threshold outside a mesh — the psum'd global threshold
        is covered by the round-level sharded tests below)."""
        cs = make_sketch(5000, 512, 3, seed=7, num_blocks=2)
        tbl = jnp.asarray(
            np.random.RandomState(5).randn(*cs.table_shape), jnp.float32)
        est = estimates_chunks(cs, tbl)
        Tn = -(-cs.T // 4)
        est_p = jnp.pad(est, ((0, 4 * Tn - cs.T), (0, 0), (0, 0)))
        for i in range(4):
            sl = est_p[i * Tn:(i + 1) * Tn]
            u_f, t_f = fused_epilogue_chunks_local(
                cs, sl, jnp.int32(i * Tn), 64, interpret=True)
            u_c = topk_dense_nd(sl, 64, interpret=True)
            t_c = sketch_chunks_local(cs, u_c, jnp.int32(i * Tn),
                                      interpret=True)
            np.testing.assert_array_equal(np.asarray(u_f), np.asarray(u_c),
                                          err_msg=f"shard {i} update")
            np.testing.assert_array_equal(np.asarray(t_f), np.asarray(t_c),
                                          err_msg=f"shard {i} partial table")


# ---- 2. round-level bit-identity ----------------------------------------

def _build(server_shard, fused, error_type="virtual",
           virtual_momentum=0.0, local_momentum=0.0):
    """A placed round on the 8-device CPU mesh, sketch mode, with or
    without --fused_epilogue — mirrors tests/test_sharded_server._build."""
    mesh = _mesh()
    rep = NamedSharding(mesh, P())
    flat, unravel = ravel_pytree({"w": jnp.zeros(D)})

    def ravel(tree):
        return ravel_pytree(tree)[0]

    wcfg = WorkerConfig(mode="sketch", error_type=error_type, k=2,
                        num_workers=N, local_momentum=local_momentum)
    scfg = ServerConfig(mode="sketch", error_type=error_type, k=2,
                        grad_size=D,
                        virtual_momentum=virtual_momentum,
                        local_momentum=local_momentum,
                        fused_epilogue=fused)
    sketch = make_sketch(D, 16, 3, seed=0, num_blocks=1)
    cfg = RoundConfig(worker=wcfg, server=scfg, grad_size=D,
                      server_shard=server_shard)
    steps = build_round_step(_linear_loss, _linear_loss, unravel, ravel,
                             cfg, sketch=sketch, mesh=mesh)
    ss = init_server_state(scfg, sketch)
    ss = ss._replace(velocity=jax.device_put(ss.velocity, rep),
                     error=jax.device_put(ss.error, rep))
    ps = jax.device_put(steps.layout.chunk(flat), rep)
    cs = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, rep),
        init_client_states(16, D, wcfg, init_weights=flat, sketch=sketch))
    return steps, ps, ss, cs


def _run_rounds(steps, ps, ss, cs, rounds=3, lr=0.1):
    traj = []
    for rnd in range(rounds):
        ps, ss, cs, _, _ = steps.train_step(ps, ss, cs, {}, _batch(seed=rnd),
                                            lr, jax.random.key(rnd))
        traj.append(np.asarray(steps.layout.unchunk(ps)))
    return traj, ss, cs


FAMILIES = [
    ("virtual", dict(virtual_momentum=0.9)),
    ("local", dict(local_momentum=0.9)),
]


class TestFusedRoundBitIdentity:
    """Acceptance criterion: fp32 --fused_epilogue trajectories are
    bit-identical to the composed path's, replicated and sharded alike."""

    @pytest.mark.parametrize("shard", [False, True],
                             ids=["replicated", "server_shard"])
    @pytest.mark.parametrize("et,mom", FAMILIES,
                             ids=[f for f, _ in FAMILIES])
    def test_trajectory_bit_identical(self, shard, et, mom):
        a, ssa, csa = _run_rounds(*_build(shard, False, et, **mom))
        b, ssb, csb = _run_rounds(*_build(shard, True, et, **mom))
        for rnd, (x, y) in enumerate(zip(a, b)):
            np.testing.assert_array_equal(
                x, y, err_msg=f"{et}/shard={shard} round {rnd} ps diverged")
        for name in ("velocity", "error"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ssa, name)),
                np.asarray(getattr(ssb, name)), err_msg=name)
        for name in ("velocities", "errors"):
            ca, cb = getattr(csa, name), getattr(csb, name)
            if ca is not None:
                np.testing.assert_array_equal(
                    np.asarray(ca), np.asarray(cb),
                    err_msg=f"client {name}")

    def test_kill_switch_restores_composed(self, monkeypatch):
        """COMMEFFICIENT_FUSED_EPILOGUE=0 must force the composed path even
        with the flag on — same trajectory (trivially: it IS composed)."""
        monkeypatch.setenv("COMMEFFICIENT_FUSED_EPILOGUE", "0")
        a, _, _ = _run_rounds(*_build(False, True,
                                      virtual_momentum=0.9), rounds=2)
        monkeypatch.delenv("COMMEFFICIENT_FUSED_EPILOGUE")
        b, _, _ = _run_rounds(*_build(False, False,
                                      virtual_momentum=0.9), rounds=2)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


# ---- 3. EF telescoping with the fused path ------------------------------

class TestFusedErrorFeedback:
    """The fused epilogue's cell masking implements exactly FetchSGD's
    error feedback: every table cell either transmits (re-sketched update
    cell nonzero → error and velocity zeroed) or is retained bit-exactly
    (error = previous error + velocity) — tracked against an independent
    numpy shadow across rounds, so a silent mask/accumulate bug in the
    kernel cannot telescope away."""

    def test_masking_invariant_over_rounds(self):
        cs = make_sketch(5000, 512, 3, seed=7, num_blocks=2)
        layout = cs.chunk_layout
        cfg = ServerConfig(mode="sketch", error_type="virtual", k=64,
                           grad_size=5000, virtual_momentum=0.9,
                           fused_epilogue=True)
        state = init_server_state(cfg, cs)
        rng = np.random.RandomState(0)
        err_shadow = np.zeros(cs.table_shape, np.float32)
        vel_shadow = np.zeros(cs.table_shape, np.float32)
        for rnd in range(3):
            g = jnp.asarray(rng.randn(*cs.table_shape), jnp.float32)
            upd, state = server_update(g, state, cfg, lr=1.0, sketch=cs,
                                       layout=layout)
            # independent reference masking from the COMPOSED re-sketch of
            # the returned update (lr=1 → update is the unscaled one)
            resk = np.asarray(sketch_chunks(cs, upd))
            vel_shadow = np.asarray(g) + 0.9 * vel_shadow
            err_shadow = err_shadow + vel_shadow
            cell_nz = resk != 0
            assert cell_nz.any(), "no transmitted cells — vacuous round"
            err_shadow = np.where(cell_nz, 0.0, err_shadow)
            vel_shadow = np.where(cell_nz, 0.0, vel_shadow)
            np.testing.assert_array_equal(
                np.asarray(state.error), err_shadow,
                err_msg=f"round {rnd} error retention")
            np.testing.assert_array_equal(
                np.asarray(state.velocity), vel_shadow,
                err_msg=f"round {rnd} velocity retention")


# ---- 4. d-scalable count kernel at > 32M --------------------------------

class TestCountKernelLargeD:
    """ops/topk.py's adaptive blocking: above _PALLAS_TOPK_MAX_D the
    kernels switch to 4x larger (1 MiB) blocks. Both the per-pass count
    kernel and the fused whole-descent kernel must still bit-equal the XLA
    descent there — the path a d=124M round would take with the gate
    lifted (never measured on the chip; ROADMAP D2)."""

    def test_bit_equal_above_gate(self):
        from commefficient_tpu.ops.topk import (
            _PALLAS_TOPK_MAX_D,
            _sub_for,
            _threshold_descent_fused,
            _threshold_descent_pallas,
            _threshold_descent_xla,
        )

        d = _PALLAS_TOPK_MAX_D + 1
        assert _sub_for(d) == 4 * _sub_for(_PALLAS_TOPK_MAX_D)
        v = jnp.asarray(
            np.random.RandomState(0).randn(d).astype(np.float32))
        raw = v.view(jnp.int32)
        p_x = int(_threshold_descent_xla(raw, 50_000))
        p_p = int(_threshold_descent_pallas(raw, 50_000, interpret=True))
        assert p_x == p_p, "per-pass kernel diverged at large-d blocking"
        p_f = int(np.asarray(
            _threshold_descent_fused(raw, 50_000, interpret=True)))
        assert p_x == p_f, "fused-descent kernel diverged at large-d blocking"
