"""Mixture-of-Experts + expert parallelism (`expert` mesh axis, GPT-2 only).

Extension beyond the reference (SURVEY.md §2.3: MoE/expert parallelism is
explicitly absent there): every other GPT-2 block gets a top-1-routed
(Switch-style) MoE MLP (parallel/moe.py) whose experts shard over the
`expert` mesh axis. Parameters stay full-shape/replicated so the federated
flat vector, compression, and checkpoints are untouched; the worker
reconciles per-shard gradients with one psum + a flat rescale mask
(federated/rounds.py ep_scale, worker.forward_grad), exactly the tensor-
parallel scheme with a different sliced-param predicate.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("COMMEFFICIENT_TINY_MODEL", "1")
os.environ.setdefault("COMMEFFICIENT_GPT2_SEQ_LEN", "64")

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from commefficient_tpu.compat import shard_map

from commefficient_tpu.federated.losses import make_gpt2_losses
from commefficient_tpu.federated.rounds import (
    RoundConfig,
    build_round_step,
    init_client_states,
)
from commefficient_tpu.federated.server import ServerConfig, init_server_state
from commefficient_tpu.federated.worker import WorkerConfig
from commefficient_tpu.models.gpt2 import GPT2DoubleHeads
from commefficient_tpu.ops.flat import ravel_pytree
from commefficient_tpu.parallel.mesh import make_mesh
from commefficient_tpu.parallel.moe import MoEMLP, ep_sliced_param

V, T, E, L, H = 128, 16, 32, 2, 4
NEXP = 4


def _models():
    dense = GPT2DoubleHeads(vocab_size=V, n_positions=T, n_embd=E,
                            n_layer=L, n_head=H, dropout=0.0,
                            n_experts=NEXP)
    ep = dense.copy(expert_axis="expert")
    return dense, ep


def _ids(seed, shape):
    return jnp.asarray(np.random.RandomState(seed).randint(0, V, shape),
                       jnp.int32)


class TestMoEMLP:
    def test_matches_manual_top1(self):
        """The module's output equals the hand-computed Switch rule: each
        token goes through exactly its argmax expert's MLP, weighted by
        that expert's softmax probability."""
        C, nexp = 8, 4
        mod = MoEMLP(C, nexp)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 4, C), jnp.float32)
        params = mod.init(jax.random.key(1), x)["params"]
        out = mod.apply({"params": params}, x)

        router = np.asarray(params["router"])
        w_fc, b_fc = np.asarray(params["w_fc"]), np.asarray(params["b_fc"])
        w_pr, b_pr = np.asarray(params["w_proj"]), np.asarray(params["b_proj"])
        xn = np.asarray(x)
        probs = np.asarray(jax.nn.softmax(jnp.asarray(xn @ router), axis=-1))
        expected = np.zeros_like(xn)
        for b in range(xn.shape[0]):
            for t in range(xn.shape[1]):
                e = int(np.argmax(probs[b, t]))
                h = np.asarray(jax.nn.gelu(
                    jnp.asarray(xn[b, t] @ w_fc[e] + b_fc[e]),
                    approximate=True))
                expected[b, t] = probs[b, t, e] * (h @ w_pr[e] + b_pr[e])
        np.testing.assert_allclose(np.asarray(out), expected,
                                   atol=1e-5, rtol=1e-5)

    def test_aux_loss_matches_manual(self):
        """The sown Switch aux equals E * sum_e f_e * P_e computed by hand,
        and equals 1.0 exactly at perfectly balanced hard routing."""
        C, nexp = 8, 4
        mod = MoEMLP(C, nexp)
        x = jnp.asarray(np.random.RandomState(5).randn(2, 6, C), jnp.float32)
        params = mod.init(jax.random.key(6), x)["params"]
        _, sown = mod.apply({"params": params}, x, mutable=["moe_losses"])
        (aux,) = sown["moe_losses"]["aux"]

        router = np.asarray(params["router"])
        probs = np.asarray(jax.nn.softmax(
            jnp.asarray(np.asarray(x) @ router), axis=-1)).reshape(-1, nexp)
        top = probs.argmax(-1)
        f = np.bincount(top, minlength=nexp) / probs.shape[0]
        P = probs.mean(0)
        np.testing.assert_allclose(float(aux), nexp * float((f * P).sum()),
                                   rtol=1e-6)
        assert float(aux) >= 1.0 - 1e-6  # E*sum(f*P) is minimized at 1

    def test_aux_loss_seq_sharded_matches_global(self):
        """With the token dimension sharded over a `seq` axis, the sown aux
        equals the aux of the full sequence (global routing stats, not
        per-shard ones) and is replicated across seq shards."""
        C, nexp, nsq = 8, 4, 2
        dense = MoEMLP(C, nexp)
        seqmod = MoEMLP(C, nexp, seq_axis="seq")
        x = jnp.asarray(np.random.RandomState(9).randn(2, 8, C), jnp.float32)
        params = dense.init(jax.random.key(10), x)["params"]
        _, sown = dense.apply({"params": params}, x, mutable=["moe_losses"])
        (aux_d,) = sown["moe_losses"]["aux"]
        mesh = make_mesh([("seq", nsq)])

        def f(p, xx):
            _, s = seqmod.apply({"params": p}, xx, mutable=["moe_losses"])
            return s["moe_losses"]["aux"][0][None]  # (1,) per shard

        aux_s = jax.jit(shard_map(
            f, mesh=mesh, in_specs=(P(), P(None, "seq", None)),
            out_specs=P("seq"), check_vma=False))(params, x)
        # every shard's sown aux equals the global (full-sequence) aux
        np.testing.assert_allclose(np.asarray(aux_s),
                                   np.full(nsq, float(aux_d)), rtol=1e-6)

    def test_aux_loss_sharded_matches_unsharded(self):
        C, nexp, ne = 8, 4, 2
        dense = MoEMLP(C, nexp)
        sharded = MoEMLP(C, nexp, expert_axis="expert")
        x = jnp.asarray(np.random.RandomState(7).randn(2, 6, C), jnp.float32)
        params = dense.init(jax.random.key(8), x)["params"]
        _, sown = dense.apply({"params": params}, x, mutable=["moe_losses"])
        (aux_d,) = sown["moe_losses"]["aux"]
        mesh = make_mesh([("expert", ne)])

        def f(p, xx):
            out, s = sharded.apply({"params": p}, xx,
                                   mutable=["moe_losses"])
            return s["moe_losses"]["aux"][0]

        aux_s = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                  out_specs=P(), check_vma=False))(params, x)
        np.testing.assert_allclose(float(aux_s), float(aux_d), rtol=1e-6)

    @pytest.mark.parametrize("ne", [2, 4])
    def test_sharded_matches_unsharded(self, ne):
        """Expert-sharded MoEMLP inside a shard_map equals the unsharded
        module with the same (full-shape) params."""
        C, nexp = 8, 4
        dense = MoEMLP(C, nexp)
        sharded = MoEMLP(C, nexp, expert_axis="expert")
        x = jnp.asarray(np.random.RandomState(2).randn(2, 4, C), jnp.float32)
        params = dense.init(jax.random.key(3), x)["params"]
        ref = dense.apply({"params": params}, x)
        mesh = make_mesh([("expert", ne)])

        def f(p, xx):
            return sharded.apply({"params": p}, xx)

        got = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                out_specs=P(), check_vma=False))(params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("ne", [2, 4])
    def test_sparse_dispatch_matches_dense_at_full_capacity(self, ne):
        """VERDICT r4 #8 parity contract: at capacity_factor >= E no token
        can drop, so sparse (capacity) dispatch must equal dense dispatch
        — unsharded AND expert-sharded."""
        C, nexp = 8, 4
        dense = MoEMLP(C, nexp)
        sparse = MoEMLP(C, nexp, dispatch="sparse",
                        capacity_factor=float(nexp))
        x = jnp.asarray(np.random.RandomState(4).randn(2, 8, C), jnp.float32)
        params = dense.init(jax.random.key(3), x)["params"]
        ref = dense.apply({"params": params}, x)
        got = sparse.apply({"params": params}, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

        sharded = MoEMLP(C, nexp, dispatch="sparse",
                         capacity_factor=float(nexp), expert_axis="expert")
        mesh = make_mesh([("expert", ne)])
        got_ep = jax.jit(shard_map(
            lambda p, xx: sharded.apply({"params": p}, xx), mesh=mesh,
            in_specs=(P(), P()), out_specs=P(), check_vma=False))(params, x)
        np.testing.assert_allclose(np.asarray(got_ep), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_sparse_dispatch_drops_overflow_tokens(self):
        """At a tiny capacity, an expert processes only its first Cap
        routed tokens (token order); every dropped token's MoE output is
        exactly zero (residual passthrough at the Block level)."""
        C, nexp = 8, 2
        # route everything to expert 0 via a rigged router: a real bias on
        # column 0, so the routing does not rest on argmax tie-breaking
        sparse = MoEMLP(C, nexp, dispatch="sparse", capacity_factor=0.25)
        x = jnp.asarray(np.abs(np.random.RandomState(7).randn(1, 8, C)),
                        jnp.float32)
        params = sparse.init(jax.random.key(8), x)["params"]
        router = np.zeros_like(np.asarray(params["router"]))
        router[:, 0] = 1.0  # positive inputs -> column 0 logit dominates
        params = dict(params, router=jnp.asarray(router))
        out = sparse.apply({"params": params}, x)
        # all 8 tokens routed to expert 0; Cap = round(0.25*8/2) = 1 ->
        # only the first token in order survives
        outn = np.asarray(out)[0]
        assert np.abs(outn[0]).sum() > 0
        np.testing.assert_array_equal(outn[1:], 0.0)

    def test_sparse_dispatch_gradients_flow(self):
        """Router and expert weights receive gradients through the sparse
        path (the dispatch mask is constant, the gate probability is not)."""
        C, nexp = 8, 4
        sparse = MoEMLP(C, nexp, dispatch="sparse",
                        capacity_factor=float(nexp))
        x = jnp.asarray(np.random.RandomState(11).randn(2, 4, C),
                        jnp.float32)
        params = sparse.init(jax.random.key(12), x)["params"]

        def loss(p):
            return jnp.sum(sparse.apply({"params": p}, x) ** 2)

        g = jax.grad(loss)(params)
        assert float(jnp.abs(g["router"]).sum()) > 0
        assert float(jnp.abs(g["w_fc"]).sum()) > 0
        assert float(jnp.abs(g["w_proj"]).sum()) > 0

    def test_sparse_dispatch_cuts_compiled_flops(self):
        """The measured FLOP reduction the stretch goal asks for: XLA's
        compiled cost analysis of the sparse forward at capacity_factor
        1.0 is well below the dense forward's at E=8 (dense pays all E
        experts per token; sparse pays ~1 plus the dispatch einsums)."""
        C, nexp = 64, 8
        x = jnp.asarray(np.random.RandomState(13).randn(4, 64, C),
                        jnp.float32)
        dense = MoEMLP(C, nexp)
        sparse = MoEMLP(C, nexp, dispatch="sparse", capacity_factor=1.0)
        params = dense.init(jax.random.key(14), x)["params"]

        def flops(mod):
            comp = (jax.jit(lambda p, xx: mod.apply({"params": p}, xx))
                    .lower(params, x).compile())
            ca = comp.cost_analysis()
            analysis = ca if isinstance(ca, dict) else ca[0]
            return float(analysis["flops"])

        f_dense, f_sparse = flops(dense), flops(sparse)
        # at E=8, C=64, N=256: dense expert compute dominates; sparse
        # should cut total compiled FLOPs by >2x even counting the
        # dispatch/combine einsums
        assert f_sparse < f_dense / 2, (f_dense, f_sparse)

    def test_ep_sliced_param_predicate(self):
        assert ep_sliced_param("h1/moe/w_fc")
        assert ep_sliced_param("h1/moe/b_proj")
        # the router's per-shard grads are disjoint partial contributions
        # (backprop of only the local experts' combine slots) — psum with
        # scale 1, like the expert-stacked weights
        assert ep_sliced_param("h1/moe/router")
        assert not ep_sliced_param("h1/attn_qkv/kernel")
        assert not ep_sliced_param("wte/embedding")


class TestMoEModel:
    def test_moe_every_other_block(self):
        """moe_every=2 gives blocks 1, 3, ... a `moe` module and leaves the
        rest dense — the GShard every-other-layer pattern."""
        dense, _ = _models()
        ids = _ids(0, (1, 2, T))
        params = dense.init(jax.random.key(0), ids, token_type_ids=ids,
                            mc_token_ids=jnp.zeros((1, 2), jnp.int32),
                            train=False)["params"]
        assert "moe" not in params["h0"] and "mlp_fc" in params["h0"]
        assert "moe" in params["h1"] and "mlp_fc" not in params["h1"]
        assert params["h1"]["moe"]["w_fc"].shape == (NEXP, E, 4 * E)

    @pytest.mark.parametrize("ne", [2, 4])
    def test_forward_matches_unsharded(self, ne):
        dense, ep = _models()
        ids = _ids(1, (2, 2, T))
        mc = jnp.asarray(np.random.RandomState(2).randint(0, T, (2, 2)),
                         jnp.int32)
        params = dense.init(jax.random.key(0), ids, token_type_ids=ids,
                            mc_token_ids=mc, train=False)["params"]
        lm_d, mc_d = dense.apply({"params": params}, ids, token_type_ids=ids,
                                 mc_token_ids=mc, train=False)
        mesh = make_mesh([("expert", ne)])

        def f(p, i, m):
            return ep.apply({"params": p}, i, token_type_ids=i,
                            mc_token_ids=m, train=False)

        lm_e, mc_e = jax.jit(shard_map(
            f, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
            check_vma=False))(params, ids, mc)
        np.testing.assert_allclose(np.asarray(lm_e), np.asarray(lm_d),
                                   atol=3e-5, rtol=3e-5)
        np.testing.assert_allclose(np.asarray(mc_e), np.asarray(mc_d),
                                   atol=3e-5, rtol=3e-5)


class TestEPRound:
    def _build(self, model, mesh, expert_axis, fuse=None):
        W, B, C = 2, 2, 2
        ids0 = jnp.zeros((1, C, T), jnp.int32)
        init_model = model.copy(expert_axis=None)
        params = init_model.init(jax.random.key(0), ids0,
                                 token_type_ids=ids0,
                                 mc_token_ids=jnp.zeros((1, C), jnp.int32),
                                 train=False)["params"]
        flat, unravel = ravel_pytree(params)
        d = int(flat.size)

        def ravel(tree):
            return ravel_pytree(tree)[0]

        wcfg = WorkerConfig(mode="uncompressed", error_type="virtual",
                            num_workers=W, expert_axis=expert_axis)
        scfg = ServerConfig(mode="uncompressed", error_type="virtual",
                            grad_size=d, virtual_momentum=0.9)
        cfg = RoundConfig(worker=wcfg, server=scfg, grad_size=d,
                          ep_sliced=ep_sliced_param if expert_axis else None,
                          fuse_gradients=fuse)
        # aux active: the round parity below then also pins the sliced-aux
        # router gradients under expert parallelism
        lt, lv = make_gpt2_losses(model, moe_aux_coef=0.01)
        steps = build_round_step(lt, lv, unravel, ravel, cfg, mesh=mesh)
        rng = np.random.RandomState(3)
        batch = {
            "input_ids": _ids(4, (W, B, C, T)),
            "token_type_ids": _ids(5, (W, B, C, T)),
            "lm_labels": _ids(6, (W, B, C, T)),
            "mc_token_ids": jnp.asarray(rng.randint(0, T, (W, B, C)),
                                        jnp.int32),
            "mc_labels": jnp.asarray(rng.randint(0, C, (W, B)), jnp.int32),
            "mask": jnp.ones((W, B), jnp.float32),
            "client_ids": jnp.arange(W, dtype=jnp.int32),
            "worker_mask": jnp.ones(W, jnp.float32),
        }
        ss = init_server_state(scfg, None)
        cs = init_client_states(4, d, wcfg)
        # Pre-place PS/server/client state replicated on the mesh, exactly
        # as the production entrypoints do (FedModel._place_replicated).
        # The round donates (the default): re-tested on jax 0.9.0 with a
        # warm persistent compile cache, the jax 0.4.37 stale-donated-input
        # bug these tests once pinned donate=False against is gone.
        from jax.sharding import NamedSharding

        rep = NamedSharding(mesh, P())
        flat = jax.device_put(flat, rep)
        ss, cs = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, rep), (ss, cs))
        return steps, flat, ss, cs, batch

    @pytest.mark.parametrize("fuse", [False, True])
    def test_round_matches_unsharded(self, fuse):
        """A full federated round over a clients x expert mesh produces the
        same new weights and metrics as the unsharded round over clients
        only — the gradient reconciliation (psum + ep_scale) is exact up to
        float summation order. Covers both client phases."""
        dense, ep = _models()
        mesh_d = make_mesh([("clients", 2)])
        mesh_e = make_mesh([("clients", 2), ("expert", 2)])

        def run(model, mesh, axis):
            steps, flat, ss, cs, batch = self._build(model, mesh, axis,
                                                     fuse=fuse)
            out = steps.train_step(flat, ss, cs, {}, batch, 0.1,
                                   jax.random.key(7))
            return np.asarray(out[0]), [np.asarray(m) for m in out[4]]

        w_d, m_d = run(dense, mesh_d, None)
        w_e, m_e = run(ep, mesh_e, "expert")
        np.testing.assert_allclose(w_e, w_d, atol=2e-5, rtol=2e-5)
        for a, b in zip(m_e, m_d):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)

    def test_expert_grads_flow(self):
        """Expert weights and the router actually receive gradient through
        the round (the top-1 estimator is not silently zero)."""
        dense, ep = _models()
        mesh_e = make_mesh([("clients", 2), ("expert", 2)])
        steps, flat, ss, cs, batch = self._build(ep, mesh_e, "expert")
        flat0 = np.asarray(flat)  # snapshot — train_step donates its input
        out = steps.train_step(flat, ss, cs, {}, batch, 0.1,
                               jax.random.key(7))
        new_flat = np.asarray(out[0])

        ids0 = jnp.zeros((1, 2, T), jnp.int32)
        params = dense.copy(expert_axis=None).init(
            jax.random.key(0), ids0, token_type_ids=ids0,
            mc_token_ids=jnp.zeros((1, 2), jnp.int32), train=False)["params"]
        _, unravel = ravel_pytree(params)
        delta = unravel(jnp.asarray(new_flat - flat0))
        moe = delta["h1"]["moe"]
        assert float(jnp.abs(moe["w_fc"]).max()) > 0
        assert float(jnp.abs(moe["router"]).max()) > 0

    def test_val_step_runs_replicated(self):
        """val_step wraps the expert-parallel model in its own shard_map."""
        _, ep = _models()
        mesh_e = make_mesh([("clients", 2), ("expert", 2)])
        steps, flat, ss, cs, batch = self._build(ep, mesh_e, "expert")
        vbatch = {k: v.reshape((-1,) + v.shape[2:])
                  for k, v in batch.items()
                  if k not in ("client_ids", "worker_mask")}
        metrics = steps.val_step(flat, {}, vbatch)
        assert all(np.isfinite(np.asarray(m)).all() for m in metrics)


class TestEPWiring:
    def test_degrades_gracefully_without_devices(self):
        """--expert_devices on a host with too few devices: the mesh policy
        warns and drops the axis, and the worker config derived from the
        REALIZED mesh clears expert_axis — no unbound-axis crash."""
        from commefficient_tpu.config import parse_args
        from commefficient_tpu.federated.aggregator import (
            worker_config_from_args,
        )
        from commefficient_tpu.parallel.mesh import default_client_mesh

        with pytest.warns(UserWarning, match="--expert_devices 2 reduced"):
            mesh = default_client_mesh(2, -1, devices=jax.devices()[:1],
                                       expert_devices=2)
        assert "expert" not in mesh.axis_names
        args = parse_args(argv=["--mode", "uncompressed",
                                "--local_momentum", "0",
                                "--n_experts", "4",
                                "--expert_devices", "2"])
        wcfg = worker_config_from_args(args, mesh=mesh)
        assert wcfg.expert_axis is None

    def test_cv_entrypoint_rejects_n_experts(self, tmp_path):
        """MoE is GPT-2 only; the CV entrypoint must say so."""
        import cv_train

        with pytest.raises(AssertionError, match="GPT-2 only"):
            cv_train.main(["--dataset_name", "CIFAR10",
                           "--dataset_dir", str(tmp_path / "d"),
                           "--mode", "uncompressed", "--local_momentum", "0",
                           "--n_experts", "4"])

    def test_validate_args_invariants(self):
        from commefficient_tpu.config import parse_args

        with pytest.raises(AssertionError, match="requires --n_experts"):
            parse_args(argv=["--mode", "uncompressed",
                             "--local_momentum", "0",
                             "--expert_devices", "2"])
        with pytest.raises(AssertionError, match="must divide"):
            parse_args(argv=["--mode", "uncompressed",
                             "--local_momentum", "0",
                             "--n_experts", "3", "--expert_devices", "2"])
        # MoE composes with pipeline parallelism (clients x stage x expert,
        # tests/test_pipeline.py TestPPxEP) — the flags must be accepted
        args = parse_args(argv=["--mode", "uncompressed",
                                "--local_momentum", "0",
                                "--n_experts", "2", "--pipeline_devices", "2",
                                "--expert_devices", "2"])
        assert args.n_experts == 2 and args.pipeline_devices == 2

    def test_mesh_degrade_keeps_expert_divisibility(self):
        """Clamping the expert axis to the device budget must land on a
        divisor of n_experts (4 devices for --expert_devices 3 with
        n_experts=4 -> ne=2, not 3), or the realized shard slice E/ne
        would not exist."""
        from commefficient_tpu.parallel.mesh import default_client_mesh

        with pytest.warns(UserWarning, match="must divide --n_experts"):
            mesh = default_client_mesh(2, -1, devices=jax.devices()[:8],
                                       expert_devices=3, n_experts=4)
        assert mesh.shape["expert"] == 2

    def test_load_hf_gpt2_warns_on_moe_blocks(self, tmp_path, capsys):
        """A local HF checkpoint loaded into an MoE model must say which
        blocks keep fresh experts instead of silently half-loading."""
        import torch

        from commefficient_tpu.models.gpt2 import load_hf_gpt2

        dense, _ = _models()
        ids = _ids(0, (1, 2, T))
        params = dense.init(jax.random.key(0), ids, token_type_ids=ids,
                            mc_token_ids=jnp.zeros((1, 2), jnp.int32),
                            train=False)["params"]
        # minimal HF-style state dict covering the non-MoE tensors
        state = {
            "transformer.wte.weight": torch.zeros(V, E),
            "transformer.wpe.weight": torch.zeros(T, E),
            "transformer.ln_f.weight": torch.ones(E),
            "transformer.ln_f.bias": torch.zeros(E),
        }
        for i in range(L):
            p = f"transformer.h.{i}."
            state[p + "ln_1.weight"] = torch.ones(E)
            state[p + "ln_1.bias"] = torch.zeros(E)
            state[p + "ln_2.weight"] = torch.ones(E)
            state[p + "ln_2.bias"] = torch.zeros(E)
            state[p + "attn.c_attn.weight"] = torch.zeros(E, 3 * E)
            state[p + "attn.c_attn.bias"] = torch.zeros(3 * E)
            state[p + "attn.c_proj.weight"] = torch.zeros(E, E)
            state[p + "attn.c_proj.bias"] = torch.zeros(E)
            state[p + "mlp.c_fc.weight"] = torch.zeros(E, 4 * E)
            state[p + "mlp.c_fc.bias"] = torch.zeros(4 * E)
            state[p + "mlp.c_proj.weight"] = torch.zeros(4 * E, E)
            state[p + "mlp.c_proj.bias"] = torch.zeros(E)
        torch.save(state, tmp_path / "pytorch_model.bin")
        loaded = load_hf_gpt2(params, str(tmp_path))
        assert loaded is not None
        out = capsys.readouterr().out
        assert "blocks [1] are MoE" in out
        # the MoE block kept its fresh experts; the dense block loaded
        assert float(jnp.abs(loaded["h1"]["moe"]["w_fc"]).max()) > 0
        assert float(jnp.abs(loaded["h0"]["mlp_fc"]["kernel"]).max()) == 0


class TestEPEndToEnd:
    @pytest.mark.parametrize("dispatch", ["dense", "sparse"])
    def test_gpt2_train_expert_parallel(self, tmp_path, monkeypatch,
                                        dispatch):
        """--n_experts/--expert_devices runs the full train+val loop with
        experts sharded over a 2-wide `expert` mesh axis (the math is
        pinned above; this pins the CLI wiring end-to-end incl. the sketch
        pipeline on the reconciled gradient), for both dispatch modes."""
        if len(jax.devices()) < 4:
            pytest.skip("needs a 4-device mesh (2 clients x 2 expert)")
        monkeypatch.setenv("COMMEFFICIENT_SYNTHETIC_CLIENTS", "8")
        import gpt2_train

        stats = gpt2_train.train(argv=[
            "--dataset_name", "PERSONA",
            "--dataset_dir", str(tmp_path / "persona"),
            "--num_epochs", "1",
            "--num_workers", "2",
            "--local_batch_size", "2",
            "--valid_batch_size", "2",
            "--num_candidates", "2",
            "--mode", "sketch",
            "--error_type", "virtual",
            "--local_momentum", "0",
            "--k", "64",
            "--num_cols", "2048",
            "--num_rows", "3",
            "--num_blocks", "2",
            "--lr_scale", "0.001",
            "--seed", "0",
            "--n_experts", "2",
            "--expert_devices", "2",
            "--moe_dispatch", dispatch,
        ])
        assert np.isfinite(stats["val_nll"])
        assert np.isfinite(stats["val_ppl"])

    def test_gpt2_train_moe_seq_parallel(self, tmp_path, monkeypatch):
        """--n_experts with --seq_parallel: the MoE aux is computed from
        global routing stats over the `seq` axis (psum_repct/nsq,
        parallel/moe.py seq_axis), pinned unit-side by
        test_aux_loss_seq_sharded_matches_global; this pins the CLI
        wiring end-to-end. TestSPxEP covers the sharded-expert variant
        (--expert_devices > 1 composes too)."""
        if len(jax.devices()) < 4:
            pytest.skip("needs a 4-device mesh (2 clients x 2 seq)")
        monkeypatch.setenv("COMMEFFICIENT_SYNTHETIC_CLIENTS", "8")
        import gpt2_train

        stats = gpt2_train.train(argv=[
            "--dataset_name", "PERSONA",
            "--dataset_dir", str(tmp_path / "persona"),
            "--num_epochs", "1",
            "--num_workers", "2",
            "--local_batch_size", "2",
            "--valid_batch_size", "2",
            "--num_candidates", "2",
            "--mode", "uncompressed",
            "--lr_scale", "0.001",
            "--seed", "0",
            "--n_experts", "2",
            "--seq_parallel", "ring",
            "--seq_devices", "2",
        ])
        assert np.isfinite(stats["val_nll"])
        assert np.isfinite(stats["val_ppl"])


from tests.test_tensor_parallel import _shift_labels  # noqa: E402


def _composed_round_fixtures():
    """Shared fixtures for the composed-mesh round-parity tests (the MoE
    GPT-2 model, its flat params, and one 2-worker batch)."""
    dense, _ = _models()
    W, B, C = 2, 2, 2
    ids0 = jnp.zeros((1, C, T), jnp.int32)
    params = dense.init(jax.random.key(0), ids0, token_type_ids=ids0,
                        mc_token_ids=jnp.zeros((1, C), jnp.int32),
                        train=False)["params"]
    flat0, unravel = ravel_pytree(params)
    d = int(flat0.size)
    rng = np.random.RandomState(3)
    lm_labels = _ids(6, (W, B, C, T))
    batch = {
        "input_ids": _ids(4, (W, B, C, T)),
        "token_type_ids": _ids(5, (W, B, C, T)),
        "lm_labels": lm_labels,
        "mc_token_ids": jnp.asarray(rng.randint(0, T, (W, B, C)),
                                    jnp.int32),
        "mc_labels": jnp.asarray(rng.randint(0, C, (W, B)), jnp.int32),
        "mask": jnp.ones((W, B), jnp.float32),
        "client_ids": jnp.arange(W, dtype=jnp.int32),
        "worker_mask": jnp.ones(W, jnp.float32),
    }
    return dense, flat0, unravel, d, batch, lm_labels


def _run_composed_round(model, mesh, seq_axis, model_axis, expert_axis,
                        fuse, flat0, unravel, d, batch, lm_labels):
    """One full federated round (aux active) under any combination of
    seq/model/expert axes; returns (new weights, metrics). The single
    round-runner for every composed-mesh parity test in this file."""
    from commefficient_tpu.models.gpt2 import tp_sliced_param

    def ravel(tree):
        return ravel_pytree(tree)[0]

    wcfg = WorkerConfig(mode="uncompressed", error_type="virtual",
                        num_workers=2, seq_axis=seq_axis,
                        model_axis=model_axis, expert_axis=expert_axis)
    scfg = ServerConfig(mode="uncompressed", error_type="virtual",
                        grad_size=d, virtual_momentum=0.9)
    cfg = RoundConfig(worker=wcfg, server=scfg, grad_size=d,
                      tp_sliced=(tp_sliced_param if model_axis else None),
                      ep_sliced=(ep_sliced_param if expert_axis else None),
                      fuse_gradients=fuse)
    lt, lv = make_gpt2_losses(model, seq_axis=seq_axis, moe_aux_coef=0.01)
    steps = build_round_step(lt, lv, unravel, ravel, cfg, mesh=mesh)
    b = dict(batch)
    if seq_axis is not None:
        b["lm_labels_shifted"] = _shift_labels(lm_labels)
        del b["lm_labels"]
    ss = init_server_state(scfg, None)
    cs = init_client_states(4, d, wcfg)
    out = steps.train_step(jnp.array(flat0), ss, cs, {}, b, 0.1,
                           jax.random.key(7))
    return np.asarray(out[0]), [np.asarray(m) for m in out[4]]



class TestSPxEP:
    """Sequence parallelism COMPOSED with expert parallelism (a clients x
    seq x expert mesh): each (seq, expert) shard dispatches its local
    tokens to its local experts; the worker reconciles with the seq psum
    (token-partial grads, scale 1) and the expert psum x ep_scale on
    orthogonal axes (federated/rounds.py)."""

    def test_logits_and_aux_match_unsharded(self):
        """MoE GPT-2 forward over a seq x expert 2x2 mesh equals the
        unsharded forward, and the sown aux equals the global-stat aux."""
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices (2 seq x 2 expert)")
        from commefficient_tpu.parallel.moe import MoEMLP

        C, nexp = 8, 4
        dense = MoEMLP(C, nexp)
        both = MoEMLP(C, nexp, expert_axis="expert", seq_axis="seq")
        x = jnp.asarray(np.random.RandomState(11).randn(2, 8, C),
                        jnp.float32)
        params = dense.init(jax.random.key(12), x)["params"]
        out_d, sown = dense.apply({"params": params}, x,
                                  mutable=["moe_losses"])
        (aux_d,) = sown["moe_losses"]["aux"]
        mesh = make_mesh([("seq", 2), ("expert", 2)])

        def f(p, xx):
            out, s = both.apply({"params": p}, xx, mutable=["moe_losses"])
            return out, s["moe_losses"]["aux"][0][None]

        out_b, aux_b = jax.jit(shard_map(
            f, mesh=mesh, in_specs=(P(), P(None, "seq", None)),
            out_specs=(P(None, "seq", None), P("seq")),
            check_vma=False))(params, x)
        np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_d),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(aux_b),
                                   np.full(2, float(aux_d)), rtol=1e-6)

    @pytest.mark.parametrize("fuse", [False, True])
    def test_round_matches_unsharded(self, fuse):
        """A full federated round (aux active) over clients x seq x expert
        equals the unsharded clients-only round, exact up to float
        summation order."""
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices (2 clients x 2 seq x 2 expert)")
        dense, flat0, unravel, d, batch, lm = _composed_round_fixtures()
        w_d, m_d = _run_composed_round(
            dense, make_mesh([("clients", 2)]), None, None, None, fuse,
            flat0, unravel, d, batch, lm)
        both = dense.copy(expert_axis="expert", attn_impl="ring")
        w_b, m_b = _run_composed_round(
            both, make_mesh([("clients", 2), ("seq", 2), ("expert", 2)]),
            "seq", None, "expert", fuse, flat0, unravel, d, batch, lm)
        np.testing.assert_allclose(w_b, w_d, atol=2e-5, rtol=2e-5)
        for a, b in zip(m_b, m_d):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)

    def test_gpt2_train_sp_ep_mesh(self, tmp_path, monkeypatch):
        """CLI end-to-end on the clients x seq x expert mesh:
        --seq_parallel ring --seq_devices 2 --n_experts 2
        --expert_devices 2 with 2 workers (8 devices)."""
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices (2 clients x 2 seq x 2 expert)")
        monkeypatch.setenv("COMMEFFICIENT_SYNTHETIC_CLIENTS", "8")
        import gpt2_train

        stats = gpt2_train.train(argv=[
            "--dataset_name", "PERSONA",
            "--dataset_dir", str(tmp_path / "persona"),
            "--num_epochs", "1",
            "--num_workers", "2",
            "--local_batch_size", "2",
            "--valid_batch_size", "2",
            "--num_candidates", "2",
            "--mode", "uncompressed",
            "--lr_scale", "0.001",
            "--seed", "0",
            "--seq_parallel", "ring",
            "--seq_devices", "2",
            "--n_experts", "2",
            "--expert_devices", "2",
        ])
        assert np.isfinite(stats["val_nll"])
        assert np.isfinite(stats["val_ppl"])


class TestTPxEP:
    """Tensor parallelism COMPOSED with expert parallelism (clients x
    model x expert): the model axis slices attention + the dense blocks'
    MLPs, the expert axis slices the MoE blocks' experts. Orthogonal
    param sets — each axis's scale mask marks the other's params
    replicated (tp_scale 1/nm on /moe/ paths, ep_scale 1/ne on
    attention), so the existing reconciliation composes unchanged."""

    _run_round = staticmethod(_run_composed_round)
    _fixtures = staticmethod(_composed_round_fixtures)

    @pytest.mark.parametrize("fuse", [False, True])
    def test_round_matches_unsharded(self, fuse):
        """A full federated round (aux active) over clients x model x
        expert equals the unsharded clients-only round."""
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices (2 clients x 2 model x 2 expert)")
        dense, flat0, unravel, d, batch, lm = self._fixtures()
        w_d, m_d = self._run_round(dense, make_mesh([("clients", 2)]),
                                   None, None, None, fuse, flat0, unravel,
                                   d, batch, lm)
        both = dense.copy(model_axis="model", expert_axis="expert")
        w_b, m_b = self._run_round(
            both, make_mesh([("clients", 2), ("model", 2), ("expert", 2)]),
            None, "model", "expert", fuse, flat0, unravel, d, batch, lm)
        np.testing.assert_allclose(w_b, w_d, atol=2e-5, rtol=2e-5)
        for a, b in zip(m_b, m_d):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)

    def test_round_matches_unsharded_4d(self):
        """The FULL composition — clients x seq x model x expert (ring
        attention TP'd over `model`, tokens over `seq`, MoE experts over
        `expert`) — equals the unsharded round on a 1 x 2 x 2 x 2 mesh."""
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices (1 x 2 seq x 2 model x 2 expert)")
        dense, flat0, unravel, d, batch, lm = self._fixtures()
        w_d, m_d = self._run_round(dense, make_mesh([("clients", 1)]),
                                   None, None, None, False, flat0, unravel,
                                   d, batch, lm)
        full = dense.copy(attn_impl="ring", model_axis="model",
                          expert_axis="expert")
        w_f, m_f = self._run_round(
            full, make_mesh([("clients", 1), ("seq", 2), ("model", 2),
                             ("expert", 2)]),
            "seq", "model", "expert", False, flat0, unravel, d, batch, lm)
        np.testing.assert_allclose(w_f, w_d, atol=2e-5, rtol=2e-5)
        for a, b in zip(m_f, m_d):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)

    def test_gpt2_train_tp_ep_mesh(self, tmp_path, monkeypatch):
        """CLI end-to-end on the clients x model x expert mesh:
        --model_devices 2 --n_experts 2 --expert_devices 2 with 2 workers
        (8 devices), through the sketch pipeline."""
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices (2 clients x 2 model x 2 expert)")
        monkeypatch.setenv("COMMEFFICIENT_SYNTHETIC_CLIENTS", "8")
        import gpt2_train

        stats = gpt2_train.train(argv=[
            "--dataset_name", "PERSONA",
            "--dataset_dir", str(tmp_path / "persona"),
            "--num_epochs", "1",
            "--num_workers", "2",
            "--local_batch_size", "2",
            "--valid_batch_size", "2",
            "--num_candidates", "2",
            "--mode", "sketch",
            "--error_type", "virtual",
            "--local_momentum", "0",
            "--k", "64",
            "--num_cols", "2048",
            "--num_rows", "3",
            "--num_blocks", "2",
            "--lr_scale", "0.001",
            "--seed", "0",
            "--model_devices", "2",
            "--n_experts", "2",
            "--expert_devices", "2",
        ])
        assert np.isfinite(stats["val_nll"])
        assert np.isfinite(stats["val_ppl"])
