"""JoyAI-LLM-Flash (models/joyai.py, parallel/moe.py ``RoutedMoE``) against
its plain reference (benchmark/configs/joyai_flash_ep32_ref.py), at tiny
widths on the CPU, float32 ``highest``, seeded random weights:

(a) loss and gradient of the flax model against the reference, every leaf;
(b) MLA against per-head attention written out with explicit RoPE pairs;
(c) the share: the routed parts of all the shares of a layer, summed, with the
    shared expert counted once, equal the uncut layer;
(d) no dropped token: a router bias that sends every token to one held
    expert, and one that sends none, both equal the reference;
(e) federated rounds in sketch mode through ``FedModel`` equal
    benchmark/reference.py's rounds.
"""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for sub in ("benchmark", os.path.join("benchmark", "configs")):
    if os.path.join(ROOT, sub) not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, sub))

import joyai_flash_ep32_ref as ref_file  # noqa: E402
import reference  # noqa: E402

from commefficient_tpu.federated.losses import (  # noqa: E402
    make_causal_lm_losses,
)
from commefficient_tpu.models.joyai import (  # noqa: E402
    MLA,
    MOE_METRIC_NAMES,
    JoyAIConfig,
    JoyAIFlash,
)
from commefficient_tpu.parallel.moe import (  # noqa: E402
    RoutedMoE,
    routed_experts,
)

T, V = 16, 96
CUT = dict(layers=3, experts_held=4, expert_offset=4, vocab_rows=V)


def ref_config(cfg: JoyAIConfig) -> dict:
    """The configuration file's keys for a model config."""
    out = {k: getattr(cfg, k) for k in (
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
        "routed_scaling_factor", "first_k_dense_replace", "rope_theta",
        "rms_norm_eps", "expert_offset")}
    out.update(num_hidden_layers=cfg.layers, n_routed_experts=cfg.experts_held,
               vocab_size=cfg.vocab_rows,
               published={"n_routed_experts": cfg.n_routed_experts})
    return out


def models(**over):
    cfg = JoyAIConfig.tiny(**{**CUT, **over})
    return cfg, JoyAIFlash(cfg), ref_file.Model(ref_config(cfg))


def client_batch(seed, W=None, B=2, K=1):
    """One client's batch (or W clients'), the loader's keys; the last
    example of each client is padding."""
    rng = np.random.RandomState(seed)
    lead = (B,) if W is None else (W, B)
    ids = rng.randint(0, V, lead + (K, T))
    labels = np.where(rng.rand(*ids.shape) < 0.5, ids, -1)
    mask = np.ones(lead, np.float32)
    mask[..., -1] = 0.0
    out = {"input_ids": ids, "lm_labels": labels, "mask": mask,
           "token_type_ids": np.zeros_like(ids),
           "mc_token_ids": np.zeros(lead + (K,), np.int64),
           "mc_labels": np.zeros(lead, np.int64)}
    if W is not None:
        out.update(worker_mask=np.ones(W, np.float32),
                   client_ids=np.arange(W, dtype=np.int32))
    return out


def assert_trees_close(got, want, rtol, what):
    flat_w = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    flat_g = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    assert flat_g.keys() == flat_w.keys()
    for path, w in flat_w.items():
        g = flat_g[path]
        scale = max(float(jnp.max(jnp.abs(w))), 1e-12)
        assert float(jnp.max(jnp.abs(g - w))) <= rtol * scale, \
            f"{what}: {jax.tree_util.keystr(path)}"


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_parameter_tree_is_the_references():
    cfg, model, ref = models()
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, T), jnp.int32))["params"]
    assert jax.tree_util.tree_map(lambda x: x.shape, shapes) == ref.shapes


def test_loss_and_gradient_match_reference_every_leaf():
    """(a): the program's loss callback and the reference's ``loss_sum`` on
    one client's batch, and the gradient of every parameter leaf."""
    cfg, model, ref = models()
    params = ref.init(3)
    batch = {k: jnp.asarray(v) for k, v in client_batch(0).items()}
    train, val = make_causal_lm_losses(model)

    def prog(p):
        loss, metrics, count, _ = train(p, {}, batch, jax.random.key(0), True)
        return loss, (metrics, count)

    (loss, (metrics, count)), grad = jax.value_and_grad(
        prog, has_aux=True)(params)
    (want, want_count), want_grad = jax.value_and_grad(
        lambda p: ref.loss_sum(p, batch), has_aux=True)(params)
    assert float(count) == float(want_count) == 1.0
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    assert_trees_close(grad, want_grad, 2e-4, "gradient")
    # e_score_correction_bias takes part in the selection only
    assert float(jnp.max(jnp.abs(grad["h1"]["moe"]["router_bias"]))) == 0.0
    # the routing counts: every (token, expert) pair is held or absent
    local, absent, _ = (float(m) for m in metrics)
    n_moe = cfg.layers - cfg.first_k_dense_replace
    assert local + absent == 2 * T * n_moe * cfg.num_experts_per_tok
    assert local > 0 and len(metrics) == len(MOE_METRIC_NAMES)
    # validation: the same loss, and an accuracy in [0, 1]
    nll, (acc,), n, _ = val(params, {}, batch, jax.random.key(0), False)
    np.testing.assert_allclose(float(nll), float(want), rtol=1e-5)
    assert 0.0 <= float(acc) <= float(n)


def test_over_clients_equals_per_client():
    """The fused client phase's one call over the clients axis gives each
    client what its own call gives."""
    cfg, model, ref = models()
    params = ref.init(4)
    batch = {k: jnp.asarray(v) for k, v in client_batch(1, W=3).items()
             if k not in ("worker_mask", "client_ids")}
    train, _ = make_causal_lm_losses(model)
    loss, metrics, counts, _ = train.over_clients(params, {}, batch, None)
    for w in range(3):
        one = jax.tree_util.tree_map(lambda x: x[w], batch)
        l1, m1, c1, _ = train(params, {}, one, None, True)
        np.testing.assert_allclose(float(loss[w]), float(l1), rtol=1e-5)
        assert float(counts[w]) == float(c1)
        # pairs are counted by sequence: the split of a client is its own
        assert float(metrics[0][w]) == float(m1[0])
        assert float(metrics[1][w]) == float(m1[1])


def per_head_attention(cfg, p, x):
    """MLA written out head by head, the rotary pairs turned one by one."""
    S, T = x.shape[:2]
    H, dn, dr = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                 cfg.qk_rope_head_dim)
    dv = cfg.v_head_dim

    def norm(v, scale):
        return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True)
                            + cfg.rms_norm_eps) * scale

    def turn(v, pos):               # v (dr,) at position pos
        out = []
        for i in range(dr // 2):
            ang = pos * cfg.rope_theta ** (-2.0 * i / dr)
            a, b = v[2 * i], v[2 * i + 1]
            out += [a * np.cos(ang) - b * np.sin(ang),
                    b * np.cos(ang) + a * np.sin(ang)]
        return jnp.stack(out)

    want = []
    for s in range(S):
        c_q = norm(x[s] @ p["q_a"], p["q_norm"]["scale"])
        q = (c_q @ p["q_b"]).reshape(T, H, dn + dr)
        kv_a = x[s] @ p["kv_a"]
        c_kv = norm(kv_a[:, :cfg.kv_lora_rank], p["kv_norm"]["scale"])
        k_r = jnp.stack([turn(kv_a[t, cfg.kv_lora_rank:], t)
                         for t in range(T)])
        kv = (c_kv @ p["kv_b"]).reshape(T, H, dn + dv)
        heads = []
        for h in range(H):
            q_h = jnp.concatenate(
                [q[:, h, :dn], jnp.stack([turn(q[t, h, dn:], t)
                                          for t in range(T)])], axis=-1)
            k_h = jnp.concatenate([kv[:, h, :dn], k_r], axis=-1)
            att = q_h @ k_h.T / np.sqrt(dn + dr)
            att = jnp.where(jnp.tril(jnp.ones((T, T), bool)), att, -jnp.inf)
            heads.append(jax.nn.softmax(att, axis=-1) @ kv[:, h, dn:])
        want.append(jnp.concatenate(heads, axis=-1) @ p["o"])
    return jnp.stack(want)


def mla_case(cfg, S, T):
    """Seeded input, perturbed weights and the module's output."""
    x = jax.random.normal(jax.random.key(1), (S, T, cfg.hidden_size))
    mla = MLA(cfg)
    p = mla.init(jax.random.key(2), x)["params"]
    p = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(3), a.shape), p)
    return x, p, mla.apply({"params": p}, x)


def test_mla_matches_per_head_attention_with_explicit_rope_pairs():
    """(b): MLA against attention written out head by head, the rotary
    pairs turned one by one."""
    cfg = JoyAIConfig.tiny(**CUT)
    x, p, got = mla_case(cfg, 2, T)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(per_head_attention(cfg, p, x)),
                               rtol=2e-4, atol=2e-5)


def moe_layer(cfg, e0, held, operand_dtype=None):
    return RoutedMoE(cfg.n_routed_experts, held, e0, cfg.num_experts_per_tok,
                     cfg.moe_intermediate_size, cfg.routed_scaling_factor,
                     operand_dtype=operand_dtype)


def moe_params(cfg, key, held=None):
    layer = moe_layer(cfg, 0, held or cfg.n_routed_experts)
    x = jnp.zeros((2, T, cfg.hidden_size))
    p = layer.init(key, x)["params"]
    return jax.tree_util.tree_map(
        lambda a: a + 0.05 * jax.random.normal(key, a.shape), p)


def test_shares_sum_to_the_uncut_layer():
    """(c): 16 experts in 4 shares of 4: the routed parts of all the shares,
    the shared expert counted once, add up to the layer that holds all 16
    (program and reference alike)."""
    cfg, _, ref = models()
    E = cfg.n_routed_experts
    p = moe_params(cfg, jax.random.key(5))
    x = jax.random.normal(jax.random.key(6), (2, T, cfg.hidden_size))
    whole, stats = moe_layer(cfg, 0, E).apply({"params": p}, x)
    assert int(jnp.sum(stats["local"])) == 2 * T * cfg.num_experts_per_tok
    shared = ref._swiglu(x, p["shared"], None)
    parts, local = 0.0, 0
    for e0 in range(0, E, 4):
        ps = dict(p, **{n: p[n][e0:e0 + 4]
                        for n in ("w_gate", "w_up", "w_down")})
        y, st = moe_layer(cfg, e0, 4).apply({"params": ps}, x)
        want = ref.experts(x, ps, e0=e0, held=4)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=2e-4, atol=2e-6)
        parts = parts + (y - shared)
        local += int(jnp.sum(st["local"]))
    assert local == 2 * T * cfg.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(whole),
                               rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(whole), np.asarray(ref.experts(x, p, e0=0, held=E)),
        rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("target", ["one_held_expert", "no_held_expert",
                                    "all_held_experts"])
def test_no_token_dropped_under_any_imbalance(target):
    """(d): a router bias that sends every token to one held expert (the
    top rung of the ladder's worst case for that expert), one that sends
    none here, one that sends every token to all four held: output and
    gradient equal the reference, which computes every expert on every
    token and masks."""
    cfg, _, ref = models()
    e0, held = 4, 4
    p = moe_params(cfg, jax.random.key(7), held=held)
    bias = np.zeros(cfg.n_routed_experts, np.float32)
    if target == "one_held_expert":
        bias[e0:e0 + held] = -10.0
        bias[e0 + 2] = 10.0
    elif target == "no_held_expert":
        bias[e0:e0 + held] = -10.0
    else:
        bias[e0:e0 + held] = 10.0
    p = dict(p, router_bias=jnp.asarray(bias))
    x = jax.random.normal(jax.random.key(8), (2, T, cfg.hidden_size))
    layer = moe_layer(cfg, e0, held)

    def prog(p, x):
        y, stats = layer.apply({"params": p}, x)
        return jnp.sum(y * jnp.cos(y)), stats

    def want(p, x):
        y = ref.experts(x, p, e0=e0, held=held)
        return jnp.sum(y * jnp.cos(y))

    (got, stats), g = jax.value_and_grad(prog, argnums=(0, 1),
                                         has_aux=True)(p, x)
    w, wg = jax.value_and_grad(want, argnums=(0, 1))(p, x)
    np.testing.assert_allclose(float(got), float(w), rtol=1e-5)
    assert_trees_close(g, wg, 2e-4, target)
    n_tok = 2 * T
    local = int(jnp.sum(stats["local"]))
    assert local == {"one_held_expert": n_tok, "no_held_expert": 0,
                     "all_held_experts": n_tok * held}[target]
    assert int(stats["max_load"]) == (0 if target == "no_held_expert"
                                      else n_tok)


def routed_call(key, n_tok=24, k=4, held=4, width=16, hidden=32):
    """Arguments of ``routed_experts``: each slot's expert held or absent
    (-1) at random, so the pairs present fall between the rungs."""
    ks = jax.random.split(key, 6)
    w = [0.3 * jax.random.normal(ks[i], shape) for i, shape in enumerate(
        [(held, hidden, width), (held, hidden, width),
         (held, width, hidden)])]
    x = jax.random.normal(ks[3], (n_tok, hidden))
    gate = jax.random.uniform(ks[4], (n_tok, k))
    e_local = jax.random.randint(ks[5], (n_tok, k), -1, held)
    return x, gate, e_local, w


@pytest.mark.parametrize("share_held", [0.1, 0.35, 1.0])
def test_ladder_rungs_give_one_answer(share_held):
    """The rung is chosen by the pairs present: whichever it is (the token
    count, a rung between, the worst case), its answer and gradients are
    those of the single worst-case rung run directly."""
    from commefficient_tpu.parallel.moe import _experts_on_rows

    x, gate, e_local, w = routed_call(jax.random.key(9))
    n_tok, k = e_local.shape
    keep = jax.random.uniform(jax.random.key(10), e_local.shape) < share_held
    e_local = jnp.where(keep, jnp.abs(e_local), -1)
    pairs = int(jnp.sum(e_local >= 0))
    assert {0.1: pairs <= n_tok, 0.35: n_tok < pairs <= 2 * n_tok,
            1.0: pairs > 2 * n_tok}[share_held], pairs

    def ladder(x, gate, *w):
        return jnp.sum(routed_experts(x, gate, e_local, *w) ** 2)

    def worst(x, gate, *w):
        return jnp.sum(_experts_on_rows(n_tok * k, x, gate, e_local, *w) ** 2)

    got = jax.value_and_grad(ladder, argnums=(0, 1, 2, 3, 4))(x, gate, *w)
    want = jax.value_and_grad(worst, argnums=(0, 1, 2, 3, 4))(x, gate, *w)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for g, wnt in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wnt),
                                   rtol=1e-4, atol=1e-5)


def test_bf16_operands_stay_near_the_float32_products():
    """The path the chip runs (``operand_dtype`` bfloat16: both
    multiplicands of every grouped product rounded, forward and backward,
    the weight gradient through ``ragged_dot_general``) against the float32
    one: the layer's output and every gradient within bfloat16's rounding."""
    x, gate, e_local, w = routed_call(jax.random.key(11))

    def loss(dtype):
        def f(x, gate, *w):
            return jnp.sum(routed_experts(x, gate, e_local, *w,
                                          operand_dtype=dtype) ** 2)
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4))(x, gate, *w)

    (lo, g_lo), (hi, g_hi) = loss(jnp.bfloat16), loss(None)
    assert abs(float(lo) - float(hi)) <= 2e-2 * abs(float(hi))
    for a, b in zip(g_lo, g_hi):
        gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert 0 < gap <= 3e-2, gap   # rounded, and only rounded
    # and through the layer, as the entry point builds it on the chip
    cfg, _, _ = models()
    p = moe_params(cfg, jax.random.key(12), held=4)
    xs = jax.random.normal(jax.random.key(13), (2, T, cfg.hidden_size))
    y_lo, _ = moe_layer(cfg, 4, 4, jnp.bfloat16).apply({"params": p}, xs)
    y_hi, _ = moe_layer(cfg, 4, 4).apply({"params": p}, xs)
    gap = float(jnp.linalg.norm(y_lo - y_hi) / jnp.linalg.norm(y_hi))
    assert 0 < gap <= 3e-2, gap


@pytest.mark.parametrize("mode", ["sketch", "uncompressed"])
def test_federated_rounds_equal_the_reference_rounds(mode, tmp_path):
    """(e): three rounds through FedModel / FedOptimizer /
    PipelinedRoundEngine against benchmark/reference.py's ``follow``: client
    losses, the first transmit's leaf norms, every leaf's change."""
    from commefficient_tpu.config import parse_args
    from commefficient_tpu.federated import (
        FedModel,
        FedOptimizer,
        LambdaLR,
        PipelinedRoundEngine,
    )
    from commefficient_tpu.parallel.mesh import default_client_mesh
    from commefficient_tpu.telemetry import attach_run_telemetry, read_events
    from commefficient_tpu.utils import PiecewiseLinear

    cfg, model, ref = models()
    W, seed, spe = 2, 11, 50
    argv = ["--dataset_name", "PERSONA", "--arch", "joyai_llm_flash",
            "--mode", mode, "--num_workers", str(W), "--num_devices", "1",
            "--local_batch_size", "2", "--microbatch_size", "1",
            "--local_momentum", "0", "--virtual_momentum", "0.9",
            "--num_epochs", "1", "--lr_scale", "0.04", "--seed", "21",
            "--weight_decay", "0.01"]
    if mode == "sketch":
        argv += ["--error_type", "virtual", "--num_rows", "3", "--num_cols",
                 "2048", "--k", "400", "--num_blocks", "2"]
    args = parse_args(default_lr=4e-2, argv=argv)
    train, val = make_causal_lm_losses(model)
    fm = FedModel(model, train, args, val, num_clients=8,
                  init_params=ref.init(seed),
                  mesh=default_client_mesh(W, 1))
    opt = FedOptimizer(fm, args)
    schedule = PiecewiseLinear([0, spe], [args.lr_scale, 0.0])
    sched = LambdaLR(opt, lr_lambda=lambda s: schedule(s))
    rt = attach_run_telemetry(args, fm, str(tmp_path), "gpt2_train")
    w0 = np.asarray(fm.ps_weights).reshape(-1)[:fm.grad_size]
    batches = [client_batch(20 + i, W=W) for i in range(3)]
    engine = PipelinedRoundEngine(fm, opt, sched, window=2, drain_every=8)
    results = []
    for b in batches:
        results += engine.submit(b)
    results += engine.drain()
    rt.close()
    fm.finalize()
    losses = [np.asarray(r.values[0], np.float64) for r in results]
    change = np.asarray(fm.ps_weights).reshape(-1)[:fm.grad_size] - w0

    traffic = dict(mode=mode, num_cols=2048, num_rows=3, k=400,
                   virtual_momentum=0.9, program_seed=21,
                   weight_decay=args.weight_decay, num_workers=W,
                   schedule={"kind": "linear_decay", "lr_scale": 0.04,
                             "pivot_epoch": 0.0, "num_epochs": 1.0})
    want = reference.follow(ref, traffic, seed, batches, spe, 1)
    for got_l, want_l in zip(losses, want["client_losses"]):
        np.testing.assert_allclose(got_l, want_l, rtol=2e-5)
    got_change = reference.leaf_norms(
        jax.tree_util.tree_leaves(fm.unravel(jnp.asarray(change))))
    assert reference.worst_leaf_gap(got_change, want["change"],
                                    want["keep"]) < 2e-3
    total = np.linalg.norm(got_change[want["keep"]])
    assert abs(total - np.linalg.norm(want["change"][want["keep"]])) \
        < 1e-4 * total
    # the routing counters of every round are in the event log
    rounds = [e for e in read_events(os.path.join(str(tmp_path),
                                                  "telemetry.jsonl"))
              if e["ev"] == "round"]
    assert len(rounds) == 3
    n_moe = cfg.layers - cfg.first_k_dense_replace
    for e in rounds:
        m = e["model"]
        assert set(m) == set(MOE_METRIC_NAMES)
        assert m["moe_local_pairs"] + m["moe_absent_pairs"] == \
            W * 2 * T * n_moe * cfg.num_experts_per_tok
        assert 1.0 <= m["moe_load_max_over_mean"] <= cfg.experts_held


def test_flags_refuse_a_per_client_gradient_path():
    from commefficient_tpu.config import parse_args

    with pytest.raises(AssertionError, match="fused-gradient"):
        parse_args(default_lr=4e-2, argv=[
            "--dataset_name", "PERSONA", "--arch", "joyai_llm_flash",
            "--mode", "local_topk", "--error_type", "local", "--k", "10"])


def test_blockwise_sketch_server_is_the_references_rule():
    """The configuration's reference file takes the sketch's estimates a
    block of chunks at a time (reference.py's stack of all rows' d estimates
    does not fit a chip at d = 414M) and the k-th largest magnitude by
    bisection on its bits (reference.py's sort of 414M entries compiles for
    a minute): bit for bit the same rule, ties at the threshold included."""
    traffic = {"num_cols": 700, "num_rows": 5, "k": 300,
               "virtual_momentum": 0.9}
    v = jnp.asarray(np.random.RandomState(0).randint(-40, 40, 5000)
                    .astype(np.float32)) * 0.37
    for k in (1, 7, 300, 4999, 5000, 9000):
        np.testing.assert_array_equal(
            np.asarray(ref_file.BlockwiseSketchServer._topk_mask(v, k)),
            np.asarray(reference.topk_mask(v, k)))
    d = 100_003
    plain = reference.SketchServer(d, traffic, 21)
    block = ref_file.BlockwiseSketchServer(d, traffic, 21)
    block.BLOCK = 7
    assert reference.SERVERS["sketch"] is ref_file.BlockwiseSketchServer
    w = jnp.zeros(d)
    for s in range(3):
        g = jax.random.normal(jax.random.key(s), (d,))
        wa = plain.step(plain.transmit(g), w, 0.1)
        wb = block.step(block.transmit(g), w, 0.1)
        assert float(jnp.max(jnp.abs(wa - wb))) == 0.0
        assert float(jnp.max(jnp.abs(plain.v - block.v))) == 0.0
        w = wa


# -- the data: ids inside a slice, sequences that fill their positions ------

SIZED = {"COMMEFFICIENT_SYNTHETIC_CLIENTS": "48",
         "COMMEFFICIENT_SYNTHETIC_WORDS": "8192",
         "COMMEFFICIENT_SYNTHETIC_SENTENCE": "48-64",
         "COMMEFFICIENT_SYNTHETIC_UTTERANCES": "8",
         "COMMEFFICIENT_SYNTHETIC_VALID": "4",
         "COMMEFFICIENT_WORD_VOCAB": "16160"}


def test_sized_personachat_fills_its_positions(monkeypatch, tmp_path):
    """With the configuration's env: every client has exactly 8 utterances,
    ids lie inside the 16,160-row slice with the special tokens in its last
    rows, words are spread Zipf-like over thousands of ids, and at least 90%
    of every 512-position sequence is not padding."""
    from commefficient_tpu.data_utils.fed_persona import (
        FedPERSONA,
        make_personachat_collate_fn,
    )
    from commefficient_tpu.data_utils.tokenization import (
        ATTR_TO_SPECIAL_TOKEN,
        SPECIAL_TOKENS,
        get_tokenizer,
    )

    for k, v in SIZED.items():
        monkeypatch.setenv(k, v)
    tok = get_tokenizer("gpt2")
    tok.add_special_tokens(ATTR_TO_SPECIAL_TOKEN)
    assert len(tok) == 16160
    assert sorted(tok.convert_tokens_to_ids(SPECIAL_TOKENS)) == \
        list(range(16155, 16160))
    ds = FedPERSONA(tok, 1, 2, 1, str(tmp_path), "PERSONA", None, False,
                    None, train=True, download=True, max_seq_len=512)
    assert ds.num_clients == 48
    assert list(ds.data_per_client) == [8] * 48
    collate = make_personachat_collate_fn(512, 1)
    batch = collate([ds[i][1:] for i in range(0, len(ds), 5)])
    ids, labels = batch["input_ids"], batch["lm_labels"]
    assert ids.max() < 16160 and ids.min() >= 0
    lengths = [len(ds[i][1][0]) for i in range(0, len(ds), 5)]
    assert min(lengths) >= 0.9 * 512, min(lengths)
    assert (labels != -1).sum(axis=-1).min() >= 40
    assert len(np.unique(ids)) > 3000          # of 77 sequences' 39k tokens
    counts = np.bincount(ids.reshape(-1), minlength=16160)
    assert counts[:8].sum() > counts[4096:8192].sum() / 4   # Zipf's head


def test_unsized_personachat_is_byte_for_byte_the_old_one(monkeypatch):
    """Without the new env the GPT-2 cells' synthetic data is what it was
    (digest of the parent commit's generator at 24 clients)."""
    from commefficient_tpu.data_utils.fed_persona import \
        _synthetic_personachat
    from commefficient_tpu.data_utils.tokenization import (
        WordTokenizer,
        get_tokenizer,
    )

    for k in SIZED:
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("COMMEFFICIENT_SYNTHETIC_CLIENTS", "24")
    blob = json.dumps(_synthetic_personachat(), sort_keys=True).encode()
    assert hashlib.sha256(blob).hexdigest() == \
        "c053deff9882b48287a3ec326b3f4a34a529b98baf310282674a80bbc10ed5ca"
    assert not isinstance(get_tokenizer("gpt2"), WordTokenizer)


@pytest.mark.parametrize("mode", ["true_topk"])
def test_entry_point_trains_through_the_normal_path(mode, monkeypatch,
                                                    tmp_path):
    """``gpt2_train.train`` with the new flags, tiny widths, the sized
    data: FedModel / PipelinedRoundEngine / telemetry / validation, one
    short epoch (sketch and uncompressed run in the rounds test above)."""
    import gpt2_train
    from commefficient_tpu.ops.attention import PATH_CALLS
    from commefficient_tpu.telemetry import read_events

    for path in PATH_CALLS:         # the process's count, from this run on
        monkeypatch.setitem(PATH_CALLS, path, 0)
    env = dict(SIZED, COMMEFFICIENT_SYNTHETIC_CLIENTS="8",
               COMMEFFICIENT_SYNTHETIC_WORDS="200",
               COMMEFFICIENT_SYNTHETIC_SENTENCE="2-3",
               COMMEFFICIENT_WORD_VOCAB="256", COMMEFFICIENT_TINY_MODEL="1",
               COMMEFFICIENT_RUN_DIR=str(tmp_path / "run"))
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    stats = gpt2_train.train([
        "--dataset_name", "PERSONA", "--dataset_dir", str(tmp_path / "d"),
        "--arch", "joyai_llm_flash", "--arch_layers", "2", "--layer_chips",
        "4", "--vocab_rows", "256", "--mode", mode, "--error_type",
        "virtual", "--k", "500", "--num_workers", "2", "--num_devices", "1",
        "--local_batch_size", "8", "--valid_batch_size", "2",
        "--microbatch_size", "4", "--num_candidates", "1", "--max_seq_len",
        "32", "--local_momentum", "0", "--num_epochs", "1", "--seed", "3",
        "--train_dataloader_workers", "0", "--val_dataloader_workers", "0"])
    assert np.isfinite(stats["val_nll"]) and stats["val_ppl"] > 1.0
    events = list(read_events(str(tmp_path / "run" / "telemetry.jsonl")))
    rounds = [e for e in events if e["ev"] == "round"]
    assert len(rounds) == 4 and all("model" in e for e in rounds)
    # which attention core ran, said once the first round was traced: more
    # calls than the initialisation's one a layer
    (said,) = [e for e in events if e["ev"] == "model"]
    assert said["attn_path"] == "einsum" and said["attn_calls"] > 2
