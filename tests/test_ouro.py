"""Ouro-2.6B (models/ouro.py: a stack of sandwich-norm layers run four times
on shared weights, a head and an exit gate after every pass, the
expected-exit loss; ops/attention.py's grouped-query core without a gate)
against its plain reference (benchmark/configs/ouro_2p6b_l4_ref.py), at tiny
widths on the CPU, float32 ``highest``, seeded random weights:

(a) the parameter tree is the reference's and holds each block once; the
    published sizes give the configuration's d; the loss and every leaf's
    gradient are the reference's;
(b) the recurrence: a block's gradient is the sum of its four uses' (four
    untied copies of the stack, summed), one pass is a plain decoder and
    differs from four, the norm between passes or the sandwich norms left
    out fail the same comparison;
(c) the objective: the exit probabilities sum to 1 and the last takes the
    remainder, beta = 0 with closed gates is the last pass's NLL, a
    saturated gate gives no NaN;
(d) the core without a gate, interpreted, equals the ``jnp`` oracle at one
    query head a key/value head (output and three gradients), and a gate of
    ones gives the numbers of ``gate=None``;
(e) federated rounds through ``FedModel`` equal benchmark/reference.py's,
    the entry point trains through the normal path and writes the
    ``model.loop_*`` counters and the ``loop`` event, the sized corpus fills
    its positions, a dense decoder refuses ``--layer_chips``;
(f) JoyAI-LLM-Flash's and Laguna-XS.2's rounds are the programs they were
    before the loss took its form from the model's configuration and the
    core learnt ``gate=None``.
"""

import dataclasses
import functools
import hashlib
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

import ouro_2p6b_l4_ref as ref_file  # noqa: E402
import reference  # noqa: E402

from commefficient_tpu.federated.losses import (  # noqa: E402
    make_causal_lm_losses,
)
from commefficient_tpu.models.ouro import (  # noqa: E402
    RECURRENCE,
    Ouro,
    OuroConfig,
    exit_log_probs,
    expected_exit_terms,
)
from commefficient_tpu.ops import attention as at  # noqa: E402

from test_joyai import assert_trees_close, client_batch  # noqa: E402
from test_laguna import (  # noqa: E402
    JOYAI_CLIENT_STEP,
    assert_close,
    core_inputs,
    joyai_client_step_text,
    rope_of,
)

T, V = 16, 96       # test_joyai.client_batch's
CUT = dict(layers=2, vocab_rows=V)


def ref_config(cfg: OuroConfig) -> dict:
    """The configuration file's keys for a model config."""
    out = {k: getattr(cfg, k) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "rms_norm_eps", "rope_theta",
        "total_ut_steps", "exit_entropy_coef")}
    out.update(num_hidden_layers=cfg.layers, vocab_size=cfg.vocab_rows)
    return out


def models(**over):
    cfg = dataclasses.replace(OuroConfig.tiny(**CUT), **over)
    return cfg, Ouro(cfg), ref_file.Model(ref_config(cfg))


def example_batch(seed=0, **kw):
    return {k: jnp.asarray(v) for k, v in client_batch(seed, **kw).items()}


def program_loss(model, params, batch, train=True):
    """One client's (loss sum, metric sums, count) and the gradient."""
    fn = make_causal_lm_losses(model)[0 if train else 1]

    def prog(p):
        loss, metrics, count, _ = fn(p, {}, batch, jax.random.key(0), train)
        return loss, (metrics, count)

    return jax.value_and_grad(prog, has_aux=True)(params)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- (a) the model is the reference's -----------------------------------------

def test_parameter_tree_is_the_references_and_holds_each_block_once():
    cfg, model, ref = models()
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, T), jnp.int32))["params"]
    shapes = jax.tree_util.tree_map(lambda x: x.shape, shapes)
    assert shapes == ref.shapes
    # L blocks for R x L block applications; a sandwich's four norms
    assert cfg.total_ut_steps == 4
    assert sorted(k for k in shapes if k.startswith("h")) == ["h0", "h1"]
    assert {k for k in shapes["h0"] if k.endswith("norm")} == {
        "attn_norm", "attn_post_norm", "ffn_norm", "ffn_post_norm"}
    assert set(shapes["h0"]["attn"]) == {"q", "k", "v", "o"}     # no gate
    assert shapes["exit"]["gate"] == (cfg.hidden_size, 1)
    assert shapes["exit"]["gate_bias"] == (1,)


def test_published_sizes_give_the_configurations_grad_size():
    """The default ``OuroConfig`` cut as ``ouro_2p6b_l4`` is cut holds
    406,884,353 parameters, the table of ISSUE 34 row by row; its reference
    counts the same, and by hand 6.19e13 model FLOPs a round of the cell, 68%
    of them in the 16 block applications."""
    cfg = dataclasses.replace(OuroConfig(), layers=4)
    shapes = jax.eval_shape(Ouro(cfg).init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]

    def count(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))

    assert [count(shapes[f"h{i}"]) for i in range(4)] == [51_388_416] * 4
    assert count(shapes["embed"]) == 49_152 * 2048
    assert count(shapes["exit"]) == 49_152 * 2048 + 2_048 + 2_049
    assert count(shapes) == 406_884_353
    ref = ref_file.Model(ref_config(cfg))
    assert count(jax.tree_util.tree_map(
        lambda s: np.zeros(s, bool), ref.shapes,
        is_leaf=lambda x: isinstance(x, tuple))) == 406_884_353
    cell = {"input_ids": (4, 2, 1, 1024)}
    tokens = 4 * 2 * 1024
    core = 3 * 4 * 128 * 16 * (1024 * 1025 // 2) * 8 * 16
    body = 3 * 16 * 2 * 51_380_224 * tokens + core
    assert ref.attention_core_flops(cell) == core
    assert ref.loop_body_flops(cell) == body
    assert ref.train_flops(cell) == body + 3 * 4 * 2 * 100_663_296 * 8 * 1023
    assert 6.18e13 < ref.train_flops(cell) < 6.19e13
    assert 0.675 < body / ref.train_flops(cell) < 0.685


def test_loss_and_gradient_match_reference_every_leaf():
    cfg, model, ref = models()
    params = ref.init(3)
    batch = example_batch(0)
    (loss, (metrics, count)), grad = program_loss(model, params, batch)
    (want, want_count), want_grad = jax.value_and_grad(
        lambda p: ref.loss_sum(p, batch), has_aux=True)(params)
    assert float(count) == float(want_count) == 1.0
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    assert_trees_close(grad, want_grad, 2e-4, "gradient")
    # the counters: each pass's NLL, the expected exit step and the labelled
    # positions of every example slot, the padded one's too
    assert cfg.metric_names == (
        "loop_nll_step1", "loop_nll_step2", "loop_nll_step3",
        "loop_nll_step4", "loop_exit_step", "loop_positions")
    assert set(cfg.metric_ratios) == set(cfg.metric_names[:-1])
    assert len(metrics) == 6
    labels = batch["lm_labels"].reshape(-1, T)[:, 1:]
    outs = ref.passes(params, batch["input_ids"].reshape(-1, T), labels)
    valid = labels != -1
    assert float(metrics[5]) == float(valid.sum())
    for t in range(4):
        np.testing.assert_allclose(
            float(metrics[t]), float(jnp.sum(outs[t][0] * valid)), rtol=1e-5)
    p = ref.exit_distribution([o[1] for o in outs])
    np.testing.assert_allclose(
        float(metrics[4]),
        float(sum(jnp.sum((t + 1) * pt * valid) for t, pt in enumerate(p))),
        rtol=1e-5)
    # validation: the last pass's NLL and its accuracy
    (nll, ((acc,), n)), _ = program_loss(model, params, batch, train=False)
    # (the mask keeps the first example alone)
    np.testing.assert_allclose(
        float(nll), float(jnp.sum(outs[3][0][0] * valid[0])
                          / valid[0].sum()), rtol=1e-5)
    hits = (outs[3][2][0] == labels[0]) & valid[0]
    np.testing.assert_allclose(float(acc), float(hits.sum() / valid[0].sum()),
                               rtol=1e-6)
    assert float(n) == 1.0


def test_over_clients_equals_per_client():
    cfg, model, ref = models()
    params = ref.init(4)
    batch = {k: v for k, v in example_batch(1, W=3).items()
             if k not in ("worker_mask", "client_ids")}
    train, _ = make_causal_lm_losses(model)
    loss, metrics, counts, _ = train.over_clients(params, {}, batch, None)
    for w in range(3):
        one = jax.tree_util.tree_map(lambda x: x[w], batch)
        l1, m1, c1, _ = train(params, {}, one, None, True)
        np.testing.assert_allclose(float(loss[w]), float(l1), rtol=1e-5)
        assert float(counts[w]) == float(c1)
        for got, want in zip(metrics, m1):
            np.testing.assert_allclose(float(got[w]), float(want), rtol=1e-5)


# -- (b) the recurrence --------------------------------------------------------

def untied_loss(ref, copies, shared, batch):
    """The reference's loss with the stack of pass t read from
    ``copies[t]``: four untied stacks where the model has one."""
    ids = batch["input_ids"].reshape(-1, T)
    labels = batch["lm_labels"].reshape(-1, T)[:, 1:]
    valid = labels != -1
    x = shared["embed"]["embedding"][ids]
    outs = []
    for stack in copies:
        for i in range(ref.L):
            x = ref.block(x, stack[f"h{i}"])
        x = ref._norm(x, shared["exit"]["norm_f"])
        outs.append(ref.read(x, shared["exit"], labels))
    tok = ref.position_loss([o[0] for o in outs], [o[1] for o in outs])
    per = (tok * valid).reshape(2, -1).sum(-1) / valid.reshape(2, -1).sum(-1)
    return jnp.sum(per * batch["mask"])


def test_a_blocks_gradient_is_the_sum_of_its_four_uses():
    cfg, model, ref = models()
    params = ref.init(5)
    batch = example_batch(2)
    (_, _), grad = program_loss(model, params, batch)
    stack = {k: v for k, v in params.items() if k.startswith("h")}
    parts = jax.grad(functools.partial(untied_loss, ref))(
        [stack] * cfg.total_ut_steps, params, batch)
    assert len(parts) == 4
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *parts)
    assert_trees_close({k: grad[k] for k in stack}, summed, 2e-4,
                       "sum of the four uses")
    # and no use is a quarter of it: the passes see different inputs
    q = parts[0]["h0"]["attn"]["q"]
    assert float(jnp.max(jnp.abs(4 * q - summed["h0"]["attn"]["q"]))) \
        > 0.1 * float(jnp.max(jnp.abs(summed["h0"]["attn"]["q"])))


def test_one_pass_is_a_plain_decoder_and_differs_from_four():
    cfg, model, ref = models(total_ut_steps=1)
    params = ref.init(6)
    batch = example_batch(3)
    (loss, _), _ = program_loss(model, params, batch)
    # one plain pass: embedding, the blocks once, the norm, the head's NLL;
    # the exit distribution is (1,) and its entropy 0
    ids = batch["input_ids"].reshape(-1, T)
    labels = batch["lm_labels"].reshape(-1, T)[:, 1:]
    x = params["embed"]["embedding"][ids]
    for i in range(ref.L):
        x = ref.block(x, params[f"h{i}"])
    nll, _, _ = ref.read(ref._norm(x, params["exit"]["norm_f"]),
                         params["exit"], labels)
    valid = labels[0] != -1
    np.testing.assert_allclose(
        float(loss), float(jnp.sum(nll[0] * valid) / valid.sum()), rtol=1e-5)
    np.testing.assert_allclose(float(loss),
                               float(ref.loss_sum(params, batch)[0]),
                               rtol=1e-5)
    four = models()[1]
    (loss4, (metrics4, _)), _ = program_loss(four, params, batch)
    assert abs(float(loss4) - float(loss)) > 1e-3 * abs(float(loss))
    # the first pass of four is the one pass; the fourth is not
    np.testing.assert_allclose(float(metrics4[0]),
                               float(jnp.sum(nll * (labels != -1))),
                               rtol=1e-5)
    assert abs(float(metrics4[3]) - float(metrics4[0])) \
        > 1e-3 * float(metrics4[0])


@pytest.mark.parametrize("left_out", ["norm_between", "sandwich"])
def test_a_norm_left_out_fails_the_same_comparison(left_out):
    """The reference with the norm between passes (pass t + 1 starting from
    u, not N_f(u)) or the sandwich's second and fourth norms left out is
    another function, by far more than the tolerance the program is held
    to."""
    cfg, model, ref = models()
    params = ref.init(7)
    # norm scales away from 1, so that a norm left out shows in every leaf
    params = jax.tree_util.tree_map(
        lambda a: a * 1.5 if a.ndim == 1 and a.shape[0] > 1 else a, params)
    batch = example_batch(4)
    (loss, _), grad = program_loss(model, params, batch)
    want, want_grad = jax.value_and_grad(
        lambda p: ref.loss_sum(p, batch)[0])(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    assert_trees_close(grad, want_grad, 2e-4, "gradient")
    wrong, wrong_grad = jax.value_and_grad(
        lambda p: ref.loss_sum(p, batch, **{left_out: False})[0])(params)
    assert abs(float(wrong) - float(loss)) > 1e-3 * abs(float(loss))
    with pytest.raises(AssertionError):
        assert_trees_close(grad, wrong_grad, 2e-4, "gradient")


# -- (c) the objective ---------------------------------------------------------

def test_exit_probabilities_sum_to_one_and_the_last_takes_the_remainder():
    z = 2.0 * jax.random.normal(jax.random.key(0), (4, 3, 7))
    p = jnp.exp(exit_log_probs(z))
    np.testing.assert_allclose(np.asarray(p.sum(0)), 1.0, rtol=1e-6)
    lam = jax.nn.sigmoid(z)
    np.testing.assert_allclose(np.asarray(p[0]), np.asarray(lam[0]),
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(p[2]), np.asarray(lam[2] * (1 - lam[0]) * (1 - lam[1])),
        rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(p[3]),
        np.asarray((1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])), rtol=1e-5)
    # the reference's, written as products
    ref = models()[2]
    for got, want in zip(p, ref.exit_distribution(list(lam))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5)
    # the last pass's gate is not read
    np.testing.assert_array_equal(
        np.asarray(exit_log_probs(z.at[3].set(9.0))),
        np.asarray(exit_log_probs(z)))
    # the loss and the expected step against the same written out
    nll = jax.random.uniform(jax.random.key(1), (4, 3, 7)) * 5
    loss, step = expected_exit_terms(nll, z, 0.05)
    np.testing.assert_allclose(
        np.asarray(loss),
        np.asarray(ref.position_loss(list(nll), list(lam), 0.05)), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(step), np.asarray(sum((t + 1) * p[t] for t in range(4))),
        rtol=1e-6)


def test_beta_zero_and_closed_gates_give_the_last_passes_nll():
    cfg, model, ref = models(exit_entropy_coef=0.0)
    params = ref.init(8)
    params["exit"]["gate_bias"] = jnp.full((1,), -40.0)
    batch = example_batch(5)
    (loss, (metrics, _)), grad = program_loss(model, params, batch)
    (val, _), _ = program_loss(model, params, batch, train=False)
    np.testing.assert_allclose(float(loss), float(val), rtol=1e-6)
    np.testing.assert_allclose(float(metrics[4]), 4 * float(metrics[5]),
                               rtol=1e-6)             # every position: step 4
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(grad))


def test_a_saturated_gate_gives_no_nan():
    """``lambda`` exactly 0 or 1 in float32 (logits of -200 and +200): the
    entropy's ``p ln p`` is 0 there and every gradient finite."""
    z = jnp.asarray([[-200.0, 200.0, 0.3], [0.5, -200.0, 200.0],
                     [200.0, 1.0, -200.0], [0.0, 0.0, 0.0]])
    nll = jnp.ones((4, 3))

    def total(z):
        return jnp.sum(expected_exit_terms(nll, z, 0.05)[0])

    val, grad = jax.value_and_grad(total)(z)
    assert bool(jnp.isfinite(val)) and bool(jnp.all(jnp.isfinite(grad)))
    p = jnp.exp(exit_log_probs(z))
    np.testing.assert_allclose(np.asarray(p.sum(0)), 1.0, rtol=1e-6)
    assert float(p[1, 0]) > 0.6 and float(p[0, 1]) == 1.0


# -- (d) the core without a gate ----------------------------------------------

# (query heads, key/value heads, head width, positions, tile): one query
# head a key/value head at the tests' tile and at the kernels' own tile and
# head width (two query tiles), and a group of two
UNGATED = {"group_of_1": (4, 4, 16, 48, 16),
           "group_of_1_real_tile": (2, 2, 128, 2 * at.GQA_TILE, at.GQA_TILE),
           "group_of_2": (4, 2, 16, 48, 16)}


@pytest.mark.parametrize("case", sorted(UNGATED))
def test_the_core_without_a_gate_equals_the_oracle(case):
    """The interpreted kernels without the gate's operand against the
    ``jnp`` oracle: the output and ``dq``, ``dk``, ``dv``, also under
    recomputation; and a gate of ones, through the gated kernels, gives the
    same numbers."""
    Hq, Hkv, d, T_, tile = UNGATED[case]
    q, k, v, w = core_inputs(2 if d == 16 else 1, T_, Hq, Hkv, d, seed=9)
    rope = rope_of(T_, d)

    def fused(q, k, v, gate=None):
        return at.gqa_attention_fused(q, k, v, gate, rope, None,
                                      interpret=True, tile=tile, heads=Hq)

    def oracle(q, k, v):
        return at.gqa_attention_einsum(q, k, v, None, rope, None, heads=Hq)

    want = oracle(q, k, v)
    assert want.shape == q.shape
    assert_close(fused(q, k, v), want, "forward")
    every = (0, 1, 2)
    got = jax.grad(lambda *a: jnp.sum(fused(*a) * w), argnums=every)(q, k, v)
    again = jax.grad(lambda *a: jnp.sum(jax.checkpoint(fused)(*a) * w),
                     argnums=every)(q, k, v)
    wants = jax.grad(lambda *a: jnp.sum(oracle(*a) * w), argnums=every)(
        q, k, v)
    ones = jnp.ones(q.shape[:2] + (Hq,))
    gated = jax.grad(lambda *a: jnp.sum(fused(*a, ones) * w),
                     argnums=every)(q, k, v)
    for name, g, g2, g3, e in zip(("dq", "dk", "dv"), got, again, gated,
                                  wants):
        assert g.shape == e.shape, name
        assert_close(g, e, name)
        assert_close(g2, e, name + " recomputed")
        assert_close(g3, g, name + " under a gate of ones", tol=1e-6)
    assert_close(fused(q, k, v, ones), fused(q, k, v), "a gate of ones",
                 tol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(at.gqa_attention_einsum(q, k, v, ones, rope, None)),
        np.asarray(want))


def test_the_path_and_the_plan_of_a_call_without_a_gate(monkeypatch):
    """16 heads over 16 of 128 at 1,024 and 4,096 positions take the fused
    path on the chip; the plan of such a call says ``gate: none``."""
    monkeypatch.setattr(at, "is_tpu_backend", lambda: True)
    with jax.default_matmul_precision(None):
        for T_ in (1024, 4096):
            assert at.gqa_attention_path(T_, 16, 16, 128) == "fused"
        assert at.gqa_attention_path(1024 + 128, 16, 16, 128) == "einsum"
    monkeypatch.setattr(at, "is_tpu_backend", lambda: False)
    monkeypatch.setattr(at, "GQA_PLAN", {})
    q, k, v, _ = core_inputs(1, 2 * at.GQA_TILE, 2, 2, 128, seed=4)
    rope = rope_of(2 * at.GQA_TILE, 128)
    before = dict(at.PATH_CALLS)
    out = at.gqa_attention(q, k, v, None, rope, interpret=True, heads=2)
    assert at.PATH_CALLS["fused"] == before["fused"] + 1
    assert at.GQA_PLAN == {"full": {"tile": at.GQA_TILE,
                                    "key_tiles_visited": 3,
                                    "key_tiles_causal": 3,
                                    "turn": "kernel", "gate": "none"}}
    at.gqa_attention(q[:, :32], k[:, :32], v[:, :32], None,
                     tuple(r[:32] for r in rope), heads=2)
    assert at.PATH_CALLS["einsum"] == before["einsum"] + 1
    assert at.GQA_PLAN["full"] == {"turn": "xla", "gate": "none"}
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(at.gqa_attention_einsum(
            q, k, v, None, rope, heads=2)), rtol=0, atol=1e-4)


def test_the_oracle_check_covers_the_ungated_shape():
    """``check_gqa_kernels`` (chip_smoke.py's kernel phase) at the
    rehearsal's sizes: Laguna's two kinds of layer and the core without a
    gate, every array within the tolerance."""
    gaps = at.check_gqa_kernels(heads=(6, 8), kv_heads=1, d=16, T=48,
                                window=20, tile=16, interpret=True,
                                ungated=((4, 4, 48),))
    assert {"out_full", "dg_full", "out_window", "dg_window",
            "out_ungated_T48", "dq_ungated_T48", "dk_ungated_T48",
            "dv_ungated_T48"} <= set(gaps)
    assert "dg_ungated_T48" not in gaps
    assert max(gaps.values()) < 1e-5


# -- (e) through the federated round and the entry point -----------------------

@pytest.mark.parametrize("mode", ["sketch", "uncompressed"])
def test_federated_rounds_equal_the_reference_rounds(mode, tmp_path):
    from commefficient_tpu.config import parse_args
    from commefficient_tpu.federated import (
        FedModel,
        FedOptimizer,
        LambdaLR,
        PipelinedRoundEngine,
    )
    from commefficient_tpu.parallel.mesh import default_client_mesh
    from commefficient_tpu.utils import PiecewiseLinear

    cfg, model, ref = models()
    W, seed, spe = 2, 11, 50
    argv = ["--dataset_name", "PERSONA", "--arch", "ouro_2p6b",
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
    w0 = np.asarray(fm.ps_weights).reshape(-1)[:fm.grad_size]
    batches = [client_batch(20 + i, W=W) for i in range(3)]
    engine = PipelinedRoundEngine(fm, opt, sched, window=2, drain_every=8)
    results = []
    for b in batches:
        results += engine.submit(b)
    results += engine.drain()
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


SIZED = {"COMMEFFICIENT_SYNTHETIC_CLIENTS": "8",
         "COMMEFFICIENT_SYNTHETIC_WORDS": "8192",
         "COMMEFFICIENT_SYNTHETIC_UTTERANCES": "2",
         "COMMEFFICIENT_SYNTHETIC_VALID": "2",
         "COMMEFFICIENT_WORD_VOCAB": "49152"}


def test_sized_personachat_fills_1024_positions_at_a_scaled_length(
        monkeypatch, tmp_path):
    """The configuration's env with sentences and positions a quarter as
    long (26-28 words for 104-112, 256 positions for 1,024): ten sentences
    and their separators pass the sequence length, so after left-truncation
    at least 95% of every sequence is not padding; ids lie inside the whole
    49,152 rows, the special tokens in the last of them."""
    from commefficient_tpu.data_utils.fed_persona import (
        FedPERSONA,
        make_personachat_collate_fn,
    )
    from commefficient_tpu.data_utils.tokenization import (
        ATTR_TO_SPECIAL_TOKEN,
        get_tokenizer,
    )

    for k_, v_ in dict(SIZED,
                       COMMEFFICIENT_SYNTHETIC_SENTENCE="26-28").items():
        monkeypatch.setenv(k_, v_)
    tok = get_tokenizer("gpt2")
    tok.add_special_tokens(ATTR_TO_SPECIAL_TOKEN)
    assert len(tok) == 49152
    ds = FedPERSONA(tok, 1, 2, 1, str(tmp_path), "PERSONA", None, False,
                    None, train=True, download=True, max_seq_len=256)
    assert ds.num_clients == 8 and list(ds.data_per_client) == [2] * 8
    items = [ds[i][1:] for i in range(len(ds))]
    batch = make_personachat_collate_fn(256, 1)(items)
    ids = batch["input_ids"]
    assert ids.shape[-1] == 256 and 0 <= ids.min() and ids.max() < 49152
    assert ids.max() >= 49152 - 5
    lengths = [len(ds[i][1][0]) for i in range(len(ds))]
    assert min(lengths) >= 0.95 * 256, min(lengths)
    assert (batch["lm_labels"] != -1).sum(axis=-1).min() >= 25


def test_entry_point_trains_and_writes_the_loop_counters(monkeypatch,
                                                         tmp_path):
    """``gpt2_train.train --arch ouro_2p6b`` at tiny widths on the sized
    data: FedModel / PipelinedRoundEngine / telemetry / validation, the
    ``model.loop_*`` counters of every round, the attention core's event
    (no gate) and the ``loop`` event."""
    import gpt2_train
    from commefficient_tpu.telemetry import read_events

    for path in at.PATH_CALLS:
        monkeypatch.setitem(at.PATH_CALLS, path, 0)
    monkeypatch.setattr(at, "GQA_PLAN", {})
    monkeypatch.setattr(gpt2_train, "GQA_PLAN", at.GQA_PLAN)
    env = dict(SIZED, COMMEFFICIENT_SYNTHETIC_WORDS="200",
               COMMEFFICIENT_SYNTHETIC_SENTENCE="2-3",
               COMMEFFICIENT_WORD_VOCAB="256", COMMEFFICIENT_TINY_MODEL="1",
               COMMEFFICIENT_RUN_DIR=str(tmp_path / "run"))
    for k_, v_ in env.items():
        monkeypatch.setenv(k_, v_)
    stats = gpt2_train.train([
        "--dataset_name", "PERSONA", "--dataset_dir", str(tmp_path / "d"),
        "--arch", "ouro_2p6b", "--arch_layers", "2",
        "--vocab_rows", "256", "--mode", "sketch", "--error_type", "virtual",
        "--num_rows", "3", "--num_cols", "2048", "--k", "500",
        "--num_blocks", "2", "--num_workers", "2", "--num_devices", "1",
        "--local_batch_size", "2", "--valid_batch_size", "1",
        "--microbatch_size", "1", "--num_candidates", "1", "--max_seq_len",
        "32", "--local_momentum", "0", "--num_epochs", "1", "--seed", "3",
        "--train_dataloader_workers", "0", "--val_dataloader_workers", "0"])
    assert np.isfinite(stats["val_nll"]) and stats["val_ppl"] > 1.0
    events = list(read_events(str(tmp_path / "run" / "telemetry.jsonl")))
    rounds = [e for e in events if e["ev"] == "round"]
    assert len(rounds) == 4
    for e in rounds:
        m = e["model"]
        assert {"loop_nll_step1", "loop_nll_step2", "loop_nll_step3",
                "loop_nll_step4", "loop_exit_step"} <= set(m)
        # means over the labelled positions: an NLL near ln 256 at the
        # seed's weights, a step between the first pass and the last
        assert all(3.0 < m[f"loop_nll_step{t}"] < 8.0 for t in (1, 2, 3, 4))
        assert 1.0 < m["loop_exit_step"] < 4.0 and m["loop_positions"] > 4
    (said,) = [e for e in events if e["ev"] == "model"]
    assert said["attn_path"] == "einsum" and said["attn_calls"] > 5
    assert said["attn_plan"] == {"full": {"turn": "xla", "gate": "none"}}
    (loop,) = [e for e in events if e["ev"] == "loop"]
    assert {k: loop[k] for k in ("passes", "layers", "recurrence",
                                 "block_applications")} == {
        "passes": 4, "layers": 2, "recurrence": RECURRENCE,
        "block_applications": 8}
    assert RECURRENCE in ("unrolled", "scan")


def test_a_dense_decoder_refuses_layer_chips():
    import argparse

    import gpt2_train

    args = argparse.Namespace(arch="ouro_2p6b", arch_layers=2, vocab_rows=V,
                              len_tokenizer=V, layer_chips=1,
                              expert_offset=0)
    model, train, val = gpt2_train.build_decoder(args, tiny=True)
    assert model.cfg.layers == 2 and model.cfg.vocab_rows == V
    assert train.metric_names == model.cfg.metric_names
    assert not hasattr(model.cfg, "experts_held")
    args.layer_chips = 4
    with pytest.raises(AssertionError, match="no routed experts"):
        gpt2_train.build_decoder(args, tiny=True)


# -- (f) the other decoders' rounds are the programs they were -----------------

def laguna_client_step_text():
    """test_laguna.joyai_client_step_text's twin for ``laguna_xs2_ep32`` at
    test size."""
    from commefficient_tpu.config import parse_args
    from commefficient_tpu.federated import FedModel
    from commefficient_tpu.models.laguna import LagunaConfig, LagunaXS2
    from commefficient_tpu.parallel.mesh import default_client_mesh

    model = LagunaXS2(LagunaConfig.tiny(layers=5, experts_held=4,
                                        expert_offset=4, vocab_rows=V))
    args = parse_args(default_lr=4e-2, argv=[
        "--dataset_name", "PERSONA", "--arch", "laguna_xs2", "--mode",
        "sketch", "--num_workers", "2", "--num_devices", "1",
        "--local_batch_size", "2", "--microbatch_size", "1",
        "--local_momentum", "0", "--error_type", "virtual", "--num_rows",
        "3", "--num_cols", "2048", "--k", "400", "--num_blocks", "2",
        "--seed", "21"])
    train, val = make_causal_lm_losses(model)
    params = jax.jit(model.init)(jax.random.key(0),
                                 jnp.zeros((1, T), jnp.int32))["params"]
    fm = FedModel(model, train, args, val, num_clients=8, init_params=params,
                  mesh=default_client_mesh(2, 1))
    seen = []
    real = fm.steps.client_step
    fm.steps = fm.steps._replace(
        client_step=lambda *a: (seen.append(real.lower(*a).as_text()),
                                real(*a))[1])
    fm.finish_round(fm.begin_round(client_batch(30, W=2)))
    fm.finalize()
    return seen[0]


def gpt2_uncompressed_client_step_text():
    """The lowered ``client_step`` (StableHLO, no locations) of GPT-2 at
    test size under ``--mode uncompressed``, as FedModel dispatches it:
    ``gpt2_uncompressed_1c``'s program, which bypasses the sketch."""
    from commefficient_tpu.config import parse_args
    from commefficient_tpu.federated import FedModel
    from commefficient_tpu.federated.losses import make_gpt2_losses
    from commefficient_tpu.models.gpt2 import GPT2DoubleHeads
    from commefficient_tpu.parallel.mesh import default_client_mesh

    W, B, C, T = 2, 2, 2, 16
    model = GPT2DoubleHeads(vocab_size=64, n_positions=T, n_embd=16,
                            n_layer=2, n_head=2)
    args = parse_args(default_lr=4e-2, argv=[
        "--dataset_name", "PERSONA", "--mode", "uncompressed",
        "--error_type", "none", "--num_workers", str(W), "--num_devices",
        "1", "--local_batch_size", str(B), "--local_momentum", "0",
        "--virtual_momentum", "0.9", "--seed", "21"])
    train, val = make_gpt2_losses(model, args.lm_coef, args.mc_coef)
    ids0 = jnp.zeros((1, C, T), jnp.int32)
    params = jax.jit(lambda k: model.init(
        k, ids0, token_type_ids=ids0,
        mc_token_ids=jnp.zeros((1, C), jnp.int32), train=False))(
            jax.random.key(0))["params"]
    fm = FedModel(model, train, args, val, num_clients=8, init_params=params,
                  mesh=default_client_mesh(W, 1))
    r = np.random.RandomState(30)
    batch = {
        "input_ids": r.randint(0, 64, (W, B, C, T)).astype(np.int32),
        "token_type_ids": r.randint(0, 64, (W, B, C, T)).astype(np.int32),
        "lm_labels": r.randint(0, 64, (W, B, C, T)).astype(np.int32),
        "mc_token_ids": r.randint(0, T, (W, B, C)).astype(np.int32),
        "mc_labels": r.randint(0, C, (W, B)).astype(np.int32),
        "mask": np.ones((W, B), np.float32),
        "client_ids": np.arange(W, dtype=np.int32),
        "worker_mask": np.ones(W, np.float32),
    }
    seen = []
    real = fm.steps.client_step
    fm.steps = fm.steps._replace(
        client_step=lambda *a: (seen.append(real.lower(*a).as_text()),
                                real(*a))[1])
    fm.finish_round(fm.begin_round(batch))
    fm.finalize()
    return seen[0]


# sha256 of the lowered ``client_step`` (StableHLO, no locations). A PR that
# changes a round on purpose pins its own: PR 35 re-pinned the two routed
# decoders (sketch mode's client phase differentiates by the parameter tree
# and sketches the leaves in groups, docs/stream_sketch.md) and added the
# pin that must NOT move with such a PR: GPT-2 under ``--mode uncompressed``
# keeps the flat route, and its digest was taken at PR 35's parent (commit
# 9c48bab), by this function in this module (whose imports configure jax:
# alone in a file the same program's text hashes to 2997d8c0... on both).
CLIENT_STEPS = {
    "joyai_llm_flash": (joyai_client_step_text, JOYAI_CLIENT_STEP),
    "laguna_xs2": (
        laguna_client_step_text,
        "b31cbc2d724346b9efe10a9550ac3692a987215d5223064e3dd53bbdbbf3a360"),
    "gpt2_uncompressed": (
        gpt2_uncompressed_client_step_text,
        "d79bb4f6b0448f417fb94a0c3c3cae56fc5b282c084f53431091e13ac0f1df36"),
}


@pytest.mark.parametrize("arch", sorted(CLIENT_STEPS))
def test_client_step_is_byte_equal_to_the_parents(arch):
    text, want = CLIENT_STEPS[arch]
    digest = hashlib.sha256(text().encode()).hexdigest()
    assert digest == want, digest
