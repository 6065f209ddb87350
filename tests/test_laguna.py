"""Laguna-XS.2 (models/laguna.py, ops/attention.py's grouped-query core,
parallel/moe.py ``RoutedMoE`` without its selection bias) against its plain
reference (benchmark/configs/laguna_xs2_ep32_ref.py), at tiny widths on the
CPU, float32 ``highest``, seeded random weights:

(a) the parameter tree, the loss and every leaf's gradient are the
    reference's; no ``router_bias`` leaf;
(b) the mechanism: the window's edge exact (and a layer with the window
    ignored fails the same comparison), grouped queries read the right
    key/value head, partial rotary leaves the upper half of a full layer's
    head unturned, YaRN's 32 frequencies equal a table written by hand;
(c) the fused kernels, interpreted, with the turn of q and k and the heads'
    gates inside, equal the ``jnp`` oracle: the gated output and four
    gradients, groups of 6 and 8 query heads, a full layer (half of a head
    turned, YaRN's factor) and a window layer, T not a multiple of the
    window, a closed gate;
(d) the share: all 32 shares of a block, the shared expert and the attention
    counted once, add up to the uncut block;
(e) federated rounds through ``FedModel`` equal benchmark/reference.py's,
    the entry point trains through the normal path, the sized corpus fills
    its positions;
(f) JoyAI-LLM-Flash's round is the program it was before its ``Block`` was
    made the home of both decoders.
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

import laguna_xs2_ep32_ref as ref_file  # noqa: E402
import reference  # noqa: E402

from commefficient_tpu.federated.losses import (  # noqa: E402
    make_causal_lm_losses,
)
from commefficient_tpu.models.joyai import (  # noqa: E402
    MOE_METRIC_NAMES,
    Block,
)
from commefficient_tpu.models.laguna import (  # noqa: E402
    GQA,
    LagunaConfig,
    LagunaXS2,
    rope_frequencies,
)
from commefficient_tpu.ops import attention as at  # noqa: E402

from test_joyai import assert_trees_close, client_batch  # noqa: E402

T, V = 16, 96       # test_joyai.client_batch's; the tiny window is 5
CUT = dict(layers=5, experts_held=4, expert_offset=4, vocab_rows=V)


def ref_config(cfg: LagunaConfig) -> dict:
    """The configuration file's keys for a model config."""
    out = {k: getattr(cfg, k) for k in (
        "hidden_size", "head_dim", "num_key_value_heads", "sliding_window",
        "intermediate_size", "moe_intermediate_size",
        "shared_expert_intermediate_size", "num_experts_per_tok",
        "moe_routed_scaling_factor", "rms_norm_eps", "expert_offset")}
    out.update(
        num_hidden_layers=cfg.layers, num_experts=cfg.experts_held,
        vocab_size=cfg.vocab_rows, published={"num_experts": cfg.num_experts},
        num_attention_heads_per_layer=list(cfg.num_attention_heads_per_layer),
        layer_types=list(cfg.layer_types),
        mlp_layer_types=list(cfg.mlp_layer_types),
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.full_rope_theta,
                "factor": cfg.yarn_factor,
                "original_max_position_embeddings":
                    cfg.yarn_original_positions,
                "beta_fast": cfg.yarn_beta_fast,
                "beta_slow": cfg.yarn_beta_slow,
                "attention_factor": cfg.yarn_attention_factor,
                "partial_rotary_factor": cfg.full_partial_rotary_factor},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": cfg.sliding_rope_theta,
                "partial_rotary_factor": cfg.sliding_partial_rotary_factor}})
    return out


def models(**over):
    cfg = LagunaConfig.tiny(**{**CUT, **over})
    return cfg, LagunaXS2(cfg), ref_file.Model(ref_config(cfg))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- (a) the model is the reference's -----------------------------------------

def test_parameter_tree_is_the_references_and_has_no_router_bias():
    cfg, model, ref = models()
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, T), jnp.int32))["params"]
    shapes = jax.tree_util.tree_map(lambda x: x.shape, shapes)
    assert shapes == ref.shapes
    # two kinds of layer with different parameter shapes in one stack
    assert shapes["h0"]["attn"]["q"] != shapes["h1"]["attn"]["q"]
    assert shapes["h0"]["attn"]["gate"] == (cfg.hidden_size, 6)
    assert "mlp" in shapes["h0"] and "moe" in shapes["h1"]
    names = {str(k.key) for path, _ in
             jax.tree_util.tree_flatten_with_path(shapes, is_leaf=lambda x:
                                                  isinstance(x, tuple))[0]
             for k in path}
    assert "router_bias" not in names and "router" in names


def test_published_sizes_give_the_configurations_grad_size():
    """The default ``LagunaConfig`` cut as ``laguna_xs2_ep32`` is cut holds
    389,634,048 parameters, the table of ISSUE 32 row by row."""
    cfg = dataclasses.replace(LagunaConfig(), layers=5, experts_held=8,
                              vocab_rows=12544)
    shapes = jax.eval_shape(LagunaXS2(cfg).init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]

    def count(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))

    assert count(shapes["h0"]) == 79_794_176
    assert [count(shapes[f"h{i}"]) for i in (1, 2, 3)] == [66_719_744] * 3
    assert count(shapes["h4"]) == 58_298_368
    assert count(shapes) == 389_634_048


def test_loss_and_gradient_match_reference_every_leaf():
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
    local, absent, _ = (float(m) for m in metrics)
    n_moe = sum(t == "sparse" for t in cfg.mlp_layer_types[:cfg.layers])
    assert local + absent == 2 * T * n_moe * cfg.num_experts_per_tok
    assert local > 0 and len(metrics) == len(MOE_METRIC_NAMES)
    nll, (acc,), n, _ = val(params, {}, batch, jax.random.key(0), False)
    np.testing.assert_allclose(float(nll), float(want), rtol=1e-5)
    assert 0.0 <= float(acc) <= float(n)


def test_over_clients_equals_per_client():
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
        assert float(metrics[0][w]) == float(m1[0])


# -- (b) the mechanism --------------------------------------------------------

def attn_case(layer, seed=1, S=2):
    """A layer's attention module on seeded input and perturbed weights,
    and the reference's on the same."""
    cfg, _, ref = models()
    x = jax.random.normal(jax.random.key(seed), (S, T, cfg.hidden_size))
    mod = GQA(cfg, layer)
    p = mod.init(jax.random.key(2), x)["params"]
    p = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(3), a.shape), p)
    return cfg, ref, mod, p, x


@pytest.mark.parametrize("layer", [0, 1], ids=["full", "sliding"])
def test_attention_module_matches_reference(layer):
    cfg, ref, mod, p, x = attn_case(layer)
    np.testing.assert_allclose(
        np.asarray(mod.apply({"params": p}, x)),
        np.asarray(ref.attention(x, p, layer)), rtol=2e-4, atol=2e-5)


def test_a_layer_with_the_window_ignored_fails_the_same_comparison():
    """The sequence (16) is longer than the window (5): the reference with
    the sliding layer's window ignored is another function, by far more
    than the tolerance the module is held to."""
    cfg, ref, mod, p, x = attn_case(1)
    got = np.asarray(mod.apply({"params": p}, x))
    np.testing.assert_allclose(got, np.asarray(ref.attention(x, p, 1)),
                               rtol=2e-4, atol=2e-5)
    wrong = np.asarray(ref.attention(x, p, 1, window=False))
    assert np.max(np.abs(got - wrong)) > 1e-2 * np.max(np.abs(got))
    # and the first `window` positions, which see the same keys, agree
    np.testing.assert_allclose(got[:, :cfg.sliding_window],
                               wrong[:, :cfg.sliding_window], rtol=2e-4,
                               atol=2e-5)


def core_inputs(S, T_, Hq, Hkv, d, seed=0):
    """q, k, v and a weight on the output, flat as the projections write
    them: (S, T, H * d)."""
    keys = jax.random.split(jax.random.key(seed), 4)
    return [jax.random.normal(k, (S, T_, h * d))
            for k, h in zip(keys, (Hq, Hkv, Hkv, Hq))]


def rope_of(T_, rotary, factor=1.0, theta=100.0):
    """(cos, sin) over ``rotary`` columns of a head, times ``factor``."""
    angle = jnp.arange(T_, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, rotary, 2) / rotary)
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def no_turn(T_, d):
    """A rope that turns nothing (angle 0 everywhere), and with ``ones`` a
    gate that passes everything: the bare core of the mask's tests."""
    return jnp.ones((T_, d // 2)), jnp.zeros((T_, d // 2))


def ones(q, d):
    return jnp.ones(q.shape[:2] + (q.shape[-1] // d,))


def interpreted(window, tile, d=16):
    def fn(q, k, v, gate=None, rope=None):
        return at.gqa_attention_fused(
            q, k, v, ones(q, d) if gate is None else gate,
            rope or no_turn(q.shape[1], d), window, interpret=True,
            tile=tile)
    return fn


def oracle(window, d=16):
    def fn(q, k, v, gate=None, rope=None):
        return at.gqa_attention_einsum(
            q, k, v, ones(q, d) if gate is None else gate,
            rope or no_turn(q.shape[1], d), window)
    return fn


def by_hand(q, k, v, gate, rope, window, d):
    """The oracle written out apart from ops/attention.py's: heads viewed
    (S, T, H, d), ``_turn``, scores, mask, softmax, values, gate."""
    S, T_ = q.shape[:2]
    q, k, v = (x.reshape(S, T_, -1, d) for x in (q, k, v))
    q, k = at._turn(q, *rope), at._turn(k, *rope)
    G = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, G, axis=2) for x in (k, v))
    att = jnp.einsum("sqhd,skhd->shqk", q, k) * d ** -0.5
    pos = jnp.arange(T_)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[:, None] - pos[None, :] < window
    att = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), axis=-1)
    out = jnp.einsum("shqk,skhd->sqhd", att, v) * gate[..., None]
    return out.reshape(S, T_, -1)


CORES = {"einsum": oracle, "fused": lambda window: interpreted(window, 16)}


@pytest.mark.parametrize("core", sorted(CORES))
def test_the_windows_edge_is_exact(core):
    """Window 7 at T = 32: a change of the key and value at position j
    moves the output at i = j + 6 (i - j = window - 1 is seen) and leaves
    i = j + 7 (i - j = window is not) and everything before j as it was."""
    W, j = 7, 9
    q, k, v, _ = core_inputs(1, 32, 4, 2, 16)
    fn = CORES[core](W)
    a = fn(q, k, v)
    b = fn(q, k.at[:, j].add(1.0), v.at[:, j].add(1.0))
    moved = np.asarray(jnp.max(jnp.abs(a - b), axis=(0, 2)))
    assert np.all(moved[:j] == 0.0) and np.all(moved[j + W:] == 0.0)
    assert np.all(moved[j:j + W] > 1e-4)


@pytest.mark.parametrize("core", sorted(CORES))
def test_grouped_queries_read_their_own_key_value_head(core):
    """6 query heads over 2 key/value heads: a change of key/value head 1
    moves query heads 3..5 (h // 3 = 1) and leaves 0..2 bit for bit."""
    q, k, v, _ = core_inputs(1, 32, 6, 2, 16, seed=1)
    fn = CORES[core](None)
    a = fn(q, k, v)
    b = fn(q, k.at[..., 16:].add(0.5), v.at[..., 16:].add(0.5))
    moved = np.asarray(jnp.max(jnp.abs(a - b).reshape(1, 32, 6, 16),
                               axis=(0, 1, 3)))
    assert np.all(moved[:3] == 0.0) and np.all(moved[3:] > 1e-4)


def test_partial_rotary_leaves_the_upper_half_of_a_full_head_unturned():
    cfg = LagunaConfig()
    x = jax.random.normal(jax.random.key(0), (1, 8, 2, cfg.head_dim))
    pos = jnp.arange(8, dtype=jnp.float32)[:, None]
    out = {}
    for kind in ("full_attention", "sliding_attention"):
        freq, factor = rope_frequencies(cfg, kind)
        angle = pos * jnp.asarray(freq, jnp.float32)
        out[kind] = at._turn(x, jnp.cos(angle) * factor,
                             jnp.sin(angle) * factor)
    full, sliding = out["full_attention"], out["sliding_attention"]
    np.testing.assert_array_equal(np.asarray(full[..., 64:]),
                                  np.asarray(x[..., 64:]))
    assert float(jnp.max(jnp.abs(full[:, 1:, :, :64] - x[:, 1:, :, :64]))) > .1
    assert float(jnp.max(jnp.abs(sliding[:, 1:, :, 64:]
                                 - x[:, 1:, :, 64:]))) > .1
    # position 0 is turned by nothing: the full layers' factor alone
    np.testing.assert_allclose(np.asarray(full[:, 0, :, :64]),
                               np.asarray(x[:, 0, :, :64])
                               * cfg.yarn_attention_factor, rtol=1e-6)
    # the pair is (x_i, x_{i+n/2}): a sliding head's norm over a pair stays
    a, b = x[..., 3], x[..., 3 + 64]
    np.testing.assert_allclose(
        np.asarray(sliding[..., 3] ** 2 + sliding[..., 3 + 64] ** 2),
        np.asarray(a ** 2 + b ** 2), rtol=1e-5)


# theta = 500,000, 64 rotary columns, factor 64 over 4,096 original
# positions, beta 64 / 1: c(64) = 5.66, c(1) = 15.80, so pairs 0..5 keep
# f_i = theta^(-i/32), pairs 16..31 turn at f_i / 64, and pairs 6..15 blend
# with r_i = (i - 5) / 11. Worked out apart from both implementations, pair by pair, to seven digits.
YARN_TABLE = [
    1.000000e+00, 6.636012e-01, 4.403666e-01, 2.922278e-01, 1.939227e-01,
    1.286874e-01, 7.775503e-02, 4.652705e-02, 2.751009e-02, 1.602251e-02,
    9.150584e-03, 5.088901e-03, 2.724390e-03, 1.374836e-03, 6.249547e-04,
    2.240097e-04, 2.209709e-05, 1.466365e-05, 9.730819e-06, 6.457384e-06,
    4.285128e-06, 2.843616e-06, 1.887027e-06, 1.252234e-06, 8.309837e-07,
    5.514418e-07, 3.659375e-07, 2.428366e-07, 1.611466e-07, 1.069371e-07,
    7.096360e-08, 4.709153e-08]


def test_yarn_frequencies_equal_the_hand_written_table():
    freq, factor = rope_frequencies(LagunaConfig(), "full_attention")
    assert len(freq) == 32 and factor == 1.4158883083359672
    np.testing.assert_allclose(np.asarray(freq), YARN_TABLE, rtol=2e-6)
    ref = ref_file.Model(ref_config(dataclasses.replace(
        LagunaConfig(), layers=1, experts_held=8)))
    np.testing.assert_allclose(ref.frequencies("full_attention")[0],
                               YARN_TABLE, rtol=2e-6)
    sliding, one = rope_frequencies(LagunaConfig(), "sliding_attention")
    assert len(sliding) == 64 and one == 1.0
    np.testing.assert_allclose(sliding[32], 0.01, rtol=1e-12)


# -- (c) the fused kernels, interpreted ----------------------------------------

# (query heads, key/value heads): 48 / 8 and 64 / 8 scaled to groups of 6
# and 8; T = 48 in tiles of 16; the window 20 divides neither. A full layer
# turns half of a head's columns and carries a factor on cos and sin (YaRN's),
# a window layer turns them all; "bare" is the core alone (no turn, gates 1)
KERNEL_CASES = [(Hq, Hkv, window, turned)
                for Hq, Hkv in ((6, 1), (16, 2)) for window in (None, 20)
                for turned in (True, False)]


def assert_close(got, want, what, tol=2e-5):
    scale = max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=tol * scale, err_msg=what)


def layer_operands(S, T_, Hq, Hkv, d, window, seed=2):
    """A layer's operands by its kind: (q, k, v, gate), the rope, and a
    weight on the output."""
    q, k, v, w = core_inputs(S, T_, Hq, Hkv, d, seed=seed)
    gate = jax.nn.sigmoid(2.0 * jax.random.normal(jax.random.key(seed + 50),
                                                  (S, T_, Hq)))
    rope = rope_of(T_, d // 2, 1.4) if window is None else rope_of(T_, d)
    return (q, k, v, gate), rope, w


@pytest.mark.parametrize("Hq,Hkv,window,turned", KERNEL_CASES)
def test_fused_kernels_match_the_einsum_oracle(Hq, Hkv, window, turned):
    """The interpreted kernels, with the turn of q and k and the heads'
    gates inside, against the ``jnp`` oracle: the gated output and all four
    gradients, also under recomputation."""
    d = 16
    args, rope, w = layer_operands(2, 48, Hq, Hkv, d, window)
    if not turned:
        args, rope = args[:3] + (ones(args[0], d),), no_turn(48, d)
    fused = functools.partial(interpreted(window, 16), rope=rope)
    want_fn = functools.partial(oracle(window), rope=rope)
    want = want_fn(*args)
    assert_close(fused(*args), want, "forward")
    assert_close(by_hand(*args, rope, window, d), want, "the oracle itself")
    every = (0, 1, 2, 3)
    got = jax.grad(lambda *a: jnp.sum(fused(*a) * w), argnums=every)(*args)
    # under recomputation too (``nn.remat`` is ``jax.checkpoint``)
    again = jax.grad(lambda *a: jnp.sum(jax.checkpoint(fused)(*a) * w),
                     argnums=every)(*args)
    want = jax.grad(lambda *a: jnp.sum(want_fn(*a) * w), argnums=every)(*args)
    for name, g, g2, e in zip(("dq", "dk", "dv", "dg"), got, again, want):
        assert g.shape == e.shape, name
        assert_close(g, e, name)
        assert_close(g2, e, name + " recomputed")


@pytest.mark.parametrize("gate_logit", [-np.inf, -100.0],
                         ids=["exactly_zero", "underflows"])
def test_a_closed_gate_gives_finite_gradients(gate_logit):
    """Head 1's gate at some positions is 0 (or ``sigmoid(-100)``, 4e-44):
    the backward kernel takes the ungated output from the forward call and
    divides by no gate, so every gradient is finite and the oracle's; the
    closed head's q takes none there."""
    d, window = 16, 20
    (q, k, v, gate), rope, w = layer_operands(1, 48, 6, 1, d, window, seed=5)
    gate = gate.at[:, 8:40, 1].set(jax.nn.sigmoid(jnp.float32(gate_logit)))
    assert float(gate[0, 8, 1]) < 1e-40
    fused = functools.partial(interpreted(window, 16), rope=rope)
    want_fn = functools.partial(oracle(window), rope=rope)
    every = (0, 1, 2, 3)
    got = jax.grad(lambda *a: jnp.sum(fused(*a) * w), argnums=every)(
        q, k, v, gate)
    want = jax.grad(lambda *a: jnp.sum(want_fn(*a) * w), argnums=every)(
        q, k, v, gate)
    for name, g, e in zip(("dq", "dk", "dv", "dg"), got, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert_close(g, e, name)
    closed = got[0].reshape(48, 6, d)[8:40, 1]
    assert float(jnp.max(jnp.abs(closed))) < 1e-30
    assert float(jnp.max(jnp.abs(got[3][0, 8:40, 1]))) > 1e-2


@pytest.mark.parametrize("window", [None, at.GQA_TILE + 44],
                         ids=["full", "window"])
def test_fused_kernels_at_the_real_tile_and_head_width(window):
    """Head width 128 and the kernels' own tile (``GQA_TILE``), two query
    tiles, a window that is no multiple of it, the turn by lane rotation of
    a 128-lane head (64 of 128 columns on the full layer, all on the window
    layer) and the gate; and the bfloat16 multiplicands of the chip against
    the oracle on operands rounded after the turn, as the kernels round."""
    d, Tq = 128, 2 * at.GQA_TILE
    args, rope, w = layer_operands(1, Tq, 2, 1, d, window, seed=3)
    want_fn = functools.partial(oracle(window, d), rope=rope)

    def fused(*a):
        return at.gqa_attention_fused(*a, rope, window, interpret=True)

    want = want_fn(*args)
    got = fused(*args)
    assert_close(got, want, "forward")
    every = (0, 1, 2, 3)
    grads = jax.grad(lambda *a: jnp.sum(fused(*a) * w), argnums=every)(*args)
    wants = jax.grad(lambda *a: jnp.sum(want_fn(*a) * w), argnums=every)(
        *args)
    for name, g, e in zip(("dq", "dk", "dv", "dg"), grads, wants):
        assert_close(g, e, name)
    with jax.default_matmul_precision(None):
        low = fused(*args)
    assert low.dtype == jnp.float32
    # the oracle on multiplicands rounded where the kernels round them:
    # q and k after their turn, v as it is
    q, k, v, gate = args

    def rounded(x, turned):
        x4 = x.reshape(1, Tq, -1, d)
        x4 = at._turn(x4, *rope) if turned else x4
        return x4.astype(jnp.bfloat16).astype(jnp.float32).reshape(x.shape)

    want_low = oracle(window, d)(rounded(q, True), rounded(k, True),
                                 rounded(v, False), gate)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(low - want_low))) <= 1e-2 * scale
    assert float(jnp.max(jnp.abs(low - got))) > 1e-4


GQA_CHOICES = {
    # name: (T, (Hq, Hkv, d), tpu backend, precision set, interpret, path)
    "cpu": (4096, (64, 8, 128), False, None, False, "einsum"),
    "tpu_window_layer": (4096, (64, 8, 128), True, None, False, "fused"),
    "tpu_full_layer": (4096, (48, 8, 128), True, None, False, "fused"),
    "interpreted": (512, (48, 8, 128), False, None, True, "fused"),
    "tpu_precision_set": (4096, (64, 8, 128), True, "highest", False,
                          "einsum"),
    "tpu_rehearsal_T32": (32, (8, 2, 16), True, None, False, "einsum"),
    "tpu_T_not_a_tile": (4096 - 128, (64, 8, 128), True, None, False,
                         "einsum"),
    "tpu_T_above_the_kernels": (at.MAX_GQA_T + at.GQA_TILE, (64, 8, 128),
                                True, None, False, "einsum"),
    "tpu_heads_not_grouped": (4096, (60, 8, 128), True, None, False,
                              "einsum"),
}


@pytest.mark.parametrize("case", sorted(GQA_CHOICES))
def test_gqa_path_chooser(case, monkeypatch):
    T_, widths, tpu, precision, interpret, want = GQA_CHOICES[case]
    monkeypatch.setattr(at, "is_tpu_backend", lambda: tpu)
    with jax.default_matmul_precision(precision):
        assert at.gqa_attention_path(T_, *widths, interpret=interpret) == want


def test_counter_and_plan_name_the_path_taken(monkeypatch):
    (q, k, v, gate), rope, _ = layer_operands(1, 2 * at.GQA_TILE, 2, 1, 128,
                                              at.GQA_TILE, seed=4)
    monkeypatch.setattr(at, "GQA_PLAN", {})
    before = dict(at.PATH_CALLS)
    out = at.gqa_attention(q, k, v, gate, rope, at.GQA_TILE, interpret=True)
    assert at.PATH_CALLS["fused"] == before["fused"] + 1
    # a window of one tile: the diagonal tile and the one before it; the
    # turn and the gate ran where the scores did
    assert at.GQA_PLAN == {"window": {"tile": at.GQA_TILE,
                                      "key_tiles_visited": 3,
                                      "key_tiles_causal": 3,
                                      "turn_and_gate": "kernel"}}
    # a shape the kernels refuse: the einsum path, turn and gate in XLA
    at.gqa_attention(q[:, :32], k[:, :32], v[:, :32], gate[:, :32],
                     tuple(r[:32] for r in rope), None, interpret=True)
    assert at.PATH_CALLS["einsum"] == before["einsum"] + 1
    assert at.GQA_PLAN["full"] == {"turn_and_gate": "xla"}
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(at.gqa_attention_einsum(
            q, k, v, gate, rope, at.GQA_TILE)), rtol=0, atol=1e-4)


# -- (d) the share -------------------------------------------------------------

def test_all_32_shares_sum_to_the_uncut_block():
    """64 routed experts in 32 shares of 2: a sparse block's output over
    all the shares, with what every chip computes alike (the residual, the
    attention, the shared expert) counted once, is the block that holds all
    64; program and reference alike."""
    cfg = dataclasses.replace(
        LagunaConfig.tiny(layers=2, vocab_rows=V, experts_held=64,
                          expert_offset=0), num_experts=64)
    x = jax.random.normal(jax.random.key(6), (2, T, cfg.hidden_size))
    whole_block = Block(cfg, 1)
    p = whole_block.init(jax.random.key(5), x)["params"]
    p = jax.tree_util.tree_map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.key(7), a.shape), p)
    whole, stats = whole_block.apply({"params": p}, x)
    assert int(jnp.sum(stats["local"])) == 2 * T * cfg.num_experts_per_tok
    ref = ref_file.Model(ref_config(cfg))
    h = x + ref.attention(ref._norm(x, p["attn_norm"]), p["attn"], 1)
    z = ref._norm(h, p["ffn_norm"])
    alike = h + ref._swiglu(z, p["moe"]["shared"], None)
    parts, local = 0.0, 0
    for e0 in range(0, 64, 2):
        share = dataclasses.replace(cfg, experts_held=2, expert_offset=e0)
        ps = dict(p, moe=dict(p["moe"], **{
            n: p["moe"][n][e0:e0 + 2] for n in ("w_gate", "w_up", "w_down")}))
        y, st = Block(share, 1).apply({"params": ps}, x)
        want = h + ref.experts(z, ps["moe"], e0=e0, held=2)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
        parts = parts + (y - alike)
        local += int(jnp.sum(st["local"]))
    assert local == 2 * T * cfg.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(parts + alike), np.asarray(whole),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(whole),
        np.asarray(h + ref.experts(z, p["moe"], e0=0, held=64)),
        rtol=2e-4, atol=2e-5)


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
    argv = ["--dataset_name", "PERSONA", "--arch", "laguna_xs2",
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
         "COMMEFFICIENT_WORD_VOCAB": "12544"}


def test_sized_personachat_fills_4096_positions_at_a_scaled_length(
        monkeypatch, tmp_path):
    """The configuration's env with sentences and positions an eighth as
    long (52-56 words for 416-448, 512 positions for 4,096): ten sentences
    and their separators pass the sequence length, so after left-truncation
    at least 95% of every sequence is not padding; ids lie inside the
    12,544-row slice."""
    from commefficient_tpu.data_utils.fed_persona import (
        FedPERSONA,
        make_personachat_collate_fn,
    )
    from commefficient_tpu.data_utils.tokenization import (
        ATTR_TO_SPECIAL_TOKEN,
        get_tokenizer,
    )

    for k_, v_ in dict(SIZED,
                       COMMEFFICIENT_SYNTHETIC_SENTENCE="52-56").items():
        monkeypatch.setenv(k_, v_)
    tok = get_tokenizer("gpt2")
    tok.add_special_tokens(ATTR_TO_SPECIAL_TOKEN)
    assert len(tok) == 12544
    ds = FedPERSONA(tok, 1, 2, 1, str(tmp_path), "PERSONA", None, False,
                    None, train=True, download=True, max_seq_len=512)
    assert ds.num_clients == 8 and list(ds.data_per_client) == [2] * 8
    items = [ds[i][1:] for i in range(len(ds))]
    batch = make_personachat_collate_fn(512, 1)(items)
    ids = batch["input_ids"]
    assert ids.shape[-1] == 512 and 0 <= ids.min() and ids.max() < 12544
    lengths = [len(ds[i][1][0]) for i in range(len(ds))]
    assert min(lengths) >= 0.95 * 512, min(lengths)
    assert (batch["lm_labels"] != -1).sum(axis=-1).min() >= 50


def test_entry_point_trains_through_the_normal_path(monkeypatch, tmp_path):
    """``gpt2_train.train --arch laguna_xs2`` at tiny widths on the sized
    data: FedModel / PipelinedRoundEngine / telemetry / validation, and the
    attention core's event."""
    import gpt2_train
    from commefficient_tpu.telemetry import read_events

    for path in at.PATH_CALLS:
        monkeypatch.setitem(at.PATH_CALLS, path, 0)
    env = dict(SIZED, COMMEFFICIENT_SYNTHETIC_WORDS="200",
               COMMEFFICIENT_SYNTHETIC_SENTENCE="2-3",
               COMMEFFICIENT_WORD_VOCAB="256", COMMEFFICIENT_TINY_MODEL="1",
               COMMEFFICIENT_RUN_DIR=str(tmp_path / "run"))
    for k_, v_ in env.items():
        monkeypatch.setenv(k_, v_)
    stats = gpt2_train.train([
        "--dataset_name", "PERSONA", "--dataset_dir", str(tmp_path / "d"),
        "--arch", "laguna_xs2", "--arch_layers", "5", "--layer_chips", "4",
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
    assert len(rounds) == 4 and all("model" in e for e in rounds)
    (said,) = [e for e in events if e["ev"] == "model"]
    assert said["attn_path"] == "einsum" and said["attn_calls"] > 5
    # the einsum path: q and k turned and the heads gated by XLA's code
    assert said["attn_plan"] == {kind: {"turn_and_gate": "xla"}
                                 for kind in ("full", "window")}


# -- (f) JoyAI-LLM-Flash's round is the program it was -------------------------

def joyai_client_step_text():
    """The lowered ``client_step`` (StableHLO, no locations) of
    ``joyai_flash_ep32`` at test size, as FedModel dispatches it."""
    from commefficient_tpu.config import parse_args
    from commefficient_tpu.federated import FedModel
    from commefficient_tpu.models.joyai import JoyAIConfig, JoyAIFlash
    from commefficient_tpu.parallel.mesh import default_client_mesh

    model = JoyAIFlash(JoyAIConfig.tiny(layers=3, experts_held=4,
                                        expert_offset=4, vocab_rows=V))
    args = parse_args(default_lr=4e-2, argv=[
        "--dataset_name", "PERSONA", "--arch", "joyai_llm_flash", "--mode",
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


# sha256 of that text. A PR that changes JoyAI's round on purpose pins its
# own: PR 35 did (sketch mode's client phase differentiates by the parameter
# tree and sketches the leaves in groups, docs/stream_sketch.md); before it
# the digest was 523235a1..., unchanged since the parent of PR 32.
JOYAI_CLIENT_STEP = \
    "d4b13deb774b2060e4cfdec32f12f8889905e4a1ef092f48a04a8689ca700955"


def test_joyai_client_step_is_byte_equal_to_the_parents():
    text = joyai_client_step_text()
    digest = hashlib.sha256(text.encode()).hexdigest()
    assert digest == JOYAI_CLIENT_STEP, digest
