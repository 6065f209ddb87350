"""Kimi-Linear (models/kimi_linear.py: Kimi Delta Attention in three layers of
four, NoPE latent attention in the fourth, routed experts; the delta rule's
chunked core in ops/linear_attention.py) against its plain reference
(benchmark/configs/kimi_linear_ep32_ref.py), at tiny widths on the CPU,
float32 ``highest``, seeded random inputs and weights:

(a) the chunked core, the XLA form and the kernels (interpreted), against
    the per-token recurrence, output and the gradients of q, k, v, log
    alpha and beta, including decays strong enough (-10 a channel a token)
    that a factorisation through the chunk's start overflows; the rule that
    picks the path and counts it;
(b) the parameter tree is the reference's, the published sizes give the
    configuration's d; logits, loss and every leaf's gradient are the
    reference's; the decay counter is the mean -log alpha;
(c) the shares of a layer's experts, the shared expert counted once and the
    router the one of all experts, add up to the uncut layer;
(d) the layer pattern: the published lists of 27 layers, layers 1-5 K K K M
    K in the program and the reference;
(e) the entry point trains two rounds in sketch and dense modes and writes
    the counters and the core's path;
(f) JoyAI-LLM-Flash's ``MLA`` (low-rank q, RoPE) gives the parameter tree
    and logits it gave before ``MLA`` learnt to leave both out.
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

import kimi_linear_ep32_ref as ref_file  # noqa: E402

from commefficient_tpu.federated.losses import (  # noqa: E402
    make_causal_lm_losses,
)
from commefficient_tpu.models.joyai import (  # noqa: E402
    JoyAIConfig,
    JoyAIFlash,
)
from commefficient_tpu.models.kimi_linear import (  # noqa: E402
    KDA_METRIC_NAMES,
    KimiLinear,
    KimiLinearConfig,
)
from commefficient_tpu.ops import linear_attention  # noqa: E402
from commefficient_tpu.ops.linear_attention import (  # noqa: E402
    kda_attention,
    kda_attention_pallas,
    kda_attention_xla,
    kda_core_path,
)
from commefficient_tpu.parallel.moe import RoutedMoE  # noqa: E402

from test_joyai import assert_trees_close, client_batch  # noqa: E402

T, V = 16, 96       # test_joyai.client_batch's
CUT = dict(layers=5, experts_held=4, expert_offset=4, vocab_rows=V)
CONFIG = os.path.join(ROOT, "benchmark", "configs", "kimi_linear_ep32.json")


def ref_config(cfg: KimiLinearConfig) -> dict:
    """The configuration file's keys for a model config."""
    out = {k: getattr(cfg, k) for k in (
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "mla_use_nope",
        "intermediate_size", "moe_intermediate_size",
        "routed_scaling_factor", "first_k_dense_replace", "rms_norm_eps",
        "expert_offset")}
    out.update(
        num_hidden_layers=cfg.layers, num_experts=cfg.experts_held,
        num_experts_per_token=cfg.num_experts_per_tok,
        vocab_size=cfg.vocab_rows, published={"num_experts": cfg.num_experts},
        linear_attn_config={
            "num_heads": cfg.kda_num_heads, "head_dim": cfg.kda_head_dim,
            "short_conv_kernel_size": cfg.short_conv_kernel_size,
            "kda_layers": list(cfg.kda_layers),
            "full_attn_layers": list(cfg.full_attn_layers)})
    return out


def models(**over):
    cfg = KimiLinearConfig.tiny(**{**CUT, **over})
    return cfg, KimiLinear(cfg), ref_file.Model(ref_config(cfg))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- (a) the chunked core against the recurrence ------------------------------

def core_inputs(seed, S, T_, H, d, strongest):
    """Unit q and k, log alpha in [strongest, 0) a channel, beta in (0, 1)."""
    r = np.random.RandomState(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q, k = (jnp.asarray(unit(r.randn(S, T_, H, d)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(r.randn(S, T_, H, d), jnp.float32)
    log_alpha = jnp.asarray(r.uniform(strongest, 0.0, (S, T_, H, d)),
                            jnp.float32)
    beta = jnp.asarray(r.uniform(0.0, 1.0, (S, T_, H)), jnp.float32)
    return q, k, v, log_alpha, beta


# (T, chunk, sub-chunk, strongest log alpha, width): one chunk padded at its
# end, several chunks through the scan, 16-wide sub-chunks, decays planted at
# -10. The XLA form takes any chunking; the kernels chunks of 64 in
# sub-chunks of 16, run interpreted
CORE = {"one_chunk": (40, 64, 16, -0.5, 16),
        "four_chunks": (40, 16, 4, -0.5, 16),
        "four_chunks_strong": (40, 16, 4, -10.0, 16),
        "sub16_strong": (64, 64, 16, -10.0, 16)}
PALLAS_CORE = {"one_chunk_padded": (40, 64, 16, -0.5, 16),
               "three_chunks_padded_strong": (150, 64, 16, -10.0, 16),
               "sub16_strong": (64, 64, 16, -10.0, 16),
               "width128": (100, 64, 16, -0.5, 128)}
# the XLA form's cases keep the names they had before the kernels
CORE_CASES = [pytest.param("xla", c, id=c) for c in sorted(CORE)] + [
    pytest.param("pallas", c, id=f"pallas-{c}") for c in sorted(PALLAS_CORE)]


def _core_fn(path, chunk, sub):
    if path == "pallas":
        return lambda *a: kda_attention_pallas(*a, interpret=True,
                                               chunk=chunk, sub=sub)
    return lambda *a: kda_attention_xla(*a, chunk=chunk, sub=sub)


@pytest.mark.parametrize("path,case", CORE_CASES)
def test_chunked_core_equals_the_recurrence(path, case):
    """Output and the five gradients against the per-token recurrence; the
    kernels also against the XLA form."""
    T_, chunk, sub, strongest, d = (CORE if path == "xla"
                                    else PALLAS_CORE)[case]
    args = core_inputs(1, 2, T_, 3 if d < 128 else 1, d, strongest)
    if strongest <= -10.0:
        # the planted decay: a chunk's summed log alpha passes float32's
        # exponent, so e^{-G_s} through the chunk's start would overflow
        G = np.cumsum(np.asarray(args[3])[:, :chunk], axis=1)
        with np.errstate(over="ignore"):
            assert np.isinf(np.exp(-G.astype(np.float32))).any()

    chunked = _core_fn(path, chunk, sub)
    forms = (chunked, ref_file.Model.delta_rule) + (
        (_core_fn("xla", chunk, sub),) if path == "pallas" else ())
    outs = [f(*args) for f in forms]
    got = outs[0]
    assert np.isfinite(np.asarray(got)).all()
    # float32, both at ``highest``: the two forms sum in other orders
    for want in outs[1:]:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=2e-6)
    d_out = jax.random.normal(jax.random.key(2), got.shape)
    grads = [jax.grad(lambda *a: jnp.sum(f(*a) * d_out),
                      argnums=(0, 1, 2, 3, 4))(*args)
             for f in forms]
    for other in grads[1:]:
        for name, g, w in zip(("q", "k", "v", "log_alpha", "beta"),
                              grads[0], other):
            assert np.isfinite(np.asarray(g)).all(), name
            # relative to the largest entry: a gradient flows through T
            # steps of the state, where the two forms' round-off compounds
            scale = float(jnp.max(jnp.abs(w)))
            assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * scale, name


def test_core_path_rule(monkeypatch):
    """The kernels where the process can see that their rounding is the
    chip's and the shape is theirs: a TPU at the default matmul precision
    (or ``interpret``), dk and dv multiples of 128, chunks of 64 in
    sub-chunks of 16; the XLA form everywhere else. ``kda_attention``
    counts the path it traced in ``PATH_CALLS``."""
    assert kda_core_path(1024, 128, 128) == "xla"             # the CPU
    assert kda_core_path(1024, 128, 128, interpret=True) == "pallas"
    monkeypatch.setattr(linear_attention, "is_tpu_backend", lambda: True)
    # the tests' own ``highest``: a TPU would not round the products
    assert kda_core_path(1024, 128, 128) == "xla"
    with jax.default_matmul_precision(None):
        assert kda_core_path(1024, 128, 128) == "pallas"
        assert kda_core_path(1000, 256, 128) == "pallas"      # padded
        for T_, dk, dv, chunk, sub in ((1024, 64, 128, 64, 16),
                                       (1024, 128, 192, 64, 16),
                                       (40, 128, 128, 64, 16),  # chunk 48
                                       (1024, 128, 128, 32, 16),
                                       (1024, 128, 128, 64, 8)):
            assert kda_core_path(T_, dk, dv, chunk, sub) == "xla"
        cfg = KimiLinearConfig()
        assert cfg.kda_core_path(1024) == "pallas"
        assert KimiLinearConfig.tiny().kda_core_path(1024) == "xla"
    args = core_inputs(1, 1, 64, 1, 128, -0.5)
    for interpret, want in ((False, "xla"), (True, "pallas")):
        before = dict(linear_attention.PATH_CALLS)
        jax.eval_shape(lambda *a: kda_attention(*a, interpret=interpret),
                       *args)
        assert linear_attention.PATH_CALLS == dict(
            before, **{want: before[want] + 1})


# -- (b) the model against the reference --------------------------------------

def test_parameter_tree_is_the_references():
    cfg, model, ref = models()
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, T), jnp.int32))["params"]
    assert jax.tree_util.tree_map(lambda x: x.shape, shapes) == ref.shapes


def test_published_sizes_give_the_configurations_grad_size():
    """The configuration file's model, built at its published widths with
    its cut, holds d parameters, and the reference's tree is the same."""
    with open(CONFIG) as f:
        conf = json.load(f)
    cfg = KimiLinearConfig(layers=conf["num_hidden_layers"],
                           experts_held=conf["num_experts"],
                           vocab_rows=conf["vocab_size"])
    shapes = jax.eval_shape(KimiLinear(cfg).init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    d = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert d == conf["grad_size"] == 602_434_432
    assert jax.tree_util.tree_map(lambda x: x.shape, shapes) \
        == ref_file.Model(conf).shapes


def test_logits_loss_and_gradient_match_reference_every_leaf():
    cfg, model, ref = models()
    params = ref.init(3)
    batch = {k: jnp.asarray(v) for k, v in client_batch(0).items()}
    ids = batch["input_ids"][:, 0]
    logits, stats = model.apply({"params": params}, ids)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(ref.logits(params, ids)),
                               rtol=2e-4, atol=2e-5)
    train, _ = make_causal_lm_losses(model)

    def prog(p):
        loss, metrics, count, _ = train(p, {}, batch, jax.random.key(0), True)
        return loss, (metrics, count)

    (loss, (metrics, count)), grad = jax.value_and_grad(
        prog, has_aux=True)(params)
    (want, want_count), want_grad = jax.value_and_grad(
        lambda p: ref.loss_sum(p, batch), has_aux=True)(params)
    assert float(count) == float(want_count) == 1.0
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    # float32 ``highest``: the chunked core against the recurrence, and the
    # expert layer's grouped products against the reference's masked sum
    assert_trees_close(grad, want_grad, 2e-4, "gradient")
    assert float(jnp.max(jnp.abs(grad["h1"]["moe"]["router_bias"]))) == 0.0
    # the decay counter: the mean -log alpha over the KDA layers, positions,
    # heads and channels, as the reference's layers compute it
    n_kda = sum(cfg.kind(i) == "kda" for i in range(cfg.layers))
    assert np.asarray(stats["kda_positions"]).tolist() == [n_kda * T] * 2
    decays = []
    for i in range(cfg.layers):
        if cfg.kind(i) == "kda":
            p = params[f"h{i}"]["attn"]
            x = params["embed"]["embedding"][ids]
            for j in range(i):
                x = ref.block(x, params[f"h{j}"], j)
            z = ref._norm(x, params[f"h{i}"]["attn_norm"])
            f = z @ p["f_a"] @ p["f_b"]
            decays.append(jnp.exp(p["A_log"]).repeat(cfg.kda_head_dim)
                          * jax.nn.softplus(f + p["dt_bias"]))
    want_mean = float(jnp.mean(jnp.stack(decays)))
    got_mean = float(jnp.sum(stats["kda_decay"])
                     / jnp.sum(stats["kda_positions"]))
    np.testing.assert_allclose(got_mean, want_mean, rtol=1e-5)
    names = model.cfg.metric_names
    assert names[-2:] == KDA_METRIC_NAMES and len(metrics) == len(names)
    assert model.cfg.metric_ratios["kda_log_decay_mean"] == "kda_positions"


# -- (c) the share ------------------------------------------------------------

def test_shares_sum_to_the_uncut_layer():
    """16 experts in 4 shares of 4 (the published 256 in 32 shares of 8, cut
    to size): the routed parts of all the shares, the shared expert counted
    once, add up to the layer that holds all 16, in the program and in the
    reference; every share's router is the one of all 16."""
    cfg, _, ref = models()
    E = cfg.num_experts

    def layer(e0, held):
        return RoutedMoE(E, held, e0, cfg.num_experts_per_tok,
                         cfg.moe_intermediate_size, cfg.routed_scaling_factor)

    x = jax.random.normal(jax.random.key(6), (2, T, cfg.hidden_size))
    p = layer(0, E).init(jax.random.key(5), x)["params"]
    p = jax.tree_util.tree_map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.key(7), a.shape), p)
    assert p["router"].shape == (cfg.hidden_size, E)
    whole, stats = layer(0, E).apply({"params": p}, x)
    assert int(jnp.sum(stats["local"])) == 2 * T * cfg.num_experts_per_tok
    shared = ref._swiglu(x, p["shared"], None)
    parts, local = 0.0, 0
    for e0 in range(0, E, 4):
        ps = dict(p, **{n: p[n][e0:e0 + 4]
                        for n in ("w_gate", "w_up", "w_down")})
        y, st = layer(e0, 4).apply({"params": ps}, x)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref.experts(x, ps, e0=e0, held=4)),
            rtol=2e-4, atol=2e-6)
        parts = parts + (y - shared)
        local += int(jnp.sum(st["local"]))
    assert local == 2 * T * cfg.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(whole),
                               rtol=2e-4, atol=2e-6)


# -- (d) the layer pattern ----------------------------------------------------

def test_the_layer_pattern_is_the_published_one():
    with open(CONFIG) as f:
        conf = json.load(f)
    published = conf["linear_attn_config"]
    cfg = KimiLinearConfig()
    assert list(cfg.kda_layers) == published["kda_layers"]
    assert list(cfg.full_attn_layers) == published["full_attn_layers"]
    assert cfg.layers == conf["published"]["num_hidden_layers"] == 27
    kinds = "".join("K" if cfg.kind(i) == "kda" else "M" for i in range(27))
    assert kinds == "KKKM" * 6 + "KKM"
    ref = ref_file.Model(conf)
    held = "".join("K" if ref.is_kda(i) else "M" for i in range(ref.L))
    assert held == kinds[:conf["num_hidden_layers"]] == "KKKMK"
    shapes = jax.eval_shape(
        KimiLinear(KimiLinearConfig.tiny(**CUT)).init, jax.random.key(0),
        jnp.zeros((1, T), jnp.int32))["params"]
    assert ["A_log" in shapes[f"h{i}"]["attn"] for i in range(5)] \
        == [True, True, True, False, True]
    assert "mlp" in shapes["h0"] and "moe" in shapes["h1"]


# -- (e) the entry point ------------------------------------------------------

SIZED = {"COMMEFFICIENT_SYNTHETIC_CLIENTS": "4",
         "COMMEFFICIENT_SYNTHETIC_WORDS": "200",
         "COMMEFFICIENT_SYNTHETIC_SENTENCE": "2-3",
         "COMMEFFICIENT_SYNTHETIC_UTTERANCES": "2",
         "COMMEFFICIENT_SYNTHETIC_VALID": "2",
         "COMMEFFICIENT_WORD_VOCAB": "256",
         "COMMEFFICIENT_TINY_MODEL": "1"}


@pytest.mark.parametrize("mode", ["sketch", "uncompressed"])
def test_entry_point_trains_two_rounds(mode, monkeypatch, tmp_path):
    """``gpt2_train.train --arch kimi_linear`` at tiny widths on the sized
    data, through the fused client phase: two rounds, validation, and the
    routing and decay counters of every round in the event log."""
    import gpt2_train
    from commefficient_tpu.telemetry import read_events

    for k_, v_ in dict(SIZED,
                       COMMEFFICIENT_RUN_DIR=str(tmp_path / "run")).items():
        monkeypatch.setenv(k_, v_)
    for path in linear_attention.PATH_CALLS:  # the process's, from here on
        monkeypatch.setitem(linear_attention.PATH_CALLS, path, 0)
    argv = ["--dataset_name", "PERSONA", "--dataset_dir", str(tmp_path / "d"),
            "--arch", "kimi_linear", "--arch_layers", "5",
            "--layer_chips", "4", "--vocab_rows", "256", "--mode", mode,
            "--num_workers", "2", "--num_devices", "1",
            "--local_batch_size", "2", "--valid_batch_size", "1",
            "--microbatch_size", "1", "--num_candidates", "1",
            "--max_seq_len", "32", "--local_momentum", "0",
            "--num_epochs", "1", "--seed", "3",
            "--train_dataloader_workers", "0", "--val_dataloader_workers", "0"]
    if mode == "sketch":
        argv += ["--error_type", "virtual", "--num_rows", "3", "--num_cols",
                 "2048", "--k", "500", "--num_blocks", "2"]
    stats = gpt2_train.train(argv)
    assert np.isfinite(stats["val_nll"]) and stats["val_ppl"] > 1.0
    events = list(read_events(str(tmp_path / "run" / "telemetry.jsonl")))
    # the run header names the delta rule's core path: 16-wide heads and
    # the CPU take the XLA form; the traced calls are counted on it
    assert events[0]["ev"] == "run_start"
    assert events[0]["kda_core_path"] == "xla"
    said = next(e for e in events if e["ev"] == "model")
    assert said["kda_core_path"] == "xla" and said["kda_core_calls"] > 0
    rounds = [e for e in events if e["ev"] == "round"]
    assert len(rounds) == 2
    for e in rounds:
        m = e["model"]
        assert m["moe_local_pairs"] > 0 and m["kda_positions"] > 0
        # -log alpha a channel and token at the seed's weights: exp(A_log)
        # in [1, 16) times softplus(dt_bias + a small product) ~ dt
        assert 1e-4 < m["kda_log_decay_mean"] < 2.0, m


# -- (f) JoyAI's MLA is what it was -------------------------------------------

# sha256 of JoyAIConfig.tiny's sorted (leaf path, shape) list and of its
# float32 logits at seed 7 (``highest``), both taken with models/joyai.py as
# it was before ``MLA`` took ``q_lora_rank`` None and ``mla_use_nope``
JOYAI_TREE = "3f1727f658111127f1f32092ecc7d0b8ed065b31d0ea3c46eaee1b24c09d7f25"
JOYAI_LOGITS = \
    "0e3324f411b36c0a983524ffc392d668d35c905de6c1d8638d5c675c8ee84996"


def test_joyai_mla_keeps_its_tree_and_logits():
    cfg = JoyAIConfig.tiny(layers=3, experts_held=4, expert_offset=4,
                           vocab_rows=V)
    model = JoyAIFlash(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, V, (2, T)),
                      jnp.int32)
    params = model.init(jax.random.key(7), ids)["params"]
    logits, stats = model.apply({"params": params}, ids)
    tree = sorted((jax.tree_util.keystr(k), v.shape) for k, v in
                  jax.tree_util.tree_flatten_with_path(params)[0])
    assert hashlib.sha256(repr(tree).encode()).hexdigest() == JOYAI_TREE
    assert hashlib.sha256(np.asarray(logits, np.float32).tobytes()
                          ).hexdigest() == JOYAI_LOGITS
    assert sorted(stats) == ["absent", "local", "max_load"]
