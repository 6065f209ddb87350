"""Ouro-2.6B (ByteDance, ``model_type`` ``ouro``; "Scaling Latent Reasoning
via Looped Language Models", arXiv:2510.25741), flax: a stack of plain
decoder layers that is run ``total_ut_steps`` = 4 times on the same weights,
with the final RMSNorm, the untied head and a one-unit exit gate read after
every pass. The sizes are the public ``config.json``'s
(huggingface.co/ByteDance/Ouro-2.6B); what it does not say is assumed, and
written out once each (the list below; benchmark/configs/ouro_2p6b_l4.json
``assumed`` has the same six).

``x_0 = E[ids]``. Pass t = 1..R: ``u = x_{t-1}``; for layer l = 0..L-1:
``a = u + N2_l(Attn_l(N1_l(u)))``, ``u = a + N4_l(MLP_l(N3_l(a)))`` (a
*sandwich*: four RMSNorms a layer); then ``x_t = N_f(u)``, ``logits_t = x_t
W_head``, ``lambda_t = sigmoid(x_t . w_g + b_g)``. The same ``N*_l``,
``Attn_l``, ``MLP_l``, ``N_f``, ``W_head``, ``w_g`` in every pass: the L
blocks are built once and applied R times, by one ``nn.scan`` over the
passes with the parameters broadcast into it (PERF.md section 6 has the
measurement against R x L unrolled block applications), so a block's
parameters receive the sum of its R uses' gradients. ``Attn``: 16 heads of
128 over 16 key/value heads, half-split RoPE (theta 1e6) on all of a head's
columns, causal, no gate: ops/attention.py ``gqa_attention`` with
``gate=None``. ``MLP``: SwiGLU of 5632.

Training never holds (S, T, vocabulary) logits of more than one pass: the
model takes the labels and returns, a pass, the next-token NLL, the exit
gate's logit and whether the largest logit was the label, each (S, T - 1);
every block application is recomputed in the backward pass (``nn.remat``),
and so is a pass's norm, head, NLL and gate.

The objective (``expected_exit_terms``; the paper's entropy-regularised one):
``p_1 = lambda_1``, ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, ``p_R`` the
remainder; a labelled position's loss is ``sum_t p_t nll_t - beta H(p)``.
Validation reads pass R alone (config.json's ``early_exit_threshold`` is 1:
no position leaves early, every pass runs).

Assumed, each in the one place named: (1) *sandwich normalisation*, four
RMSNorms a layer, the second and fourth on the attention's and the MLP's
output before the residual adds it (``OuroBlock``); (2) *the final norm inside
the recurrence*: ``x_t = N_f(u)`` is what pass t + 1 starts from, not only
what the head and the gate read (``PassHead`` returns it as the carry); (3)
*no biases* in q, k, v, o or the MLP; the gate is ``Linear(hidden -> 1)`` with
a bias, one for all passes, read on ``x_t`` (``PassHead``); (4) *RoPE pairs
half-split*, ``(x_i, x_{i + d/2})`` over the whole head (``gqa_attention``'s
turn); (5) *beta* = 0.05 (``OuroConfig.exit_entropy_coef``; the paper lowers
it from 0.1 during pre-training), and the gate-alone second stage is not run;
(6) *init* N(0, 0.02) for matrices, embedding and gate, norms 1, the gate's
bias 0 (``_kernel``, ``nn.Embed``'s ``embedding_init``).

What is held here is a cut the caller names: the first ``layers`` layers,
``vocab_rows`` rows of the vocabulary.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from commefficient_tpu.models.joyai import RMSNorm, _kernel
from commefficient_tpu.ops.attention import gqa_attention, gqa_scope
from commefficient_tpu.parallel.moe import SwiGLU

__all__ = ["Ouro", "OuroConfig", "OuroBlock", "exit_log_probs",
           "expected_exit_terms", "RECURRENCE"]

# how ``Ouro`` runs its passes: one ``nn.scan`` over them with the
# parameters broadcast (``scan``: the program of L blocks, a leaf's gradient
# accumulated in the loop), not R x L block applications in one program
# (``unrolled``). Both were measured (PERF.md section 6); this one is kept
RECURRENCE = "scan"


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """The published sizes by default; ``layers`` and ``vocab_rows`` are the
    cut, ``exit_entropy_coef`` the one constant config.json leaves open that
    the code reads (the other assumptions are the module docstring's)."""

    hidden_size: int = 2048
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    total_ut_steps: int = 4
    # assumed (5): beta of the entropy term
    exit_entropy_coef: float = 0.05
    # the cut
    layers: int = 48
    vocab_rows: int = 49152

    @classmethod
    def tiny(cls, **cut):
        """Widths for the CPU tests; the same code paths: four passes, two
        layers, four heads over four key/value heads."""
        cut.setdefault("layers", 2)
        return cls(hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=4, head_dim=16, intermediate_size=128,
                   **cut)

    # what gpt2_train.build_decoder asks of a configuration: no routed
    # experts to share out over --layer_chips
    routed = 0

    @property
    def loop_plan(self):
        """The run's ``loop`` event (gpt2_train.report_attention_core)."""
        return {"passes": self.total_ut_steps, "layers": self.layers,
                "recurrence": RECURRENCE,
                "block_applications": self.total_ut_steps * self.layers}

    # what losses.make_causal_lm_losses asks of a configuration whose model
    # takes the labels. The counters a round leaves in the event log
    # (telemetry "model" record), as sums over the labelled positions: each
    # pass's NLL, the expected exit step ``sum_t t p_t``, and the positions
    # themselves, by which the others are divided
    reads_labels = True

    @property
    def metric_names(self):
        return tuple(f"loop_nll_step{t}" for t in
                     range(1, self.total_ut_steps + 1)) \
            + ("loop_exit_step", "loop_positions")

    @property
    def metric_ratios(self):
        return {n: "loop_positions" for n in self.metric_names[:-1]}

    def position_terms(self, nll, gate_logit, hit, train):
        """(a position's loss, whether its prediction was the label, its
        counters in ``metric_names``' order) from what ``Ouro`` returns: the
        expected-exit objective in training and the last pass's NLL in
        validation; the last pass's prediction."""
        loss, exit_step = expected_exit_terms(nll, gate_logit,
                                              self.exit_entropy_coef)
        return (loss if train else nll[-1], hit[-1],
                (*nll, exit_step, jnp.ones_like(exit_step)))


def exit_log_probs(gate_logit):
    """``ln p_t`` of the exit distribution, (R, ...), from the passes'
    exit-gate logits (R, ...): ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``
    for t < R and ``p_R`` the remainder ``prod_{j<R} (1 - lambda_j)`` (the
    last pass's gate is not read), formed from ``ln lambda =
    log_sigmoid(z)`` and ``ln (1 - lambda) = log_sigmoid(-z)``, so that a
    saturated gate gives ``p = 0`` and ``p ln p = 0``, not a NaN."""
    z = gate_logit[:-1]
    stay = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)
    before = jnp.concatenate([jnp.zeros_like(gate_logit[:1]), stay], axis=0)
    return before.at[:-1].add(jax.nn.log_sigmoid(z))


def expected_exit_terms(nll, gate_logit, beta):
    """``sum_t p_t nll_t - beta H(p)`` (``H(p) = -sum_t p_t ln p_t``) and
    the expected exit step ``sum_t t p_t`` of every position, from the
    passes' NLLs and exit-gate logits (R, ...)."""
    log_p = exit_log_probs(gate_logit)
    p = jnp.exp(log_p)
    step = jnp.arange(1, p.shape[0] + 1, dtype=p.dtype)
    return (jnp.sum(p * (nll + beta * log_p), axis=0),
            jnp.tensordot(step, p, axes=1))


class Attention(nn.Module):
    cfg: OuroConfig

    @nn.compact
    def __call__(self, x, rope):
        c, C = self.cfg, x.shape[-1]
        H, Hkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        # as the products write them, (S, T, H * d): the core takes them so
        q = x @ _kernel(self, "q", (C, H * d))
        k = x @ _kernel(self, "k", (C, Hkv * d))
        v = x @ _kernel(self, "v", (C, Hkv * d))
        w_o = _kernel(self, "o", (H * d, C))
        with jax.named_scope("fed_gqa_attn"), \
                jax.named_scope(gqa_scope(None)):
            out = gqa_attention(q, k, v, None, rope, heads=H)
        return out @ w_o


class OuroBlock(nn.Module):
    """One sandwich layer: ``a = x + N2(Attn(N1(x)))``, then ``a +
    N4(MLP(N3(a)))``."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, x, rope):
        c = self.cfg

        def norm(name):
            return RMSNorm(c.rms_norm_eps, name=name)

        a = x + norm("attn_post_norm")(
            Attention(c, name="attn")(norm("attn_norm")(x), rope))
        return a + norm("ffn_post_norm")(
            SwiGLU(c.intermediate_size, name="mlp")(norm("ffn_norm")(a)))


class PassHead(nn.Module):
    """What is read after a pass: ``x_t = N_f(u)`` and, of it, the
    next-token NLL against ``labels`` (S, T - 1; -1 where there is none),
    the exit gate's logit and whether the largest logit is the label. The
    (S, T - 1, vocabulary) logits live inside this call alone."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, u, labels):
        c = self.cfg
        with jax.named_scope("fed_loop_head"):
            x = RMSNorm(c.rms_norm_eps, name="norm_f")(u)
            head = _kernel(self, "head", (c.hidden_size, c.vocab_rows))
            w_g = _kernel(self, "gate", (c.hidden_size, 1))
            b_g = self.param("gate_bias", nn.initializers.zeros, (1,))
            z = x[:, :-1]
            logits = (z @ head).astype(jnp.float32)
            picked = jnp.take_along_axis(
                logits, jnp.where(labels != -1, labels, 0)[..., None],
                axis=-1)[..., 0]
            nll = jax.nn.logsumexp(logits, axis=-1) - picked
            hit = jnp.argmax(logits, axis=-1) == labels
            gate_logit = ((z @ w_g)[..., 0] + b_g).astype(jnp.float32)
        return x, (nll, gate_logit, hit)


class Ouro(nn.Module):
    """``input_ids`` (S, T) and their ``labels`` (S, T - 1; none: nothing
    labelled) -> the passes' next-token NLLs, exit-gate logits and hits,
    each (R, S, T - 1)."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, input_ids, labels=None):
        c = self.cfg
        if labels is None:
            labels = jnp.full(input_ids[:, 1:].shape, -1, jnp.int32)
        x = nn.Embed(c.vocab_rows, c.hidden_size, name="embed",
                     embedding_init=nn.initializers.normal(0.02))(input_ids)

        # the turn's cos and sin, (T, head_dim / 2), once for every pass and
        # layer: made here and broadcast into the loop
        d = c.head_dim
        angle = jnp.arange(input_ids.shape[1], dtype=jnp.float32)[:, None] \
            * c.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        rope = (jnp.cos(angle), jnp.sin(angle))

        def one_pass(mdl, x, labels, rope):
            with jax.named_scope("fed_loop_body"):
                for i in range(c.layers):
                    x = nn.remat(OuroBlock)(c, name=f"h{i}")(x, rope)
            return nn.remat(PassHead)(c, name="exit")(x, labels)

        # one loop over the passes, the parameters broadcast into it: the L
        # blocks exist once (in the tree and in the program) and are used,
        # and take gradient, ``total_ut_steps`` times
        _, outs = nn.scan(one_pass, variable_broadcast="params",
                          split_rngs={"params": False}, in_axes=nn.broadcast,
                          length=c.total_ut_steps)(self, x, labels, rope)
        return outs
