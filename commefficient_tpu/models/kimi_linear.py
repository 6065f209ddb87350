"""Kimi-Linear-48B-A3B (moonshotai, ``model_type`` ``kimi_linear``; the Kimi
Linear report, arXiv:2510.26692), flax: Kimi Delta Attention (KDA, a gated
delta rule with a decay per key channel and short convolutions) in three
layers of four and NoPE multi-head latent attention in the fourth, one
leading dense SwiGLU layer, then layers of 256 routed experts (sigmoid
scores, 8 a token, one shared expert), RMSNorm, an untied head. The sizes
are the public ``config.json``'s
(huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct); the KDA layer's
equations follow the report and flash-linear-attention's KDA layer.

Block l (models/joyai.py ``Block``, whose attention module and expert layer
this configuration supplies): ``h = x + Mixer_l(N(x)); y = h + FFN_l(N(h))``,
N an RMSNorm (eps 1e-5), ``FFN_0`` a SwiGLU of 9,216, every other layer the
expert layer (1,024 wide) with its shared expert; after the last block N and
the head. ``Mixer_l`` is KDA where config.json's ``linear_attn_config``
lists layer l + 1 under ``kda_layers`` and MLA where it lists it under
``full_attn_layers`` (period KDA, KDA, KDA, MLA; the 27th layer MLA).

KDA (H = 32 heads, d_k = d_v = 128):
``q, k, v = SiLU(Conv4(x W_q)), SiLU(Conv4(x W_k)), SiLU(Conv4(x W_v))``,
Conv4 a causal depthwise convolution of width 4 over time on each of the
4,096 channels, no bias; per head ``q^ = q / |q|_2``, ``k^ = k / |k|_2``
(eps 1e-6); ``beta_t = sigmoid(x_t W_b)`` one a head; ``log alpha_t =
-exp(A_log_h) softplus(x_t W_fa W_fb + dt_bias)`` one a channel (32 x 128);
the state ``S_t = (I - beta_t k^_t k^_t^T) Diag(alpha_t) S_{t-1} + beta_t
k^_t v_t^T`` (128 x 128 a head, S_0 = 0) and ``o_t = S_t^T q^_t / sqrt(128)``
(ops/linear_attention.py, its chunked form); the output ``W_o
[RMSNorm_128(o_t) w_o * sigmoid(x_t W_ga W_gb)]``, the norm a head with one
128-wide weight.

MLA (models/joyai.py ``MLA`` with ``q_lora_rank`` None and
``mla_use_nope``): ``q = x W_q`` (32 heads of 128 + 64), ``[c_kv; k_r] = x
W_kva`` (512 + 64), ``c_kv = N(c_kv)``, ``[k_n; v] = c_kv W_kvb``; no
rotation anywhere, the 64-wide parts as the products wrote them, ``k_r``
shared by all heads; causal ``softmax(q k^T / sqrt(192)) v`` through W_o.

Experts (parallel/moe.py ``RoutedMoE``): ``s = sigmoid(x W_r)`` over 256;
the 8 largest ``s + b`` selected (b, the selection bias, in the selection
only); ``g_i = 2.446 s_i / sum_selected s_j``; the held experts' part and
the shared expert.

Assumed where config.json is silent (the configuration file's ``assumed``
has each with its reason): the selection bias (DeepSeek-V3's class, which
the lineage uses); that NoPE keeps the unrotated 64-wide parts; the
initialisers of ``A_log`` (log U(1, 16)) and ``dt_bias`` (inverse softplus
of log-uniform dt in [1e-3, 1e-1]) as flash-linear-attention's KDA layer
draws them, and of the convolutions (U(-1/2, 1/2), PyTorch's ``Conv1d``
default at width 4).

What is held here is a cut the caller names, as in models/joyai.py: the
first ``layers`` layers, ``experts_held`` routed experts from
``expert_offset`` on (the router keeps all its columns), ``vocab_rows`` rows
of the vocabulary. Every block is recomputed in the backward pass.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from commefficient_tpu.models.joyai import (
    MLA,
    MOE_METRIC_NAMES,
    MOE_METRIC_RATIOS,
    Decoder,
    _kernel,
    routing_sums,
)
from commefficient_tpu.ops.linear_attention import (
    causal_conv,
    kda_attention,
    kda_core_path,
)
from commefficient_tpu.parallel.moe import RoutedMoE

__all__ = ["KimiLinear", "KimiLinearConfig", "KDA", "KDA_METRIC_NAMES"]

# config.json's ``linear_attn_config``, 1-based layer numbers
_KDA_LAYERS = tuple(i for i in range(1, 27) if i % 4)
_FULL_LAYERS = (4, 8, 12, 16, 20, 24, 27)

# the counters a round of KDA leaves in the event log beside the routing's:
# the mean -log alpha over the KDA layers, tokens, heads and channels (whether
# the state forgets or holds) as a sum over the positions a KDA layer saw,
# divided by that count
KDA_METRIC_NAMES = ("kda_log_decay_mean", "kda_positions")


def _kimi_sums(cfg, stats, n_seq):
    return routing_sums(cfg, stats, n_seq) + (stats["kda_decay"],
                                              stats["kda_positions"])


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The published sizes by default; ``layers``, ``experts_held``,
    ``expert_offset`` and ``vocab_rows`` are the cut."""

    hidden_size: int = 2304
    # MLA (``q_lora_rank`` null and ``mla_use_nope`` below)
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # KDA (``linear_attn_config``)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_layers: Tuple[int, ...] = _KDA_LAYERS
    full_attn_layers: Tuple[int, ...] = _FULL_LAYERS
    # feed-forward
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_experts: int = 256
    num_experts_per_tok: int = 8          # config.json num_experts_per_token
    routed_scaling_factor: float = 2.446
    first_k_dense_replace: int = 1
    rms_norm_eps: float = 1e-5
    # the cut
    layers: int = 27
    experts_held: int = 256
    expert_offset: int = 0
    vocab_rows: int = 163840
    expert_operand_dtype: Optional[Any] = None

    def __post_init__(self):
        assert sorted(self.kda_layers + self.full_attn_layers) \
            == list(range(1, len(self.kda_layers)
                          + len(self.full_attn_layers) + 1)), \
            "every layer is KDA or MLA, once"
        assert self.layers <= len(self.kda_layers) + len(self.full_attn_layers)

    @classmethod
    def tiny(cls, **cut):
        """Widths for the CPU tests; the same code paths: layers 1-5 are
        KDA, KDA, KDA, MLA, KDA as published."""
        cut.setdefault("layers", 5)
        return cls(hidden_size=64, num_attention_heads=2, kv_lora_rank=16,
                   qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                   kda_num_heads=2, kda_head_dim=16, intermediate_size=128,
                   moe_intermediate_size=32, num_experts=16,
                   num_experts_per_tok=4, **cut)

    # config.json's: models/joyai.py ``MLA`` without a low-rank q and
    # without RoPE (so its ``rope_theta`` is read by nothing)
    q_lora_rank = None
    mla_use_nope = True
    # what models/joyai.py ``Block`` and the entry point ask of a
    # configuration
    routed = property(lambda self: self.num_experts)
    # ... and losses.make_causal_lm_losses
    reads_labels = False
    metric_names = MOE_METRIC_NAMES + KDA_METRIC_NAMES
    metric_ratios = {**MOE_METRIC_RATIOS,
                     "kda_log_decay_mean": "kda_positions"}
    metric_sums = _kimi_sums

    def kind(self, layer: int) -> str:
        """``kda`` or ``mla``, by the published lists (layer is 0-based)."""
        if layer + 1 in self.kda_layers:
            return "kda"
        assert layer + 1 in self.full_attn_layers
        return "mla"

    def attention(self, layer: int):
        if self.kind(layer) == "kda":
            return KDA(self, name="attn")
        return MLA(self, name="attn")

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def kda_core_path(self, T: int) -> str:
        """The path the delta rule's core takes on sequences of T positions
        in this process (ops/linear_attention.py ``kda_core_path``)."""
        return kda_core_path(T, self.kda_head_dim, self.kda_head_dim)

    def experts(self):
        return RoutedMoE(
            self.num_experts, self.experts_held, self.expert_offset,
            self.num_experts_per_tok, self.moe_intermediate_size,
            self.routed_scaling_factor,
            operand_dtype=self.expert_operand_dtype, name="moe")


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus^-1 of dt, log-uniform in [1e-3, 1e-1]."""
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi))
    return dt + jnp.log(-jnp.expm1(-dt))


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class KDA(nn.Module):
    """The module docstring's KDA mixer. Returns ``(output, counters)``:
    the sum a sequence over its positions of ``-log alpha`` meaned over heads
    and channels (``kda_decay``), and the positions (``kda_positions``)."""

    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        S, T, C = x.shape
        H, d, W = c.kda_num_heads, c.kda_head_dim, c.short_conv_kernel_size
        conv_init = nn.initializers.uniform(1.0)
        # as the products write them, (S, T, H * d)
        q, k, v = (x @ _kernel(self, n, (C, H * d)) for n in "qkv")
        convs = [self.param(f"{n}_conv", lambda key, s: conv_init(key, s)
                            - 0.5, (W, H * d)) for n in "qkv"]
        f = (x @ _kernel(self, "f_a", (C, d))) @ _kernel(self, "f_b",
                                                         (d, H * d))
        gate = (x @ _kernel(self, "g_a", (C, d))) @ _kernel(self, "g_b",
                                                            (d, H * d))
        # (H, C), as the published b_proj's weight: a (C, 32) leaf would be
        # the only one whose minor dimension is not a multiple of 128, and
        # XLA:TPU then lays the whole flat parameter vector out as
        # (d / 32, 32), four times its size, wherever it concatenates it
        b = jnp.einsum("stc,hc->sth", x, _kernel(self, "b", (H, C)))
        a_log = self.param("A_log", _a_log_init, (H,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (H * d,))
        o_scale = self.param("o_norm", nn.initializers.ones, (d,))
        w_o = _kernel(self, "o", (H * d, C))
        with jax.named_scope("fed_kda_attn"):
            q, k, v = (jax.nn.silu(causal_conv(y, w)).reshape(S, T, H, d)
                       for y, w in zip((q, k, v), convs))
            q, k = _l2norm(q), _l2norm(k)
            beta = jax.nn.sigmoid(b)
            log_alpha = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                f + dt_bias).reshape(S, T, H, d)
            with jax.named_scope("fed_kda_core"):
                o = kda_attention(q, k, v, log_alpha, beta)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + c.rms_norm_eps) * o_scale
            o = o * jax.nn.sigmoid(gate.reshape(S, T, H, d))
            decay = jax.lax.stop_gradient(
                -jnp.sum(jnp.mean(log_alpha, axis=(-2, -1)), axis=-1))
        stats = {"kda_decay": decay,
                 "kda_positions": jnp.full((S,), T, jnp.float32)}
        return o.reshape(S, T, H * d) @ w_o, stats


class KimiLinear(Decoder):
    """The decoder of a ``KimiLinearConfig``."""
