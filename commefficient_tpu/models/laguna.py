"""Laguna-XS.2 (poolside, ``model_type`` ``laguna``), flax: grouped-query
attention whose layers are of two kinds in one stack — ``full_attention``
(48 query heads, YaRN-scaled RoPE on half of a head's columns) and
``sliding_attention`` (64 query heads, a window of 512, plain RoPE on all of
them) — over 8 key/value heads of 128, a sigmoid gate on every head's
output, one leading dense SwiGLU layer, then layers of 256 routed experts
(sigmoid scores, 8 a token, one shared expert, no selection bias), RMSNorm,
an untied head. The sizes are the public ``config.json``'s
(huggingface.co/poolside/Laguna-XS.2); what it does not say is a field of
``LagunaConfig`` (its five assumptions).

Block l (models/joyai.py ``Block``, whose attention module and expert layer
this configuration supplies): ``h = x + W_o (g * Core_l(q, k, v))``,
``y = h + FFN_l(N(h))`` with ``q = N(x) W_q`` (H_l heads of 128),
``k, v = N(x) W_k, N(x) W_v`` (8 of 128), ``g = sigmoid(N(x) W_g)`` one
scalar a head, RoPE on q and k by the layer's kind, and ``Core_l`` =
``softmax(q k^T / sqrt(128) + mask_l) v`` where query head h reads key/value
head ``h // (H_l / 8)`` and a sliding layer's query i sees the keys j with
``0 <= i - j < 512``. The core with the rotation and the gate lives in
ops/attention.py (``gqa_attention``: q, k, v go in as the products wrote
them and ``W_o``'s operand comes out), which also says on which path a call
runs.

What is held here is a cut the caller names, as in models/joyai.py: the
first ``layers`` layers of the published lists, ``experts_held`` of the
routed experts from ``expert_offset`` on (the router keeps all its columns),
``vocab_rows`` rows of the vocabulary.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from commefficient_tpu.models.joyai import (
    MOE_METRIC_NAMES,
    MOE_METRIC_RATIOS,
    Decoder,
    _kernel,
    routing_sums,
)
from commefficient_tpu.ops.attention import gqa_attention, gqa_scope
from commefficient_tpu.parallel.moe import RoutedMoE

__all__ = ["LagunaXS2", "LagunaConfig", "GQA", "rope_frequencies"]

_PERIOD = ("full_attention",) + ("sliding_attention",) * 3

@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The published sizes by default; ``layers``, ``experts_held``,
    ``expert_offset`` and ``vocab_rows`` are the cut, the five choices
    config.json leaves open are fields."""

    hidden_size: int = 2048
    head_dim: int = 128
    num_key_value_heads: int = 8
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64) * 10
    layer_types: Tuple[str, ...] = _PERIOD * 10
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 39
    sliding_window: int = 512
    intermediate_size: int = 8192
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    # rope_parameters, by kind of layer
    full_rope_theta: float = 500000.0
    full_partial_rotary_factor: float = 0.5
    yarn_factor: float = 64.0
    yarn_original_positions: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4158883083359672
    sliding_rope_theta: float = 10000.0
    sliding_partial_rotary_factor: float = 1.0
    # assumed. ``gating: true`` is one sigmoid scalar a head on the head's
    # output before W_o (Laguna-S-2.1 spells it "per-head")
    gate_per_head: bool = True
    # sigmoid scores, the 8 largest, gates renormalised over the selected,
    # x 2.5; no bias in the selection (the config names none)
    router_selection_bias: bool = False
    # no q/k normalisation, no gate on the shared expert, no biases
    qk_norm: bool = False
    # pairs (x_i, x_{i+n/2}) over the n rotary columns, not (x_2i, x_2i+1)
    rope_half_split: bool = True
    # a sliding layer's query i sees the keys j with 0 <= i - j < window
    window_includes_self: bool = True
    # the cut
    layers: int = 40
    experts_held: int = 256
    expert_offset: int = 0
    vocab_rows: int = 100352
    expert_operand_dtype: Optional[Any] = None

    def __post_init__(self):
        # one choice each is written out; a correction is these lines and
        # the place each names
        assert self.gate_per_head and not self.router_selection_bias \
            and not self.qk_norm and self.rope_half_split \
            and self.window_includes_self, "only the assumed forms are built"
        assert self.layers <= len(self.layer_types)
        # ``RoutedMoE``'s shared expert has its routed experts' width
        assert self.shared_expert_intermediate_size \
            == self.moe_intermediate_size

    @classmethod
    def tiny(cls, **cut):
        """Widths for the CPU tests; the same code paths: two kinds of
        layer with unequal head counts, groups of 3 and 4 query heads a
        key/value head, a window shorter than the tests' sequences, partial
        rotary and YaRN; two periods of the layer pattern."""
        cut.setdefault("layers", 8)
        return cls(hidden_size=64, head_dim=16, num_key_value_heads=2,
                   num_attention_heads_per_layer=(6, 8, 8, 8) * 2,
                   layer_types=_PERIOD * 2,
                   mlp_layer_types=("dense",) + ("sparse",) * 7,
                   sliding_window=5, intermediate_size=128,
                   moe_intermediate_size=32,
                   shared_expert_intermediate_size=32, num_experts=16,
                   num_experts_per_tok=4, yarn_original_positions=8,
                   yarn_factor=4.0, yarn_beta_fast=4.0, **cut)

    # what models/joyai.py ``Block`` and the entry point ask of a configuration
    routed = property(lambda self: self.num_experts)
    # ... and losses.make_causal_lm_losses
    reads_labels = False
    metric_names = MOE_METRIC_NAMES
    metric_ratios = MOE_METRIC_RATIOS
    metric_sums = routing_sums

    def attention(self, layer: int):
        return GQA(self, layer, name="attn")

    def is_dense(self, layer: int) -> bool:
        return self.mlp_layer_types[layer] == "dense"

    def experts(self):
        return RoutedMoE(
            self.num_experts, self.experts_held, self.expert_offset,
            self.num_experts_per_tok, self.moe_intermediate_size,
            self.moe_routed_scaling_factor,
            operand_dtype=self.expert_operand_dtype,
            selection_bias=self.router_selection_bias, name="moe")


def rope_frequencies(cfg: LagunaConfig, kind: str):
    """(frequencies of the rotary pairs, the factor on cos and sin) of a
    kind of layer. ``sliding_attention``: ``theta^(-2i/n)`` over all of a
    head's columns. ``full_attention``: YaRN on the first ``n`` =
    ``partial_rotary_factor * head_dim`` columns: pair i turns at ``f_i =
    theta^(-2i/n)`` below the ramp, at ``f_i / factor`` above it, blended
    linearly over the pairs between ``lo = floor(c(beta_fast))`` and ``hi =
    ceil(c(beta_slow))``, ``c(beta) = n ln(original / (2 pi beta)) / (2 ln
    theta)``."""
    if kind == "sliding_attention":
        n = int(cfg.head_dim * cfg.sliding_partial_rotary_factor)
        return cfg.sliding_rope_theta ** (-np.arange(0, n, 2) / n), 1.0
    n = int(cfg.head_dim * cfg.full_partial_rotary_factor)
    theta = cfg.full_rope_theta
    f = theta ** (-np.arange(0, n, 2) / n)

    def pair_at(beta):
        return n * math.log(cfg.yarn_original_positions
                            / (2 * math.pi * beta)) / (2 * math.log(theta))

    lo = max(math.floor(pair_at(cfg.yarn_beta_fast)), 0)
    hi = min(math.ceil(pair_at(cfg.yarn_beta_slow)), n // 2 - 1)
    ramp = np.clip((np.arange(n // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (f / cfg.yarn_factor) * ramp + f * (1.0 - ramp), \
        cfg.yarn_attention_factor


class GQA(nn.Module):
    cfg: LagunaConfig
    layer: int

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        _, T, C = x.shape
        H, Hkv, d = (c.num_attention_heads_per_layer[self.layer],
                     c.num_key_value_heads, c.head_dim)
        kind = c.layer_types[self.layer]
        window = c.sliding_window if kind == "sliding_attention" else None
        # as the products write them, (S, T, H * d): the core takes them so
        q = x @ _kernel(self, "q", (C, H * d))
        k = x @ _kernel(self, "k", (C, Hkv * d))
        v = x @ _kernel(self, "v", (C, Hkv * d))
        gate = x @ _kernel(self, "gate", (C, H))
        w_o = _kernel(self, "o", (H * d, C))
        with jax.named_scope("fed_gqa_attn"), \
                jax.named_scope(gqa_scope(window)):
            freq, factor = rope_frequencies(c, kind)
            angle = jnp.arange(T, dtype=jnp.float32)[:, None] \
                * jnp.asarray(freq, jnp.float32)
            rope = jnp.cos(angle) * factor, jnp.sin(angle) * factor
            out = gqa_attention(q, k, v, jax.nn.sigmoid(gate), rope, window)
        return out @ w_o


class LagunaXS2(Decoder):
    """The decoder of a ``LagunaConfig``."""
