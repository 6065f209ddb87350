"""JoyAI-LLM-Flash (DeepSeek-V3's configuration class), flax: multi-head
latent attention, one leading dense SwiGLU layer, then layers of 256 routed
experts (sigmoid scores, 8 a token, one shared expert), RMSNorm, an untied
head. The equations follow DeepSeek-V3's report (arXiv:2412.19437 section
2.1); the sizes are the public ``config.json``'s
(huggingface.co/jdopensource/JoyAI-LLM-Flash).

A block: ``h = x + MLA(N(x)); y = h + FFN(N(h))`` with ``N`` an RMSNorm
(eps 1e-6). After the last block ``N`` and the head.

MLA: ``c_q = N(x W_qa)``; ``q = c_q W_qb`` gives every head a 128-wide part
without position and a 64-wide part with; ``[c_kv; k_r] = x W_kva``;
``c_kv = N(c_kv)``; ``[k_n; v] = c_kv W_kvb`` per head; RoPE (theta 32e6,
interleaved pairs, no scaling) on each head's ``q_r`` and on the one ``k_r``
all heads share; causal ``softmax(q k^T / sqrt(192)) v``; the heads
concatenated through ``W_o``. No biases anywhere. The core (scores, mask,
softmax, values) lives in ops/attention.py, which also says on which path a
call runs.

What is held here is a cut the caller names (``JoyAIConfig``): how many
layers, which of the routed experts (parallel/moe.py ``RoutedMoE``: the
router keeps all its columns), how many rows of the vocabulary (ids, logits
and loss are over the slice). Not held: the multi-token-prediction module.
Every block is recomputed in the backward pass (``nn.remat``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from commefficient_tpu.ops.attention import mla_attention
from commefficient_tpu.parallel.moe import RoutedMoE, SwiGLU

__all__ = ["JoyAIFlash", "JoyAIConfig", "Decoder", "Block", "RMSNorm",
           "MOE_METRIC_NAMES", "MOE_METRIC_RATIOS", "routing_sums"]

# the routing counters a round of a ``Decoder`` leaves in the event log, in
# the order of the loss's metric sums (telemetry.RunTelemetry "model" record)
MOE_METRIC_NAMES = ("moe_local_pairs", "moe_absent_pairs",
                    "moe_load_max_over_mean")
# ... of which these are summed as numerators and divided by another's sum
MOE_METRIC_RATIOS = {"moe_load_max_over_mean": "moe_local_pairs"}


def routing_sums(cfg, stats, n_seq):
    """What losses.make_causal_lm_losses asks of a configuration whose model
    returns ``(logits, routing counts)``: the counts as metric sums a
    sequence (``MOE_METRIC_NAMES``: the (token, expert) pairs computed here,
    the pairs routed to absent experts, and the largest held expert's load
    over the mean load, summed over the expert layers as ``max load x
    experts held`` and split evenly over the call's ``n_seq`` sequences, so
    that the round's sum over the round's pairs is the ratio:
    ``MOE_METRIC_RATIOS``)."""
    return (stats["local"], stats["absent"],
            jnp.full((n_seq,), stats["max_load"] * cfg.experts_held / n_seq))


@dataclasses.dataclass(frozen=True)
class JoyAIConfig:
    """The published sizes by default; ``layers``, ``experts_held``,
    ``expert_offset`` and ``vocab_rows`` are the cut."""

    hidden_size: int = 2048
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    first_k_dense_replace: int = 1
    rope_theta: float = 32e6
    rms_norm_eps: float = 1e-6
    layers: int = 40
    experts_held: int = 256
    expert_offset: int = 0
    vocab_rows: int = 129280
    # multiplicands of the routed experts' grouped products (None: as
    # stored); the entry point sets bfloat16 on the TPU at the default
    # precision, where every other product's are rounded by the unit
    expert_operand_dtype: Optional[Any] = None

    @classmethod
    def tiny(cls, **cut):
        """Widths for the CPU tests; the same code paths."""
        return cls(hidden_size=64, num_attention_heads=2, q_lora_rank=32,
                   kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                   v_head_dim=16, intermediate_size=128,
                   moe_intermediate_size=32, n_routed_experts=16,
                   num_experts_per_tok=4, **cut)

    # what ``Block`` and the entry point ask of a configuration
    routed = property(lambda self: self.n_routed_experts)
    # ... and losses.make_causal_lm_losses
    reads_labels = False
    metric_names = MOE_METRIC_NAMES
    metric_ratios = MOE_METRIC_RATIOS
    metric_sums = routing_sums

    def attention(self, layer: int):
        return MLA(self, name="attn")

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def experts(self):
        return RoutedMoE(
            self.n_routed_experts, self.experts_held, self.expert_offset,
            self.num_experts_per_tok, self.moe_intermediate_size,
            self.routed_scaling_factor,
            operand_dtype=self.expert_operand_dtype, name="moe")


def _kernel(mod, name, shape):
    return mod.param(name, nn.initializers.normal(0.02), shape)


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                                + self.eps)
        return (y * scale).astype(x.dtype)


def rope(x, theta: float):
    """Rotary position embedding on interleaved pairs: (x[2i], x[2i+1]) of
    position p turned by ``p * theta**(-2i/n)``. x: (S, T, H, n)."""
    n = x.shape[-1]
    freq = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


class MLA(nn.Module):
    cfg: JoyAIConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        S, T, C = x.shape
        H, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim, c.v_head_dim)
        q = RMSNorm(c.rms_norm_eps, name="q_norm")(
            x @ _kernel(self, "q_a", (C, c.q_lora_rank)))
        q = (q @ _kernel(self, "q_b", (c.q_lora_rank, H * (dn + dr)))
             ).reshape(S, T, H, dn + dr)
        kv = x @ _kernel(self, "kv_a", (C, c.kv_lora_rank + dr))
        c_kv = RMSNorm(c.rms_norm_eps, name="kv_norm")(
            kv[..., :c.kv_lora_rank])
        k_r = rope(kv[..., None, c.kv_lora_rank:], c.rope_theta)[:, :, 0]
        kv = (c_kv @ _kernel(self, "kv_b", (c.kv_lora_rank, H * (dn + dv)))
              ).reshape(S, T, H, dn + dv)
        w_o = _kernel(self, "o", (H * dv, C))
        with jax.named_scope("fed_mla_attn"):
            q_r = rope(q[..., dn:], c.rope_theta)
            out = mla_attention(q, q_r, kv, k_r).reshape(S, T, H * dv)
        return out @ w_o


class Block(nn.Module):
    """One decoder block of a configuration (``JoyAIConfig``, or
    models/laguna.py's): the configuration says which attention module layer
    ``layer`` has, whether its feed-forward part is dense, and builds its
    expert layer."""

    cfg: Any
    layer: int

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        h = x + c.attention(self.layer)(RMSNorm(c.rms_norm_eps,
                                                name="attn_norm")(x))
        z = RMSNorm(c.rms_norm_eps, name="ffn_norm")(h)
        if c.is_dense(self.layer):
            stats = {"local": jnp.zeros(x.shape[:-1], jnp.int32),
                     "max_load": jnp.int32(0)}
            return h + SwiGLU(c.intermediate_size, name="mlp")(z), stats
        y, stats = c.experts()(z)
        return h + y, stats


class Decoder(nn.Module):
    """``input_ids`` (S, T) -> logits (S, T, vocab_rows) and the routing
    counts of the call: held pairs by sequence (``local``, (S,)), the pairs
    routed to absent experts (``absent``, (S,)) and the sum over the expert
    layers of the largest held expert's load (``max_load``)."""

    cfg: Any

    @nn.compact
    def __call__(self, input_ids):
        c = self.cfg
        x = nn.Embed(c.vocab_rows, c.hidden_size, name="embed",
                     embedding_init=nn.initializers.normal(0.02))(input_ids)
        local = jnp.zeros(input_ids.shape[:1], jnp.int32)
        max_load, n_moe = jnp.int32(0), 0
        for i in range(c.layers):
            x, stats = nn.remat(Block)(c, i, name=f"h{i}")(x)
            local = local + jnp.sum(stats["local"], axis=-1)
            max_load = max_load + stats["max_load"]
            n_moe += not c.is_dense(i)
        x = RMSNorm(c.rms_norm_eps, name="norm_f")(x)
        logits = x @ _kernel(self, "head", (c.hidden_size, c.vocab_rows))
        absent = (n_moe * c.num_experts_per_tok * input_ids.shape[1]) - local
        return logits, {"local": local, "absent": absent,
                        "max_load": max_load}


class JoyAIFlash(Decoder):
    """The decoder of a ``JoyAIConfig``."""
