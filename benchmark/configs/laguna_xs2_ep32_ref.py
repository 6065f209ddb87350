"""Plain reference of Laguna-XS.2's decoder (poolside, model_type laguna) as
one chip's share of a deployment in which 32 chips share each layer, with its
next-token loss: jax.numpy only, float32, experts by mask, no kernels,
nothing of the program. Each block is recomputed in the backward pass
(``jax.checkpoint``; the same numbers), and the attention of a block is taken
one key/value head at a time (``lax.map`` over the 8 groups, each under
``jax.checkpoint``): at 4,096 positions one group's scores are 8 heads x
4,096^2 x 4 B = 537 MB, all 64 heads' 4.3 GB, and the harness's ``follow``
keeps seven d-sized vectors (1.45 GiB each) on the chip beside them.

Block l: h = x + W_o (g * Core_l(q, k, v)); y = h + FFN_l(N(h)); N is an
RMSNorm (eps 1e-6). q = N(x) W_q -> H_l heads of 128 (48 on a full_attention
layer, 64 on a sliding_attention one); k, v = N(x) W_k, N(x) W_v -> 8 heads of
128; RoPE on q and k by the layer's kind; Core = softmax(q k^T / sqrt(128) +
mask) v, query head h reading key/value head h // (H_l / 8); the mask lets
query i see key j iff 0 <= i - j (< 512 on a sliding layer). After the last
block N and the untied head over the vocabulary's slice.

RoPE, sliding layers: pair i of a head's 64 pairs turns at theta^(-2i/128),
theta = 10,000. Full layers: YaRN on the first 64 columns (partial rotary
0.5), theta = 500,000: f_i = theta^(-2i/64), i = 0..31; c(beta) = 64
ln(4096 / (2 pi beta)) / (2 ln theta); lo = floor(c(64)) = 5, hi = ceil(c(1))
= 16; r_i = clip((i - lo) / (hi - lo), 0, 1); f'_i = (f_i / 64) r_i + f_i (1 -
r_i); cos and sin times 1.4158883083359672; columns 64..127 pass unturned.

FFN_0 = SwiGLU(8192). FFN_l, l >= 1: s = sigmoid(x W_r) over all 256 experts
in float32; the 8 with the largest s; g_i = 2.5 s_i / (sum of the eight s_j),
held here or not; the chip holds experts e0 .. e0+7 and computes the sum over
the selected-and-held of g_i SwiGLU_512,i(x), plus the shared SwiGLU(512).
What the absent experts would add is left out.

Assumed, where config.json is silent (each is one field of the program's
``LagunaConfig``):
1. the gate: ``gating: true`` is one sigmoid scalar a head, g = sigmoid(N(x)
   W_g), W_g 2048 x H_l, on head h's output before W_o (the sibling
   Laguna-S-2.1 spells ``gating: "per-head"``);
2. the router: sigmoid scores, top 8, gates renormalised over the selected,
   x 2.5, and no selection bias (the config names none);
3. no q/k normalisation, no gate on the shared expert, no biases;
4. RoPE pairs are half-split: (x_i, x_{i+n/2}) over the n rotary columns
   (the ``transformers`` default);
5. the window's edge: query i sees keys j with 0 <= i - j < 512, itself
   included.

Loss of an example: mean next-token NLL over its labelled positions.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import reference
from joyai_flash_ep32_ref import BlockwiseSketchServer
from reference import lowp

# importing joyai_flash_ep32_ref put its block-wise server in
# reference.SERVERS["sketch"]: at d = 390M reference.py's own stacks 23 GB of
# estimates and sorts for a minute, as at 414M
assert reference.SERVERS["sketch"] is BlockwiseSketchServer


def yarn_frequencies(rope: dict, n: int):
    """The ``n // 2`` pair frequencies of a YaRN-scaled RoPE, pair by pair."""
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def pair_at(beta):
        return n * math.log(original / (2 * math.pi * beta)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair_at(float(rope["beta_fast"]))), 0)
    hi = min(math.ceil(pair_at(float(rope["beta_slow"]))), n // 2 - 1)
    out = []
    for i in range(n // 2):
        f = theta ** (-2.0 * i / n)
        r = min(max((i - lo) / max(hi - lo, 1e-3), 0.0), 1.0)
        out.append((f / factor) * r + f * (1.0 - r))
    return out


class Model:
    def __init__(self, config: dict):
        c = config
        self.C = int(c["hidden_size"])
        self.d = int(c["head_dim"])
        self.Hkv = int(c["num_key_value_heads"])
        self.L = int(c["num_hidden_layers"])
        self.heads = [int(h) for h in
                      c["num_attention_heads_per_layer"][:self.L]]
        self.kinds = list(c["layer_types"][:self.L])
        self.dense = [t == "dense" for t in c["mlp_layer_types"][:self.L]]
        self.window = int(c["sliding_window"])
        self.F_dense = int(c["intermediate_size"])
        self.F = int(c["moe_intermediate_size"])
        self.F_shared = int(c["shared_expert_intermediate_size"])
        self.E = int(c["published"]["num_experts"])
        self.E_held = int(c["num_experts"])
        self.e0 = int(c["expert_offset"])
        self.k = int(c["num_experts_per_tok"])
        self.scale = float(c["moe_routed_scaling_factor"])
        self.eps = float(c["rms_norm_eps"])
        self.V = int(c["vocab_size"])
        self.rope = c["rope_parameters"]
        C, d = self.C, self.d

        def swiglu(width):
            return {"gate": {"kernel": (C, width)}, "up": {"kernel": (C, width)},
                    "down": {"kernel": (width, C)}}

        def block(i):
            H = self.heads[i]
            out = {
                "attn_norm": {"scale": (C,)},
                "attn": {"q": (C, H * d), "k": (C, self.Hkv * d),
                         "v": (C, self.Hkv * d), "gate": (C, H),
                         "o": (H * d, C)},
                "ffn_norm": {"scale": (C,)},
            }
            if self.dense[i]:
                out["mlp"] = swiglu(self.F_dense)
            else:
                out["moe"] = {"router": (C, self.E),
                              "w_gate": (self.E_held, C, self.F),
                              "w_up": (self.E_held, C, self.F),
                              "w_down": (self.E_held, self.F, C),
                              "shared": swiglu(self.F_shared)}
            return out

        self.shapes = {f"h{i}": block(i) for i in range(self.L)}
        self.shapes.update({"embed": {"embedding": (self.V, C)},
                            "norm_f": {"scale": (C,)},
                            "head": (C, self.V)})

    def make(self, key):
        """The weights of a key: N(0, 0.02) matrices and embeddings, unit
        norm scales."""
        paths, treedef = jax.tree_util.tree_flatten_with_path(
            self.shapes, is_leaf=lambda x: isinstance(x, tuple))
        out = []
        for i, (path, shape) in enumerate(paths):
            if str(path[-1].key) == "scale":
                out.append(jnp.ones(shape, jnp.float32))
            else:
                out.append(0.02 * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, out)

    def init(self, seed: int):
        return jax.jit(self.make)(jax.random.key(seed))

    # -- forward ------------------------------------------------------------

    def _norm(self, x, p):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                 + self.eps) * p["scale"]

    @staticmethod
    def _mm(x, w, cast):
        return lowp(x, cast) @ lowp(w, cast)

    def _swiglu(self, x, p, cast):
        h = jax.nn.silu(self._mm(x, p["gate"]["kernel"], cast)) \
            * self._mm(x, p["up"]["kernel"], cast)
        return self._mm(h, p["down"]["kernel"], cast)

    def frequencies(self, kind: str):
        """(pair frequencies, factor on cos and sin) of a kind of layer."""
        rope = self.rope[kind]
        n = int(self.d * float(rope["partial_rotary_factor"]))
        if rope["rope_type"] == "yarn":
            return yarn_frequencies(rope, n), float(rope["attention_factor"])
        theta = float(rope["rope_theta"])
        return [theta ** (-2.0 * i / n) for i in range(n // 2)], 1.0

    def _rope(self, x, kind):
        """x (N, T, H, d): the pair (x[i], x[i + n/2]) of the n rotary
        columns at position p turned by p * f_i; the columns past n pass."""
        freq, factor = self.frequencies(kind)
        half = len(freq)
        angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
            * jnp.asarray(freq, jnp.float32)[None, :]           # (T, n/2)
        cos = (jnp.cos(angle) * factor)[None, :, None, :]
        sin = (jnp.sin(angle) * factor)[None, :, None, :]
        a, b = x[..., :half], x[..., half:2 * half]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                                x[..., 2 * half:]], axis=-1)

    def core(self, q, k, v, kind, cast=None):
        """q (N, T, H, d), k and v (N, T, Hkv, d) -> (N, T, H, d): one
        key/value head and the H / Hkv query heads that read it at a time."""
        N, T, H, d = q.shape
        G = H // self.Hkv
        i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        sees = (i - j >= 0)
        if kind == "sliding_attention":
            sees = sees & (i - j < self.window)

        @jax.checkpoint
        def group(xs):
            qg, kg, vg = xs                     # (N, T, G, d), (N, T, d) x 2
            att = jnp.einsum("nqgd,nkd->ngqk", lowp(qg, cast),
                             lowp(kg, cast)) * d ** -0.5
            att = jnp.where(sees, att, jnp.finfo(att.dtype).min)
            att = jax.nn.softmax(att, axis=-1)
            return jnp.einsum("ngqk,nkd->nqgd", lowp(att, cast),
                              lowp(vg, cast))

        out = jax.lax.map(group, (
            jnp.moveaxis(q.reshape(N, T, self.Hkv, G, d), 2, 0),
            jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
        return jnp.moveaxis(out, 0, 2).reshape(N, T, H, d)

    def attention(self, x, p, layer, cast=None, window=True):
        """x (N, T, C), already normed -> (N, T, C). ``window=False``
        ignores a sliding layer's window (the tests' planted fault)."""
        N, T, _ = x.shape
        H, d = self.heads[layer], self.d
        kind = self.kinds[layer]
        q = self._mm(x, p["q"], cast).reshape(N, T, H, d)
        k = self._mm(x, p["k"], cast).reshape(N, T, self.Hkv, d)
        v = self._mm(x, p["v"], cast).reshape(N, T, self.Hkv, d)
        g = jax.nn.sigmoid(self._mm(x, p["gate"], cast))         # (N, T, H)
        out = self.core(self._rope(q, kind), self._rope(k, kind), v,
                        kind if window else "full_attention", cast)
        return self._mm((out * g[..., None]).reshape(N, T, H * d), p["o"],
                        cast)

    def experts(self, x, p, cast=None, e0=None, held=None):
        """x (..., C), already normed -> the share's expert layer output.
        ``e0`` / ``held`` name another share of the same routed weights (the
        tests sum the shares); by default the configuration's own."""
        e0 = self.e0 if e0 is None else e0
        held = self.E_held if held is None else held
        s = jax.nn.sigmoid(self._mm(x, p["router"], cast))     # (..., E)
        _, top = jax.lax.top_k(s, self.k)
        chosen = (top[..., None] == jnp.arange(self.E)).any(-2)  # (..., E)
        g = self.scale * s * chosen / jnp.sum(s * chosen, axis=-1,
                                              keepdims=True)
        # every held expert on every token; the gate's zeros leave out the
        # tokens that did not choose it
        xe = lowp(x, cast)
        h = jax.nn.silu(jnp.einsum("...c,ecf->e...f", xe,
                                   lowp(p["w_gate"][:held], cast))) \
            * jnp.einsum("...c,ecf->e...f", xe, lowp(p["w_up"][:held], cast))
        out = jnp.einsum("e...f,efc->e...c", lowp(h, cast),
                         lowp(p["w_down"][:held], cast))
        g_held = jnp.moveaxis(g[..., e0:e0 + held], -1, 0)[..., None]
        return self._swiglu(x, p["shared"], cast) + jnp.sum(g_held * out,
                                                            axis=0)

    def logits(self, params, ids, cast=None):
        """ids (N, T) -> (N, T, V)."""
        def block(x, p, layer):
            x = x + self.attention(self._norm(x, p["attn_norm"]), p["attn"],
                                   layer, cast)
            z = self._norm(x, p["ffn_norm"])
            return x + (self._swiglu(z, p["mlp"], cast) if self.dense[layer]
                        else self.experts(z, p["moe"], cast))

        x = params["embed"]["embedding"][ids]
        for i in range(self.L):
            x = jax.checkpoint(block, static_argnums=2)(x, params[f"h{i}"], i)
        return self._mm(self._norm(x, params["norm_f"]), params["head"], cast)

    def loss_sum(self, params, batch, cast=None):
        """One client's summed loss over its valid examples, and their
        count. batch: input_ids / lm_labels (B, K, T), mask (B,)."""
        ids = batch["input_ids"].astype(jnp.int32)
        B, K, T = ids.shape
        lg = self.logits(params, ids.reshape(B * K, T), cast)[:, :-1]
        labels = batch["lm_labels"].astype(jnp.int32).reshape(B * K, T)[:, 1:]
        valid = labels != -1
        picked = jnp.take_along_axis(
            lg, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
        nll = ((jax.nn.logsumexp(lg, axis=-1) - picked) * valid
               ).reshape(B, -1).sum(axis=-1)
        n_valid = valid.reshape(B, -1).sum(axis=-1)
        mask = batch["mask"].astype(jnp.float32)
        return jnp.sum(nll / jnp.maximum(n_valid, 1) * mask), jnp.sum(mask)

    # -- work of one round (for round_mfu, moe_expert_mfu, gqa_attn_mfu) ----

    def seen_pairs(self, kind: str, T: int) -> int:
        """The (query, key) pairs a layer's mask lets through in one
        sequence of T positions."""
        W = min(self.window, T)
        if kind == "sliding_attention":
            return W * (W + 1) // 2 + (T - W) * W
        return T * (T + 1) // 2

    def attention_core_flops(self, batch_shapes: dict) -> float:
        """Forward + backward model FLOPs of the attention cores of one
        round (q k^T and p v: 4 x 128 FLOPs a seen pair a head, backward
        twice the forward); recomputation is not counted, nor the masked
        halves of the tiles a kernel would multiply whole."""
        W, B, K, T = batch_shapes["input_ids"]
        pairs = sum(h * self.seen_pairs(kind, T)
                    for h, kind in zip(self.heads, self.kinds))
        return 3.0 * 4.0 * self.d * pairs * (W * B * K)

    def train_flops(self, batch_shapes: dict) -> float:
        """Forward + backward model FLOPs of one round; recomputation is not
        counted. Per token and layer: the attention's five projections, the
        dense SwiGLU or router + shared expert + the routed experts at the
        expected pairs held here (k * held / routed a token); the head once;
        and the attention cores over the pairs their masks let through."""
        W, B, K, T = batch_shapes["input_ids"]
        C, d = self.C, self.d
        macs = C * self.V
        for h, dense in zip(self.heads, self.dense):
            macs += C * h * d + 2 * C * self.Hkv * d + C * h + h * d * C
            macs += (3 * C * self.F_dense if dense else
                     C * self.E + 3 * C * self.F_shared
                     + 3 * C * self.F * self.k * self.E_held / self.E)
        return 3.0 * 2.0 * macs * (W * B * K * T) \
            + self.attention_core_flops(batch_shapes)
