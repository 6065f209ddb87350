"""Plain reference of JoyAI-LLM-Flash's decoder (DeepSeek-V3's layer
equations, arXiv:2412.19437 section 2.1) as one chip's share of a deployment
in which 32 chips share each layer, with its next-token loss: jax.numpy only,
float32, experts by mask, no kernels, nothing of the program. Each block is
recomputed in the backward pass (``jax.checkpoint``; the same numbers): at
d = 414M the harness's ``follow`` keeps seven d-sized vectors on the chip
and one client's activations have to fit beside them.

Block: h = x + MLA(N(x)); y = h + FFN(N(h)); N is an RMSNorm (eps 1e-6); FFN
is a SwiGLU of width 7168 in layer 0 and the expert layer after it. After the
last block N and the untied head over the vocabulary's slice.

MLA: c_q = N(x W_qa); q = c_q W_qb -> heads x (128 + 64);
[c_kv ; k_r] = x W_kva (512 + 64); c_kv = N(c_kv); [k_n ; v] = c_kv W_kvb ->
heads x (128 + 128); RoPE (theta 32e6, interleaved pairs, no scaling) on each
head's 64-wide q_r and on the one k_r all heads share; k = [k_n ; k_r],
q = [q_n ; q_r]; causal softmax(q k^T / sqrt(192)) v; heads concatenated
through W_o.

Expert layer: s = sigmoid(x W_r) over all 256 experts; the 8 with the largest
s + b (b takes part in the selection only); g_i = 2.5 s_i / (sum of the eight
s_j), held here or not; the chip holds experts e0 .. e0+7 and computes
sum over the selected-and-held of g_i SwiGLU_768,i(x), plus the shared
expert. What the absent experts would add is left out.

Loss of an example: mean next-token NLL over its labelled positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import reference
from reference import lowp


class BlockwiseSketchServer(reference.SketchServer):
    """``reference.SketchServer`` at d = 414M. The rule is the same (median
    over the rows of every coordinate's signed bucket, every entry whose
    magnitude reaches the k-th largest, the cells the update hits zeroed);
    two of its steps are taken another way, bit for bit the same answer
    (tests/test_joyai.py holds both to reference.py's on the CPU):

    - the estimates a block of chunks at a time: reference.py's
      ``estimates`` stacks all five rows' d estimates before the median,
      24.7 GB of temporaries on a 16 GiB chip (AOT for v5e, PR 28);
    - the k-th largest magnitude by bisection on its bits, 31 counts:
      reference.py's ``jnp.sort`` of 414M entries takes XLA:TPU 62 s to
      compile (AOT for v5e, PR 28) in every run's check.

    Importing this configuration's reference puts it in
    ``reference.SERVERS``: no other configuration imports this file, and
    this cell's ``correct`` is decided with it (PERF.md section 2)."""

    BLOCK = 36          # chunks a block: 23 blocks of the 828 at d = 414M

    def _estimates(self, table):
        g = self.geom
        n_blocks = -(-g["T"] // self.BLOCK)
        pad = n_blocks * self.BLOCK - g["T"]
        chunks = jnp.arange(n_blocks * self.BLOCK).reshape(n_blocks, -1)
        shifts = jnp.pad(g["shifts"], ((0, 0), (0, pad))).reshape(
            g["r"], n_blocks, -1)

        def one(row, key, t, m):
            return jnp.roll(row, -m) * reference._signs(g, t, key)

        def block(xs):
            ts, ms = xs                      # (BLOCK,), (r, BLOCK)
            rows = jax.vmap(jax.vmap(one, in_axes=(None, None, 0, 0)),
                            in_axes=(0, 0, None, 0))(table, g["keys"], ts, ms)
            return jnp.median(rows, axis=0)  # (BLOCK, c_pad)

        est = jax.lax.map(block, (chunks, jnp.moveaxis(shifts, 1, 0)))
        return est.reshape(-1)[:g["d"]]

    @staticmethod
    def _topk_mask(v, k: int):
        """``reference.topk_mask`` without the sort: a non-negative float's
        bits order as the float does, so the k-th largest magnitude is the
        largest bit pattern that at least k magnitudes reach."""
        mag = jnp.abs(v)
        bits = jax.lax.bitcast_convert_type(mag, jnp.int32)
        k = min(k, v.shape[0])

        def bit(i, thr):
            cand = thr | (jnp.int32(1) << (30 - i))
            return jnp.where(jnp.sum(bits >= cand) >= k, cand, thr)

        thr = jax.lax.fori_loop(0, 31, bit, jnp.int32(0))
        return jnp.where(bits >= thr, v, 0.0)

    def _rule(self, table, u, v, w, lr):
        u = table + self.rho * u
        v = v + u
        update = self._topk_mask(self._estimates(v), self.k)
        hit = reference.sketch(self.geom, update) != 0
        return (w - lr * update, jnp.where(hit, 0.0, u),
                jnp.where(hit, 0.0, v))


reference.SERVERS["sketch"] = BlockwiseSketchServer


class Model:
    def __init__(self, config: dict):
        c = config
        self.C = int(c["hidden_size"])
        self.H = int(c["num_attention_heads"])
        self.rq = int(c["q_lora_rank"])
        self.rkv = int(c["kv_lora_rank"])
        self.dn = int(c["qk_nope_head_dim"])
        self.dr = int(c["qk_rope_head_dim"])
        self.dv = int(c["v_head_dim"])
        self.F_dense = int(c["intermediate_size"])
        self.F = int(c["moe_intermediate_size"])
        self.L = int(c["num_hidden_layers"])
        self.L_dense = int(c["first_k_dense_replace"])
        self.E = int(c["published"]["n_routed_experts"])
        self.E_held = int(c["n_routed_experts"])
        self.e0 = int(c["expert_offset"])
        self.k = int(c["num_experts_per_tok"])
        self.scale = float(c["routed_scaling_factor"])
        self.theta = float(c["rope_theta"])
        self.eps = float(c["rms_norm_eps"])
        self.V = int(c["vocab_size"])
        C, H = self.C, self.H

        def swiglu(width):
            return {"gate": {"kernel": (C, width)}, "up": {"kernel": (C, width)},
                    "down": {"kernel": (width, C)}}

        def block(dense):
            out = {
                "attn_norm": {"scale": (C,)},
                "attn": {"q_a": (C, self.rq), "q_norm": {"scale": (self.rq,)},
                         "q_b": (self.rq, H * (self.dn + self.dr)),
                         "kv_a": (C, self.rkv + self.dr),
                         "kv_norm": {"scale": (self.rkv,)},
                         "kv_b": (self.rkv, H * (self.dn + self.dv)),
                         "o": (H * self.dv, C)},
                "ffn_norm": {"scale": (C,)},
            }
            if dense:
                out["mlp"] = swiglu(self.F_dense)
            else:
                out["moe"] = {"router": (C, self.E), "router_bias": (self.E,),
                              "w_gate": (self.E_held, C, self.F),
                              "w_up": (self.E_held, C, self.F),
                              "w_down": (self.E_held, self.F, C),
                              "shared": swiglu(self.F)}
            return out

        self.shapes = {f"h{i}": block(i < self.L_dense)
                       for i in range(self.L)}
        self.shapes.update({"embed": {"embedding": (self.V, C)},
                            "norm_f": {"scale": (C,)},
                            "head": (C, self.V)})

    def make(self, key):
        """The weights of a key: N(0, 0.02) matrices and embeddings, unit
        norm scales, N(0, 0.01) e_score_correction_bias."""
        paths, treedef = jax.tree_util.tree_flatten_with_path(
            self.shapes, is_leaf=lambda x: isinstance(x, tuple))
        out = []
        for i, (path, shape) in enumerate(paths):
            name = str(path[-1].key)
            if name == "scale":
                out.append(jnp.ones(shape, jnp.float32))
            else:
                std = 0.01 if name == "router_bias" else 0.02
                out.append(std * jax.random.normal(
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

    def _rope(self, x):
        """x (N, T, H, dr): the pair (x[2i], x[2i+1]) at position p turned
        by the angle p * theta^(-2i/dr)."""
        T, n = x.shape[1], x.shape[-1]
        out = []
        for i in range(n // 2):
            angle = jnp.arange(T, dtype=jnp.float32) * self.theta ** (-2.0 * i
                                                                      / n)
            cos, sin = (f(angle)[None, :, None] for f in (jnp.cos, jnp.sin))
            a, b = x[..., 2 * i], x[..., 2 * i + 1]
            out += [a * cos - b * sin, b * cos + a * sin]
        return jnp.stack(out, axis=-1)

    def attention(self, x, p, cast=None):
        """x (N, T, C), already normed -> (N, T, C)."""
        N, T, _ = x.shape
        H, dn, dr, dv = self.H, self.dn, self.dr, self.dv
        c_q = self._norm(self._mm(x, p["q_a"], cast), p["q_norm"])
        q = self._mm(c_q, p["q_b"], cast).reshape(N, T, H, dn + dr)
        kv = self._mm(x, p["kv_a"], cast)
        c_kv = self._norm(kv[..., :self.rkv], p["kv_norm"])
        k_r = self._rope(kv[..., None, self.rkv:])            # (N, T, 1, dr)
        kv = self._mm(c_kv, p["kv_b"], cast).reshape(N, T, H, dn + dv)
        q = jnp.concatenate([q[..., :dn], self._rope(q[..., dn:])], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (N, T, H, dr))], axis=-1)
        att = jnp.einsum("bqhd,bkhd->bhqk", lowp(q, cast), lowp(k, cast)) \
            * (dn + dr) ** -0.5
        att = jnp.where(jnp.tril(jnp.ones((T, T), bool)), att,
                        jnp.finfo(att.dtype).min)
        att = jax.nn.softmax(att, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", lowp(att, cast),
                         lowp(kv[..., dn:], cast)).reshape(N, T, H * dv)
        return self._mm(out, p["o"], cast)

    def experts(self, x, p, cast=None, e0=None, held=None):
        """x (..., C), already normed -> the share's expert layer output.
        ``e0`` / ``held`` name another share of the same routed weights (the
        tests sum the shares); by default the configuration's own."""
        e0 = self.e0 if e0 is None else e0
        held = self.E_held if held is None else held
        s = jax.nn.sigmoid(self._mm(x, p["router"], cast))     # (..., E)
        _, top = jax.lax.top_k(s + p["router_bias"], self.k)
        chosen = (top[..., None] == jnp.arange(self.E)).any(-2)  # (..., E)
        g = self.scale * s * chosen / jnp.sum(s * chosen, axis=-1,
                                              keepdims=True)
        # every held expert on every token, the gate's zeros leave out the
        # tokens that did not choose it: one product over the stacked
        # experts a projection (a loop over the experts compiles each apart:
        # 650 MiB of program, 190 s, AOT for v5e, PR 28)
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
        def block(x, p, dense):
            x = x + self.attention(self._norm(x, p["attn_norm"]), p["attn"],
                                   cast)
            z = self._norm(x, p["ffn_norm"])
            return x + (self._swiglu(z, p["mlp"], cast) if dense
                        else self.experts(z, p["moe"], cast))

        x = params["embed"]["embedding"][ids]
        for i in range(self.L):
            x = jax.checkpoint(block, static_argnums=2)(
                x, params[f"h{i}"], i < self.L_dense)
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

    # -- work of one round (for round_mfu and moe_expert_mfu) ---------------

    def expert_pair_flops(self) -> float:
        """Forward + backward FLOPs of one (token, expert) pair: three
        C x F products, 2 FLOPs a MAC, backward twice the forward."""
        return 3.0 * 2.0 * 3 * self.C * self.F

    def train_flops(self, batch_shapes: dict) -> float:
        """Forward + backward model FLOPs of one round; recomputation is not
        counted. Per token and layer: the MLA's five products and causal
        scores over all T positions (the products are made for the whole
        T x T square, as in the GPT-2 reference's count),
        the dense SwiGLU or router + shared expert + the routed experts at
        the expected pairs held here (k * held / routed a token); the head
        once."""
        W, B, K, T = batch_shapes["input_ids"]
        C, H = self.C, self.H
        attn = (C * self.rq + self.rq * H * (self.dn + self.dr)
                + C * (self.rkv + self.dr) + self.rkv * H * (self.dn + self.dv)
                + H * self.dv * C
                + H * (self.dn + self.dr + self.dv) * T)
        pairs = self.k * self.E_held / self.E
        moe = C * self.E + 3 * C * self.F * (1 + pairs)
        macs = (self.L * attn + self.L_dense * 3 * C * self.F_dense
                + (self.L - self.L_dense) * moe + C * self.V)
        return 3.0 * 2.0 * macs * (W * B * K * T)
