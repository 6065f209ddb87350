"""Plain reference of Kimi-Linear-48B-A3B (moonshotai, model_type
kimi_linear; the Kimi Linear report, arXiv:2510.26692, and the KDA layer of
flash-linear-attention) as one chip's share of a deployment in which 32 chips
share each layer, with its next-token loss: jax.numpy only, float32, experts
by mask, no kernels, nothing of the program. The delta rule is the per-token
recurrence (a scan over t, checkpointed every 64 tokens), not a chunked form.
Each block is recomputed in the backward pass (``jax.checkpoint``).

Block l: h = x + Mixer_l(N(x)); y = h + FFN_l(N(h)); N is an RMSNorm (eps
1e-5); FFN_0 is a SwiGLU of width 9,216, every other layer the expert layer
(1,024 wide) with its shared expert. After the last block N and the untied
head over the vocabulary's slice. Mixer_l is KDA where config.json's
linear_attn_config lists layer l + 1 (1-based) under kda_layers, MLA where it
lists it under full_attn_layers.

KDA (H = 32 heads, d_k = d_v = 128): q, k, v = SiLU(Conv4(x W_q)),
SiLU(Conv4(x W_k)), SiLU(Conv4(x W_v)), Conv4 a causal depthwise convolution
of width 4 over time on each of the 4,096 channels, no bias; per head
q^ = q / |q|_2, k^ = k / |k|_2 (eps 1e-6); beta_t = sigmoid(x_t W_b), one a
head; log alpha_t = -exp(A_log_h) softplus(x_t W_fa W_fb + dt_bias), one a
channel; S_t = (I - beta_t k^_t k^_t^T) Diag(alpha_t) S_{t-1} + beta_t k^_t
v_t^T, S_0 = 0, 128 x 128 a head; o_t = S_t^T q^_t / sqrt(128); output
W_o [RMSNorm_128(o_t) w_o * sigmoid(x_t W_ga W_gb)], the norm a head with one
128-wide weight.

MLA (mla_use_nope, q_lora_rank null): q = x W_q -> heads x (128 + 64);
[c_kv ; k_r] = x W_kva (512 + 64); c_kv = N(c_kv); [k_n ; v] = c_kv W_kvb ->
heads x (128 + 128); no rotation: the 64-wide parts as the products wrote
them, k_r shared by all heads; causal softmax(q k^T / sqrt(192)) v through
W_o.

Expert layer: s = sigmoid(x W_r) over all 256 experts; the 8 with the
largest s + b (b takes part in the selection only); g_i = 2.446 s_i / (sum
of the eight s_j), held here or not; the chip holds experts e0 .. e0+7 and
computes the sum over the selected-and-held of g_i SwiGLU_1024,i(x), plus the
shared expert. What the absent experts would add is left out.

Assumed, where config.json is silent: the selection bias (DeepSeek-V3's
class, which Kimi's lineage uses); that NoPE keeps the unrotated 64-wide
parts; the initialisers of A_log (log U(1, 16)), dt_bias (softplus^-1 of
log-uniform dt in [1e-3, 1e-1]) and the convolutions (U(-1/2, 1/2)), as
flash-linear-attention's KDA layer leaves them.

Loss of an example: mean next-token NLL over its labelled positions.

At d = 602M the harness's ``reference.follow`` would keep seven d-sized
vectors on the chip (15.7 GiB). Importing this file puts a lean ``follow``
in its place for this configuration's model (any other model still takes
reference.py's): the seed's flat weights go to the host, the weights stay a
tree, each client block's gradient is added into one donated buffer, and the
server rule returns the update, which is subtracted leaf by leaf; no more
than four d-sized vectors are alive on the chip at once. Its outputs are
reference.py's (tests/test_kimi_linear.py).
"""

from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np

import reference
from joyai_flash_ep32_ref import BlockwiseSketchServer
from reference import lowp

# importing joyai_flash_ep32_ref put its block-wise server in
# reference.SERVERS["sketch"]: at d = 602M reference.py's own stacks the
# rows' estimates and sorts for minutes, as at 414M
assert reference.SERVERS["sketch"] is BlockwiseSketchServer

CHECKPOINT_TOKENS = 64      # the recurrence's backward recomputes 64 tokens


class Model:
    def __init__(self, config: dict):
        c = config
        la = c["linear_attn_config"]
        self.C = int(c["hidden_size"])
        self.H = int(c["num_attention_heads"])
        self.rkv = int(c["kv_lora_rank"])
        self.dn = int(c["qk_nope_head_dim"])
        self.dr = int(c["qk_rope_head_dim"])
        self.dv = int(c["v_head_dim"])
        self.Hk = int(la["num_heads"])
        self.dk = int(la["head_dim"])
        self.W = int(la["short_conv_kernel_size"])
        self.kda_layers = [int(i) for i in la["kda_layers"]]
        self.F_dense = int(c["intermediate_size"])
        self.F = int(c["moe_intermediate_size"])
        self.L = int(c["num_hidden_layers"])
        self.L_dense = int(c["first_k_dense_replace"])
        self.E = int(c["published"]["num_experts"])
        self.E_held = int(c["num_experts"])
        self.e0 = int(c["expert_offset"])
        self.k = int(c["num_experts_per_token"])
        self.scale = float(c["routed_scaling_factor"])
        self.eps = float(c["rms_norm_eps"])
        self.V = int(c["vocab_size"])
        assert c["q_lora_rank"] is None and c["mla_use_nope"]
        C, H, Hk, dk = self.C, self.H, self.Hk, self.dk

        def swiglu(width):
            return {"gate": {"kernel": (C, width)},
                    "up": {"kernel": (C, width)},
                    "down": {"kernel": (width, C)}}

        def kda():
            return {"q": (C, Hk * dk), "k": (C, Hk * dk), "v": (C, Hk * dk),
                    "q_conv": (self.W, Hk * dk), "k_conv": (self.W, Hk * dk),
                    "v_conv": (self.W, Hk * dk),
                    "f_a": (C, dk), "f_b": (dk, Hk * dk),
                    "g_a": (C, dk), "g_b": (dk, Hk * dk), "b": (Hk, C),
                    "A_log": (Hk,), "dt_bias": (Hk * dk,), "o_norm": (dk,),
                    "o": (Hk * dk, C)}

        def mla():
            return {"q": (C, H * (self.dn + self.dr)),
                    "kv_a": (C, self.rkv + self.dr),
                    "kv_norm": {"scale": (self.rkv,)},
                    "kv_b": (self.rkv, H * (self.dn + self.dv)),
                    "o": (H * self.dv, C)}

        def block(i):
            out = {"attn_norm": {"scale": (C,)},
                   "attn": kda() if self.is_kda(i) else mla(),
                   "ffn_norm": {"scale": (C,)}}
            if i < self.L_dense:
                out["mlp"] = swiglu(self.F_dense)
            else:
                out["moe"] = {"router": (C, self.E), "router_bias": (self.E,),
                              "w_gate": (self.E_held, C, self.F),
                              "w_up": (self.E_held, C, self.F),
                              "w_down": (self.E_held, self.F, C),
                              "shared": swiglu(self.F)}
            return out

        self.shapes = {f"h{i}": block(i) for i in range(self.L)}
        self.shapes.update({"embed": {"embedding": (self.V, C)},
                            "norm_f": {"scale": (C,)},
                            "head": (C, self.V)})

    def is_kda(self, i: int) -> bool:
        """Layer i (0-based) is KDA by the published 1-based list."""
        return i + 1 in self.kda_layers

    def make(self, key):
        """The weights of a key: N(0, 0.02) matrices and embeddings, unit
        norm scales, N(0, 0.01) e_score_correction_bias, and the KDA layer's
        A_log, dt_bias and convolutions as the module docstring assumes."""
        paths, treedef = jax.tree_util.tree_flatten_with_path(
            self.shapes, is_leaf=lambda x: isinstance(x, tuple))
        out = []
        for i, (path, shape) in enumerate(paths):
            name, key_i = str(path[-1].key), jax.random.fold_in(key, i)
            if name in ("scale", "o_norm"):
                out.append(jnp.ones(shape, jnp.float32))
            elif name == "A_log":
                out.append(jnp.log(jax.random.uniform(
                    key_i, shape, jnp.float32, 1.0, 16.0)))
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    key_i, shape, jnp.float32, math.log(1e-3),
                    math.log(1e-1)))
                out.append(dt + jnp.log(-jnp.expm1(-dt)))
            elif name.endswith("_conv"):
                out.append(jax.random.uniform(key_i, shape, jnp.float32,
                                              -0.5, 0.5))
            else:
                std = 0.01 if name == "router_bias" else 0.02
                out.append(std * jax.random.normal(key_i, shape, jnp.float32))
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

    @staticmethod
    def delta_rule(q, k, v, log_alpha, beta):
        """The recurrence, a token at a time. q, k, log_alpha (N, T, H, dk),
        v (N, T, H, dv), beta (N, T, H) -> o (N, T, H, dv)."""
        N, T, H, dk = q.shape
        n = CHECKPOINT_TOKENS
        pad = -T % n

        def token(S, xs):                   # S (N, H, dk, dv)
            q_t, k_t, v_t, la_t, b_t = xs
            S = jnp.exp(la_t)[..., None] * S                 # Diag(alpha) S
            err = v_t - jnp.einsum("nhkv,nhk->nhv", S, k_t)
            S = S + (b_t[..., None] * k_t)[..., :, None] * err[..., None, :]
            return S, jnp.einsum("nhkv,nhk->nhv", S, q_t) * dk ** -0.5

        def stretch(S, xs):
            return jax.lax.scan(token, S, xs)

        def by_time(x):        # (N, T, ...) -> (T / n, n, N, ...); pads
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            x = jnp.moveaxis(x, 1, 0)
            return x.reshape((-1, n) + x.shape[1:])

        S0 = jnp.zeros((N, H, dk, v.shape[-1]), jnp.float32)
        _, o = jax.lax.scan(jax.checkpoint(stretch), S0,
                            tuple(map(by_time, (q, k, v, log_alpha, beta))))
        o = o.reshape((-1,) + o.shape[2:])[:T]
        return jnp.moveaxis(o, 0, 1)

    def kda(self, x, p, cast=None):
        """x (N, T, C), already normed -> (N, T, C)."""
        N, T, _ = x.shape
        H, dk = self.Hk, self.dk

        def conv(y, w):        # causal, depthwise, width W
            y = jnp.pad(y, ((0, 0), (self.W - 1, 0), (0, 0)))
            return sum(w[i] * y[:, i:i + T] for i in range(self.W))

        def unit(y):
            return y / jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)

        q, k, v = (jax.nn.silu(conv(self._mm(x, p[n], cast), p[n + "_conv"])
                               ).reshape(N, T, H, dk) for n in "qkv")
        q, k = unit(q), unit(k)
        beta = jax.nn.sigmoid(self._mm(x, p["b"].T, cast))
        f = self._mm(self._mm(x, p["f_a"], cast), p["f_b"], cast)
        log_alpha = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
            f + p["dt_bias"]).reshape(N, T, H, dk)
        o = self.delta_rule(lowp(q, cast), lowp(k, cast), lowp(v, cast),
                            log_alpha, beta)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + self.eps) * p["o_norm"]
        g = self._mm(self._mm(x, p["g_a"], cast), p["g_b"], cast)
        o = o * jax.nn.sigmoid(g.reshape(N, T, H, dk))
        return self._mm(o.reshape(N, T, H * dk), p["o"], cast)

    def mla(self, x, p, cast=None):
        """x (N, T, C), already normed -> (N, T, C)."""
        N, T, _ = x.shape
        H, dn, dr, dv = self.H, self.dn, self.dr, self.dv
        q = self._mm(x, p["q"], cast).reshape(N, T, H, dn + dr)
        kv = self._mm(x, p["kv_a"], cast)
        c_kv = self._norm(kv[..., :self.rkv], p["kv_norm"])
        k_r = kv[..., None, self.rkv:]                         # (N, T, 1, dr)
        kv = self._mm(c_kv, p["kv_b"], cast).reshape(N, T, H, dn + dv)
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
        xe = lowp(x, cast)
        h = jax.nn.silu(jnp.einsum("...c,ecf->e...f", xe,
                                   lowp(p["w_gate"][:held], cast))) \
            * jnp.einsum("...c,ecf->e...f", xe, lowp(p["w_up"][:held], cast))
        out = jnp.einsum("e...f,efc->e...c", lowp(h, cast),
                         lowp(p["w_down"][:held], cast))
        g_held = jnp.moveaxis(g[..., e0:e0 + held], -1, 0)[..., None]
        return self._swiglu(x, p["shared"], cast) + jnp.sum(g_held * out,
                                                            axis=0)

    def block(self, x, p, i, cast=None):
        mixer = self.kda if self.is_kda(i) else self.mla
        x = x + mixer(self._norm(x, p["attn_norm"]), p["attn"], cast)
        z = self._norm(x, p["ffn_norm"])
        return x + (self._swiglu(z, p["mlp"], cast) if i < self.L_dense
                    else self.experts(z, p["moe"], cast))

    def logits(self, params, ids, cast=None):
        """ids (N, T) -> (N, T, V)."""
        x = params["embed"]["embedding"][ids]
        for i in range(self.L):
            x = jax.checkpoint(self.block, static_argnums=(2, 3))(
                x, params[f"h{i}"], i, cast)
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

    # -- work of one round (round_mfu, kda_core_roofline) -------------------

    def kda_core_cost(self, batch_shapes: dict, chunk: int = 64):
        """(FLOPs, bytes) of the KDA cores of one round, whatever implements
        them. FLOPs: the chunkwise form's at C = ``chunk`` (a chunk and head:
        the pair matrices A and M, C^2 d_k each; the triangular solve for
        [W | U~], C^2 (d_k + d_v) / 2; U = U~ - W S, Q_G S and the state's
        update, C d_k d_v each; M U, C^2 d_v), 2 FLOPs a MAC, backward twice
        the forward; recomputation is time, not work. Bytes: the least, in
        float32: forward reads q, k, v, log alpha and beta and writes o;
        backward reads those and dO and writes their five gradients."""
        W, B, K, T = batch_shapes["input_ids"]
        dk, dv, C = self.dk, self.dk, chunk
        n_kda = sum(self.is_kda(i) for i in range(self.L))
        chunks = -(-T // C) * (W * B * K) * self.Hk * n_kda
        macs = (2 * C * C * dk + C * C * (dk + dv) / 2 + 3 * C * dk * dv
                + C * C * dv)
        per_token = 4.0 * ((3 * dk + dv + 1) + dv
                           + (3 * dk + dv + 1 + dv) + (3 * dk + dv + 1))
        return (3.0 * 2.0 * macs * chunks,
                per_token * T * (W * B * K) * self.Hk * n_kda)

    def train_flops(self, batch_shapes: dict) -> float:
        """Forward + backward model FLOPs of one round; recomputation is not
        counted. Per token and layer: KDA's eight products (q, k, v, the two
        low-rank pairs of the decay and the output gate, beta, W_o) and its
        core (``kda_core_cost``); MLA's four products and causal scores over
        all T positions (made for the whole T x T square, as in the other
        references' count); the dense SwiGLU or router + shared expert + the
        routed experts at the expected pairs held here (k * held / routed a
        token); the head once."""
        W, B, K, T = batch_shapes["input_ids"]
        C, H, Hk, dk = self.C, self.H, self.Hk, self.dk
        kda = (3 * C * Hk * dk + 2 * (C * dk + dk * Hk * dk) + C * Hk
               + Hk * dk * C)
        mla = (C * H * (self.dn + self.dr) + C * (self.rkv + self.dr)
               + self.rkv * H * (self.dn + self.dv) + H * self.dv * C
               + H * (self.dn + self.dr + self.dv) * T)
        pairs = self.k * self.E_held / self.E
        moe = C * self.E + 3 * C * self.F * (1 + pairs)
        n_kda = sum(self.is_kda(i) for i in range(self.L))
        macs = (n_kda * kda + (self.L - n_kda) * mla
                + self.L_dense * 3 * C * self.F_dense
                + (self.L - self.L_dense) * moe + C * self.V)
        return 3.0 * 2.0 * macs * (W * B * K * T) \
            + self.kda_core_cost(batch_shapes)[0]


# -------------------------------------------------------------------------
# the lean follow
# -------------------------------------------------------------------------

# where a test looks: called with a stage's name at every stage boundary
observe = None


def _stage(name):
    if observe is not None:
        observe(name)


def _sketch_update(server):
    """``BlockwiseSketchServer._rule`` without the weights: (update, u, v)
    of a table, the same operations in the same order."""

    @jax.jit
    def rule(table, u, v):
        u = table + server.rho * u
        v = v + u
        update = server._topk_mask(server._estimates(v), server.k)
        hit = reference.sketch(server.geom, update) != 0
        return update, jnp.where(hit, 0.0, u), jnp.where(hit, 0.0, v)

    def step(transmit, lr):
        update, server.u, server.v = rule(transmit, server.u, server.v)
        return update

    return step


def _dense_update(server):
    """``reference.DenseServer.step`` without the weights."""
    def step(transmit, lr):
        server.vel = transmit + server.rho * server.vel
        return server.vel

    return step


def lean_follow(model, traffic, seed, batches, steps_per_epoch,
                block_clients, cast=None):
    """``reference.follow`` (same arguments, same result) in at most four
    d-sized float32 vectors on the chip: the weights (a tree), the summed
    gradient, a block's gradient or the flat gradient, and the server
    rule's estimates and update, which are alive when the gradients are
    not."""
    from jax.flatten_util import ravel_pytree

    with jax.default_matmul_precision("highest"):
        params = model.init(seed)
        flat0, unravel = ravel_pytree(params)
        d = flat0.shape[0]
        w0 = np.asarray(flat0)              # the seed's weights, on the host
        del flat0
        server = reference.SERVERS[traffic["mode"]](d, traffic,
                                                    traffic["program_seed"])
        update_of = (_dense_update(server)
                     if isinstance(server, reference.DenseServer)
                     else _sketch_update(server))
        _stage("init")

        def block_loss(p, blk):
            sums, counts = jax.vmap(lambda b: model.loss_sum(p, b, cast))(blk)
            sums = sums.astype(jnp.float32) * blk["worker_mask"]
            return jnp.sum(sums), (sums, counts)

        grad_fn = jax.jit(jax.value_and_grad(block_loss, has_aux=True))
        add = jax.jit(lambda acc, g: jax.tree_util.tree_map(
            lambda a, b: a + b.astype(jnp.float32), acc, g),
            donate_argnums=0)
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
        finish = jax.jit(lambda g, p, total, scale: jax.tree_util.tree_map(
            lambda g, p: g / total + scale * p, g, p), donate_argnums=0)
        apply = jax.jit(lambda p, upd, lr: jax.tree_util.tree_map(
            lambda a, b: a - lr * b, p, unravel(upd)), donate_argnums=0)

        client_losses, first_leaves, grad_leaf = [], None, None
        for s, batch in enumerate(batches, start=1):
            wmask = np.asarray(batch["worker_mask"], np.float32)
            W = wmask.shape[0]
            total = float(np.sum(np.asarray(batch["mask"]) * wmask.reshape(
                (W,) + (1,) * (batch["mask"].ndim - 1))))
            g_sum, losses = zeros(params), []
            for lo in range(0, W, block_clients):
                blk = {k: jnp.asarray(np.asarray(v)[lo:lo + block_clients])
                       for k, v in batch.items() if k != "client_ids"}
                (_, (sums, counts)), g = grad_fn(params, blk)
                _stage("block gradient")
                g_sum = add(g_sum, g)
                del g
                losses.append(np.asarray(sums)
                              / np.maximum(np.asarray(counts), 1.0))
            losses = np.concatenate(losses)[wmask > 0].astype(np.float64)
            client_losses.append(losses)
            grad = finish(g_sum, params, max(total, 1.0),
                          traffic["weight_decay"] / traffic["num_workers"])
            del g_sum
            if s == 1:
                grad_leaf = reference.leaf_norms(
                    jax.tree_util.tree_leaves(grad))
            g_flat = ravel_pytree(grad)[0]
            _stage("flat gradient")
            transmit = server.transmit(g_flat)
            if s == 1:
                first_leaves = reference.leaf_norms(
                    server.leaves(transmit) if server.leaves
                    else jax.tree_util.tree_leaves(grad))
            del grad, g_flat
            lr = reference.lr_at(traffic["schedule"], s, steps_per_epoch)
            update = update_of(transmit, lr)
            _stage("update")
            params = apply(params, update, lr)
            del update, transmit
            _stage("step")
        change, off = [], 0
        for leaf in jax.tree_util.tree_leaves(params):
            n = leaf.size
            change.append(leaf - jnp.asarray(w0[off:off + n]).reshape(
                leaf.shape))
            change[-1] = float(jnp.linalg.norm(change[-1]))
            off += n
        change = np.asarray(change, np.float64)
    keep = grad_leaf >= 1e-3 * statistics.median(grad_leaf.tolist())
    return {"client_losses": client_losses, "first": first_leaves,
            "change": change, "keep": keep}


_plain_follow = reference.follow


def follow(model, *args, **kw):
    """reference.py's ``follow``, lean for this configuration's model."""
    if isinstance(model, Model):
        return lean_follow(model, *args, **kw)
    return _plain_follow(model, *args, **kw)


reference.follow = follow
