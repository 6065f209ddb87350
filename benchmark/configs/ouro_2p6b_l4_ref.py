"""Plain reference of Ouro-2.6B (ByteDance, model_type ouro; "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741) cut in depth, with
its expected-exit training loss: jax.numpy only, float32, no kernels, nothing
of the program. The stack of L layers is run R = total_ut_steps = 4 times on
the same weights, written out as a Python loop over passes and layers. Each
block application is recomputed in the backward pass (``jax.checkpoint``;
the same numbers), and so is each pass's norm, head, NLL and gate, so that
one pass's logits (2 x 1,024 x 49,152 x 4 B = 403 MB a client) are the most
that lives beside the seven d-sized vectors the harness's ``follow`` keeps on
the chip.

x_0 = E[ids]. Pass t = 1..R: u = x_{t-1}; for layer l = 0..L-1:
a = u + N2_l(Attn_l(N1_l(u))); u = a + N4_l(MLP_l(N3_l(a))); then
x_t = N_f(u); logits_t = x_t W_head; lambda_t = sigmoid(x_t . w_g + b_g).
N* are RMSNorms (eps 1e-6); the same N*_l, Attn_l, MLP_l, N_f, W_head, w_g,
b_g in every pass. Attn: q, k, v = z W_q, z W_k, z W_v (16 heads of 128
each); RoPE on all 128 columns of q and k, pair (x_i, x_{i+64}) of position p
turned by p * theta^(-2i/128), theta = 1e6; softmax(q k^T / sqrt(128) +
causal) v; W_o. MLP(z) = (silu(z W_gate) * z W_up) W_down, width 5632.

Exit distribution of a position: p_1 = lambda_1; p_t = lambda_t prod_{j<t}
(1 - lambda_j), t = 2..R-1; p_R = prod_{j<R} (1 - lambda_j). Loss of a
labelled position with label y: sum_t p_t l_t - beta H(p), l_t =
NLL(logits_t, y), H(p) = -sum_t p_t ln p_t. Loss of an example: the mean over
its labelled positions.

Assumed, where config.json is silent (each is one field of the program's
``OuroConfig``):
1. sandwich normalisation: four RMSNorms a layer (input_layernorm,
   input_layernorm_2 on the attention's output, post_attention_layernorm,
   post_attention_layernorm_2 on the MLP's output), as the paper states and
   the released modelling file names them;
2. the final norm inside the recurrence: x_t = N_f(u) is what pass t + 1
   starts from, not only what the head reads;
3. no biases in W_q, W_k, W_v, W_o or the MLP (the config names none); the
   gate is Linear(2048 -> 1) with a bias, one for all passes, read on x_t;
4. RoPE pairs half-split (``transformers``' rotate_half), the whole head
   turned;
5. the objective's constant beta = 0.05 (the paper lowers it from 0.1 during
   pre-training; config.json holds no loss constants). Departure: the
   paper's second stage (the gate alone, on a frozen model) is not run;
6. init N(0, 0.02), norms 1, the gate's bias 0; data and tokenizer as
   laguna_xs2_ep32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import reference
from joyai_flash_ep32_ref import BlockwiseSketchServer
from reference import lowp

# importing joyai_flash_ep32_ref put its block-wise server in
# reference.SERVERS["sketch"]: at d = 407M reference.py's own stacks 24 GB of
# estimates and sorts for a minute, as at 414M
assert reference.SERVERS["sketch"] is BlockwiseSketchServer


class Model:
    def __init__(self, config: dict):
        c = config
        self.C = int(c["hidden_size"])
        self.H = int(c["num_attention_heads"])
        self.Hkv = int(c["num_key_value_heads"])
        self.d = int(c["head_dim"])
        self.F = int(c["intermediate_size"])
        self.L = int(c["num_hidden_layers"])
        self.R = int(c["total_ut_steps"])
        self.V = int(c["vocab_size"])
        self.eps = float(c["rms_norm_eps"])
        self.theta = float(c["rope_theta"])
        self.beta = float(c["exit_entropy_coef"])
        # one query head a key/value head, as published; the loops below
        # are written for that
        assert self.H == self.Hkv
        C, F, w = self.C, self.F, self.H * self.d

        def block():
            return {
                "attn_norm": {"scale": (C,)},
                "attn": {"q": (C, w), "k": (C, w), "v": (C, w), "o": (w, C)},
                "attn_post_norm": {"scale": (C,)},
                "ffn_norm": {"scale": (C,)},
                "mlp": {"gate": {"kernel": (C, F)}, "up": {"kernel": (C, F)},
                        "down": {"kernel": (F, C)}},
                "ffn_post_norm": {"scale": (C,)},
            }

        # every block ONCE: a pass reads the same L blocks
        self.shapes = {f"h{i}": block() for i in range(self.L)}
        self.shapes.update({
            "embed": {"embedding": (self.V, C)},
            "exit": {"norm_f": {"scale": (C,)}, "head": (C, self.V),
                     "gate": (C, 1), "gate_bias": (1,)}})

    def make(self, key):
        """The weights of a key: N(0, 0.02) matrices, embeddings and gate,
        unit norm scales, the gate's bias 0."""
        paths, treedef = jax.tree_util.tree_flatten_with_path(
            self.shapes, is_leaf=lambda x: isinstance(x, tuple))
        out = []
        for i, (path, shape) in enumerate(paths):
            name = str(path[-1].key)
            if name == "scale":
                out.append(jnp.ones(shape, jnp.float32))
            elif name == "gate_bias":
                out.append(jnp.zeros(shape, jnp.float32))
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

    def _mlp(self, x, p, cast):
        h = jax.nn.silu(self._mm(x, p["gate"]["kernel"], cast)) \
            * self._mm(x, p["up"]["kernel"], cast)
        return self._mm(h, p["down"]["kernel"], cast)

    def _rope(self, x):
        """x (N, T, H, d): the pair (x[i], x[i + d/2]) at position p turned
        by p * theta^(-2i/d)."""
        half = self.d // 2
        freq = jnp.asarray([self.theta ** (-2.0 * i / self.d)
                            for i in range(half)], jnp.float32)
        angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
        cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def attention(self, x, p, cast=None):
        """x (N, T, C), already normed -> (N, T, C)."""
        N, T, _ = x.shape
        H, d = self.H, self.d
        q, k, v = (self._mm(x, p[n], cast).reshape(N, T, H, d)
                   for n in ("q", "k", "v"))
        q, k = self._rope(q), self._rope(k)
        att = jnp.einsum("nqhd,nkhd->nhqk", lowp(q, cast),
                         lowp(k, cast)) * d ** -0.5
        i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        att = jax.nn.softmax(
            jnp.where(j <= i, att, jnp.finfo(att.dtype).min), axis=-1)
        out = jnp.einsum("nhqk,nkhd->nqhd", lowp(att, cast), lowp(v, cast))
        return self._mm(out.reshape(N, T, H * d), p["o"], cast)

    def block(self, u, p, cast=None, sandwich=True):
        """One layer. ``sandwich=False`` leaves out the norms on the
        attention's and the MLP's output (the tests' planted fault)."""
        def post(y, name):
            return self._norm(y, p[name]) if sandwich else y

        a = u + post(self.attention(self._norm(u, p["attn_norm"]),
                                    p["attn"], cast), "attn_post_norm")
        return a + post(self._mlp(self._norm(a, p["ffn_norm"]), p["mlp"],
                                  cast), "ffn_post_norm")

    def read(self, x, p, labels, cast=None):
        """Of a pass's x_t (N, T, C): the next-token NLL against ``labels``
        (N, T - 1), lambda_t there, and the largest logit's index."""
        z = x[:, :-1]
        logits = self._mm(z, p["head"], cast)
        picked = jnp.take_along_axis(
            logits, jnp.where(labels != -1, labels, 0)[..., None],
            axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        lam = jax.nn.sigmoid(self._mm(z, p["gate"], cast)[..., 0]
                             + p["gate_bias"])
        return nll, lam, jnp.argmax(logits, axis=-1)

    def passes(self, params, ids, labels, cast=None, steps=None,
               sandwich=True, norm_between=True):
        """ids (N, T), labels (N, T - 1) -> a list over the passes of (NLL,
        lambda, argmax), each (N, T - 1). ``steps`` runs another count of
        passes than the configuration's; ``norm_between=False`` lets the
        head and the gate read N_f(u) but the next pass start from u (the
        tests' planted fault)."""
        x = params["embed"]["embedding"][ids]
        out = []
        for _ in range(self.R if steps is None else steps):
            u = x
            for i in range(self.L):
                u = jax.checkpoint(self.block, static_argnums=(2, 3))(
                    u, params[f"h{i}"], cast, sandwich)
            x_t = self._norm(u, params["exit"]["norm_f"])
            out.append(jax.checkpoint(self.read, static_argnums=3)(
                x_t, params["exit"], labels, cast))
            x = x_t if norm_between else u
        return out

    def exit_distribution(self, lams):
        """p_1 .. p_R of the passes' lambdas (the last one's is not read:
        p_R takes the remainder)."""
        p, stay = [], 1.0
        for lam in lams[:-1]:
            p.append(lam * stay)
            stay = stay * (1.0 - lam)
        return p + [stay]

    def position_loss(self, nlls, lams, beta=None):
        """sum_t p_t l_t - beta H(p) of every position."""
        beta = self.beta if beta is None else beta
        p = self.exit_distribution(lams)
        expected = sum(pt * lt for pt, lt in zip(p, nlls))
        entropy = -sum(pt * jnp.log(pt) for pt in p)
        return expected - beta * entropy

    def loss_sum(self, params, batch, cast=None, **variant):
        """One client's summed loss over its valid examples, and their
        count. batch: input_ids / lm_labels (B, K, T), mask (B,)."""
        ids = batch["input_ids"].astype(jnp.int32)
        B, K, T = ids.shape
        labels = batch["lm_labels"].astype(jnp.int32).reshape(B * K, T)[:, 1:]
        valid = labels != -1
        outs = self.passes(params, ids.reshape(B * K, T), labels, cast,
                           **variant)
        tok = self.position_loss([o[0] for o in outs], [o[1] for o in outs])
        loss = (tok * valid).reshape(B, -1).sum(axis=-1)
        n_valid = valid.reshape(B, -1).sum(axis=-1)
        mask = batch["mask"].astype(jnp.float32)
        return jnp.sum(loss / jnp.maximum(n_valid, 1) * mask), jnp.sum(mask)

    # -- work of one round (round_mfu, loop_body_mfu, gqa_attn_mfu) --------

    def attention_core_flops(self, batch_shapes: dict) -> float:
        """Forward + backward model FLOPs of the attention cores of one
        round: q k^T and p v are 4 x 128 FLOPs a seen (query, key) pair a
        head, T (T + 1) / 2 pairs a sequence a block application, R x L
        block applications, backward twice the forward; recomputation is not
        counted, nor the masked halves of the tiles a kernel multiplies
        whole."""
        W, B, K, T = batch_shapes["input_ids"]
        pairs = self.H * (T * (T + 1) // 2) * self.R * self.L
        return 3.0 * 4.0 * self.d * pairs * (W * B * K)

    def loop_body_flops(self, batch_shapes: dict) -> float:
        """... of the R x L block applications alone: per token and
        application the four attention projections and the SwiGLU's three
        products, and the cores."""
        W, B, K, T = batch_shapes["input_ids"]
        macs = 4 * self.C * self.H * self.d + 3 * self.C * self.F
        return 3.0 * 2.0 * macs * self.R * self.L * (W * B * K * T) \
            + self.attention_core_flops(batch_shapes)

    def train_flops(self, batch_shapes: dict) -> float:
        """Forward + backward model FLOPs of one round; recomputation is not
        counted: the block applications, and the head after every pass on
        the T - 1 positions that predict a next token (the gate's 2,048
        products a position are left out)."""
        W, B, K, T = batch_shapes["input_ids"]
        head = 3.0 * 2.0 * self.C * self.V * self.R * (W * B * K * (T - 1))
        return self.loop_body_flops(batch_shapes) + head
