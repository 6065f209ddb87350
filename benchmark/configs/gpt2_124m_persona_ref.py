"""Plain reference of GPT-2 small with double heads (weight-tied LM head and a
one-logit multiple-choice head) and its PersonaChat loss: jax.numpy only,
float32, no dropout, nothing of the program.

Pre-LN blocks: x += proj(attn(LN1(x))); x += proj(gelu_tanh(fc(LN2(x)))).
Embedding = wte[ids] + wpe[pos] + wte[token_type_ids]. The LM loss of an
example is the mean next-token NLL over its labelled positions (both
candidates pooled); the MC loss is cross-entropy over the candidates' logits
read at mc_token_ids. loss = lm_coef * lm + mc_coef * mc.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import lowp


class Model:
    def __init__(self, config: dict):
        self.C = int(config["n_embd"])
        self.L = int(config["n_layer"])
        self.H = int(config["n_head"])
        self.P = int(config["n_positions"])
        self.T = int(config["n_ctx"])
        self.V = int(config.get("vocab_rows", config["vocab_size"]
                                + config["added_special_tokens"]))
        self.eps = float(config["layer_norm_epsilon"])
        self.lm_coef = float(config["lm_coef"])
        self.mc_coef = float(config["mc_coef"])
        C = self.C
        block = {
            "ln_1": {"bias": (C,), "scale": (C,)},
            "attn_qkv": {"bias": (3 * C,), "kernel": (C, 3 * C)},
            "attn_proj": {"bias": (C,), "kernel": (C, C)},
            "ln_2": {"bias": (C,), "scale": (C,)},
            "mlp_fc": {"bias": (4 * C,), "kernel": (C, 4 * C)},
            "mlp_proj": {"bias": (C,), "kernel": (4 * C, C)},
        }
        self.shapes = {f"h{i}": block for i in range(self.L)}
        self.shapes.update({
            "ln_f": {"bias": (C,), "scale": (C,)},
            "mc_head": {"bias": (1,), "kernel": (C, 1)},
            "wpe": {"embedding": (self.P, C)},
            "wte": {"embedding": (self.V, C)},
        })

    def make(self, key):
        """The weights of a key: N(0, 0.02) kernels and token embeddings,
        N(0, 0.01) positions, zero biases, unit layer-norm scales."""
        paths, treedef = jax.tree_util.tree_flatten_with_path(
            self.shapes, is_leaf=lambda x: isinstance(x, tuple))
        out = []
        for i, (path, shape) in enumerate(paths):
            name = str(path[-1].key)
            owner = str(path[-2].key)
            if name == "bias":
                out.append(jnp.zeros(shape, jnp.float32))
            elif name == "scale":
                out.append(jnp.ones(shape, jnp.float32))
            else:
                std = 0.01 if owner == "wpe" else 0.02
                out.append(std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, out)

    def init(self, seed: int):
        return jax.jit(self.make)(jax.random.key(seed))

    # -- forward ------------------------------------------------------------

    def _ln(self, x, p):
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + self.eps)
        return (y * p["scale"].astype(jnp.float32)
                + p["bias"].astype(jnp.float32)).astype(x.dtype)

    @staticmethod
    def _dense(x, p, cast=None):
        return lowp(x, cast) @ lowp(p["kernel"], cast) + p["bias"]

    def hidden(self, params, ids, types, cast=None):
        """ids, types: (N, T) -> final hidden states (N, T, C)."""
        N, T = ids.shape
        wte = params["wte"]["embedding"]
        x = wte[ids] + params["wpe"]["embedding"][jnp.arange(T)][None] \
            + wte[types]
        causal = jnp.tril(jnp.ones((T, T), bool))[None, None]
        dh = self.C // self.H
        for i in range(self.L):
            p = params[f"h{i}"]
            h = self._ln(x, p["ln_1"])
            q, k, v = jnp.split(self._dense(h, p["attn_qkv"], cast), 3,
                                axis=-1)
            q, k, v = (lowp(t.reshape(N, T, self.H, dh), cast)
                       for t in (q, k, v))
            att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (dh ** -0.5)
            att = jnp.where(causal, att, jnp.finfo(att.dtype).min)
            att = jax.nn.softmax(att, axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", lowp(att, cast),
                             v).reshape(N, T, self.C)
            x = x + self._dense(out, p["attn_proj"], cast)
            h = self._ln(x, p["ln_2"])
            h = jax.nn.gelu(self._dense(h, p["mlp_fc"], cast),
                            approximate=True)
            x = x + self._dense(h, p["mlp_proj"], cast)
        return self._ln(x, params["ln_f"])

    def loss_sum(self, params, batch, cast=None):
        """One client's summed loss over its valid examples, and their
        count. batch: input_ids/token_type_ids/lm_labels (B, K, T),
        mc_token_ids (B, K), mc_labels (B,), mask (B,)."""
        ids = batch["input_ids"].astype(jnp.int32)
        B, K, T = ids.shape
        x = self.hidden(params, ids.reshape(B * K, T),
                        batch["token_type_ids"].astype(jnp.int32)
                        .reshape(B * K, T), cast)
        logits = lowp(x, cast) @ lowp(params["wte"]["embedding"], cast).T
        labels = batch["lm_labels"].astype(jnp.int32).reshape(B * K, T)[:, 1:]
        valid = labels != -1
        lg = logits[:, :-1].astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(
            lg, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
        nll = ((lse - picked) * valid).reshape(B, -1).sum(axis=-1)
        n_valid = valid.reshape(B, -1).sum(axis=-1)
        lm = nll / jnp.maximum(n_valid, 1)
        mc_tok = batch["mc_token_ids"].astype(jnp.int32).reshape(B * K)
        cls = x[jnp.arange(B * K), mc_tok]
        mc_logits = self._dense(cls, params["mc_head"],
                                cast)[:, 0].reshape(B, K)
        logp = jax.nn.log_softmax(mc_logits.astype(jnp.float32), axis=-1)
        mc = -jnp.take_along_axis(
            logp, batch["mc_labels"].astype(jnp.int32)[:, None], axis=-1)[:, 0]
        mask = batch["mask"].astype(jnp.float32)
        loss = self.lm_coef * lm + self.mc_coef * mc
        return jnp.sum(loss * mask), jnp.sum(mask)

    # -- work of one round (for round_mfu) ----------------------------------

    def train_flops(self, batch_shapes: dict) -> float:
        """Forward + backward model FLOPs of one round (copy of
        bench.gpt2_train_flops_per_token, times the round's token slots):
        per token 12 C^2 MACs a layer, 2 T C for attention, C V for the tied
        LM head; 2 FLOPs a MAC; backward twice the forward."""
        W, B, K, T = batch_shapes["input_ids"]
        C = self.C
        macs = self.L * 12 * C * C + self.L * 2 * T * C + C * self.V
        return 3.0 * 2.0 * macs * (W * B * K * T)
