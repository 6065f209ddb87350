"""The gated delta rule with a decay per key channel (Kimi Delta Attention,
arXiv:2510.26692 section 3; the chunked form of flash-linear-attention's
``chunk_kda``), in XLA: the core of models/kimi_linear.py's KDA mixer, with
the causal depthwise convolution that comes before it.

Per head, with q_t, k_t in R^dk (normalised by the caller), v_t in R^dv,
``log_alpha_t`` in R^dk (<= 0) and beta_t in (0, 1), the state S_t in
R^{dk x dv} follows, from S_0 = 0,

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,
    o_t = scale * S_t^T q_t.

Chunked form (chunk C = 64). Inside a chunk G_t = sum_{s <= t} log alpha_s
per channel, kept in float32, and S_0 the state at the chunk's start.
Unrolled, S_t = Diag(e^{G_t}) S_0 + sum_{s <= t} Diag(e^{G_t - G_s}) k_s u_s^T
with the pseudo-values u_s, which solve the unit lower-triangular system

    (I + A) U = Diag(beta) (V - K_G S_0),
    A_ts = beta_t sum_c k_tc k_sc e^{G_tc - G_sc} for s < t, else 0

(rows of K_G: k_s e^{G_s}); so ``U = U~ - W S_0`` with ``[W | U~] = (I +
A)^{-1} Diag(beta) [K_G | V]`` (the WY / UT representation), found for all
chunks at once. Then a scan over the chunks, in float32:

    U = U~ - W S_0;   O = Q_G S_0 + M U;   S_C = Diag(e^{G_C}) S_0 + K_C^T U

with ``M_ts = sum_c q_tc k_sc e^{G_tc - G_sc}`` for s <= t, rows of Q_G
``q_t e^{G_t}``, rows of K_C ``k_s e^{G_C - G_s}``: every exponent there is
<= 0.

The pair matrices A and M never form ``e^{-G_s}``, which overflows float32
once a chunk's decay passes 88 nats (a channel forgetting at 10 nats a token
does in 9 tokens). Pairs in different sub-chunks of 16 are factored through
the first position r of the later sub-chunk, ``e^{G_t - G_r} e^{G_r - G_s}``,
both factors <= 1; pairs inside one sub-chunk are formed from the
differences directly. The backward is JAX's autodiff of this form; the
caller's block is recomputed in the backward pass, this with it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["kda_attention", "causal_conv", "CHUNK", "SUB_CHUNK"]

CHUNK = 64
SUB_CHUNK = 16


def causal_conv(x, w):
    """Causal depthwise convolution over time: ``y_t = sum_i w[i] x_{t - W +
    1 + i}`` per channel, no bias. x: (S, T, D), w: (W, D)."""
    W, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(xp[:, i:i + T] * w[i] for i in range(W))


def _pairs(a, b, g, strict, sub):
    """``R_ts = sum_c a_tc b_sc e^{g_tc - g_sc}`` over the pairs s < t
    (``strict``) or s <= t of every chunk, 0 elsewhere. a, b, g: (..., C,
    dk) -> (..., C, C)."""
    C, dk = a.shape[-2:]
    n = C // sub
    lead = a.shape[:-2]
    a4, b4, g4 = (x.reshape(lead + (n, sub, dk)) for x in (a, b, g))
    # pairs in different sub-chunks, through the later one's first position:
    # (n, sub, C) rows of sub-chunk I against every s before it
    ref = g4[..., :, :1, :]                                  # (.., n, 1, dk)
    a_r = a4 * jnp.exp(g4 - ref)
    b_r = b[..., None, :, :] * jnp.exp(
        jnp.minimum(ref - g[..., None, :, :], 0.0))          # (.., n, C, dk)
    off = jnp.einsum("...itc,...isc->...its", a_r, b_r)
    before = (jnp.arange(C) // sub)[None, :] < jnp.arange(n)[:, None]
    off = jnp.where(before[:, None, :], off, 0.0)
    # pairs inside one sub-chunk, from the differences
    t, s = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    keep = s < t if strict else s <= t                       # (sub, sub)
    diff = g4[..., :, None, :] - g4[..., None, :, :]      # (.., n, t, s, dk)
    e = jnp.where(keep[..., None], jnp.exp(
        jnp.where(keep[..., None], diff, 0.0)), 0.0)
    inner = jnp.sum(a4[..., :, None, :] * b4[..., None, :, :] * e, axis=-1)
    eye = jnp.eye(n, dtype=inner.dtype)                      # (I, J)
    inner = inner[..., :, :, None, :] * eye[:, None, :, None]
    return (off + inner.reshape(off.shape)).reshape(lead + (C, C))


def kda_attention(q, k, v, log_alpha, beta, chunk=CHUNK, sub=SUB_CHUNK):
    """The module docstring's recurrence in its chunked form, scale dk^-1/2.
    q, k, log_alpha: (S, T, H, dk); v: (S, T, H, dv); beta: (S, T, H) -> o
    (S, T, H, dv), float32. T is padded to a multiple of ``chunk`` with
    positions that neither decay nor write."""
    S, T, H, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, -(-T // sub) * sub)
    pad = -T % chunk
    N = (T + pad) // chunk

    def heads_first(x):       # (S, T, H, ...) -> (S, H, N, C, ...), float32
        x = jnp.pad(x.astype(jnp.float32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((S, N, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, log_alpha, beta = map(heads_first,
                                   (q, k, v, log_alpha, beta))
    g = jnp.cumsum(log_alpha, axis=-2)                       # (S, H, N, C, dk)
    bk = k * beta[..., None]
    A = _pairs(bk, k, g, True, sub)
    M = _pairs(q, k, g, False, sub)
    rhs = jnp.concatenate([bk * jnp.exp(g), v * beta[..., None]], axis=-1)
    eye = jnp.eye(chunk, dtype=A.dtype)
    wu = jax.lax.linalg.triangular_solve(
        A + eye, rhs, left_side=True, lower=True, unit_diagonal=True)
    w, u = wu[..., :dk], wu[..., dk:]
    q_g = q * jnp.exp(g)
    k_c = k * jnp.exp(g[..., -1:, :] - g)
    decay = jnp.exp(g[..., -1, :])                           # (S, H, N, dk)

    def step(state, xs):
        w, u, q_g, k_c, M, decay = xs
        u = u - w @ state
        o = q_g @ state + M @ u
        state = decay[..., None] * state + jnp.swapaxes(k_c, -1, -2) @ u
        return state, o

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (w, u, q_g, k_c, M, decay))
    _, o = jax.lax.scan(step, jnp.zeros((S, H, dk, dv), jnp.float32), xs)
    o = jnp.moveaxis(o, 0, 2).reshape(S, H, N * chunk, dv)[:, :, :T]
    return jnp.swapaxes(o, 1, 2) * dk ** -0.5
