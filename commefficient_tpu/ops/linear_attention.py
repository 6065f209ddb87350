"""The gated delta rule with a decay per key channel (Kimi Delta Attention,
arXiv:2510.26692 section 3; the chunked form of flash-linear-attention's
``chunk_kda``): the core of models/kimi_linear.py's KDA mixer, with the
causal depthwise convolution that comes before it.

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
chunks at once: the chunk-local phase. Then a scan over the chunks, in
float32:

    U = U~ - W S_0;   O = Q_G S_0 + M U;   S_C = Diag(e^{G_C}) S_0 + K_C^T U

with ``M_ts = sum_c q_tc k_sc e^{G_tc - G_sc}`` for s <= t, rows of Q_G
``q_t e^{G_t}``, rows of K_C ``k_s e^{G_C - G_s}``: every exponent there is
<= 0.

The pair matrices A and M never form ``e^{-G_s}``, which overflows float32
once a chunk's decay passes 88 nats (a channel forgetting at 10 nats a token
does in 9 tokens). Pairs in different sub-chunks of 16 are factored through
the first position r of the later sub-chunk, ``e^{G_t - G_r} e^{G_r - G_s}``,
both factors <= 1; pairs inside one sub-chunk are formed from the
differences directly.

Two implementations of the chunk-local phase, one scan after them:

- XLA (``_pairs`` and ``lax.linalg.triangular_solve`` on (S, H, N, C, .)
  arrays; JAX's autodiff backward). It runs everywhere, and is the kernels'
  second reference in the tests.
- Two Pallas kernels under one ``jax.custom_vjp``, ``(q, k, v, G, beta) ->
  (W, U~, M)``. A grid step takes every chunk of one (sequence, head): a
  (1, T, d) block at the head's lane block of the (S, T, H*d) arrays the
  projections wrote, no transpose. Per chunk, in VMEM: the pair matrices
  (cross pairs as bfloat16-multiplicand products, the pairs inside a
  sub-chunk from the float32 differences, one partner row at a time), the
  inverse of I + A by products alone (each 16 x 16 diagonal block by
  forward substitution, then merged 16 -> 32 -> 64 by ``[[X, 0], [Y, Z]]^-1
  = [[X^-1, 0], [-Z^-1 Y X^-1, Z^-1]]``, float32), and ``[W | U~]``
  (float32). W, U~ and M are written in the (N, S, H, C, .) order the scan
  reads, and T = (I + A)^-1 beside them for the backward (a chunk's T is
  half the size of its k). The backward kernel reads T, ``X = [W | U~]``
  and carries ``dX`` and ``dM`` back: ``d rhs = T^T dX``, ``dA = -(T^T dX)
  X^T`` below the diagonal, then dA and dM through the same factored pairs
  to dq, dk, dv, dG and d beta. G (a cumulative sum), the rows Q_G and K_C
  and the scan stay in XLA, and their backward is autodiff's.

Precision is the chip's: the pair products' multiplicands are rounded to
bfloat16 where the XLA form's float32 ``einsum`` is at the default matmul
precision, the products of the inverse are float32 as the solve (and its
gradient's ``HIGHEST`` products) are, accumulation is float32.
``kda_core_path`` takes the kernels where it can see that this holds and
that they take the shape (TPU backend, no matmul precision set, dk and dv
multiples of 128, chunks of 64 in sub-chunks of 16); everything else runs
the XLA form. Which one a call was traced on is counted in ``PATH_CALLS``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from commefficient_tpu.ops.attention import _rounds_to_bfloat16
from commefficient_tpu.utils import is_tpu_backend

__all__ = ["kda_attention", "kda_attention_xla", "kda_attention_pallas",
           "kda_core_path", "causal_conv", "CHUNK", "SUB_CHUNK", "LANES",
           "PATH_CALLS"]

CHUNK = 64
SUB_CHUNK = 16
LANES = 128         # dk and dv of the kernel path are multiples of this
# calls of ``kda_attention`` traced on each path, this process
PATH_CALLS = {"pallas": 0, "xla": 0}

_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


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


def _scan(w, u, q_g, k_c, M, decay):
    """The scan over chunks; every operand (N, S, H, C, .), decay (N, S, H,
    dk) -> o (N, S, H, C, dv), float32."""
    S, H, _, dk = q_g.shape[1:]
    dv = u.shape[-1]

    def step(state, xs):
        w, u, q_g, k_c, M, decay = xs
        u = u - w @ state
        o = q_g @ state + M @ u
        state = decay[..., None] * state + jnp.swapaxes(k_c, -1, -2) @ u
        return state, o

    return jax.lax.scan(step, jnp.zeros((S, H, dk, dv), jnp.float32),
                        (w, u, q_g, k_c, M, decay))[1]


def kda_attention_xla(q, k, v, log_alpha, beta, chunk=CHUNK, sub=SUB_CHUNK):
    """The chunked core in XLA (shapes as ``kda_attention``'s)."""
    S, T, H, dk = q.shape
    dv = v.shape[-1]
    chunk = _chunk_of(T, chunk, sub)
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
    o = _scan(*(jnp.moveaxis(x, 2, 0)
                for x in (w, u, q_g, k_c, M, decay)))
    o = jnp.moveaxis(o, 0, 2).reshape(S, H, N * chunk, dv)[:, :, :T]
    return jnp.swapaxes(o, 1, 2) * dk ** -0.5


# -- the chunk-local phase in VMEM --------------------------------------------
# Inside the kernels a chunk is (C, d) float32 values; ``sub`` is a power of
# two, so a row's sub-chunk starts at ``row & -sub``.

def _dot(a, b, dims, mul):
    """A pair product: multiplicands in ``mul``, float32 accumulation."""
    return lax.dot_general(a.astype(mul), b.astype(mul), dims,
                           preferred_element_type=jnp.float32)


def _dot32(a, b, dims):
    """A product of the inverse's: float32, as the solve it replaces."""
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _partner(x, j, sub):
    """Row j of each sub-chunk of x (C, w), repeated over its sub-chunk."""
    w = x.shape[1]
    return jnp.concatenate([jnp.broadcast_to(x[i + j:i + j + 1], (sub, w))
                            for i in range(0, x.shape[0], sub)], axis=0)


def _offsets(C, sub):
    """(C, C): a column's offset from the first position of its row's
    sub-chunk (< 0 in an earlier sub-chunk, >= sub in a later one)."""
    return _iota((C, C), 1) - (_iota((C, C), 0) & -sub)


def _column(x, j, off):
    """``x[t, start(t) + j]`` a row t of x (C, C): column j of the row's own
    sub-chunk, (C, 1)."""
    return jnp.sum(jnp.where(off == j, x, 0.0), axis=1, keepdims=True)


def _lane(x, h):
    """Column h of x (C, H), (C, 1)."""
    return jnp.sum(jnp.where(_iota(x.shape, 1) == h, x, 0.0), axis=1,
                   keepdims=True)


def _chunk_pairs(lhs, k, g, sub, mul):
    """``R_ts = sum_c a_tc k_sc e^{g_tc - g_sc}`` for each a of ``lhs``,
    over the chunk's pairs s <= t (C, C), 0 above the diagonal."""
    C, w = k.shape
    rows, cols = _iota((C, C), 0), _iota((C, C), 1)
    off = _offsets(C, sub)
    # inside a sub-chunk: partner j's column, one row of differences a time
    inner = [jnp.zeros((C, C), jnp.float32) for _ in lhs]
    for j in range(sub):
        x = _partner(k, j, sub) * jnp.exp(
            jnp.minimum(g - _partner(g, j, sub), 0.0))
        inner = [jnp.where(off == j, jnp.sum(a * x, axis=1, keepdims=True),
                           r) for a, r in zip(lhs, inner)]
    # across sub-chunks, through the later one's first position r
    cross = [[jnp.zeros((sub, C), jnp.float32)] for _ in lhs]
    for r in range(sub, C, sub):
        ga = jnp.exp(g[r:r + sub] - g[r:r + 1])
        kb = k * jnp.exp(jnp.minimum(g[r:r + 1] - g, 0.0))
        p = _dot(jnp.concatenate([a[r:r + sub] * ga for a in lhs]), kb, _NT,
                 mul)
        for i, c in enumerate(cross):
            c.append(p[i * sub:(i + 1) * sub])
    return [jnp.where(off < 0, jnp.concatenate(c),
                      jnp.where(rows >= cols, r, 0.0))
            for c, r in zip(cross, inner)]


def _inverse(A, sub):
    """``(I + A)^-1`` of a strictly lower-triangular A (C, C), float32: each
    diagonal sub-chunk block by forward substitution (column j of every
    block at once), then the blocks merged two at a time."""
    C = A.shape[0]
    rows, cols = _iota((C, C), 0), _iota((C, C), 1)
    off = _offsets(C, sub)
    X = (rows == cols).astype(jnp.float32)
    for j in range(sub - 1):
        X = X - _column(A, j, off) * _partner(X, j, sub)
    w = sub
    while w < C:
        # [[X, 0], [Y, Z]]^-1 = [[X^-1, 0], [-Z^-1 Y X^-1, Z^-1]]: of every
        # pair of w-blocks the second's rows change, by products of them
        c = _iota((w, C), 1)
        pairs = range(0, C, 2 * w)
        Y = jnp.concatenate([jnp.where((c >= p) & (c < p + w),
                                       A[p + w:p + 2 * w], 0.0)
                             for p in pairs])
        YX = _dot32(Y, X, _NN)
        zero = jnp.zeros((w, C), jnp.float32)
        ZYX = _dot32(
            jnp.concatenate([X[p + w:p + 2 * w] for p in pairs]),
            jnp.concatenate([x for i in range(len(pairs))
                             for x in (zero, YX[i * w:(i + 1) * w])]), _NN)
        X = jnp.concatenate([x for i, p in enumerate(pairs) for x in (
            X[p:p + w], X[p + w:p + 2 * w] - ZYX[i * w:(i + 1) * w])])
        w *= 2
    return X


def _chunk_fwd(q, k, v, g, b, sub, mul):
    """W, U~, M and T = (I + A)^-1 of one chunk."""
    C = k.shape[0]
    a_hat, M = _chunk_pairs((k, q), k, g, sub, mul)
    A = jnp.where(_iota((C, C), 0) > _iota((C, C), 1), b * a_hat, 0.0)
    T = _inverse(A, sub)
    return (_dot32(T, b * k * jnp.exp(g), _NN), _dot32(T, b * v, _NN), M,
            T)


def _chunk_pairs_bwd(lhs, grads, k, g, sub, mul):
    """The gradients of ``_chunk_pairs`` given ``grads`` of its matrices
    (0 where they are): ``da_ts = sum_s G_ts k_s e^{g_t - g_s}`` for each a
    of ``lhs``, and ``dk_s = sum_a sum_t G_ts a_t e^{g_t - g_s}``."""
    C, w = k.shape
    cols = _iota((sub, C), 1)
    # across sub-chunks
    da = [[jnp.zeros((sub, w), jnp.float32)] for _ in lhs]
    dk = jnp.zeros((C, w), jnp.float32)
    for r in range(sub, C, sub):
        ga = jnp.exp(g[r:r + sub] - g[r:r + 1])
        eb = jnp.exp(jnp.minimum(g[r:r + 1] - g, 0.0))
        G = jnp.concatenate([jnp.where(cols < r, x[r:r + sub], 0.0)
                             for x in grads])
        p = _dot(G, k * eb, _NN, mul)
        for i, d in enumerate(da):
            d.append(ga * p[i * sub:(i + 1) * sub])
        dk = dk + eb * _dot(
            G, jnp.concatenate([a[r:r + sub] * ga for a in lhs]), _TN, mul)
    da = [jnp.concatenate(d) for d in da]
    # inside a sub-chunk, one partner row a time
    own = _iota((C, w), 0) & (sub - 1)
    off = _offsets(C, sub)
    for j in range(sub):
        e = jnp.exp(jnp.minimum(g - _partner(g, j, sub), 0.0))
        kp = _partner(k, j, sub) * e
        y = jnp.zeros((C, w), jnp.float32)
        for i, (a, x) in enumerate(zip(lhs, grads)):
            c = _column(x, j, off)
            da[i] = da[i] + c * kp
            y = y + c * a
        y = jnp.sum((y * e).reshape(C // sub, sub, w), axis=1)
        dk = dk + jnp.where(own == j, _spread(y, sub), 0.0)
    return da, dk


def _spread(y, sub):
    """(n, w) -> (n * sub, w): row I over sub-chunk I's rows."""
    return jnp.concatenate([jnp.broadcast_to(y[i:i + 1], (sub, y.shape[1]))
                            for i in range(y.shape[0])])


def _chunk_bwd(q, k, v, g, b, T, W, U, dW, dU, dM, sub, mul):
    """dq, dk, dv, dg, d beta of one chunk from ``dX = [dW | dU~]`` and dM.
    With A = Diag(beta) A^: ``d rhs = T^T dX``, ``dA = -(d rhs) X^T`` below
    the diagonal; A's pairs are carried back with ``a = beta k`` against
    dA, so that d beta's part ``sum_s dA_ts A^_ts = k_t . da_t``."""
    C = k.shape[0]
    rows, cols = _iota((C, C), 0), _iota((C, C), 1)
    dRw, dRu = _dot32(T, dW, _TN), _dot32(T, dU, _TN)
    dA = jnp.where(rows > cols,
                   -(_dot32(dRw, W, _NT) + _dot32(dRu, U, _NT)), 0.0)
    bk, eg = b * k, jnp.exp(g)
    (da_a, da_m), dkp = _chunk_pairs_bwd(
        (bk, q), (dA, jnp.where(rows >= cols, dM, 0.0)), k, g, sub, mul)
    db = sum(jnp.sum(x, axis=1, keepdims=True)
             for x in (k * eg * dRw, v * dRu, k * da_a))
    dk = b * (eg * dRw + da_a) + dkp
    dg = bk * (eg * dRw + da_a) + q * da_m - k * dkp
    return da_m, dk, b * dRu, dg, db


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, w_ref, u_ref, m_ref,
                t_ref, *, sub, mul):
    from jax.experimental import pallas as pl

    h, C = pl.program_id(1), m_ref.shape[-1]

    def chunk(n, carry):
        rows = pl.ds(pl.multiple_of(n * C, C), C)
        out = _chunk_fwd(
            *(r[0, rows, :] for r in (q_ref, k_ref, v_ref, g_ref)),
            _lane(b_ref[0, rows, :], h), sub, mul)
        for ref, x in zip((w_ref, u_ref, m_ref, t_ref), out):
            ref[n, 0, 0] = x
        return carry

    lax.fori_loop(0, m_ref.shape[0], chunk, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, w_ref, u_ref, t_ref,
                dw_ref, du_ref, dm_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                db_ref, *, sub, mul):
    from jax.experimental import pallas as pl

    h, C = pl.program_id(1), dm_ref.shape[-1]

    # d beta's block holds every head of the sequence: revisited over h
    @pl.when(h == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)

    def chunk(n, carry):
        rows = pl.ds(pl.multiple_of(n * C, C), C)
        dq, dk, dv, dg, db = _chunk_bwd(
            *(r[0, rows, :] for r in (q_ref, k_ref, v_ref, g_ref)),
            _lane(b_ref[0, rows, :], h),
            *(r[n, 0, 0] for r in (t_ref, w_ref, u_ref, dw_ref, du_ref,
                                   dm_ref)), sub, mul)
        for ref, x in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv),
                       (dg_ref, dg)):
            ref[0, rows, :] = x
        old = db_ref[0, rows, :]
        db_ref[0, rows, :] = jnp.where(_iota(old.shape, 1) == h, db, old)
        return carry

    lax.fori_loop(0, dm_ref.shape[0], chunk, 0)


def _call(kernel, name, semantics, shape, ins, outs, sub, mul, interpret):
    """One ``pallas_call`` over (sequences, heads). ``shape`` is (S, Tp, H,
    dk, dv, C); ``ins`` / ``outs`` name the operands' blocks: a head's
    columns of a (S, Tp, H*d) array (``q`` for dk wide, ``v`` for dv), every
    head's beta (``beta``), or a head's chunks of an (N, S, H, C, d) array
    (``w``, ``u`` and ``m`` for dk, dv and C wide)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, Tp, H, dk, dv, C = shape
    N = Tp // C

    def head(d):
        return pl.BlockSpec((1, Tp, d), lambda s, h: (s, 0, h))

    def chunks(d):
        return pl.BlockSpec((N, 1, 1, C, d), lambda s, h: (0, s, h, 0, 0))

    specs = {"q": head(dk), "v": head(dv),
             "beta": pl.BlockSpec((1, Tp, H), lambda s, h: (s, 0, 0)),
             "w": chunks(dk), "u": chunks(dv), "m": chunks(C)}
    shapes = {"q": (S, Tp, H * dk), "v": (S, Tp, H * dv), "beta": (S, Tp, H),
              "w": (N, S, H, C, dk), "u": (N, S, H, C, dv),
              "m": (N, S, H, C, C)}
    # every block twice (the pipeline's two buffers), lanes padded to 128,
    # and room for a chunk's values
    lanes = {"q": dk, "v": dv, "beta": H, "w": dk, "u": dv, "m": C}
    blocks = sum(Tp * -(-lanes[x] // LANES) * LANES * 4 for x in ins + outs)
    return pl.pallas_call(
        functools.partial(kernel, sub=sub, mul=mul),
        grid=(S, H),
        in_specs=[specs[x] for x in ins],
        out_specs=[specs[x] for x in outs],
        out_shape=[jax.ShapeDtypeStruct(shapes[x], jnp.float32)
                   for x in outs],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=2 * blocks + (16 << 20)),
        interpret=interpret,
        name=name)


def _shape(q, v, beta, chunk):
    S, Tp, H = beta.shape
    return S, Tp, H, q.shape[-1] // H, v.shape[-1] // H, chunk


_INS = ["q", "q", "v", "q", "beta"]


def _forward(q, k, v, g, beta, chunk, sub, mul, interpret):
    """W, U~, M and T = (I + A)^-1."""
    return _call(_fwd_kernel, "fed_kda_core", ("parallel", "parallel"),
                 _shape(q, v, beta, chunk), _INS, ["w", "u", "m", "m"], sub,
                 mul, interpret)(q, k, v, g, beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _chunk_local(q, k, v, g, beta, chunk, sub, mul, interpret):
    """``(q, k, v, G, beta) -> (W, U~, M)``: q, k, G (S, Tp, H*dk), v (S, Tp,
    H*dv), beta (S, Tp, H), float32; W (N, S, H, C, dk), U~ (N, S, H, C,
    dv), M (N, S, H, C, C)."""
    return tuple(_forward(q, k, v, g, beta, chunk, sub, mul, interpret)[:3])


def _chunk_local_fwd(q, k, v, g, beta, chunk, sub, mul, interpret):
    w, u, m, t = _forward(q, k, v, g, beta, chunk, sub, mul, interpret)
    return (w, u, m), (q, k, v, g, beta, w, u, t)


def _chunk_local_bwd(chunk, sub, mul, interpret, res, cts):
    q, v, beta = res[0], res[2], res[4]
    # the backward pass's operations carry the scope themselves: they are
    # traced here, not where the forward call was
    with jax.named_scope("fed_kda_core"):
        return tuple(_call(
            _bwd_kernel, "fed_kda_core",
            # d beta's block is revisited over the heads
            ("parallel", "arbitrary"), _shape(q, v, beta, chunk),
            _INS + ["w", "u", "m"] * 2,
            _INS, sub, mul, interpret,
        )(*res, *cts))


_chunk_local.defvjp(_chunk_local_fwd, _chunk_local_bwd)


def kda_attention_pallas(q, k, v, log_alpha, beta, interpret=False,
                         chunk=CHUNK, sub=SUB_CHUNK):
    """The chunked core with its chunk-local phase in the kernels (shapes as
    ``kda_attention``'s). The pair products' multiplicands are rounded as
    the chip rounds a float32 ``einsum`` here: to bfloat16 at the default
    matmul precision, not at all where one is set (only the interpreted
    kernels are reached then)."""
    S, T, H, dk = q.shape
    dv = v.shape[-1]
    pad = -T % chunk
    Tp = T + pad
    N = Tp // chunk

    def flat(x):              # (S, T, H, ...) -> (S, Tp, H*...), float32
        x = jnp.pad(x.astype(jnp.float32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape(S, Tp, -1)

    q, k, v, log_alpha, beta = map(flat, (q, k, v, log_alpha, beta))
    g = jnp.cumsum(log_alpha.reshape(S, N, chunk, H * dk), axis=2)
    mul = jnp.bfloat16 if _rounds_to_bfloat16() else jnp.float32
    w, u, M = _chunk_local(q, k, v, g.reshape(S, Tp, H * dk), beta, chunk,
                           sub, mul, interpret)

    def chunk_major(x):       # (S, N, C, H, d) -> (N, S, H, C, d)
        return x.transpose(1, 0, 3, 2, 4)

    g = g.reshape(S, N, chunk, H, dk)
    q_g = chunk_major(q.reshape(g.shape) * jnp.exp(g))
    k_c = chunk_major(k.reshape(g.shape) * jnp.exp(g[:, :, -1:] - g))
    decay = jnp.exp(g[:, :, -1]).transpose(1, 0, 2, 3)      # (N, S, H, dk)
    o = _scan(w, u, q_g, k_c, M, decay)
    o = o.transpose(1, 0, 3, 2, 4).reshape(S, Tp, H, dv)[:, :T]
    return o * dk ** -0.5


def _chunk_of(T, chunk, sub):
    """The chunk a sequence of T positions is cut into."""
    return min(chunk, -(-T // sub) * sub)


def kda_core_path(T, dk, dv, chunk=CHUNK, sub=SUB_CHUNK,
                  interpret=False) -> str:
    """``"pallas"`` or ``"xla"`` for a call of this shape, from what the
    process can see: the backend, whether a matmul precision is set (the
    tests' and the reference's ``highest``: the kernels round the pair
    products' multiplicands as the unit does at the default precision
    only), the widths and the chunking. ``interpret`` stands in for the
    backend in the CPU tests."""
    on_chip = is_tpu_backend() and _rounds_to_bfloat16()
    if ((on_chip or interpret) and dk % LANES == 0 and dv % LANES == 0
            and (_chunk_of(T, chunk, sub), sub) == (CHUNK, SUB_CHUNK)):
        return "pallas"
    return "xla"


def kda_attention(q, k, v, log_alpha, beta, chunk=CHUNK, sub=SUB_CHUNK,
                  interpret=False):
    """The module docstring's recurrence in its chunked form, scale dk^-1/2,
    on the path this call's shape and process take (``kda_core_path``;
    counted in ``PATH_CALLS``). q, k, log_alpha: (S, T, H, dk); v: (S, T,
    H, dv); beta: (S, T, H) -> o (S, T, H, dv), float32. T is padded to a
    multiple of ``chunk`` with positions that neither decay nor write."""
    T, dk, dv = q.shape[1], q.shape[-1], v.shape[-1]
    path = kda_core_path(T, dk, dv, chunk, sub, interpret)
    PATH_CALLS[path] += 1
    if path == "pallas":
        return kda_attention_pallas(q, k, v, log_alpha, beta, interpret,
                                    chunk, sub)
    return kda_attention_xla(q, k, v, log_alpha, beta, chunk, sub)
