"""The causal core of multi-head latent attention (models/joyai.py ``MLA``):
``softmax((q_n k_n^T + q_r k_r^T) / sqrt(dn + dr)) v`` per head, as the
projections leave its operands: ``q`` (S, T, H, dn + dr) whose first ``dn``
columns a head are the position-free ``q_n`` (the rest, before their
rotation, are not read), ``q_r`` (S, T, H, dr) those columns turned by RoPE,
``kv`` (S, T, H, dn + dv) = ``[k_n ; v]`` a head, and the ONE turned ``k_r``
(S, T, dr) all heads share; output (S, T, H, dv).

Two implementations of the one function:

- ``mla_attention_einsum``: three ``einsum``s around a float32 softmax. XLA
  writes the (S, H, T, T) float32 scores and probabilities to HBM between
  the steps, in the forward pass, its recomputation and the backward pass.
  It runs everywhere, and is the fused kernels' oracle in the tests.
- ``mla_attention_fused``: two Pallas kernels under one ``jax.custom_vjp``.
  A grid step holds one sequence's keys and values of a group of heads in
  VMEM whole and walks the query tiles; of a query tile's row only the keys
  up to its diagonal are multiplied, so nothing above the diagonal is
  computed and nothing ``T x T`` leaves VMEM. Forward: scores, scale on the
  float32 scores, mask, float32 softmax, values; written are the output and
  one float32 log-sum-exp a row. Backward: the probabilities rebuilt from
  the log-sum-exp, key-major (keys on sublanes, queries on lanes, so the
  row statistics broadcast without a relayout), all gradients in one pass,
  ``dq`` and ``dkv`` in their operands' own column order (``dq``'s rotary
  columns zero: the turned ones' gradient is ``dq_r``); ``dk_r`` sums over
  heads in its revisited output block. The arrays stay (S, T, H*d) float32
  as the projections wrote them: a group of heads is a lane-aligned column
  block, a head's parts are cut and rounded in VMEM, so XLA makes no slice,
  cast, concatenation or transpose around the kernels.

Precision is the chip's for a float32 ``einsum`` at the default precision:
multiplicands rounded to bfloat16, float32 accumulation, softmax statistics
in float32. ``mla_attention`` takes the fused path where it can see that this
holds and that the kernels take the shape (TPU backend, no matmul precision
set, T a multiple of 128 up to ``MAX_FUSED_T``, head widths that make
lane-aligned groups); everything else runs the ``einsum``s. Which one a
call was traced on is counted in ``PATH_CALLS``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from commefficient_tpu.utils import is_tpu_backend

__all__ = ["mla_attention", "mla_attention_einsum", "mla_attention_fused",
           "fused_shape_ok", "attention_path", "PATH_CALLS", "TILE",
           "MAX_FUSED_T"]

TILE = 128          # query tile, and the lane width head groups align to
# a group's keys, values and a query tile's scores (TILE x T float32) are
# held in VMEM whole; longer sequences take the einsum path. The longest
# the kernels were compiled for (tests/test_tpu_aot.py) and run at on a v5e
MAX_FUSED_T = 1024
# calls of ``mla_attention`` traced on each path, this process
PATH_CALLS = {"fused": 0, "einsum": 0}

_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _dims(q, q_r, kv):
    """(S, T, H, dn, dr, dv) of a call's operands."""
    S, T, H, dr = q_r.shape
    dn = q.shape[-1] - dr
    return S, T, H, dn, dr, kv.shape[-1] - dn


def _rounds_to_bfloat16() -> bool:
    """Whether a float32 product's multiplicands are rounded to bfloat16 on
    the chip: at the default matmul precision (gpt2_train.build_joyai asks
    the same of the expert layer's operands)."""
    return jax.config.jax_default_matmul_precision is None


def mla_attention_einsum(q, q_r, kv, k_r):
    _, T, _, dn, dr, _ = _dims(q, q_r, kv)
    v = kv[..., dn:]
    # k = [k_n ; k_r] with the one k_r for all heads: two products summed,
    # the shared part never copied per head
    att = (jnp.einsum("sqhd,skhd->shqk", q[..., :dn], kv[..., :dn])
           + jnp.einsum("sqhd,skd->shqk", q_r, k_r)) * ((dn + dr) ** -0.5)
    causal = jnp.tril(jnp.ones((T, T), bool))
    att = jnp.where(causal, att, jnp.finfo(att.dtype).min)
    att = jax.nn.softmax(att.astype(jnp.float32), axis=-1)
    return jnp.einsum("shqk,skhd->sqhd", att.astype(v.dtype), v)


def _group(dr: int) -> int:
    """Heads a grid step takes: the fewest whose rotary columns fill whole
    lanes (the other widths are multiples of the lane width themselves)."""
    return TILE // math.gcd(TILE, dr)


def fused_shape_ok(T: int, H: int, dn: int, dr: int, dv: int) -> bool:
    """Whether the kernels take this shape."""
    return (T % TILE == 0 and 0 < T <= MAX_FUSED_T and dn % TILE == 0
            and dv % TILE == 0 and dr > 0 and H % _group(dr) == 0)


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _mask_diagonal(s, keys_on_rows):
    """Scores of one query tile against the keys up to its diagonal: the
    last ``TILE`` keys are the tile's own positions, masked to keys at or
    before their query; the keys before them pass whole."""
    axis = 0 if keys_on_rows else 1
    n = s.shape[axis] - TILE
    own = s[n:] if keys_on_rows else s[:, n:]
    key = lax.broadcasted_iota(jnp.int32, own.shape, axis)
    query = lax.broadcasted_iota(jnp.int32, own.shape, 1 - axis)
    own = jnp.where(key <= query, own, _MASKED)
    if not n:
        return own
    first = s[:n] if keys_on_rows else s[:, :n]
    return jnp.concatenate([first, own], axis=axis)


def _as_row(col):
    """(n, 1) -> (1, n) without a transpose unit: the column against an
    identity mask, summed over sublanes (exact: one term a lane)."""
    n = col.shape[0]
    eye = (lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _cut(ref, first, width, out, mul):
    """A head's columns of a float32 block into VMEM scratch, rounded to the
    multiplicands' dtype once for all the query tiles."""
    out[...] = ref[0, :, first:first + width].astype(mul)


def _fwd_kernel(q_ref, qr_ref, kv_ref, kr_ref, o_ref, lse_ref,
                qn, kn, v, *, heads, dn, dr, dv, scale, mul):
    T = q_ref.shape[1]
    for h in range(heads):
        _cut(q_ref, h * (dn + dr), dn, qn, mul)
        _cut(kv_ref, h * (dn + dv), dn, kn, mul)
        _cut(kv_ref, h * (dn + dv) + dn, dv, v, mul)
        r_cols = slice(h * dr, (h + 1) * dr)
        v_cols = slice(h * dv, (h + 1) * dv)
        for q0 in range(0, T, TILE):
            rows, keys = slice(q0, q0 + TILE), slice(0, q0 + TILE)
            s = (_dot(qn[rows], kn[keys], _NT)
                 + _dot(qr_ref[0, rows, r_cols], kr_ref[0, keys, :], _NT)
                 ) * scale
            s = _mask_diagonal(s, keys_on_rows=False)
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            o = _dot(p.astype(mul), v[keys], _NN)
            o_ref[0, rows, v_cols] = (o / l).astype(o_ref.dtype)
            lse_ref[0, 0, h:h + 1, rows] = _as_row(m + jnp.log(l))


def _bwd_kernel(q_ref, qr_ref, kv_ref, kr_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dqr_ref, dkv_ref, dkr_ref, qn, kn, v, do, *,
                heads, dn, dr, dv, scale, mul):
    from jax.experimental import pallas as pl

    T = q_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        dkr_ref[...] = jnp.zeros_like(dkr_ref)

    for h in range(heads):
        q_col, kv_col = h * (dn + dr), h * (dn + dv)
        _cut(q_ref, q_col, dn, qn, mul)
        _cut(kv_ref, kv_col, dn, kn, mul)
        _cut(kv_ref, kv_col + dn, dv, v, mul)
        _cut(do_ref, h * dv, dv, do, mul)
        n_cols = slice(q_col, q_col + dn)
        kn_cols = slice(kv_col, kv_col + dn)
        v_cols = slice(kv_col + dn, kv_col + dn + dv)
        r_cols = slice(h * dr, (h + 1) * dr)
        # the columns the rotation replaces take no gradient here
        dq_ref[0, :, q_col + dn:q_col + dn + dr] = jnp.zeros(
            (T, dr), dq_ref.dtype)
        # last query tile first: it sees every key, so its products
        # initialise the whole of this head's dk_n and dv
        for q0 in range(T - TILE, -1, -TILE):
            rows, keys = slice(q0, q0 + TILE), slice(0, q0 + TILE)
            qr, kr = qr_ref[0, rows, r_cols], kr_ref[0, keys, :]
            # key-major: (keys, queries)
            s = (_dot(kn[keys], qn[rows], _NT) + _dot(kr, qr, _NT)) * scale
            s = _mask_diagonal(s, keys_on_rows=True)
            p = jnp.exp(s - lse_ref[0, 0, h:h + 1, rows])
            dp = _dot(v[keys], do[rows], _NT)
            ds = (p * (dp - delta_ref[0, 0, h:h + 1, rows])) * scale
            ds_k = ds.astype(mul)
            d_v = _dot(p.astype(mul), do[rows], _NN)
            d_kn = _dot(ds_k, qn[rows], _NN)
            if q0 == T - TILE:
                dkv_ref[0, keys, v_cols] = d_v
                dkv_ref[0, keys, kn_cols] = d_kn
            else:
                dkv_ref[0, keys, v_cols] += d_v
                dkv_ref[0, keys, kn_cols] += d_kn
            dkr_ref[0, keys, :] += _dot(ds_k, qr, _NN)
            ds_q = ds.T.astype(mul)                     # (queries, keys)
            dq_ref[0, rows, n_cols] = _dot(ds_q, kn[keys], _NN)
            dqr_ref[0, rows, r_cols] = _dot(ds_q, kr, _NN)


def _call(kernel, name, semantics, dims, in_specs, out_specs, out_shape,
          scratch, operand_dtype, interpret):
    """One ``pallas_call`` over (sequences, head groups); ``in_specs`` /
    ``out_specs`` name the operands' blocks, ``scratch`` the widths of the
    (T, d) buffers a head's rounded columns are cut into."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, T, H, dn, dr, dv = dims
    G = _group(dr)

    def head_cols(d):
        return pl.BlockSpec((1, T, G * d), lambda s, g: (s, 0, g))

    specs = {"q": head_cols(dn + dr), "r": head_cols(dr),
             "kv": head_cols(dn + dv), "v": head_cols(dv),
             "shared": pl.BlockSpec((1, T, dr), lambda s, g: (s, 0, 0)),
             "stat": pl.BlockSpec((1, 1, G, T), lambda s, g: (s, g, 0, 0))}
    return pl.pallas_call(
        functools.partial(kernel, heads=G, dn=dn, dr=dr, dv=dv,
                          scale=(dn + dr) ** -0.5, mul=operand_dtype),
        grid=(S, H // G),
        in_specs=[specs[k] for k in in_specs],
        out_specs=[specs[k] for k in out_specs],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((T, d), operand_dtype) for d in scratch],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=semantics,
            # the blocks grow with T: the compiler's own 16 MiB holds them
            # up to T = 512, twice that at 1,024. No more than they need:
            # what a call reserves XLA cannot leave its own buffers in
            vmem_limit_bytes=max(16, T // 32) * 1024 * 1024),
        interpret=interpret,
        name=name)


def _flat(x):
    """(S, T, H, d) -> (S, T, H*d)."""
    return x.reshape(x.shape[:2] + (-1,))


def _forward(q, q_r, kv, k_r, operand_dtype, interpret):
    S, T, H, dn, dr, dv = dims = _dims(q, q_r, kv)
    G = _group(dr)
    out, lse = _call(
        _fwd_kernel, "fed_mla_attn_fwd", ("parallel", "parallel"), dims,
        ("q", "r", "kv", "shared"), ("v", "stat"),
        [jax.ShapeDtypeStruct((S, T, H * dv), kv.dtype),
         jax.ShapeDtypeStruct((S, H // G, G, T), jnp.float32)],
        (dn, dn, dv), operand_dtype, interpret,
    )(_flat(q), _flat(q_r).astype(operand_dtype), _flat(kv),
      k_r.astype(operand_dtype))
    return out.reshape(S, T, H, dv), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused(q, q_r, kv, k_r, operand_dtype, interpret):
    return _forward(q, q_r, kv, k_r, operand_dtype, interpret)[0]


def _fused_fwd(q, q_r, kv, k_r, operand_dtype, interpret):
    out, lse = _forward(q, q_r, kv, k_r, operand_dtype, interpret)
    return out, (q, q_r, kv, k_r, out, lse)


def _fused_bwd(operand_dtype, interpret, res, d_out):
    q, q_r, kv, k_r, out, lse = res
    S, T, H, dn, dr, dv = dims = _dims(q, q_r, kv)
    G = _group(dr)
    # the backward pass's operations carry the scope themselves: they are
    # traced here, not where the forward call was
    with jax.named_scope("fed_mla_attn"):
        # sum_k p dp = d_out . out, a row: (S, T, H) -> the kernels' layout
        delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)
        delta = delta.transpose(0, 2, 1).reshape(S, H // G, G, T)
        dq, dqr, dkv, dkr = _call(
            _bwd_kernel, "fed_mla_attn_bwd",
            # dk_r's block is revisited over the head groups
            ("parallel", "arbitrary"), dims,
            ("q", "r", "kv", "shared", "v", "stat", "stat"),
            ("q", "r", "kv", "shared"),
            [jax.ShapeDtypeStruct((S, T, H * (dn + dr)), jnp.float32),
             jax.ShapeDtypeStruct((S, T, H * dr), jnp.float32),
             jax.ShapeDtypeStruct((S, T, H * (dn + dv)), jnp.float32),
             jax.ShapeDtypeStruct((S, T, dr), jnp.float32)],
            (dn, dn, dv, dv), operand_dtype, interpret,
        )(_flat(q), _flat(q_r).astype(operand_dtype), _flat(kv),
          k_r.astype(operand_dtype), _flat(d_out), lse, delta)
        return (dq.reshape(q.shape).astype(q.dtype),
                dqr.reshape(q_r.shape).astype(q_r.dtype),
                dkv.reshape(kv.shape).astype(kv.dtype),
                dkr.astype(k_r.dtype))


_fused.defvjp(_fused_fwd, _fused_bwd)


def mla_attention_fused(q, q_r, kv, k_r, interpret=False):
    """The fused kernels. The products' operands are rounded as the chip
    rounds a float32 ``einsum``'s here: to bfloat16 at the default matmul
    precision, not at all where one is set (only the interpreted kernels
    are reached then); float32 accumulation either way."""
    return _fused(q, q_r, kv, k_r,
                  jnp.bfloat16 if _rounds_to_bfloat16() else jnp.float32,
                  interpret)


def attention_path(T, H, dn, dr, dv, interpret=False) -> str:
    """``"fused"`` or ``"einsum"`` for a call of this shape, from what the
    process can see: the backend, whether a matmul precision is set (the
    tests' and the reference's ``highest``: the kernels round multiplicands
    as the unit does at the default precision only), the shape.
    ``interpret`` stands in for the backend in the CPU tests."""
    on_chip = is_tpu_backend() and _rounds_to_bfloat16()
    if (on_chip or interpret) and fused_shape_ok(T, H, dn, dr, dv):
        return "fused"
    return "einsum"


def mla_attention(q, q_r, kv, k_r, interpret=False):
    """The attention core on the path this call's shape and process take
    (``attention_path``); counts the call in ``PATH_CALLS``."""
    path = attention_path(*_dims(q, q_r, kv)[1:], interpret)
    PATH_CALLS[path] += 1
    if path == "einsum":
        return mla_attention_einsum(q, q_r, kv, k_r)
    return mla_attention_fused(q, q_r, kv, k_r, interpret)
