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

The same module holds the grouped-query attention of models/laguna.py ``GQA``
from the projections' outputs to ``W_o``'s operand: ``g * softmax(R(q) R(k)^T
/ sqrt(d) + mask) v`` on ``q`` (S, T, Hq*d), ``k``, ``v`` (S, T, Hkv*d), flat
as ``x @ W`` wrote them, the gates ``g`` (S, T, Hq) and ``R`` the half-split
RoPE of the layer's ``(cos, sin)``; query head ``h`` reads key/value head ``h
// (Hq / Hkv)``, query ``i`` sees the keys ``j`` with ``0 <= i - j < window``
(no window: every ``j <= i``). ``gqa_attention_einsum`` is the oracle, in
``jnp``; ``gqa_attention_fused`` is two Pallas kernels under one
``custom_vjp`` that, unlike the latent core's, tile the keys: the grid walks
(sequence, key/value head, query tile, key tile) with a running max and sum
and visits only the key tiles the mask can reach (``_visits``), so the
sequence length is bounded by the backward kernel's whole-sequence ``dk`` /
``dv`` blocks alone (``MAX_GQA_T``). A head is a 128-lane column slice of a
tile in VMEM: there q and k are turned in float32 (lane rotations against a
table of cos and sin, ``_rope_table``) before they are rounded, the output is
multiplied by its head's gate, and the backward kernel runs the gate's and
the turn's transposes on the same tiles, so no (S, T, H, d) array exists.
The forward writes the gated output, the ungated one and a log-sum-exp a row
(the backward's residuals: it divides by no gate). Rounding and the path's
rule (``gqa_attention_path``) are the latent core's; ``GQA_PLAN`` is the plan.
A call without a gate (``gate=None``, the head count in ``heads``:
models/ouro.py, one query head a key/value head) runs the same kernels
without the gate's operand: one output and the log-sum-exp forward, ``dq``,
``dk``, ``dv`` and no ``d_g`` backward.
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
           "MAX_FUSED_T", "gqa_attention", "gqa_attention_einsum",
           "gqa_attention_fused", "gqa_shape_ok", "gqa_attention_path",
           "GQA_TILE", "MAX_GQA_T", "GQA_PLAN"]

TILE = 128          # query tile, and the lane width head groups align to
# a group's keys, values and a query tile's scores (TILE x T float32) are
# held in VMEM whole; longer sequences take the einsum path. The longest
# the kernels were compiled for (tests/test_tpu_aot.py) and run at on a v5e
MAX_FUSED_T = 1024
# calls of ``mla_attention`` / ``gqa_attention`` traced on each path, this
# process
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
    the chip: at the default matmul precision (gpt2_train.build_decoder asks
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


# -- grouped queries, an optional window, the keys tiled ----------------------

GQA_TILE = 512      # query tile = key tile
# the backward kernel keeps one key/value head's dk and dv of a whole
# sequence in VMEM (T x d float32 each, twice for the pipeline). The longest
# the kernels were compiled for (tests/test_tpu_aot.py) and run at on a v5e
MAX_GQA_T = 4096
# the fused calls traced in this process, by kind of layer ("full" /
# "window"): tile, key tiles visited over one sequence's query tiles, how
# many a causal mask alone would visit; and for every call, fused or not,
# where q and k were turned and the heads gated ("kernel" / "xla")
GQA_PLAN: dict = {}
# a running max starts above a masked score, so that a row whose keys in a
# visited tile are all masked adds exp(masked - start) = 0, not exp(0)
_M_START = -1e30


def _gqa_dims(q, k, gate, heads=None):
    """(S, T, Hq, Hkv, d) of a call's operands; the query heads are the
    gate's last axis, or ``heads`` where there is no gate."""
    S, T, width = q.shape
    Hq = heads if gate is None else gate.shape[-1]
    d = width // Hq
    return S, T, Hq, k.shape[-1] // d, d


def _sees(query, key, window):
    """The mask: ``key <= query`` and, with a window, ``query - key <
    window``."""
    ok = key <= query
    return ok if window is None else ok & (query - key < window)


def _turn(x, cos, sin):
    """Half-split RoPE on the first ``2 * cos.shape[-1]`` columns of every
    head: the pair (x_i, x_{i+n/2}) of position p turned by p's angle, by a
    roll of the rotary columns (no strided slice, no stack); the columns
    past them pass. x (S, T, H, d), cos and sin (T, n/2)."""
    half = cos.shape[-1]
    cos2 = jnp.concatenate([cos, cos], axis=-1)[None, :, None]
    sin2 = jnp.concatenate([-sin, sin], axis=-1)[None, :, None]
    rot = x[..., :2 * half]
    out = rot * cos2 + jnp.roll(rot, half, axis=-1) * sin2
    if 2 * half == x.shape[-1]:
        return out.astype(x.dtype)
    return jnp.concatenate([out.astype(x.dtype), x[..., 2 * half:]], axis=-1)


def gqa_attention_einsum(q, k, v, gate, rope, window=None, heads=None):
    """The oracle, in ``jnp``: the heads viewed (S, T, H, d), q and k turned
    (``_turn``), two ``einsum``s around a float32 softmax, the gate where
    there is one."""
    S, T, Hq, Hkv, d = _gqa_dims(q, k, gate, heads)
    q, k, v = (x.reshape(S, T, -1, d) for x in (q, k, v))
    qg = _turn(q, *rope).reshape(S, T, Hkv, Hq // Hkv, d)
    k = _turn(k, *rope)
    att = jnp.einsum("sqhgd,skhd->shgqk", qg, k) * (d ** -0.5)
    pos = jnp.arange(T)
    att = jnp.where(_sees(pos[:, None], pos[None, :], window), att,
                    jnp.finfo(att.dtype).min)
    att = jax.nn.softmax(att.astype(jnp.float32), axis=-1)
    out = jnp.einsum("shgqk,skhd->sqhgd", att.astype(v.dtype),
                     v).reshape(S, T, Hq, d)
    if gate is None:
        return out.reshape(S, T, Hq * d)
    return (out * gate[..., None].astype(out.dtype)).reshape(S, T, Hq * d)


def _visits(T: int, tile: int, window) -> int:
    """Key tiles a query tile's grid row visits: those that end on its
    diagonal tile. Without a window all that a causal mask can reach (the
    ones before the sequence's start are skipped without a load)."""
    n = T // tile
    return n if window is None else min(n, -(-(window - 1) // tile) + 1)


def gqa_shape_ok(T: int, Hq: int, Hkv: int, d: int, tile=GQA_TILE) -> bool:
    """Whether the kernels take this shape."""
    return (T % tile == 0 and 0 < T <= MAX_GQA_T and d % TILE == 0
            and Hkv > 0 and Hq % Hkv == 0)


def _walk(tile, n_visits, window):
    """Where a grid step stands: (query tile, key tile, whether the key
    tile exists, whether every score of the pair is seen)."""
    from jax.experimental import pallas as pl

    i, step = pl.program_id(2), pl.program_id(3)
    j = i - (n_visits - 1) + step
    whole = j < i
    if window is not None:
        whole = whole & ((i - j + 1) * tile <= window)
    return i, j, j >= 0, whole


def _scores(q, k, i, j, tile, window, scale, masked):
    """(queries, keys) float32 scores of one head's tile pair."""
    s = _dot(q, k, _NT) * scale
    if not masked:
        return s
    query = i * tile + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    key = j * tile + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(_sees(query, key, window), s, _MASKED)


def _both(exists, whole, step):
    """Run ``step(masked)`` once: without the mask where the whole tile pair
    is seen, with it on the pairs the mask's edge crosses."""
    from jax.experimental import pallas as pl

    pl.when(exists & whole)(functools.partial(step, False))
    pl.when(exists & jnp.logical_not(whole))(functools.partial(step, True))


def _lanes(col):
    """(n, 1) -> (n, LANES): a row statistic on every lane, the shape the
    kernels keep them in (a one-lane column costs a vector register a
    sublane group all the same, and every use of it a lane broadcast: 7 of
    the forward kernel's 15.6 ms at the published shape, PERF.md PR 32)."""
    return jnp.broadcast_to(col, (col.shape[0], TILE))


def _lanes_to(x, d):
    """A (n, LANES) row statistic against (n, d) columns of a head."""
    return x[:, :d] if d <= TILE else jnp.tile(x, (1, d // TILE))


def _head_stat(block, h):
    """Head ``h``'s column of a (tile, G) block of row statistics, on every
    lane."""
    lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return _lanes(jnp.sum(jnp.where(lane == h, block, 0.0), axis=1,
                          keepdims=True))


def _stat_block(cols):
    """G row statistics on every lane -> their (tile, G) block."""
    group = len(cols)
    lane = lax.broadcasted_iota(jnp.int32, (cols[0].shape[0], group), 1)
    block = jnp.zeros(lane.shape, jnp.float32)
    for h, col in enumerate(cols):
        block = jnp.where(lane == h, col[:, :group], block)
    return block


def _rope_table(cos, sin, d):
    """A layer's (T, n/2) cos and sin as lane tables a head wide, side by
    side: ``C`` (cos on the rotary columns, 1 past them), ``A`` (-sin on
    ``[0, n/2)``) and ``B`` (+sin on ``[n/2, n)``), zero elsewhere, so that
    a head's turn is ``x C + roll(x, -n/2) A + roll(x, +n/2) B``. Where the
    rotary columns are the whole head the two rolls are one, and the table
    is ``C`` and ``A + B``."""
    T, half = cos.shape
    zero = functools.partial(jnp.zeros, dtype=cos.dtype)
    c = jnp.concatenate([cos, cos, jnp.ones((T, d - 2 * half), cos.dtype)], 1)
    a = jnp.concatenate([-sin, zero((T, d - half))], 1)
    b = jnp.concatenate([zero((T, half)), sin, zero((T, d - 2 * half))], 1)
    return jnp.concatenate([c, a + b] if 2 * half == d else [c, a, b], 1)


def _rotate(x, table, half, back=False):
    """A head's (tile, d) float32 columns turned by the positions of the
    rows of ``table`` (a block of ``_rope_table``'s, in VMEM); ``back`` is
    the transpose, which takes a gradient of the turned columns to the raw
    ones (the turn by the opposite angle, times the same factor)."""
    from jax.experimental.pallas import tpu as pltpu

    d = x.shape[1]
    if 2 * half == d:
        swapped = pltpu.roll(x, half, 1) * table[:, d:]
    else:
        swapped = pltpu.roll(x, d - half, 1) * table[:, d:2 * d] \
            + pltpu.roll(x, half, 1) * table[:, 2 * d:]
    straight = x * table[:, :d]
    return straight - swapped if back else straight + swapped


def _gqa_fwd_kernel(*refs, gated, group, d, half, tile, n_visits, window,
                    scale, mul):
    from jax.experimental import pallas as pl

    if gated:
        (q_ref, k_ref, v_ref, g_ref, tq_ref, tk_ref, og_ref, o_ref, lse_ref,
         qb, m_s, l_s, acc) = refs
    else:
        (q_ref, k_ref, v_ref, tq_ref, tk_ref, o_ref, lse_ref,
         qb, m_s, l_s, acc) = refs
    i, j, exists, whole = _walk(tile, n_visits, window)
    first = pl.program_id(3) == 0
    last = pl.program_id(3) == n_visits - 1

    @pl.when(first)
    def _():
        for h in range(group):
            cols = slice(h * d, (h + 1) * d)
            qb[:, cols] = _rotate(q_ref[0, :, cols], tq_ref,
                                  half).astype(mul)
        m_s[...] = jnp.full_like(m_s, _M_START)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)

    def step(masked):
        k = _rotate(k_ref[0], tk_ref, half).astype(mul)
        v = v_ref[0].astype(mul)
        for h in range(group):
            cols = slice(h * d, (h + 1) * d)
            s = _scores(qb[:, cols], k, i, j, tile, window, scale, masked)
            m_old = m_s[h]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            turn = jnp.exp(m_old - m_new)
            l_s[h] = turn * l_s[h] + jnp.sum(p, axis=1, keepdims=True)
            acc[:, cols] = _lanes_to(turn, d) * acc[:, cols] \
                + _dot(p.astype(mul), v, _NN)
            m_s[h] = m_new

    _both(exists, whole, step)

    @pl.when(last)
    def _():
        for h in range(group):
            cols = slice(h * d, (h + 1) * d)
            o = acc[:, cols] / _lanes_to(l_s[h], d)
            if gated:
                gate = _lanes_to(_head_stat(g_ref[0, 0], h), d)
            o_ref[0, :, cols] = o.astype(o_ref.dtype)
            if gated:
                og_ref[0, :, cols] = (o * gate).astype(og_ref.dtype)
        lse_ref[0, 0] = _stat_block(
            [m_s[h] + jnp.log(l_s[h]) for h in range(group)])


def _gqa_bwd_kernel(*refs, gated, group, d, half, tile, n_visits, window,
                    scale, mul):
    from jax.experimental import pallas as pl

    if gated:
        (q_ref, k_ref, v_ref, g_ref, tq_ref, tk_ref, o_ref, dog_ref, lse_ref,
         dq_ref, dk_ref, dv_ref, dg_ref, qb, dob, lse_s, delta) = refs
    else:
        # ``dog_ref`` is then the output's own gradient
        (q_ref, k_ref, v_ref, tq_ref, tk_ref, o_ref, dog_ref, lse_ref,
         dq_ref, dk_ref, dv_ref, qb, dob, lse_s, delta) = refs
    i, j, exists, whole = _walk(tile, n_visits, window)
    first = pl.program_id(3) == 0
    last = pl.program_id(3) == n_visits - 1

    @pl.when(first & (i == 0))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(first)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        d_gate = []
        for h in range(group):
            cols = slice(h * d, (h + 1) * d)
            qb[:, cols] = _rotate(q_ref[0, :, cols], tq_ref,
                                  half).astype(mul)
            if gated:
                gate = _head_stat(g_ref[0, 0], h)
            d_gated = dog_ref[0, :, cols]
            # the ungated output's gradient, formed in float32
            dob[:, cols] = (d_gated * _lanes_to(gate, d) if gated
                            else d_gated).astype(mul)
            # the gate's gradient: d_gated . out of a row; times the gate
            # it is sum_k p dp of that row (d_out . out)
            d_gate.append(_lanes(jnp.sum(d_gated * o_ref[0, :, cols],
                                         axis=1, keepdims=True)))
            delta[h] = gate * d_gate[h] if gated else d_gate[h]
            lse_s[h] = _head_stat(lse_ref[0, 0], h)
        if gated:
            dg_ref[0, 0] = _stat_block(d_gate)

    def step(masked):
        k = _rotate(k_ref[0], tk_ref, half).astype(mul)
        v = v_ref[0].astype(mul)
        d_k = jnp.zeros((tile, d), jnp.float32)
        d_v = jnp.zeros((tile, d), jnp.float32)
        for h in range(group):
            cols = slice(h * d, (h + 1) * d)
            q, do = qb[:, cols], dob[:, cols]
            s = _scores(q, k, i, j, tile, window, scale, masked)
            p = jnp.exp(s - lse_s[h][:, :1])
            dp = _dot(do, v, _NT)
            ds = p * (dp - delta[h][:, :1]) * scale
            d_v = d_v + _dot(p.T.astype(mul), do, _NN)
            d_k = d_k + _dot(ds.T.astype(mul), q, _NN)
            dq_ref[0, :, cols] += _dot(ds.astype(mul), k, _NN)
        keys = pl.ds(pl.multiple_of(j * tile, tile), tile)
        # the turn is linear: each tile pair's part of dk goes back through
        # it by the key tile's own rows of the table
        dk_ref[0, keys, :] += _rotate(d_k, tk_ref, half, back=True)
        dv_ref[0, keys, :] += d_v

    _both(exists, whole, step)

    @pl.when(last)
    def _():
        for h in range(group):
            cols = slice(h * d, (h + 1) * d)
            dq_ref[0, :, cols] = _rotate(dq_ref[0, :, cols], tq_ref,
                                         half, back=True)


def _gqa_call(kernel, name, dims, half, tile, window, gated, in_specs,
              out_specs, out_shape, scratch, operand_dtype, interpret):
    """One ``pallas_call`` over (sequences, key/value heads, query tiles,
    the key tiles a query tile visits). Without a gate (``gated`` false) the
    gate's operands and results (``"gate"``, a row statistic; ``"gated"``,
    the gated output) are left out of the lists."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, T, Hq, Hkv, d = dims
    G = Hq // Hkv
    n_visits = _visits(T, tile, window)
    table = (2 if 2 * half == d else 3) * d

    def key_tile(i, step):
        # a tile before the sequence's start is not loaded: the index stays
        # on tile 0, which the next existing step wants anyway
        return jnp.maximum(i - (n_visits - 1) + step, 0)

    specs = {"q": pl.BlockSpec((1, tile, G * d), lambda s, g, i, _: (s, i, g)),
             "kv": pl.BlockSpec((1, tile, d), lambda s, g, i, step:
                                (s, key_tile(i, step), g)),
             "whole": pl.BlockSpec((1, T, d), lambda s, g, i, _: (s, 0, g)),
             "stat": pl.BlockSpec((1, 1, tile, G),
                                  lambda s, g, i, _: (s, g, i, 0)),
             # the rope table's rows of the query tile, of the key tile
             "rope_q": pl.BlockSpec((tile, table), lambda s, g, i, _: (i, 0)),
             "rope_k": pl.BlockSpec((tile, table), lambda s, g, i, step:
                                    (key_tile(i, step), 0))}
    specs.update(gate=specs["stat"], gated=specs["q"])
    shapes = {"q": (tile, G * d), "stat": (G, tile, TILE)}

    def kept(keys, of):
        return [x for k, x in zip(keys, of)
                if gated or k not in ("gate", "gated")]

    return pl.pallas_call(
        functools.partial(kernel, gated=gated, group=G, d=d, half=half,
                          tile=tile, n_visits=n_visits, window=window,
                          scale=d ** -0.5, mul=operand_dtype),
        grid=(S, Hkv, T // tile, n_visits),
        in_specs=kept(in_specs, [specs[k] for k in in_specs]),
        out_specs=kept(out_specs, [specs[k] for k in out_specs]),
        out_shape=kept(out_specs, out_shape),
        scratch_shapes=[pltpu.VMEM(shapes[k], dt) for k, dt in scratch],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name=name)


def _by_kv_head(x, Hkv):
    """A row statistic (S, T, Hq) in the kernels' layout (S, Hkv, T, G)."""
    S, T, Hq = x.shape
    return x.reshape(S, T, Hkv, Hq // Hkv).transpose(0, 2, 1, 3)


def _gqa_forward(q, k, v, gate, table, static):
    """(gated output, output, log-sum-exp), without a gate (output,
    log-sum-exp); ``static`` is (rotary pairs, window, tile, the
    multiplicands' dtype, interpret, the query heads of a call without a
    gate)."""
    half, window, tile, operand_dtype, interpret, heads = static
    S, T, Hq, Hkv, d = dims = _gqa_dims(q, k, gate, heads)
    wide = jax.ShapeDtypeStruct((S, T, Hq * d), q.dtype)
    gates = () if gate is None else (_by_kv_head(gate, Hkv),)
    return _gqa_call(
        _gqa_fwd_kernel, "fed_gqa_attn_fwd", dims, half, tile, window,
        gate is not None,
        ("q", "kv", "kv", "gate", "rope_q", "rope_k"), ("gated", "q", "stat"),
        [wide, wide,
         jax.ShapeDtypeStruct((S, Hkv, T, Hq // Hkv), jnp.float32)],
        (("q", operand_dtype), ("stat", jnp.float32), ("stat", jnp.float32),
         ("q", jnp.float32)),
        operand_dtype, interpret)(q, k, v, *gates, table, table)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gqa_fused(q, k, v, gate, table, static):
    return _gqa_forward(q, k, v, gate, table, static)[0]


def _gqa_fused_fwd(q, k, v, gate, table, static):
    *outs, lse = _gqa_forward(q, k, v, gate, table, static)
    # the ungated output is the backward's residual: the only one of a call
    # without a gate
    return outs[0], (q, k, v, gate, table, outs[-1], lse)


def _kind(window) -> str:
    return "full" if window is None else "window"


def gqa_scope(window) -> str:
    """The inner scope of a layer's kind, under ``fed_gqa_attn``."""
    return "fed_gqa_attn_" + _kind(window)


def _gqa_fused_bwd(static, res, d_gated):
    half, window, tile, operand_dtype, interpret, heads = static
    q, k, v, gate, table, out, lse = res
    S, T, Hq, Hkv, d = dims = _gqa_dims(q, k, gate, heads)
    gates = () if gate is None else (_by_kv_head(gate, Hkv),)
    # traced here, not where the forward call was: the scopes again
    with jax.named_scope("fed_gqa_attn"), jax.named_scope(gqa_scope(window)):
        dq, dk, dv, *dg = _gqa_call(
            _gqa_bwd_kernel, "fed_gqa_attn_bwd", dims, half, tile, window,
            gate is not None,
            ("q", "kv", "kv", "gate", "rope_q", "rope_k", "q", "q", "stat"),
            ("q", "whole", "whole", "gate"),
            [jax.ShapeDtypeStruct((S, T, Hq * d), jnp.float32),
             jax.ShapeDtypeStruct((S, T, Hkv * d), jnp.float32),
             jax.ShapeDtypeStruct((S, T, Hkv * d), jnp.float32),
             jax.ShapeDtypeStruct((S, Hkv, T, Hq // Hkv), jnp.float32)],
            (("q", operand_dtype), ("q", operand_dtype),
             ("stat", jnp.float32), ("stat", jnp.float32)),
            operand_dtype, interpret,
        )(q, k, v, *gates, table, table, out, d_gated, lse)
        if gate is not None:
            dg = dg[0].transpose(0, 2, 1, 3).reshape(gate.shape).astype(
                gate.dtype)
        # positions are no parameter: the table takes no gradient
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
                dg if gate is not None else None, jnp.zeros_like(table))


_gqa_fused.defvjp(_gqa_fused_fwd, _gqa_fused_bwd)


def _plan(gate, where: str) -> dict:
    """``GQA_PLAN``'s entry for where q and k were turned (and the heads
    gated, where the call has a gate)."""
    if gate is None:
        return {"turn": where, "gate": "none"}
    return {"turn_and_gate": where}


def gqa_attention_fused(q, k, v, gate, rope, window=None, interpret=False,
                        tile=GQA_TILE, heads=None):
    """The fused kernels; multiplicands rounded as ``mla_attention_fused``
    rounds them. ``tile`` is for the interpreted tests' small shapes;
    ``gate=None`` is a core without a gate, of ``heads`` query heads."""
    T = q.shape[1]
    n = T // tile
    GQA_PLAN[_kind(window)] = {
        "tile": tile,
        "key_tiles_visited": sum(min(i + 1, _visits(T, tile, window))
                                 for i in range(n)),
        "key_tiles_causal": n * (n + 1) // 2,
        **_plan(gate, "kernel")}
    cos, sin = rope
    return _gqa_fused(
        q, k, v, gate,
        _rope_table(cos, sin, _gqa_dims(q, k, gate, heads)[-1]),
        (cos.shape[-1], window, tile,
         jnp.bfloat16 if _rounds_to_bfloat16() else jnp.float32, interpret,
         heads))


def gqa_attention_path(T, Hq, Hkv, d, interpret=False) -> str:
    """``attention_path``'s twin for the grouped-query core."""
    on_chip = is_tpu_backend() and _rounds_to_bfloat16()
    if (on_chip or interpret) and gqa_shape_ok(T, Hq, Hkv, d):
        return "fused"
    return "einsum"


def gqa_attention(q, k, v, gate, rope, window=None, interpret=False,
                  heads=None):
    """The grouped-query core, from the projections' outputs to ``W_o``'s
    operand, on the path this call's shape and process take
    (``gqa_attention_path``); counts the call in ``PATH_CALLS``. ``gate`` is
    (S, T, Hq), or ``None`` for a core without one: ``heads`` then says how
    many query heads q holds."""
    path = gqa_attention_path(*_gqa_dims(q, k, gate, heads)[1:], interpret)
    PATH_CALLS[path] += 1
    if path == "einsum":
        GQA_PLAN[_kind(window)] = _plan(gate, "xla")
        return gqa_attention_einsum(q, k, v, gate, rope, window, heads)
    return gqa_attention_fused(q, k, v, gate, rope, window, interpret,
                               heads=heads)


# the kernels against the oracle on the chip: both round multiplicands to
# bfloat16 (2^-9 relative), the kernels round the unnormalised probabilities
# of a key tile where the oracle rounds the normalised ones, and the sums
# over keys run in another order; so not bit for bit, but within rounding of
# the largest entry
GQA_CHECK_TOL = 2e-2


def check_gqa_kernels(heads=(48, 64), kv_heads=8, d=128, T=MAX_GQA_T,
                      window=512, interpret=False, tile=GQA_TILE,
                      ungated=((16, 16, 1024), (16, 16, MAX_GQA_T))) -> dict:
    """``gqa_attention_fused`` against ``gqa_attention_einsum`` at the
    published shapes (one sequence). models/laguna.py: 48 query heads with
    no window, half of a head's columns turned, a factor on cos and sin; 64
    with the window, every column turned. models/ouro.py (``ungated``:
    query heads, key/value heads, positions): 16 over 16, no gate, no
    window, every column turned. The output and every gradient within
    ``GQA_CHECK_TOL`` of the oracle's largest entry. The oracle's scores do
    not fit a chip whole at 4,096 positions, so it runs one key/value head
    (and its query heads) at a time. Returns the largest gaps seen."""
    worst = {}
    cases = [(Hq, kv_heads, T, win, True)
             for Hq, win in zip(heads, (None, window))]
    cases += [(Hq, Hkv, T_, None, False) for Hq, Hkv, T_ in ungated]
    for n, (Hq, Hkv, T_, win, gated) in enumerate(cases):
        G = Hq // Hkv
        rotary, factor = (d // 2, 1.4) if gated and win is None else (d, 1.0)
        pos = jnp.arange(T_, dtype=jnp.float32)[:, None]
        angle = pos * 10000.0 ** (-jnp.arange(0, rotary, 2) / rotary)
        rope = (jnp.cos(angle) * factor, jnp.sin(angle) * factor)
        keys = jax.random.split(jax.random.key(n), 5)
        q, k, v, w = (jax.random.normal(key, (1, T_, h * d), jnp.float32)
                      for key, h in zip(keys, (Hq, Hkv, Hkv, Hq)))
        gate = (jax.nn.sigmoid(jax.random.normal(keys[4], (1, T_, Hq)))
                if gated else None)

        def both(fn):
            def run(q, k, v, gate, w):
                args = (q, k, v) if gate is None else (q, k, v, gate)

                def f(*a):
                    return fn(*a, None) if gate is None else fn(*a)
                return (f(*args),) + jax.grad(
                    lambda *a: jnp.sum(f(*a) * w),
                    argnums=tuple(range(len(args))))(*args)
            return jax.jit(run)

        got = both(functools.partial(
            gqa_attention_fused, rope=rope, window=win, interpret=interpret,
            tile=tile, heads=Hq))(q, k, v, gate, w)
        one = both(functools.partial(gqa_attention_einsum, rope=rope,
                                     window=win, heads=G))
        label = _kind(win) if gated else f"ungated_T{T_}"
        for g in range(Hkv):
            hq = slice(g * G, (g + 1) * G)
            cq = slice(g * G * d, (g + 1) * G * d)
            ck = slice(g * d, (g + 1) * d)
            want = one(q[..., cq], k[..., ck], v[..., ck],
                       gate[..., hq] if gated else None, w[..., cq])
            for name, a, b, cut in zip(("out", "dq", "dk", "dv", "dg"), got,
                                       want, (cq, cq, ck, ck, hq)):
                gap = float(jnp.max(jnp.abs(a[..., cut] - b))
                            / jnp.max(jnp.abs(b)))
                key = f"{name}_{label}"
                worst[key] = max(worst.get(key, 0.0), gap)
    bad = {k: v for k, v in worst.items() if not v <= GQA_CHECK_TOL}
    if bad:
        raise AssertionError(f"gqa kernels off the oracle: {bad}")
    return worst
