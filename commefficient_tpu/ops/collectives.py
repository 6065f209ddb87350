"""Collective operators: pinned-VJP psums for SPMD parallelism, and the
sharded server data plane's transmit collectives.

Part 1 — autodiff-pinned psums. Under ``shard_map`` without replication
tracking, JAX transposes a plain ``lax.psum`` to another ``psum`` — so
differentiating through a forward reduction scales every upstream gradient
by the axis size (measured as an exact nm×/nsq× error on tensor- and
sequence-parallel gradients). The two operators here pin the transposes the
parallel layers actually mean, the Megatron f/g pair:

- ``psum_repct`` (the g operator): psum forward, **identity** backward —
  for reductions whose output's cotangent is replicated across the axis
  (the loss is computed identically on every shard downstream).
- ``ident_psumct`` (the f operator): identity forward (the input is
  replicated), **psum** backward — entering a sliced computation, each
  shard's backward produces only its slice's share of the input
  cotangent; the psum reassembles the full one.

Together they make sharded autodiff exact regardless of JAX's default
psum transpose, and keep the per-shard gradients on the contract the
federated worker reconciliation assumes (``federated/rounds.py``: psum
the shard grads over each axis, rescale masks only where a computation is
replicated). Used by tensor parallelism (``models/gpt2.py`` TPDense),
sequence parallelism (``federated/losses.py`` nll reduction, the GPT-2 mc
head), expert parallelism and the MoE aux (``parallel/moe.py``). Lives in
``ops`` (not ``parallel``) so ``models`` can import it without pulling in
the ``parallel`` package's model-importing submodules (circular import).

Part 2 — transmit collectives for the sharded server data plane
(``--server_shard``, docs/sharded_server.md). Forward-only (used in the
server phase, outside autodiff):

- ``reduce_scatter_sum`` / ``all_gather_tiled``: the Xu et al.
  (arXiv:2004.13336) reduce-scatter → per-shard update → all-gather pair.
  ``lax.psum_scatter(tiled=True)`` is bit-identical to ``psum`` + the
  shard's static slice (all-reduce ≡ reduce-scatter + all-gather, same
  ring reduction order), which is what makes the fp32 sharded server
  trajectory bit-identical to the replicated one — pinned by
  tests/test_sharded_server.py.
- ``quantized_psum_scatter`` / ``quantized_psum`` /
  ``quantized_all_gather``: EQuARX-style (arXiv:2506.17615) block-scaled
  quantized collectives with **stochastic rounding** and an explicit
  **error-feedback residual**: each chip's un-transmitted quantization
  remainder is returned to the caller, persisted (``ServerState.qres``
  for the reduce legs, ``ServerState.dres`` for the downlink gather),
  and added back into the chip's next-round contribution before
  quantization — the transmit error telescopes instead of accumulating,
  the same compensation contract as the server's top-k error feedback.
  The reduces move quantized payloads + per-block f32 scales with one
  ``all_to_all`` and dequantize-and-sum in f32 on the destination shard;
  the gather moves each chip's quantized dim-0 tile + scales and
  dequantizes on arrival (pure data movement of a compressed payload).

Wire dtypes (``quantize_blocks``/``dequantize_blocks``, selected per leg
by the ``CollectivePlan`` — docs/compressed_collectives.md):

- ``int8``  — 1 B/elem, scale = max|block|/127, integer stochastic
  rounding (the PR-2 contract, bit-for-bit unchanged);
- ``fp8_e4m3`` — 1 B/elem, scale = max|block|/448, stochastic rounding
  between the two neighboring e4m3fn values (sign-magnitude bitcast
  neighbors), so the quantizer stays unbiased like the integer SR;
- ``int4``  — 0.5 B/elem, scale = max|block|/7, integer stochastic
  rounding, two values nibble-packed per transmitted byte.

``payload_bytes`` prices all of them (element payload + per-block f32
scales) and is the ONE formula the telemetry ledger uses, so the
accounting and the collectives can never disagree on any dtype's wire
cost. ``autotune_collective_plan`` closes the loop: a one-time on-chip
probe times each {leg x dtype} candidate's quantize->dequantize round
trip against a calibration transmit and picks the cheapest dtype per leg
within an error budget (``--collective_plan auto``).

Part 3 — per-MESH-AXIS wire dtypes (docs/multihost.md). On a 2D
(clients × shard) mesh whose leading axis spans hosts over DCN, one
dtype per leg prices the slow cross-host hop and the fast ICI hop
identically. A leg may instead carry slash-joined ``axis:dtype`` pairs
(``uplink=ici:fp32/dcn:int8``; ``ici``/``dcn`` are placement aliases
resolved against ``parallel.mesh.mesh_axis_placement``, explicit mesh
axis names also work, unnamed axes stay float32).
``resolve_leg_lowering`` turns such a leg into an ordered
``((axis, dtype), ...)`` lowering over the server reduce axes — or
collapses it back to ONE dtype when every axis resolves equal, so an
fp32-everywhere per-axis spelling runs the existing flat collectives
bit-identically. The genuinely mixed case runs the EQuARX-style
(arXiv:2506.17615) hierarchical collectives below: reduce level by level
in the tuple order (gather in reverse), each quantized level carrying
ITS OWN error-feedback residual slot (``ServerState.qres``/``dres``
generalize to per-axis tuples), each level's SR stream decorrelated by
folding the level index into the rng. Conservation holds per axis: each
level's folded tile + new carry ≡ its exact tile + old carry.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

__all__ = [
    "psum_repct",
    "ident_psumct",
    "reduce_scatter_sum",
    "all_gather_tiled",
    "quantize_blocks",
    "dequantize_blocks",
    "quantize_int8_blocks",
    "dequantize_int8_blocks",
    "quantized_psum_scatter",
    "quantized_psum",
    "quantized_all_gather",
    "hierarchical_psum_scatter",
    "hierarchical_psum",
    "hierarchical_all_gather",
    "leg_axis_entries",
    "leg_quantized",
    "resolve_leg_lowering",
    "PLACEMENT_ALIASES",
    "payload_bytes",
    "int8_payload_bytes",
    "CollectivePlan",
    "FP32_PLAN",
    "PLAN_LEGS",
    "QUANT_DTYPES",
    "WIRE_DTYPES",
    "parse_collective_plan",
    "plan_from_reduce_dtype",
    "autotune_collective_plan",
    "DEFAULT_QUANT_BLOCK",
]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def psum_repct(x, axis_name):
    """``psum`` whose backward passes the cotangent through unchanged
    (correct when the output's cotangent is replicated across the axis)."""
    return jax.lax.psum(x, axis_name)


def _psum_repct_fwd(x, axis_name):
    return jax.lax.psum(x, axis_name), None


def _psum_repct_bwd(axis_name, _, ct):
    return (ct,)


psum_repct.defvjp(_psum_repct_fwd, _psum_repct_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def ident_psumct(x, axis_name):
    """Identity forward (x is replicated across the axis); psum backward
    (reassembles the full cotangent from the shards' partial ones)."""
    return x


def _ident_psumct_fwd(x, axis_name):
    return x, None


def _ident_psumct_bwd(axis_name, _, ct):
    return (jax.lax.psum(ct, axis_name),)


ident_psumct.defvjp(_ident_psumct_fwd, _ident_psumct_bwd)


# --------------------------------------------------------------------------
# sharded-server transmit collectives (forward-only; see module docstring)
# --------------------------------------------------------------------------

# Default quantization block: 64 sublanes x 128 lanes = 8192 elements per
# f32 scale (0.05% scale overhead). The chunked sketch plane instead passes
# its own (S, 128) chunk size so one scale covers exactly one resident
# chunk; the sketch-table all-reduce passes one table row (c_pad = S·128).
DEFAULT_QUANT_BLOCK = 64 * 128

_INT8_MAX = 127.0
_INT4_MAX = 7.0
_FP8_MAX = 448.0          # max finite float8_e4m3fn
_FP8_MAX_BITS = 0x7E      # magnitude bits of 448.0 (0x7F is NaN)

# quantized wire element types; "float32" everywhere means the exact leg
QUANT_DTYPES = ("int8", "fp8_e4m3", "int4")
WIRE_DTYPES = ("float32",) + QUANT_DTYPES


def payload_bytes(size: int, dtype: str = "int8",
                  block=DEFAULT_QUANT_BLOCK) -> int:
    """Logical wire bytes of a ``size``-element operand at wire ``dtype``:
    the element payload (4 B fp32; 1 B int8/fp8; int4 nibble-packed PER
    BLOCK — ``⌈b/2⌉`` bytes per b-element block, so an odd ``block`` pads
    one nibble per block exactly as ``_pack_int4`` does) plus one f32
    scale per ``block`` for the quantized dtypes. The telemetry plane's
    static ledger (telemetry.collective_ledger) prices every leg with
    this, so the accounting and the collectives can never disagree on any
    dtype's scale/packing overhead."""
    assert dtype in WIRE_DTYPES, dtype
    size = int(size)
    if dtype == "float32":
        return 4 * size
    if block is None:
        block = DEFAULT_QUANT_BLOCK
    block = int(block)
    nb = -(-size // block)
    if dtype == "int4":
        nfull, tail = divmod(size, block)
        elem = nfull * ((block + 1) // 2) + (tail + 1) // 2
    else:
        elem = size
    return elem + 4 * nb


def int8_payload_bytes(size: int, block=DEFAULT_QUANT_BLOCK) -> int:
    """Legacy alias of ``payload_bytes(size, "int8", block)`` (the PR-2/6
    spelling — same formula, kept so older callers and docs stay valid)."""
    return payload_bytes(size, "int8", block)


def reduce_scatter_sum(x, axis_name):
    """Sum ``x`` elementwise across ``axis_name`` and return this shard's
    dim-0 tile (``x.shape[0]`` must divide by the axis size). Must run
    inside ``shard_map``. Bit-identical to ``psum`` + the shard's static
    slice (all-reduce ≡ reduce-scatter + all-gather)."""
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=0,
                                tiled=True)


def all_gather_tiled(x, axis_name):
    """Concatenate the shards' dim-0 tiles back into the full array
    (replicated). Pure data movement — exact."""
    return jax.lax.all_gather(x, axis_name, axis=0, tiled=True)


def _sr_int(y, rng, qmax):
    """Integer stochastic rounding of the scaled values ``y`` to
    ``[-qmax, qmax]`` — the PR-2 int8 contract, shared by int4."""
    lo = jnp.floor(y)
    frac = y - lo
    u = jax.random.uniform(rng, y.shape, dtype=y.dtype)
    q = lo + (u < frac).astype(y.dtype)
    return jnp.clip(q, -qmax, qmax)


def _sr_fp8(y, rng):
    """Stochastic rounding of ``y`` (f32, |y| <= 448) to float8_e4m3fn:
    pick between the two neighboring representable values with
    probability proportional to proximity, so the cast is unbiased like
    the integer SR. Neighbors come from the sign-magnitude bit layout
    (uint8 bitcast ±1); the magnitude path never wraps because the cast
    of a clipped non-negative value is itself in [0, 0x7E]."""
    sign = jnp.sign(y)
    a = jnp.minimum(jnp.abs(y), _FP8_MAX)
    f8 = a.astype(jnp.float8_e4m3fn)
    c = f8.astype(jnp.float32)  # the round-to-nearest neighbor
    bits = jax.lax.bitcast_convert_type(f8, jnp.uint8)
    # bits of the representable value <= a: the RNE cast itself when it
    # rounded down, else one magnitude step below it (c > a implies
    # bits >= 1 since a >= 0, so the decrement never wraps on the lane
    # the select actually picks)
    lo_bits = jnp.where(c <= a, bits, bits - jnp.uint8(1))
    hi_bits = jnp.minimum(lo_bits + jnp.uint8(1), jnp.uint8(_FP8_MAX_BITS))
    lo = jax.lax.bitcast_convert_type(lo_bits, jnp.float8_e4m3fn) \
        .astype(jnp.float32)
    hi = jax.lax.bitcast_convert_type(hi_bits, jnp.float8_e4m3fn) \
        .astype(jnp.float32)
    gap = hi - lo
    frac = jnp.where(gap > 0, (a - lo) / jnp.where(gap > 0, gap, 1.0), 0.0)
    u = jax.random.uniform(rng, y.shape, dtype=jnp.float32)
    mag = jnp.where(u < frac, hi, lo)
    return (sign * mag).astype(jnp.float8_e4m3fn)


def _pack_int4(q):
    """Nibble-pack int4 values (f32 in [-7, 7]) two-per-byte along the
    last axis: value + 8 occupies 4 bits; even positions take the low
    nibble. An odd last dimension gets one zero-nibble of padding."""
    v = q.astype(jnp.int32) + 8
    if v.shape[-1] % 2:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, 1)], constant_values=8)
    v = v.reshape(v.shape[:-1] + (-1, 2))
    return (v[..., 0] | (v[..., 1] << 4)).astype(jnp.uint8)


def _unpack_int4(p, block: int):
    """Inverse of ``_pack_int4``: packed uint8 -> f32 values in [-7, 7],
    sliced back to ``block`` elements along the last axis."""
    lo = (p & 0xF).astype(jnp.int32) - 8
    hi = (p >> 4).astype(jnp.int32) - 8
    q = jnp.stack([lo, hi], axis=-1).reshape(p.shape[:-1]
                                             + (2 * p.shape[-1],))
    return q[..., :block].astype(jnp.float32)


def quantize_blocks(x, rng, dtype: str = "int8"):
    """Block-scaled stochastic-rounding quantization, dtype-parameterized.

    ``x`` is ``(..., block)``; returns ``(payload, scale)`` with one f32
    scale per leading index: ``scale = max|block| / qmax`` (127 int8, 448
    fp8_e4m3, 7 int4) and ``payload = SR(x / scale)`` in the wire layout —
    int8 values, raw float8_e4m3fn bytes, or nibble-packed uint8 whose
    last dim is ``ceil(block/2)``. Stochastic rounding (integer SR for the
    int dtypes, neighbor-SR for fp8) makes every quantizer unbiased
    (``E[deq(payload)·scale] = x``); the deterministic residual
    ``x − dequantize_blocks(payload, scale)`` is what the EF collectives
    below carry forward. An all-zero block gets scale 0 and payload 0
    (exact)."""
    assert dtype in QUANT_DTYPES, dtype
    qmax = {"int8": _INT8_MAX, "fp8_e4m3": _FP8_MAX,
            "int4": _INT4_MAX}[dtype]
    scale = jnp.max(jnp.abs(x), axis=-1) / qmax
    safe = jnp.where(scale > 0, scale, 1.0)
    y = x / safe[..., None]
    if dtype == "int8":
        q = _sr_int(y, rng, _INT8_MAX).astype(jnp.int8)
    elif dtype == "fp8_e4m3":
        q = _sr_fp8(y, rng)
    else:  # int4
        q = _pack_int4(_sr_int(y, rng, _INT4_MAX))
    return q, scale


def dequantize_blocks(q, scale, dtype: str = "int8", block=None):
    """payload + per-block scales -> f32 values. ``block`` is required for
    int4 (the packed payload's last dim is ``ceil(block/2)``); the other
    dtypes carry their element count in the payload shape."""
    assert dtype in QUANT_DTYPES, dtype
    if dtype == "int4":
        assert block is not None, "int4 dequantize needs the block size"
        v = _unpack_int4(q, int(block))
    else:
        v = q.astype(jnp.float32)
    return v * scale[..., None]


def quantize_int8_blocks(x, rng):
    """The PR-2 spelling of ``quantize_blocks(x, rng, "int8")`` — kept as
    the documented int8 entry point (bit-identical math)."""
    return quantize_blocks(x, rng, "int8")


def dequantize_int8_blocks(q, scale):
    return dequantize_blocks(q, scale, "int8")


def _wire(q, dtype: str):
    """Wire view of a quantized payload: fp8 bitcasts to uint8 so the
    collective moves a plain byte tensor (some backends reject f8
    collectives); int8/int4 payloads already are byte tensors."""
    if dtype == "fp8_e4m3":
        return jax.lax.bitcast_convert_type(q, jnp.uint8)
    return q


def _unwire(q, dtype: str):
    if dtype == "fp8_e4m3":
        return jax.lax.bitcast_convert_type(q, jnp.float8_e4m3fn)
    return q


def quantized_psum_scatter(x, axis_name, rng, residual=None,
                           block=DEFAULT_QUANT_BLOCK, dtype: str = "int8"):
    """Error-feedback block-scaled quantized reduce-scatter over dim 0.

    Must run inside ``shard_map``; ``x.shape[0]`` must divide by the axis
    size ``n``. Each chip adds its carried ``residual`` (same shape as
    ``x``; None ⇒ zeros) to its contribution, quantizes each
    destination's tile with per-``block`` scales + stochastic rounding at
    wire ``dtype`` (int8 / fp8_e4m3 / nibble-packed int4), moves the byte
    payloads with one ``all_to_all``, and the destination dequantizes and
    sums the ``n`` contributions in f32.

    Returns ``(local_sum_tile, new_residual)``:
    ``local_sum_tile`` is this shard's dim-0 tile of
    ``Σ_chips Q(x_chip + residual_chip)``; ``new_residual`` is this
    chip's un-transmitted remainder ``(x + residual) − Q(x + residual)``,
    to be persisted and passed back next round. Conservation (pinned in
    tests): gathered sums + psum of new residuals ≡ Σ (x + residual).
    """
    n = jax.lax.psum(1, axis_name)
    if residual is not None:
        x = x + residual
    shape = x.shape
    assert shape[0] % n == 0, (shape, n)
    per = shape[0] // n
    tile_elems = x.size // n
    # block each destination tile independently (zero-padded to a block
    # multiple) so block boundaries never straddle two destinations
    nbd = -(-tile_elems // block)
    rows = jnp.pad(x.reshape(n, tile_elems),
                   ((0, 0), (0, nbd * block - tile_elems)))
    xb = rows.reshape(n, nbd, block)
    # per-chip rng stream: fold in the shard index so the SR draws
    # decorrelate across chips (same key on every chip otherwise)
    rng = jax.random.fold_in(rng, jax.lax.axis_index(axis_name))
    q, scale = quantize_blocks(xb, rng, dtype)
    new_residual = (xb - dequantize_blocks(q, scale, dtype, block)) \
        .reshape(n, nbd * block)[:, :tile_elems].reshape(shape)
    # all_to_all: send destination j's quantized tile (and scales) to
    # shard j; receive the n chips' tiles for MY slice
    q_in = jax.lax.all_to_all(_wire(q, dtype), axis_name, split_axis=0,
                              concat_axis=0, tiled=True)
    s_in = jax.lax.all_to_all(scale, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)
    tile = jnp.sum(dequantize_blocks(_unwire(q_in, dtype), s_in, dtype,
                                     block), axis=0)
    tile = tile.reshape(-1)[:tile_elems]
    return tile.reshape((per,) + shape[1:]), new_residual


def quantized_psum(x, axis_name, rng, residual=None,
                   block=DEFAULT_QUANT_BLOCK, dtype: str = "int8"):
    """Error-feedback block-scaled quantized all-reduce (reduce-scatter
    over a padded flat view + exact f32 all-gather): every shard receives
    the same summed array, so replicated state updated from it stays
    replicated. Returns ``(sum, new_residual)`` with ``new_residual`` in
    ``x``'s shape (see ``quantized_psum_scatter``)."""
    n = jax.lax.psum(1, axis_name)
    size = x.size
    # Small arrays (size < n·block — e.g. a few-row sketch table on a
    # wide mesh): rounding every per-shard tile up to a full block would
    # pad the transmit to n·block elements, which can EXCEED the fp32
    # reduce's bytes (the opposite of the feature's point). Shrink the
    # block to the per-shard tile instead — finer scales are tighter
    # quantization, and the padding stays < n elements.
    block = min(block, max(1, -(-size // n)))
    # block-aligned per-shard tiles: every scale block then sits inside
    # one tile AND at a block-multiple offset of the flat array, so a
    # caller-chosen block boundary (e.g. one sketch-table row, block =
    # c_pad) is never straddled by a scale whenever tiles hold ≥ 1 block
    tile = -(-size // (n * block)) * block
    flat = jnp.pad(x.reshape(-1), (0, n * tile - size))
    res_flat = None
    if residual is not None:
        res_flat = jnp.pad(residual.reshape(-1), (0, n * tile - size))
    local, new_res = quantized_psum_scatter(flat, axis_name, rng,
                                            residual=res_flat, block=block,
                                            dtype=dtype)
    full = all_gather_tiled(local, axis_name)[:size].reshape(x.shape)
    return full, new_res[:size].reshape(x.shape)


def quantized_all_gather(x, axis_name, rng, residual=None,
                         block=DEFAULT_QUANT_BLOCK, dtype: str = "int8"):
    """Error-feedback block-scaled quantized all-gather over dim 0 — the
    downlink half of the compressed round (Konecny's server->client
    direction, docs/compressed_collectives.md).

    Must run inside ``shard_map``. Each chip adds its carried ``residual``
    (same shape as ``x``; None ⇒ zeros) to its dim-0 tile, quantizes it
    with per-``block`` scales + stochastic rounding at wire ``dtype``,
    and the gather moves the byte payloads + scales instead of f32 —
    every chip then dequantizes the ``n`` tiles into the full array. The
    gathered result is identical on every chip (same payloads, same
    dequantize), so replicated state updated from it stays replicated.

    Returns ``(gathered, new_residual)``: ``gathered`` is the
    concatenation of the chips' QUANTIZED tiles ``Q(x_i + residual_i)``
    (shape ``(n·x.shape[0],) + x.shape[1:]``), and ``new_residual`` this
    chip's un-transmitted remainder ``(x + residual) − Q(x + residual)``
    in ``x``'s shape, to be persisted (``ServerState.dres``) and folded
    into the next round's tile before quantization. Conservation (pinned
    in tests): each gathered tile + its new residual ≡ the exact tile +
    its old residual — the telescoping contract of the qres uplink carry,
    leg by leg."""
    n = jax.lax.psum(1, axis_name)
    if residual is not None:
        x = x + residual
    shape = x.shape
    elems = x.size
    nbd = -(-elems // block)
    xb = jnp.pad(x.reshape(-1), (0, nbd * block - elems)).reshape(nbd, block)
    # per-chip SR stream, like the reduce legs
    rng = jax.random.fold_in(rng, jax.lax.axis_index(axis_name))
    q, scale = quantize_blocks(xb, rng, dtype)
    new_residual = (xb - dequantize_blocks(q, scale, dtype, block)) \
        .reshape(-1)[:elems].reshape(shape)
    q_all = jax.lax.all_gather(_wire(q, dtype), axis_name, axis=0,
                               tiled=True)
    s_all = jax.lax.all_gather(scale, axis_name, axis=0, tiled=True)
    full = dequantize_blocks(_unwire(q_all, dtype), s_all, dtype, block)
    full = full.reshape(n, nbd * block)[:, :elems] \
        .reshape((n * shape[0],) + shape[1:])
    return full, new_residual


# --------------------------------------------------------------------------
# per-mesh-axis hierarchical collectives (docs/multihost.md)
# --------------------------------------------------------------------------

def hierarchical_psum_scatter(x, axis_dtypes, rng, residuals=None,
                              block=DEFAULT_QUANT_BLOCK):
    """Level-by-level reduce-scatter over an ORDERED ``((axis, dtype), ...)``
    lowering (``resolve_leg_lowering``; ICI axes first, the DCN axis
    last), each level at its own wire dtype with its own error-feedback
    residual. Must run inside ``shard_map``; ``x.shape[0]`` must divide
    by the product of the axis sizes. Reducing level by level in the
    tuple order tiles IDENTICALLY to one flat tuple collective over the
    same ordering (both linearize first-name-major), which is what lets
    the fp32-everywhere plan skip this path entirely.

    ``residuals`` is a sequence of per-level carries aligned with
    ``axis_dtypes`` (None for a float32 level — exact levels carry
    nothing; None also ⇒ zeros on first use); each level-j residual has
    the shape of that level's INPUT (the dim-0 tile shrinks by the axis
    size per level). Each quantized level folds its level index into
    ``rng`` so the SR streams decorrelate across levels, then its shard
    index inside ``quantized_psum_scatter``. Returns
    ``(local_sum_tile, new_residuals)`` with ``new_residuals`` a tuple
    aligned with ``axis_dtypes`` (None at float32 levels). Conservation
    per axis (pinned in tests/test_multihost.py): each level's folded
    tile + new carry ≡ its exact tile + old carry."""
    new_residuals = []
    t = x
    for lvl, (ax, dt) in enumerate(axis_dtypes):
        if dt == "float32":
            t = reduce_scatter_sum(t, ax)
            new_residuals.append(None)
        else:
            res = residuals[lvl] if residuals is not None else None
            t, nr = quantized_psum_scatter(
                t, ax, jax.random.fold_in(rng, lvl), residual=res,
                block=block, dtype=dt)
            new_residuals.append(nr)
    return t, tuple(new_residuals)


def hierarchical_psum(x, axis_dtypes, rng, residuals=None,
                      block=DEFAULT_QUANT_BLOCK):
    """Level-by-level all-reduce over an ordered ``((axis, dtype), ...)``
    lowering — the sketch-table leg's hierarchical form. Each level runs
    the exact ``psum`` (float32) or ``quantized_psum`` (its own EF
    residual, ``x``-shaped at EVERY level since an all-reduce preserves
    shape). Returns ``(sum, new_residuals)`` aligned with
    ``axis_dtypes``."""
    new_residuals = []
    t = x
    for lvl, (ax, dt) in enumerate(axis_dtypes):
        if dt == "float32":
            t = jax.lax.psum(t, ax)
            new_residuals.append(None)
        else:
            res = residuals[lvl] if residuals is not None else None
            t, nr = quantized_psum(
                t, ax, jax.random.fold_in(rng, lvl), residual=res,
                block=block, dtype=dt)
            new_residuals.append(nr)
    return t, tuple(new_residuals)


def hierarchical_all_gather(x, axis_dtypes, rng, residuals=None,
                            block=DEFAULT_QUANT_BLOCK):
    """Level-by-level all-gather over an ordered ``((axis, dtype), ...)``
    lowering — the downlink's hierarchical form, run in REVERSE tuple
    order (the minor/last-reduced axis gathers first), which reassembles
    exactly the tiling ``hierarchical_psum_scatter`` produced.

    ``residuals``/``new_residuals`` stay aligned with ``axis_dtypes``
    (slot j carries axis j's gather residual even though level j runs at
    reverse position). Slot j has the shape of level j's gather INPUT —
    the full array divided by the sizes of axes 0..j — and is identical
    across the already-gathered later axes (the level's rng folds only
    axis j's own index, so sibling chips quantize identical data with
    identical draws), i.e. globally it lives sharded over axes 0..j and
    replicated over the rest. Returns ``(gathered, new_residuals)``."""
    new_residuals = [None] * len(axis_dtypes)
    t = x
    for lvl in reversed(range(len(axis_dtypes))):
        ax, dt = axis_dtypes[lvl]
        if dt == "float32":
            t = all_gather_tiled(t, ax)
        else:
            res = residuals[lvl] if residuals is not None else None
            t, nr = quantized_all_gather(
                t, ax, jax.random.fold_in(rng, lvl), residual=res,
                block=block, dtype=dt)
            new_residuals[lvl] = nr
    return t, tuple(new_residuals)


# --------------------------------------------------------------------------
# per-leg collective plan (--collective_plan, docs/compressed_collectives.md)
# --------------------------------------------------------------------------

# the three wire legs of a federated round, Konecny-style (arXiv:1610.05492
# accounts uplink and downlink separately; EQuARX arXiv:2506.17615 shows the
# quantized collectives are native-XLA cheap):
#   uplink   — the dense transmit reduce-scatter (dense modes);
#   table    — the sketch-table exchange (sketch mode's transmit psum);
#   downlink — the update all-gather (both mode families).
PLAN_LEGS = ("uplink", "table", "downlink")

# placement aliases a per-axis plan entry may use instead of a mesh axis
# name; resolved against parallel.mesh.mesh_axis_placement at round build
PLACEMENT_ALIASES = ("ici", "dcn")


def leg_axis_entries(value: str):
    """Parse one leg value's per-axis form: ``axis:dtype`` pairs joined
    by ``/`` (``ici:fp32/dcn:int8``) -> ordered ``[(token, dtype), ...]``
    with dtypes normalized to ``WIRE_DTYPES`` spelling. Returns None for
    a plain single-dtype leg. Raises ValueError on a malformed pair, an
    unknown dtype, or a token named twice — grammar-level checks only
    (token-vs-mesh validation needs the resolved mesh:
    ``resolve_leg_lowering``)."""
    if ":" not in value:
        return None
    entries = []
    seen = set()
    for part in value.split("/"):
        part = part.strip()
        if not part:
            continue
        if ":" not in part:
            raise ValueError(
                f"collective plan per-axis entry {part!r}: expected "
                f"axis:dtype (e.g. dcn:int8)")
        tok, dt = part.split(":", 1)
        tok = tok.strip()
        dt = {"fp32": "float32", "fp8": "fp8_e4m3"}.get(dt.strip(),
                                                        dt.strip())
        if not tok:
            raise ValueError(
                f"collective plan per-axis entry {part!r}: empty axis name")
        if dt not in WIRE_DTYPES:
            raise ValueError(
                f"collective plan per-axis dtype {dt!r}: choose from "
                f"{WIRE_DTYPES}")
        if tok in seen:
            raise ValueError(
                f"collective plan names axis {tok!r} twice in one leg")
        seen.add(tok)
        entries.append((tok, dt))
    if not entries:
        raise ValueError(f"collective plan leg {value!r}: no axis:dtype "
                         f"entries")
    return entries


def leg_quantized(value: str) -> bool:
    """True iff the leg moves any non-fp32 bytes (per-axis legs: any
    entry quantized)."""
    entries = leg_axis_entries(value)
    if entries is None:
        return value != "float32"
    return any(dt != "float32" for _, dt in entries)


def resolve_leg_lowering(value: str, axis_order, placement: dict):
    """Resolve one leg value against the mesh: plain dtype -> itself;
    per-axis form -> an ordered ``((axis, dtype), ...)`` lowering over
    ``axis_order`` (the server reduce axes, a name or ordered tuple),
    for ``hierarchical_psum_scatter``/``_psum``/``_all_gather``.

    Entry tokens may be mesh axis names from ``axis_order`` or the
    placement aliases ``ici``/``dcn`` (an alias covers EVERY reduce axis
    with that placement in ``placement``, per
    ``parallel.mesh.mesh_axis_placement``). Axes no entry covers stay
    float32. A token matching neither — a mesh axis this mesh doesn't
    have, an alias no axis resolves to — raises ValueError naming the
    available axes and their placements, at startup rather than at first
    collective. When every resolved axis lands on the SAME dtype the leg
    collapses back to that plain dtype: the flat tuple collective over
    the same ordering is bit-identical (and cheaper — one hop), and it
    keeps fp32-everywhere per-axis spellings on the exact legacy path."""
    entries = leg_axis_entries(value)
    if entries is None:
        return value
    axes = (axis_order,) if isinstance(axis_order, str) else tuple(axis_order)
    resolved = {}
    for tok, dt in entries:
        if tok in axes:
            targets = [tok]
        elif tok in PLACEMENT_ALIASES:
            targets = [a for a in axes if placement.get(a) == tok]
            if not targets:
                raise ValueError(
                    f"collective plan entry {tok}:{dt} resolves to no mesh "
                    f"axis: no server reduce axis has {tok!r} placement "
                    f"(axes: " + ", ".join(
                        f"{a}={placement.get(a, '?')}" for a in axes) + ")")
        else:
            raise ValueError(
                f"collective plan entry names mesh axis {tok!r} which the "
                f"resolved mesh does not have (server reduce axes: "
                + ", ".join(f"{a}={placement.get(a, '?')}" for a in axes)
                + f"; placement aliases: {'/'.join(PLACEMENT_ALIASES)})")
        for a in targets:
            if a in resolved:
                raise ValueError(
                    f"collective plan covers mesh axis {a!r} twice "
                    f"(entry {tok}:{dt} overlaps an earlier entry)")
            resolved[a] = dt
    lowering = tuple((a, resolved.get(a, "float32")) for a in axes)
    dtypes = {dt for _, dt in lowering}
    if len(dtypes) == 1:
        return next(iter(dtypes))
    return lowering


@dataclass(frozen=True)
class CollectivePlan:
    """Wire dtype per collective leg. Frozen + hashable so it can ride
    ``RoundConfig`` into jit closures. ``float32`` legs run the exact
    collectives (bit-identical to the pre-plan code paths); quantized legs
    run the block-scaled stochastic-rounding EF collectives above with
    their residual carried in ``ServerState.qres`` (uplink/table) or
    ``ServerState.dres`` (downlink). A leg may also hold a per-mesh-axis
    value (``ici:fp32/dcn:int8`` — ``leg_axis_entries`` grammar); such
    legs lower hierarchically per ``resolve_leg_lowering`` with per-axis
    residual slots."""

    uplink: str = "float32"
    table: str = "float32"
    downlink: str = "float32"

    def __post_init__(self):
        for leg in PLAN_LEGS:
            dt = getattr(self, leg)
            if ":" in dt:
                leg_axis_entries(dt)  # grammar check; raises ValueError
                continue
            assert dt in WIRE_DTYPES, \
                f"collective plan leg {leg}={dt!r}: choose from " \
                f"{WIRE_DTYPES} or per-axis axis:dtype pairs"

    @property
    def quantized(self) -> bool:
        return any(leg_quantized(getattr(self, leg)) for leg in PLAN_LEGS)

    @property
    def per_axis(self) -> bool:
        """True iff any leg carries a per-mesh-axis value."""
        return any(":" in getattr(self, leg) for leg in PLAN_LEGS)

    def spec(self) -> str:
        return ",".join(f"{leg}={getattr(self, leg)}" for leg in PLAN_LEGS)


FP32_PLAN = CollectivePlan()


def parse_collective_plan(spec: str) -> CollectivePlan:
    """``--collective_plan`` grammar -> CollectivePlan. Three spellings:

    - ``''``/None — the fp32 plan (every leg exact);
    - one bare dtype (``int8``) — that dtype on EVERY leg;
    - comma-separated ``leg=dtype`` pairs
      (``uplink=int8,downlink=fp8_e4m3,table=fp32``) — unnamed legs stay
      float32. ``fp32`` is accepted as a spelling of ``float32``.

    A leg's dtype may also be PER MESH AXIS: slash-joined ``axis:dtype``
    pairs (``uplink=ici:fp32/dcn:int8``; bare ``ici:fp32/dcn:int8``
    applies to every leg), where ``axis`` is a mesh axis name or the
    ``ici``/``dcn`` placement alias — see ``resolve_leg_lowering``
    (grammar checked here; axis-vs-mesh validation happens when the mesh
    is known).

    ``auto`` is NOT handled here — callers resolve it through
    ``autotune_collective_plan`` first."""
    if not spec:
        return FP32_PLAN
    spec = spec.strip()
    assert spec != "auto", \
        "resolve --collective_plan auto via autotune_collective_plan " \
        "before parsing"

    def norm(dt):
        dt = dt.strip()
        if ":" in dt:
            # per-axis form: normalize each pair's dtype, keep the tokens
            return "/".join(f"{tok}:{d}" for tok, d in leg_axis_entries(dt))
        dt = {"fp32": "float32", "fp8": "fp8_e4m3"}.get(dt, dt)
        assert dt in WIRE_DTYPES, \
            f"collective plan dtype {dt!r}: choose from {WIRE_DTYPES}"
        return dt

    if "=" not in spec:
        dt = norm(spec)
        return CollectivePlan(uplink=dt, table=dt, downlink=dt)
    kv = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        assert "=" in part, \
            f"collective plan entry {part!r}: expected leg=dtype"
        leg, dt = part.split("=", 1)
        leg = leg.strip()
        assert leg in PLAN_LEGS, \
            f"collective plan leg {leg!r}: choose from {PLAN_LEGS}"
        assert leg not in kv, f"collective plan names leg {leg!r} twice"
        kv[leg] = norm(dt)
    return CollectivePlan(**{leg: kv.get(leg, "float32")
                             for leg in PLAN_LEGS})


def plan_from_reduce_dtype(reduce_dtype: str) -> CollectivePlan:
    """The legacy ``--reduce_dtype`` alias: ``float32`` is the fp32 plan;
    ``int8`` sets EVERY leg to int8 (the full-compressed round — PR 2's
    flag compressed only the transmit reduce, but keeping a partial alias
    would leave the downlink the one fp32 leg forever)."""
    assert reduce_dtype in ("float32", "int8"), reduce_dtype
    if reduce_dtype == "int8":
        return CollectivePlan(uplink="int8", table="int8", downlink="int8")
    return FP32_PLAN


def autotune_collective_plan(leg_geoms, error_budget: float = 0.05,
                             seed: int = 0, sample_cap: int = 1 << 20,
                             candidates=QUANT_DTYPES):
    """``--collective_plan auto``: one-time on-chip probe that picks the
    cheapest wire dtype per leg within an error budget.

    ``leg_geoms``: ``{leg: (elements, block)}`` for the legs the config
    actually exercises (absent/None legs resolve to float32). For each
    {leg x dtype} candidate the probe (a) times a jitted
    quantize->dequantize round trip over a calibration transmit (standard
    normal, capped at ``sample_cap`` elements so GPT-2-sized legs don't
    stall startup — the error statistic is per-block, so a sample of
    blocks estimates it), and (b) measures the round trip's relative L2
    error. A candidate is admissible iff its error is within
    ``error_budget``; among admissible candidates (float32 always is, at
    error 0) the CHEAPEST by ``payload_bytes`` wins, ties broken by lower
    error. Probe timings are reported, not gated — wall-clock per
    candidate is microseconds and the quantize cost rides the round step
    itself (not measured on the chip: no cell runs a quantized plan).

    Returns ``(plan, report)`` where ``report[leg][dtype]`` carries
    ``{"rel_err", "probe_ms", "bytes_per_round"}`` (plus ``"error"`` for
    a candidate whose probe failed to compile on this backend) — logged
    into the telemetry run_start event so the chosen plan is auditable
    from the run log alone."""
    import time as _time

    import numpy as _np

    report = {}
    chosen = {}
    for leg in PLAN_LEGS:
        geom = leg_geoms.get(leg)
        if geom is None:
            chosen[leg] = "float32"
            continue
        elems, block = geom
        elems = int(elems)
        block = int(min(block or DEFAULT_QUANT_BLOCK, max(1, elems)))
        n_elem = min(elems, int(sample_cap))
        nb = max(1, n_elem // block)
        cal = jnp.asarray(
            _np.random.RandomState(seed).randn(nb, block).astype(_np.float32))
        cal_norm = float(jnp.sqrt(jnp.sum(jnp.square(cal))))
        rng = jax.random.key(seed)
        rows = {"float32": {"rel_err": 0.0, "probe_ms": 0.0,
                            "bytes_per_round": payload_bytes(
                                elems, "float32", block)}}
        best = ("float32", rows["float32"]["bytes_per_round"], 0.0)
        for dt in candidates:
            bytes_ = payload_bytes(elems, dt, block)

            def rt(x, r, dt=dt):
                q, s = quantize_blocks(x, r, dt)
                return dequantize_blocks(q, s, dt, block)

            try:
                f = jax.jit(rt)
                y = jax.block_until_ready(f(cal, rng))
                t_best = float("inf")
                for _ in range(3):
                    t0 = _time.perf_counter()
                    jax.block_until_ready(f(cal, rng))
                    t_best = min(t_best, _time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001 — backend w/o the dtype
                rows[dt] = {"error": f"{type(e).__name__}: {str(e)[:120]}"}
                continue
            rel = float(jnp.sqrt(jnp.sum(jnp.square(cal - y)))) \
                / max(cal_norm, 1e-30)
            rows[dt] = {"rel_err": round(rel, 6),
                        "probe_ms": round(t_best * 1e3, 3),
                        "bytes_per_round": bytes_}
            if rel <= error_budget and (
                    bytes_ < best[1]
                    or (bytes_ == best[1] and rel < best[2])):
                best = (dt, bytes_, rel)
        chosen[leg] = best[0]
        report[leg] = rows
    return CollectivePlan(**chosen), report
