"""Count-sketch compression (the CSVec replacement), TPU-first.

Re-implements the capability surface of the external ``csvec`` package the
reference depends on (used at reference fed_aggregator.py:5,464-467,584-611 and
fed_worker.py:10,313-320):

- sketch a d-dim vector into an ``(r, c)`` table with r independent bucket
  hashes and ±1 sign hashes  (``CSVec.accumulateVec``  → ``sketch_vec``)
- tables are linear: sum of sketches == sketch of sum
  (``CSVec.accumulateTable`` → plain ``+`` on tables)
- recover the top-k heavy hitters via median-of-rows estimation
  (``CSVec.unSketch(k)``    → ``unsketch``)
- L2-norm estimate of the sketched vector (``CSVec.l2estimate``)

Hash-family design (deliberate, documented deviation). CSVec draws bucket
hashes from polynomial families mod 2**61-1 — int64 math that is emulated on
TPU — and accumulates with a scatter, which XLA serializes. Both are wrong for
the hardware. We instead use a **chunked-cyclic family**: the coordinate space
is split into ``T = ceil(d / c_pad)`` contiguous chunks of the (lane-aligned)
table width; chunk ``t`` maps into row ``j`` by a full cyclic shift,

    bucket_j(i) = (pos(i) + m[j, t]) mod c_pad ,       pos(i) = i mod c_pad

with ``m[j, t]`` drawn uniformly from ``[0, c_pad)`` by a seeded host-side
RNG. Sign hashes are per-(row, coordinate) murmur3-finalizer bits. Properties:

- *linear & mergeable*: geometry is fully determined by ``(seed, r, c, d)``;
- *within-chunk collision-free*: a cyclic shift is a permutation, so two
  coordinates in the same chunk never collide — strictly better than
  2-universal hashing for those pairs;
- *cross-chunk*: two coordinates in different chunks collide in a row iff the
  two chunks' shifts differ by exactly their position offset — probability
  ``1/c_pad`` per row, independent across rows: identical to ideal
  count-sketch collision behavior;
- *scatter-free*: a cyclic roll by ``m = 128·q + w`` decomposes into a lane
  rotation by ``w`` (a per-row roll plus a sublane-carry select for the
  wrapped lanes) followed by a sublane roll by ``q`` — pure data movement,
  bit-exact. No scatter, no gather, no int64, no matmuls (an earlier
  permutation-matmul formulation hit XLA:TPU's bf16 matmul passes and
  silently cost ~3 digits of table precision).

The accumulate path also ships as a fused Pallas kernel (``_sketch_vec_pallas``)
that keeps each table row resident in VMEM across all T chunks (grid
``(r, T)`` with output revisiting), computing sign hashes on the fly from
``broadcasted_iota`` and the roll via the hardware lane-rotate unit — only
the gradient is read from HBM. ``sketch_vec`` dispatches to it on TPU.

All paths are jit/vmap/shard_map-safe: static shapes, no data-dependent
control flow, chunk loop is a ``lax.scan``.

Fidelity at FetchSGD scale (d≈6.5M, 5×500k, k=50k, power-law inputs) is
measured in ``scripts/sketch_fidelity.py`` and recorded in
``docs/sketch_fidelity.md``: top-k mass recall 1.0000 and relative L2 of the
recovered update 0.0012 vs 0.0014 for an ideal fully-random-hash
count-sketch — within noise of (marginally better than) 2-universal hashing,
because within-chunk heavy-hitter pairs never collide.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from flax import struct

from commefficient_tpu.ops.topk import require_equal

_LANES = 128
_M1 = np.int32(np.uint32(0x85EBCA6B).astype(np.int64) - (1 << 32))
_M2 = np.int32(np.uint32(0xC2B2AE35).astype(np.int64) - (1 << 32))

# zero chunk offset for the full-range kernel calls (a jit-time constant)
_T0 = np.zeros(1, np.int32)


def _mix32(x: jax.Array) -> jax.Array:
    """murmur3 fmix32 avalanche over int32 bit patterns (wrapping mul +
    logical shifts — identical bits to the uint32 formulation, but lowers to
    plain VPU int32 ops inside Pallas kernels)."""
    srl = jax.lax.shift_right_logical
    x = x ^ srl(x, 16)
    x = x * _M1
    x = x ^ srl(x, 13)
    x = x * _M2
    x = x ^ srl(x, 16)
    return x


def _signs_for(idx: jax.Array, key: jax.Array) -> jax.Array:
    """±1 float32 sign hash for int32 coordinate indices."""
    h = _mix32(idx ^ key)
    return (h & 1).astype(jnp.float32) * 2.0 - 1.0


def _lane_rotate(x2d: jax.Array, w: jax.Array) -> jax.Array:
    """Rotate the flattened ``(S, 128)`` array right by ``w ∈ [0, 128)`` flat
    positions: lane rotation with sublane carry.

    ``y[a, j] = x[a, j-w]`` for ``j >= w`` and ``x[(a-1) mod S, j-w+128]``
    otherwise — a per-row lane roll plus a sublane-carry select for the
    wrapped lanes. Pure data movement, bit-exact. (An earlier formulation
    multiplied by a 128×128 0/1 permutation matrix "for the MXU"; XLA:TPU
    computes f32 matmuls in bf16 passes, which silently rounded every
    sketched value to ~3 decimal digits — measured ~1% table error vs a
    float64 reference. Rolls are both exact and cheaper.)
    """
    z = jnp.roll(x2d, w, axis=1)
    zc = jnp.roll(z, 1, axis=0)
    j = jax.lax.broadcasted_iota(jnp.int32, x2d.shape, 1)
    return jnp.where(j >= w, z, zc)


def _roll2d(x2d: jax.Array, q: jax.Array, w: jax.Array) -> jax.Array:
    """Cyclic roll of the flattened ``(S, 128)`` array by ``128·q + w``."""
    z = _lane_rotate(x2d, w)
    return jnp.roll(z, q, axis=0)


@struct.dataclass
class CountSketch:
    """Hash geometry for a count-sketch. A pytree; static ints are aux data."""

    shift_q: jax.Array   # (r, T) int32 — sublane part of the forward shift
    shift_w: jax.Array   # (r, T) int32 — lane part of the forward shift
    inv_q: jax.Array     # (r, T) int32 — sublane part of the inverse shift
    inv_w: jax.Array     # (r, T) int32 — lane part of the inverse shift
    sign_keys: jax.Array  # (r,) int32 — per-row sign-hash keys
    d: int = struct.field(pytree_node=False)
    c: int = struct.field(pytree_node=False)       # user-requested columns
    c_pad: int = struct.field(pytree_node=False)   # lane-aligned columns
    r: int = struct.field(pytree_node=False)
    T: int = struct.field(pytree_node=False)       # number of chunks
    num_blocks: int = struct.field(pytree_node=False)

    @property
    def table_shape(self):
        return (self.r, self.c_pad)

    @property
    def sublanes(self):
        return self.c_pad // _LANES

    @property
    def chunk_layout(self):
        """The ``(T, S, 128)`` resident layout this sketch's kernels consume
        (ops/flat.ChunkLayout) — the layout the chunked-resident round keeps
        PS state in so ``sketch_chunks``/``estimates_chunks`` need no per-round
        pad/reshape."""
        from commefficient_tpu.ops.flat import ChunkLayout

        return ChunkLayout(d=self.d, T=self.T, S=self.sublanes)


def make_sketch(d: int, c: int, r: int, seed: int = 42,
                num_blocks: int = 20) -> CountSketch:
    """Build sketch geometry (mirrors ``args2sketch``, reference
    fed_aggregator.py:464-467). Host-side, deterministic in ``seed``.

    ``num_blocks`` is accepted for CLI parity (reference utils.py:145); the
    chunked-cyclic layout already bounds transient memory to O(r·c_pad), so it
    is recorded but not needed for correctness.
    """
    c_pad = -(-int(c) // _LANES) * _LANES
    T = max(1, -(-int(d) // c_pad))
    rng = np.random.RandomState(seed)
    m = rng.randint(0, c_pad, size=(r, T))
    inv = (-m) % c_pad
    keys = rng.randint(1, 2**31 - 1, size=(r,))
    # primary trigger for the one-time kernel self-checks: sketch
    # geometry construction is always eager host-side setup, while
    # ``sketch_vec``/``estimates`` themselves usually run inside a jit
    # trace where the checks cannot execute
    _check_sketch_kernel_once(eager=True)
    _check_estimates_kernel_once(eager=True)
    return CountSketch(
        shift_q=jnp.asarray(m // _LANES, jnp.int32),
        shift_w=jnp.asarray(m % _LANES, jnp.int32),
        inv_q=jnp.asarray(inv // _LANES, jnp.int32),
        inv_w=jnp.asarray(inv % _LANES, jnp.int32),
        sign_keys=jnp.asarray(keys, jnp.int32),
        d=int(d),
        c=int(c),
        c_pad=int(c_pad),
        r=int(r),
        T=int(T),
        num_blocks=int(num_blocks),
    )


def _chunks3(cs: CountSketch, v: jax.Array) -> jax.Array:
    """Pad ``(d,)`` → ``(T, S, 128)`` chunk/sublane/lane layout."""
    v_p = jnp.pad(v.astype(jnp.float32), (0, cs.T * cs.c_pad - cs.d))
    return v_p.reshape(cs.T, cs.sublanes, _LANES)


def _chunk_signs(cs: CountSketch, t_base: jax.Array) -> jax.Array:
    """Sign hashes for one chunk, all rows — ``(r, S, 128)``."""
    S = cs.sublanes
    idx = t_base + (
        jax.lax.broadcasted_iota(jnp.int32, (S, _LANES), 0) * _LANES
        + jax.lax.broadcasted_iota(jnp.int32, (S, _LANES), 1))
    return jax.vmap(lambda k: _signs_for(idx, k))(cs.sign_keys)


def _median_small(rows):
    """Elementwise median of a static-length list via a min/max sorting
    network — avoids ``sort`` lowerings that Pallas TPU lacks, and is used by
    both the pure and kernel paths so results match bit-for-bit."""
    arr = list(rows)
    n = len(arr)
    for i in range(n):
        for j in range(n - 1 - i):
            lo = jnp.minimum(arr[j], arr[j + 1])
            hi = jnp.maximum(arr[j], arr[j + 1])
            arr[j], arr[j + 1] = lo, hi
    if n % 2:
        return arr[n // 2]
    return 0.5 * (arr[n // 2 - 1] + arr[n // 2])


# --------------------------------------------------------------------------
# accumulate: dense (d,) -> (r, c_pad) table
# --------------------------------------------------------------------------

def _sketch_vec_jax(cs: CountSketch, v: jax.Array) -> jax.Array:
    return _sketch_chunks_jax(cs, _chunks3(cs, v))


def _local_shift_cols(q: jax.Array, w: jax.Array, t0, Tn: int):
    """Columns ``[t0, t0+Tn)`` of the ``(r, T)`` shift arrays, for a
    TRACED global chunk offset ``t0``. Zero-padded by ``Tn`` first so the
    dynamic slice never clamps across valid columns: a slice containing
    any valid chunk (``t0 < T``) is fully in bounds, and a slice entirely
    past ``T`` (sharded-server tail shards) reads padding/clamped values
    whose outputs are tail-masked anyway (all their coordinates ≥ d)."""
    qp = jnp.pad(q, ((0, 0), (0, Tn)))
    wp = jnp.pad(w, ((0, 0), (0, Tn)))
    q_cols = jax.lax.dynamic_slice_in_dim(qp, t0, Tn, axis=1)
    w_cols = jax.lax.dynamic_slice_in_dim(wp, t0, Tn, axis=1)
    return q_cols, w_cols


def _sketch_chunks_jax(cs: CountSketch, v3: jax.Array,
                       t0=None) -> jax.Array:
    """Accumulate chunk layout → table. ``t0`` (traced, default chunk 0)
    offsets the chunks' global coordinate base — the sharded-server
    partial accumulate: ``v3`` then holds ``Tn ≤ T`` chunks starting at
    global chunk ``t0`` and the result is that range's PARTIAL table
    (linearity: the psum of the shards' partials is the full table)."""
    S = cs.sublanes
    Tn = v3.shape[0]

    def body(table, xs):
        chunk, q_r, w_r, t_base = xs
        sv = chunk[None, :, :] * _chunk_signs(cs, t_base)          # (r, S, 128)
        rolled = jax.vmap(_roll2d)(sv, q_r, w_r)
        return table + rolled, None

    if t0 is None:
        q_cols, w_cols = cs.shift_q, cs.shift_w
        t_bases = jnp.arange(Tn, dtype=jnp.int32) * (S * _LANES)
    else:
        q_cols, w_cols = _local_shift_cols(cs.shift_q, cs.shift_w, t0, Tn)
        t_bases = (jnp.asarray(t0, jnp.int32)
                   + jnp.arange(Tn, dtype=jnp.int32)) * (S * _LANES)
    init = jnp.zeros((cs.r, S, _LANES), jnp.float32)
    table, _ = jax.lax.scan(
        body, init, (v3, q_cols.T, w_cols.T, t_bases))
    return table.reshape(cs.r, cs.c_pad)


@functools.partial(jax.jit, static_argnames=("S", "T", "interpret"))
def _sketch_vec_pallas(v3, shift_q, shift_w, sign_keys, t0, *, S, T,
                       interpret=False):
    """Fused accumulate kernel. Grid ``(r, T)``: each table row stays resident
    in VMEM while the T gradient chunks stream through; sign hashes come from
    iotas and the cyclic shift from the hardware lane-rotate plus a doubled-
    buffer sublane slice (only the gradient is read from HBM).

    ``t0`` ((1,) int32 scalar prefetch) is the chunks' global index offset:
    0 for the full accumulate, the shard's first global chunk for the
    sharded-server partial accumulate (shift arrays then arrive pre-sliced
    to the local range; only the sign-hash coordinate base needs the
    offset). With ``t0 == 0`` the math is bit-identical to the pre-offset
    kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = shift_q.shape[0]
    chunk_elems = S * _LANES

    def kernel(q_ref, w_ref, key_ref, t0_ref, v_ref, out_ref, dbl):
        row = pl.program_id(0)
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        idx = (t0_ref[0] + t) * chunk_elems + (
            jax.lax.broadcasted_iota(jnp.int32, (S, _LANES), 0) * _LANES
            + jax.lax.broadcasted_iota(jnp.int32, (S, _LANES), 1))
        sv = v_ref[0] * _signs_for(idx, key_ref[row])
        # flattened cyclic roll by 128·q + w: lane roll by w via the hardware
        # rotate unit (tpu.dynamic_rotate — far cheaper than the permutation-
        # matmul formulation the pure-XLA path uses; lanes are always 128-
        # aligned, while sublane rotates reject the unaligned S here), a
        # sublane-carry select for the wrapped lanes, then a sublane roll by
        # q — both sublane shifts via the double-buffer scratch + dynamic
        # slice, which is alignment-agnostic.
        w = w_ref[row, t]
        z = pltpu.roll(sv, w, axis=1)
        dbl[:S] = z
        dbl[S:] = z
        # fused carry + sublane roll: the target is
        #   out[a, j] = y[(a-q) mod S, j],  y[a, j] = z[a, j]   (j >= w)
        #                                            z[a-1, j]  (j <  w)
        # with z doubled in dbl both cases are plain slices (indices stay in
        # [0, 2S) for q in [0, S-1]), so one select finishes the job without
        # materializing y through VMEM again
        q = q_ref[row, t]
        j = jax.lax.broadcasted_iota(jnp.int32, (S, _LANES), 1)
        out_ref[0] += jnp.where(j >= w, dbl[pl.ds(S - q, S), :],
                                dbl[pl.ds(S - q - 1, S), :])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r, T),
        in_specs=[
            pl.BlockSpec((1, S, _LANES), lambda row, t, *_: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, S, _LANES), lambda row, t, *_: (row, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2 * S, _LANES), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, S, _LANES), jnp.float32),
        interpret=interpret,
        name="fed_sketch_vec",
    )(shift_q, shift_w, sign_keys, t0, v3)
    return out


def _use_pallas() -> bool:
    import os

    from commefficient_tpu.utils import is_tpu_backend

    return (is_tpu_backend()
            and os.environ.get("COMMEFFICIENT_PALLAS", "1") != "0")


def _use_pallas_sketch() -> bool:
    """Kill-switch for the accumulate kernel, separate from the query
    kernel's, so a Mosaic regression in either path can be disabled without
    losing the other."""
    import os

    return (_use_pallas()
            and os.environ.get("COMMEFFICIENT_PALLAS_SKETCH", "1") != "0")


def _sketch_interpret_forced() -> bool:
    """COMMEFFICIENT_PALLAS_SKETCH=interpret forces the running-table
    accumulate kernel through the Pallas interpreter even off-TPU — the
    CPU-mesh test hook (mirroring COMMEFFICIENT_FUSED_EPILOGUE=interpret)
    that lets the structural launch-count asserts of
    tests/test_sketch_coalesce.py see real ``pallas_call`` eqns in the
    jitted client phase instead of the pure-XLA scan fold."""
    import os

    return os.environ.get("COMMEFFICIENT_PALLAS_SKETCH") == "interpret"


def _use_pallas_estimates() -> bool:
    """Separate kill-switch for the query kernel so a failure there (newer,
    DMA-based) can be disabled without losing the proven accumulate kernel."""
    import os

    return (_use_pallas()
            and os.environ.get("COMMEFFICIENT_PALLAS_ESTIMATES", "1") != "0")


def _trace_state_clean() -> bool:
    """True when no jit trace is active (private API; a jax that moves it
    raises here rather than silently skipping the self-checks)."""
    from jax._src import core as _core

    return bool(_core.trace_state_clean())


# The kernel self-checks. Each compares one compiled kernel (or, with
# ``interpret=True``, its interpreted body — the CPU rehearsal of
# chip_smoke.py) against the pure ``jnp`` path on geometry ``cs`` and
# raises on any difference; a kernel Mosaic refuses raises from the
# compile itself. There is NO fallback: on a TPU a kernel that cannot be
# trusted stops the run instead of quietly moving it onto the XLA path.
# chip_smoke.py runs every one of them at the full ResNet9 geometry; the
# ``_check_*_once`` wrappers below run them once per process, at a small
# multi-chunk geometry, before first use.

def _check_geometry() -> CountSketch:
    # S > 1024 sublanes: the estimates kernel's multi-sub-block (G > 1)
    # window path, whose DMA starts reach into the doubled+padded region
    return make_sketch(d=450_000, c=140_000, r=3, seed=11, num_blocks=2)


def _check_vec(cs: CountSketch, seed: int):
    return jnp.asarray(np.random.RandomState(seed).randn(cs.d), jnp.float32)


def _check_table(cs: CountSketch, seed: int):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*cs.table_shape), jnp.float32)


def check_estimates_kernel(cs: CountSketch, interpret: bool = False) -> None:
    """``_estimates_pallas`` == ``_estimates_jax``, plus the sharded-server
    local query (t0 ≠ 0, pre-sliced shifts) == the full path's slice."""
    tbl = _check_table(cs, 5)
    got = _estimates_pallas(
        _doubled_table(cs, tbl), cs.shift_q, cs.shift_w, cs.sign_keys,
        _T0, S=cs.sublanes, T=cs.T, c_pad=cs.c_pad, interpret=interpret)
    require_equal(np.asarray(got).reshape(-1)[: cs.d],
                  _estimates_jax(cs, tbl), "estimates")
    t0v, Tn = 1, min(2, cs.T - 1)
    got_l = estimates_chunks_local(cs, tbl, jnp.int32(t0v), Tn,
                                   interpret=interpret)
    want_l = cs.chunk_layout.mask_tail(got)[t0v:t0v + Tn]
    require_equal(got_l, want_l, "estimates (local query)")


def check_sketch_vec_kernel(cs: CountSketch, interpret: bool = False) -> None:
    """``_sketch_vec_pallas`` == ``_sketch_vec_jax``, plus the
    sharded-server partial accumulate (t0 ≠ 0) == the pure partial."""
    v = _check_vec(cs, 6)
    v3 = _chunks3(cs, v)
    got = _sketch_vec_pallas(
        v3, cs.shift_q, cs.shift_w, cs.sign_keys, _T0,
        S=cs.sublanes, T=cs.T, interpret=interpret).reshape(cs.r, cs.c_pad)
    require_equal(got, _sketch_vec_jax(cs, v), "sketch_vec")
    t0v, Tn = 1, min(2, cs.T - 1)
    got_l = sketch_chunks_local(cs, v3[t0v:t0v + Tn], jnp.int32(t0v),
                                interpret=interpret)
    want_l = _sketch_chunks_jax(cs, v3[t0v:t0v + Tn], jnp.int32(t0v))
    require_equal(got_l, want_l, "sketch_vec (local accumulate)")


def _check_segment(cs: CountSketch):
    """A vector, a running table, and the bounds of an unaligned flat
    segment of the vector that spans a chunk boundary."""
    return (_check_vec(cs, 6), _check_table(cs, 8), 137,
            min(cs.d, cs.c_pad + 50_011))


def check_sketch_segments_kernel(cs: CountSketch,
                                 interpret: bool = False) -> None:
    """``_sketch_segments_pallas`` (docs/stream_sketch.md): ONE launch over
    a group of contiguous segments, at an unaligned element offset spanning
    a chunk boundary, must bit-continue the pure fold of the same span
    onto a running table (through the dispatcher, which assembles the
    group)."""
    v, tbl0, a, b = _check_segment(cs)
    cuts = (a, a + 11_003, a + 11_004, b)
    got = sketch_segments_accum(
        cs, tbl0, [v[x:y] for x, y in zip(cuts[:-1], cuts[1:])], a,
        interpret=interpret)
    seg3, t_a = _segment_chunks(cs, v[a:b], a)
    require_equal(got, _sketch_accum_chunks_jax(cs, tbl0, seg3, t_a),
                  "sketch_segments")


_ESTIMATES_KERNEL_CHECKED = False


def _check_estimates_kernel_once(eager: bool = False) -> None:
    """One-time on-TPU self-check of the DMA query kernel before first
    use, process-wide. Must run OUTSIDE any jit trace (inside one, every
    jax op lifts into the trace); the primary trigger is ``make_sketch`` —
    always host-side eager setup — which passes ``eager=True``."""
    global _ESTIMATES_KERNEL_CHECKED
    if _ESTIMATES_KERNEL_CHECKED:
        return
    if not _use_pallas_estimates():
        # respect the operator kill-switch: never compile a kernel the env
        # disabled (a Mosaic hard-crash there is not a catchable exception)
        return
    if not eager and not _trace_state_clean():
        return
    _ESTIMATES_KERNEL_CHECKED = True
    check_estimates_kernel(_check_geometry())


_SKETCH_KERNEL_CHECKED = False


def _check_sketch_kernel_once(eager: bool = False) -> None:
    """One-time on-TPU self-check of the accumulate kernels (zero-init and
    running-table), mirroring
    ``_check_estimates_kernel_once``. Primary trigger is ``make_sketch``;
    the accumulate entry points also trigger it when called eagerly,
    covering CountSketch objects that bypassed ``make_sketch`` (e.g.
    deserialized ones)."""
    global _SKETCH_KERNEL_CHECKED
    if _SKETCH_KERNEL_CHECKED:
        return
    if not _use_pallas_sketch():
        return
    if not eager and not _trace_state_clean():
        return
    _SKETCH_KERNEL_CHECKED = True
    cs = _check_geometry()
    check_sketch_vec_kernel(cs)
    check_sketch_segments_kernel(cs)


def sketch_vec(cs: CountSketch, v: jax.Array) -> jax.Array:
    """Accumulate a dense ``(d,)`` vector into an ``(r, c_pad)`` table.

    Equivalent of ``CSVec.accumulateVec`` + ``.table`` (reference
    fed_worker.py:313-320). Linear in ``v``.
    """
    if _trace_state_clean():
        # entry point for sketches that bypassed make_sketch (e.g.
        # deserialized): an eager first call still gets the self-check
        _check_sketch_kernel_once(eager=True)
    if _use_pallas_sketch():
        v3 = _chunks3(cs, v)
        out = _sketch_vec_pallas(v3, cs.shift_q, cs.shift_w, cs.sign_keys,
                                 _T0, S=cs.sublanes, T=cs.T)
        return out.reshape(cs.r, cs.c_pad)
    return _sketch_vec_jax(cs, v)


def sketch_chunks(cs: CountSketch, v3: jax.Array) -> jax.Array:
    """Accumulate a vector already in the ``(T, S, 128)`` resident chunk
    layout (ops/flat.ChunkLayout — zero-padded tail) into an ``(r, c_pad)``
    table. Identical result to ``sketch_vec(cs, unchunk(v3))`` — the chunking
    is pure layout — but with no per-call pad/reshape: the chunked-resident
    round's accumulate entry point."""
    assert v3.shape == (cs.T, cs.sublanes, _LANES), \
        f"expected chunk layout {(cs.T, cs.sublanes, _LANES)}, got {v3.shape}"
    if _trace_state_clean():
        _check_sketch_kernel_once(eager=True)
    if _use_pallas_sketch():
        out = _sketch_vec_pallas(v3, cs.shift_q, cs.shift_w, cs.sign_keys,
                                 _T0, S=cs.sublanes, T=cs.T)
        return out.reshape(cs.r, cs.c_pad)
    return _sketch_chunks_jax(cs, v3)


@functools.partial(jax.jit, static_argnames=("S", "T", "interpret"))
def _sketch_segments_pallas(tbl3, v3, shift_q, shift_w, sign_keys, t0, *, S,
                            T, interpret=False):
    """``_sketch_vec_pallas`` with a RUNNING-TABLE init (on the device the
    kernel ``fed_sketch_accum``; docs/stream_sketch.md): the output row
    starts from ``tbl3``'s row instead of zeros, then accumulates the T
    chunks exactly like the zero-init kernel. Per (row, cell) the f32 adds
    are ``tbl + c_0 + c_1 + ...`` in chunk order — bit-continuing the pure
    scan's left fold, which is what lets the client phase sketch a gradient
    group by group and still match the flat ``sketch_chunks`` route's
    per-cell add order. ``v3`` holds a whole group's covering chunk range
    (many leaves, one launch), so the table row block is read and written
    once per group. ``t0`` is the chunks' global index offset as in
    ``_sketch_vec_pallas`` (shift arrays arrive pre-sliced to the local
    chunk range)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = shift_q.shape[0]
    chunk_elems = S * _LANES

    def kernel(q_ref, w_ref, key_ref, t0_ref, tbl_ref, v_ref, out_ref, dbl):
        row = pl.program_id(0)
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _():
            out_ref[...] = tbl_ref[...]

        idx = (t0_ref[0] + t) * chunk_elems + (
            jax.lax.broadcasted_iota(jnp.int32, (S, _LANES), 0) * _LANES
            + jax.lax.broadcasted_iota(jnp.int32, (S, _LANES), 1))
        sv = v_ref[0] * _signs_for(idx, key_ref[row])
        # identical roll scheme to _sketch_vec_pallas (see its docstring)
        w = w_ref[row, t]
        z = pltpu.roll(sv, w, axis=1)
        dbl[:S] = z
        dbl[S:] = z
        q = q_ref[row, t]
        j = jax.lax.broadcasted_iota(jnp.int32, (S, _LANES), 1)
        out_ref[0] += jnp.where(j >= w, dbl[pl.ds(S - q, S), :],
                                dbl[pl.ds(S - q - 1, S), :])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r, T),
        in_specs=[
            pl.BlockSpec((1, S, _LANES), lambda row, t, *_: (row, 0, 0)),
            pl.BlockSpec((1, S, _LANES), lambda row, t, *_: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, S, _LANES), lambda row, t, *_: (row, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2 * S, _LANES), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, S, _LANES), jnp.float32),
        interpret=interpret,
        name="fed_sketch_accum",
    )(shift_q, shift_w, sign_keys, t0, tbl3, v3)


def _sketch_accum_chunks_jax(cs: CountSketch, table: jax.Array,
                             v3: jax.Array, t_a: int) -> jax.Array:
    """Pure-XLA running-table accumulate of ``Tn`` chunks starting at
    STATIC global chunk ``t_a``: the same scan body as
    ``_sketch_chunks_jax`` with ``init = table`` — per cell, one f32 add
    per chunk onto the incoming value, in chunk order."""
    S = cs.sublanes
    Tn = v3.shape[0]
    q_cols = cs.shift_q[:, t_a:t_a + Tn]
    w_cols = cs.shift_w[:, t_a:t_a + Tn]
    t_bases = (t_a + jnp.arange(Tn, dtype=jnp.int32)) * (S * _LANES)

    def body(tbl, xs):
        chunk, q_r, w_r, t_base = xs
        sv = chunk[None, :, :] * _chunk_signs(cs, t_base)
        rolled = jax.vmap(_roll2d)(sv, q_r, w_r)
        return tbl + rolled, None

    tbl, _ = jax.lax.scan(
        body, table.reshape(cs.r, S, _LANES), (v3, q_cols.T, w_cols.T,
                                               t_bases))
    return tbl.reshape(cs.r, cs.c_pad)


def _segment_chunks(cs: CountSketch, seg: jax.Array, e0: int):
    """STATIC-offset segment prep: zero-pad the 1-D segment out to the
    chunk boundaries it touches and reshape to the ``(Tn, S, 128)`` chunk
    layout of chunks ``[t_a, t_a + Tn)``. Pads are segment-sized (+ < 2
    chunks), never d-sized. Zero-padded positions contribute sign·0 =
    ±0.0 to their cells, the one deviation from the flat route (cells
    whose every contribution is a signed zero can differ in the SIGN of
    their zero; never in ``==``)."""
    n = int(seg.size)
    ce = cs.c_pad
    t_a = e0 // ce
    lpad = e0 - t_a * ce
    Tn = -(-(lpad + n) // ce)
    v = jnp.pad(seg.reshape(-1).astype(jnp.float32),
                (lpad, Tn * ce - lpad - n))
    return v.reshape(Tn, cs.sublanes, _LANES), t_a


# staging ceiling for the segment coalescer's auto budget: far above any
# single covering chunk range worth coalescing, far below the d-plane
_COALESCE_MAX_BUDGET = 32 * 1024 * 1024


def coalesce_vmem_budget(cs: CountSketch) -> int:
    """Auto group-sizing budget (bytes) for ``ops/flat.coalesce_segments``
    (docs/stream_sketch.md). The accumulate kernel streams a group's
    chunks through VMEM one ``(S, 128)`` block at a time while the table
    row block stays resident, so its per-step VMEM is
    group-size-INDEPENDENT; what the budget bounds is the group's covering
    chunk-range STAGING buffer — the concatenate+pad of the group's
    leaves — which must stay well under d, or the client phase holds a
    second copy of the gradient after all. ``min(32 MiB, max(one chunk,
    padded/4))``: GPT-2 124M (c_pad≈500k, T=249) gets 32 MiB ≈ 16-chunk
    groups, ~150 leaves in ~16 launches, while the CIFAR FetchSGD geometry
    (T=14) gets ~7 MiB ≈ 3-chunk groups, and no geometry ever stages more
    than max(one chunk, a quarter of its padded plane) — the one-chunk
    floor means a T<4 geometry can stage up to its whole (tiny) plane,
    which is already smaller than a single launch's table block."""
    chunk_bytes = cs.c_pad * 4
    padded = cs.T * chunk_bytes
    return int(min(_COALESCE_MAX_BUDGET, max(chunk_bytes, padded // 4)))


def sketch_segments_accum(cs: CountSketch, table: jax.Array, segs,
                          e0: int, decay=None,
                          interpret: bool = False) -> jax.Array:
    """ONE kernel launch for a GROUP of contiguous flat segments
    (docs/stream_sketch.md), accumulated onto a RUNNING ``(r, c_pad)``
    table: ``segs`` is a sequence of 1-D arrays where segment ``i`` starts
    exactly where ``i-1`` ends and the first starts at STATIC flat offset
    ``e0`` (leaf offsets of a pytree layout are trace-time constants, and
    adjacent leaves of the ``ops/flat.leaf_segments`` layout are
    contiguous by construction — ``ops/flat.coalesce_segments`` plans the
    groups). The group's covering chunk-range buffer is assembled at trace
    time (concatenate + the chunk-boundary pads of ``_segment_chunks`` —
    group-sized, never d-sized) and handed to the running-table kernel,
    which keeps each table row block VMEM-resident across EVERY chunk of
    the group: one table read + one table write per group.

    Bit-compatibility (pinned in tests/test_sketch_coalesce.py and
    tests/test_stream_sketch.py): per table cell and chunk exactly one
    coordinate contributes and consecutive groups visit the chunks in the
    order ``sketch_vec`` does, so sketching a d-vector group by group in
    offset order equals ``sketch_vec`` of the whole vector — the only
    deviation is the boundary chunks' extra ``±0.0`` terms (a chunk two
    groups straddle is visited once by each), i.e. the sign of all-zero
    cells; never a value under ``==``. Zero-size segments are skipped.

    ``decay`` (optional, ``(coef, plane)``) adds ``coef · plane`` over the
    group's coordinates, ``plane`` being a ``(T, S, 128)`` resident vector
    (the weights): the covering chunk range of the plane IS the staging
    buffer's layout, so weight decay is one multiply-add inside the
    staging pass (``g + coef · w``, the flat route's arithmetic element for
    element) and reads the plane where it lies: no leaf of the weights is
    sliced out or kept alive for it. Positions of the boundary chunks
    outside ``[e0, e0 + n)`` belong to the neighbouring groups and are
    masked to zero."""
    e0 = int(e0)
    xs = [s.reshape(-1).astype(jnp.float32) for s in segs if int(s.size)]
    n = sum(int(x.size) for x in xs)
    assert table.shape == cs.table_shape, (table.shape, cs.table_shape)
    if n == 0:
        return table
    assert 0 <= e0 and e0 + n <= cs.d, (e0, n, cs.d)
    v = xs[0] if len(xs) == 1 else jnp.concatenate(xs)
    v3, t_a = _segment_chunks(cs, v, e0)
    if decay is not None:
        coef, plane = decay
        assert plane.shape == (cs.T, cs.sublanes, _LANES), plane.shape
        w3 = plane[t_a:t_a + v3.shape[0]]
        lo = e0 - t_a * cs.c_pad
        if lo or lo + n != v3.size:
            pos = sum(jax.lax.broadcasted_iota(jnp.int32, v3.shape, ax) * m
                      for ax, m in enumerate((cs.c_pad, _LANES, 1)))
            w3 = jnp.where((pos >= lo) & (pos < lo + n), w3, 0.0)
        v3 = v3 + coef * w3
    if _trace_state_clean():
        _check_sketch_kernel_once(eager=True)
    interpret = interpret or _sketch_interpret_forced()
    if _use_pallas_sketch() or interpret:
        out = _sketch_segments_pallas(
            table.reshape(cs.r, cs.sublanes, _LANES), v3,
            cs.shift_q[:, t_a:t_a + v3.shape[0]],
            cs.shift_w[:, t_a:t_a + v3.shape[0]], cs.sign_keys,
            np.full(1, t_a, np.int32), S=cs.sublanes, T=v3.shape[0],
            interpret=interpret)
        return out.reshape(cs.r, cs.c_pad)
    return _sketch_accum_chunks_jax(cs, table, v3, t_a)


def sketch_chunks_local(cs: CountSketch, v3: jax.Array, t0,
                        interpret: bool = False) -> jax.Array:
    """PARTIAL ``(r, c_pad)`` table of ``Tn`` resident-layout chunks
    starting at global chunk ``t0`` (a traced scalar) — the sharded
    server's re-sketch of its local update slice. Linearity makes the
    psum of the shards' partial tables equal the full ``sketch_chunks``
    *mathematically* — but only up to float summation order (psum of
    partials vs one sequential scan), so the sharded server consumes the
    psum'd table for its **zero-cell pattern only** (cell masking), never
    for values; an exact cross-order cancellation could in principle flip
    a cell's zeroness (see docs/sharded_server.md). Per chunk the math IS
    bit-identical to the full path's (same shifts, same sign-hash
    coordinates). Chunks past ``cs.T`` (tail shards of an uneven split)
    must be all-zero — their sliced shift values are padding, and zero
    input contributes zero regardless."""
    Tn = v3.shape[0]
    assert v3.shape[1:] == (cs.sublanes, _LANES), v3.shape
    if _use_pallas_sketch() or interpret:
        q_cols, w_cols = _local_shift_cols(cs.shift_q, cs.shift_w, t0, Tn)
        out = _sketch_vec_pallas(
            v3, q_cols, w_cols, cs.sign_keys,
            jnp.asarray(t0, jnp.int32).reshape(1), S=cs.sublanes, T=Tn,
            interpret=interpret)
        return out.reshape(cs.r, cs.c_pad)
    return _sketch_chunks_jax(cs, v3, t0=jnp.asarray(t0, jnp.int32))


# --------------------------------------------------------------------------
# query: (r, c_pad) table -> (d,) estimates
# --------------------------------------------------------------------------

def _estimates_chunks_jax(cs: CountSketch, table: jax.Array,
                          t0=None, Tn: Optional[int] = None) -> jax.Array:
    """Pure-XLA query producing the ``(T, S, 128)`` estimate chunks. Tail
    positions (flat index ≥ d) hold hash noise — callers re-entering the
    resident data plane must ``mask_tail`` them.

    ``t0``/``Tn`` (sharded server): produce only the ``Tn`` chunks
    starting at global chunk ``t0`` (traced) — per chunk bit-identical to
    the full query."""
    S = cs.sublanes
    table3 = table.reshape(cs.r, S, _LANES)

    def body(_, xs):
        q_r, w_r, t_base = xs
        rolled = jax.vmap(_roll2d)(table3, q_r, w_r)                # (r, S, 128)
        est = rolled * _chunk_signs(cs, t_base)
        return None, _median_small([est[i] for i in range(cs.r)])

    if t0 is None:
        q_cols, w_cols = cs.inv_q, cs.inv_w
        t_bases = jnp.arange(cs.T, dtype=jnp.int32) * (S * _LANES)
    else:
        assert Tn is not None
        q_cols, w_cols = _local_shift_cols(cs.inv_q, cs.inv_w, t0, Tn)
        t_bases = (jnp.asarray(t0, jnp.int32)
                   + jnp.arange(Tn, dtype=jnp.int32)) * (S * _LANES)
    _, out = jax.lax.scan(body, None, (q_cols.T, w_cols.T, t_bases))
    return out


def _estimates_jax(cs: CountSketch, table: jax.Array) -> jax.Array:
    out = _estimates_chunks_jax(cs, table)
    return out.reshape(cs.T * cs.c_pad)[: cs.d]


def _est_subblock(S: int) -> int:
    """Output sub-block height (sublanes) for the estimates kernel."""
    return min(1024, -(-S // 8) * 8)


@functools.partial(jax.jit,
                   static_argnames=("S", "T", "c_pad", "interpret"))
def _estimates_pallas(tbl2, shift_q, shift_w, sign_keys, t0, *, S, T, c_pad,
                      interpret=False):
    """Fused query kernel producing the ``(T, S, 128)`` estimate chunks.

    The pure path re-rolls the whole ``(r, c_pad)`` table for every one of
    the T chunks, so XLA materializes ~5 table-sized intermediates per chunk
    (~1 GB of HBM round-trips at the FetchSGD geometry — measured 2.9 ms on
    a v5e chip, the single hottest op of the server round). Here the table
    is pre-doubled along sublanes in HBM (``tbl2[j] = [row_j; row_j; pad]``)
    so that *any* cyclically-wrapped window is one static-size dynamic-offset
    DMA; the grid walks (chunk, sub-block) and each step copies the r shifted
    windows into VMEM, finishes the roll with the hardware lane-rotate plus
    a carry select, applies the on-the-fly sign hashes, and writes the
    elementwise median-of-rows — the table is read ~once and the estimates
    written once (~175 MB of traffic total at the same geometry).

    Window math: output position ``p`` of chunk ``t`` reads
    ``row[(p + m) mod c_pad]`` with ``m = 128·q + w`` the *forward* shift, so
    the sub-block starting at sublane ``g·SB`` needs input sublanes
    ``[g·SB + q, g·SB + q + SB]`` of the doubled row, lane-rotated left by
    ``w`` with the wrapped lanes drawn from the next sublane.

    ``t0`` ((1,) int32 scalar prefetch): the chunks' global index offset —
    0 for the full query, the shard's first global chunk for the
    sharded-server local query (shift arrays pre-sliced; only the
    sign-hash coordinate base shifts). ``t0 == 0`` is bit-identical to
    the pre-offset kernel.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = shift_q.shape[0]
    SB = _est_subblock(S)
    G = -(-S // SB)

    def kernel(q_ref, w_ref, key_ref, t0_ref, tbl2_ref, out_ref, buf, sems):
        t = pl.program_id(0)
        g = pl.program_id(1)
        for j in range(r):
            s0 = g * SB + q_ref[j, t]
            pltpu.make_async_copy(
                tbl2_ref.at[j, pl.ds(s0, SB + 1), :],
                buf.at[j], sems.at[j]).start()
        base = (t0_ref[0] + t) * c_pad + g * (SB * _LANES)
        idx = base + (
            jax.lax.broadcasted_iota(jnp.int32, (SB, _LANES), 0) * _LANES
            + jax.lax.broadcasted_iota(jnp.int32, (SB, _LANES), 1))
        l = jax.lax.broadcasted_iota(jnp.int32, (SB, _LANES), 1)
        rows = []
        for j in range(r):
            pltpu.make_async_copy(
                tbl2_ref.at[j, pl.ds(0, SB + 1), :],  # shape-only for wait
                buf.at[j], sems.at[j]).wait()
            w = w_ref[j, t]
            z = pltpu.roll(buf[j], (_LANES - w) % _LANES, axis=1)
            y = jnp.where(l < _LANES - w, z[:SB], z[1:])
            rows.append(y * _signs_for(idx, key_ref[j]))
        out_ref[...] = _median_small(rows)[None]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(T, G),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, SB, _LANES), lambda t, g, *_: (t, g, 0)),
        scratch_shapes=[
            pltpu.VMEM((r, SB + 1, _LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((r,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, S, _LANES), jnp.float32),
        interpret=interpret,
        name="fed_estimates",
    )(shift_q, shift_w, sign_keys, t0, tbl2)


def _doubled_table(cs: CountSketch, table: jax.Array) -> jax.Array:
    """``(r, P, 128)`` doubled-and-padded sublane layout for the query
    kernel: P covers the largest window start ``(G-1)·SB + (S-1)`` plus the
    ``SB+1`` window, rounded up to the sublane tile."""
    S = cs.sublanes
    SB = _est_subblock(S)
    G = -(-S // SB)
    P = -(-((G - 1) * SB + S + SB + 1) // 8) * 8
    t3 = table.reshape(cs.r, S, _LANES)
    t6 = jnp.concatenate([t3, t3], axis=1)
    return jnp.pad(t6, ((0, 0), (0, P - 2 * S), (0, 0)))


def estimates(cs: CountSketch, table: jax.Array) -> jax.Array:
    """Median-of-rows unbiased estimate of every coordinate — ``(d,)``.

    The Pallas query kernel is self-checked once per process at
    ``make_sketch`` time (the only ``CountSketch`` constructor). A sketch
    that bypassed ``make_sketch`` (e.g. deserialized) still gets the check
    on an eager first call here; only the bypass-AND-first-call-inside-a-
    trace combination runs the kernel unverified."""
    if _trace_state_clean():
        _check_estimates_kernel_once(eager=True)
    if _use_pallas_estimates():
        out = _estimates_pallas(
            _doubled_table(cs, table), cs.shift_q, cs.shift_w, cs.sign_keys,
            _T0, S=cs.sublanes, T=cs.T, c_pad=cs.c_pad)
        return out.reshape(cs.T * cs.c_pad)[: cs.d]
    return _estimates_jax(cs, table)


def estimates_chunks(cs: CountSketch, table: jax.Array) -> jax.Array:
    """Median-of-rows estimates in the ``(T, S, 128)`` resident chunk layout
    — same values as ``estimates`` at flat indices < d, but without the
    table→flat reshape. The padded tail is **masked to zero** (the raw
    kernel output there is hash noise), so the result satisfies the
    resident-layout invariant (ops/flat.ChunkLayout)."""
    if _trace_state_clean():
        _check_estimates_kernel_once(eager=True)
    if _use_pallas_estimates():
        out = _estimates_pallas(
            _doubled_table(cs, table), cs.shift_q, cs.shift_w, cs.sign_keys,
            _T0, S=cs.sublanes, T=cs.T, c_pad=cs.c_pad)
    else:
        out = _estimates_chunks_jax(cs, table)
    return cs.chunk_layout.mask_tail(out)


def estimates_chunks_local(cs: CountSketch, table: jax.Array, t0, Tn: int,
                           interpret: bool = False) -> jax.Array:
    """Median-of-rows estimates for the ``Tn`` resident-layout chunks
    starting at global chunk ``t0`` (a traced scalar) — the sharded
    server's local slice of ``estimates_chunks``. Per chunk bit-identical
    to the full query's output; positions whose GLOBAL flat index is ≥ d
    (the padded tail, including entire chunks past ``cs.T`` on tail
    shards of an uneven split) are masked to zero, so the slice satisfies
    the resident-layout invariant."""
    S = cs.sublanes
    if _use_pallas_estimates() or interpret:
        # the DMA kernel takes the FORWARD shifts (it reads the window at
        # p + m rather than rolling by the inverse — see its docstring)
        q_cols, w_cols = _local_shift_cols(cs.shift_q, cs.shift_w, t0, Tn)
        out = _estimates_pallas(
            _doubled_table(cs, table), q_cols, w_cols, cs.sign_keys,
            jnp.asarray(t0, jnp.int32).reshape(1), S=S, T=Tn,
            c_pad=cs.c_pad, interpret=interpret)
    else:
        out = _estimates_chunks_jax(cs, table, t0=jnp.asarray(t0, jnp.int32),
                                    Tn=Tn)
    # global-coordinate tail mask (ChunkLayout.mask_tail is full-range only)
    idx = (jnp.asarray(t0, jnp.int32).reshape(1, 1, 1) * (S * _LANES)
           + jax.lax.broadcasted_iota(jnp.int32, (Tn, S, _LANES), 0)
           * (S * _LANES)
           + jax.lax.broadcasted_iota(jnp.int32, (Tn, S, _LANES), 1) * _LANES
           + jax.lax.broadcasted_iota(jnp.int32, (Tn, S, _LANES), 2))
    return jnp.where(idx < cs.d, out, jnp.zeros((), out.dtype))


def unsketch(cs: CountSketch, table: jax.Array, k: int) -> jax.Array:
    """Dense ``(d,)`` vector holding the estimated values of the k
    largest-magnitude coordinates, zero elsewhere (``CSVec.unSketch(k)``,
    reference fed_aggregator.py:590).

    Routed through ONE shared ``(T, S, 128)`` view: the GPT-2 profile
    (v5e, 2026-08-01, capture since deleted) showed the flat formulation —
    flatten the estimates, threshold flat, re-pad the flat update for the
    re-sketch — paying twin d-sized ``pad``/``reshape`` pairs
    (~3.1 ms/round) for the SAME plane; thresholding the chunked
    estimates in place (``topk_dense_nd``) keeps the one flat
    materialization at the very end. Identical values: the chunking is
    pure layout and the threshold descent counts the same d coordinates
    (the masked zero tail can never win a nonzero threshold)."""
    return cs.chunk_layout.unchunk(unsketch_chunks(cs, table, k))


def unsketch_chunks(cs: CountSketch, table: jax.Array, k: int) -> jax.Array:
    """``unsketch`` in the ``(T, S, 128)`` resident chunk layout: top-k of
    the masked estimate chunks, shape-preserving (tail stays zero). Same
    selected set and values as ``unsketch`` — the threshold descent counts
    magnitudes over the same d real coordinates plus zero-valued tail
    positions, which can never win a nonzero threshold."""
    from commefficient_tpu.ops.topk import topk_dense_nd

    return topk_dense_nd(estimates_chunks(cs, table), k)


# --------------------------------------------------------------------------
# fused server epilogue: estimates -> threshold mask -> update + re-sketch
# --------------------------------------------------------------------------

# |bit-pattern| masks, same values as ops/topk.py (kept literal here so the
# kernel body has no cross-module closure)
_FE_ABS_MASK = 0x7FFFFFFF
_FE_INF_BITS = 0x7F800000


def _fe_subblock(S: int) -> int:
    """Sub-block height (sublanes) for the fused epilogue kernel. Smaller
    than the query kernel's (512 vs 1024): the unwrapped re-sketch
    accumulator ``(r, S + SB + pad, 128)`` must stay VMEM-resident across
    the whole grid alongside the est/update pipeline buffers, and SB only
    sizes the per-step working set, not the streamed bytes."""
    return min(512, -(-S // 8) * 8)


def _fe_ext_sublanes(S: int) -> int:
    """Sublane height of the UNWRAPPED accumulator: a sub-block's rolled
    contribution starts at sublane ``(g·SB + q) mod S`` ∈ [0, S) and spans
    ``SB + 1`` rows (lane carry), so ``S + SB + 1`` rows hold every
    contribution without cyclic wrap; rows ≥ S are folded back mod S by
    ``_fold_ext_table`` after the kernel."""
    return -(-(S + _fe_subblock(S) + 1) // 8) * 8


def _fold_ext_table(cs: CountSketch, ext: jax.Array) -> jax.Array:
    """``(r, S_ext, 128)`` kernel output → ``(r, c_pad)`` table. The kernel
    folds its wrap region back per chunk (see its docstring), so rows ≥ S
    are zero on exit and this is a pure slice — kept as a fold (add) so the
    contract doesn't depend on the zeroing, at table-sized cost."""
    S = cs.sublanes
    tbl = ext[:, :S, :]
    rest = ext[:, S:, :]
    while rest.shape[1] > 0:
        w = min(S, rest.shape[1])
        tbl = tbl + jnp.pad(rest[:, :w], ((0, 0), (0, S - w), (0, 0)))
        rest = rest[:, w:, :]
    return tbl.reshape(cs.r, cs.c_pad)


@functools.partial(jax.jit, static_argnames=("S", "T", "interpret"))
def _fused_epilogue_pallas(est3, shift_q, shift_w, sign_keys, t0, p, *,
                           S, T, interpret=False):
    """The one-sweep server epilogue megakernel (docs/fused_epilogue.md):
    one pass over the ``(T, S, 128)`` estimate chunks that

      1. applies the PRECOMPUTED top-k threshold mask ``|est| ≥ p`` (p is
         the k-th-magnitude int32 bit pattern from the radix descent,
         ops/topk.resolve_threshold — tie-inclusive, NaN passthrough,
         exactly ``_apply_threshold``'s semantics),
      2. emits the masked update chunks (the transmitted update, unscaled
         — lr multiplies outside where XLA fuses it into ``ps -= upd·lr``),
      3. accumulates the re-sketch of the masked update into an UNWRAPPED
         ``(r, S + SB + pad, 128)`` count-sketch accumulator that stays
         VMEM-resident across the whole grid (constant out-block index):
         per row the sub-block's sign-weighted values are lane-rotated by
         ``w`` (hardware rotate unit), given their sublane lane-carry row,
         and added at dynamic sublane offset ``(g·SB + q) mod S``; at each
         chunk's last sub-block the wrap region (rows ≥ S) folds back onto
         [0, S) and re-zeroes, so a cell's contributions land strictly in
         chunk order.

    Replaces the composed path's separate ``compare_select`` masking sweep
    and ``sketch_chunks`` re-sketch sweep: est is read once and the update
    written once — the re-sketch's own d-plane read disappears. The
    per-chunk fold adds ~SB/S extra accumulator RMW traffic (~13% at the
    FetchSGD geometry), in VMEM, not HBM.

    Bit-compatibility with the composed path: per table cell and chunk
    exactly one position contributes (the roll is a permutation), the grid
    walks chunks in the same t order as ``sketch_chunks``'s scan, and the
    per-chunk fold lands each chunk's wrapped contributions before the
    next chunk's adds — so every cell sees the same f32 adds in the same
    order as the composed re-sketch. The one deviation: masked/overhang
    positions and the fold's pass-through rows contribute +0.0 where the
    composed kernels add sign·0 = ±0.0 — cells whose every contribution
    is a signed zero can differ in the SIGN of their zero (never in ``==``
    or the ``!= 0`` cell-masking pattern the server consumes).

    ``t0``/pre-sliced shifts: the sharded-server local variant, exactly as
    in ``_sketch_vec_pallas``/``_estimates_pallas`` — with ``t0 == 0`` the
    math is bit-identical to the full-range call.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = shift_q.shape[0]
    SB = _fe_subblock(S)
    G = -(-S // SB)
    S_ext = _fe_ext_sublanes(S)
    chunk_elems = S * _LANES

    def kernel(q_ref, w_ref, key_ref, t0_ref, p_ref, est_ref, upd_ref,
               tbl_ref):
        t = pl.program_id(0)
        g = pl.program_id(1)

        @pl.when(jnp.logical_and(t == 0, g == 0))
        def _():
            tbl_ref[...] = jnp.zeros_like(tbl_ref)

        est = est_ref[0]                                       # (SB, 128)
        raw = jax.lax.bitcast_convert_type(est, jnp.int32)
        m = raw & _FE_ABS_MASK
        mag = jnp.where(m > _FE_INF_BITS, 0, m)
        upd = jnp.where(mag >= p_ref[0], est, jnp.zeros_like(est))
        upd = jnp.where(m > _FE_INF_BITS, est, upd)   # NaNs stay visible
        upd_ref[0] = upd

        # re-sketch contribution of this sub-block; rows past S are the
        # partial last block's overhang — masked so garbage never lands
        sub_i = g * SB + jax.lax.broadcasted_iota(jnp.int32, (SB, _LANES), 0)
        contrib = jnp.where(sub_i < S, upd, jnp.zeros_like(upd))
        base = (t0_ref[0] + t) * chunk_elems + g * (SB * _LANES)
        idx = base + (
            jax.lax.broadcasted_iota(jnp.int32, (SB, _LANES), 0) * _LANES
            + jax.lax.broadcasted_iota(jnp.int32, (SB, _LANES), 1))
        zz = jnp.zeros((1, _LANES), jnp.float32)
        l1 = jax.lax.broadcasted_iota(jnp.int32, (SB + 1, _LANES), 1)
        for j in range(r):
            sv = contrib * _signs_for(idx, key_ref[j])
            w = w_ref[j, t]
            q = q_ref[j, t]
            z = pltpu.roll(sv, w, axis=1)
            # lane-carry rows: y[b] = z[b] (lanes ≥ w) | z[b-1] (lanes < w)
            # with z[-1] = z[SB] = 0 — the (SB+1)-row unwrapped image
            y = jnp.where(l1 >= w,
                          jnp.concatenate([z, zz], axis=0),
                          jnp.concatenate([zz, z], axis=0))
            s0 = g * SB + q
            s0 = jnp.where(s0 >= S, s0 - S, s0)
            tbl_ref[j, pl.ds(s0, SB + 1), :] += y

            # per-chunk wrap fold: move rows ≥ S back onto [0, S) before
            # the next chunk's adds, so per-cell add order matches the
            # composed scan's exactly (static strips handle SB > S)
            @pl.when(g == G - 1)
            def _(j=j):
                off = S
                while off < S_ext:
                    h = min(S, S_ext - off)
                    wrap = tbl_ref[j, off:off + h, :]
                    tbl_ref[j, 0:h, :] += wrap
                    tbl_ref[j, off:off + h, :] = jnp.zeros(
                        (h, _LANES), jnp.float32)
                    off += h

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(T, G),
        in_specs=[
            pl.BlockSpec((1, SB, _LANES), lambda t, g, *_: (t, g, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, SB, _LANES), lambda t, g, *_: (t, g, 0)),
            pl.BlockSpec((r, S_ext, _LANES), lambda t, g, *_: (0, 0, 0)),
        ],
        scratch_shapes=[],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((T, S, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((r, S_ext, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="fed_epilogue",
    )(shift_q, shift_w, sign_keys, t0, p, est3)


def fused_epilogue_supported(cs: CountSketch) -> bool:
    """VMEM-budget guard: the unwrapped accumulator plus the pipeline
    buffers must fit comfortably under the ~16 MB/core VMEM. The FetchSGD
    geometry (r=5, c=500k → ~11.3 MB accumulator) fits; a much wider/
    deeper sketch falls back to the composed path."""
    S = cs.sublanes
    vmem = (cs.r * _fe_ext_sublanes(S) + 4 * _fe_subblock(S)) * _LANES * 4
    return vmem <= 13 * 1024 * 1024


def fused_epilogue_mode(cs: Optional[CountSketch] = None) -> str:
    """``'kernel' | 'interpret' | 'off'`` — how (whether) the fused
    epilogue runs. COMMEFFICIENT_FUSED_EPILOGUE: ``0`` is the operator
    kill-switch (same pattern as COMMEFFICIENT_PALLAS_TOPK), ``interpret``
    forces the kernel through the Pallas interpreter (the CPU-mesh test
    path — bit-identical math, no Mosaic), unset/``1`` enables the real
    kernel on TPU backends that pass the VMEM guard."""
    import os

    env = os.environ.get("COMMEFFICIENT_FUSED_EPILOGUE")
    if env == "0":
        return "off"
    if env == "interpret":
        # the interpreter has no VMEM constraint — never veto it with the
        # TPU guard, or a guarded geometry silently turns the CPU-mesh
        # bit-identity tests into composed-vs-composed
        return "interpret"
    if cs is not None and not fused_epilogue_supported(cs):
        return "off"
    return "kernel" if _use_pallas() else "off"


def fused_epilogue_chunks(cs: CountSketch, est3: jax.Array, k: int,
                          interpret: bool = False):
    """Fused epilogue over the full chunk range: masked-update chunks plus
    the ``(r, c_pad)`` re-sketch of that update, one d-plane read.

    Drop-in for the composed pair
    ``upd = topk_dense_nd(est3, k); tbl = sketch_chunks(cs, upd)`` —
    same update bits, same table values (see the kernel docstring for the
    ±0.0 caveat), same tie-inclusive threshold (the descent is shared via
    ops/topk.resolve_threshold)."""
    from commefficient_tpu.ops.topk import resolve_threshold

    if _trace_state_clean():
        _check_fused_epilogue_once(eager=True)
    with jax.named_scope("fed_server_topk"):
        p = resolve_threshold(est3, k, interpret=interpret)
    # the one kernel masks AND re-sketches; the whole sweep is the
    # re-sketch stage's (the mask alone is a compare on the way)
    with jax.named_scope("fed_server_resketch"):
        upd, ext = _fused_epilogue_pallas(
            est3, cs.shift_q, cs.shift_w, cs.sign_keys, _T0, p.reshape(1),
            S=cs.sublanes, T=cs.T, interpret=interpret)
        return upd, _fold_ext_table(cs, ext)


def fused_epilogue_chunks_local(cs: CountSketch, est3: jax.Array, t0, k: int,
                                axis_name=None, interpret: bool = False):
    """Sharded-server fused epilogue (docs/sharded_server.md): ``est3``
    is this shard's ``Tn`` estimate chunks starting at global chunk ``t0``
    (a traced scalar). The threshold is GLOBAL — the descent's counts
    psum over ``axis_name`` (16 ints per pass) — and the returned table is
    this shard's PARTIAL re-sketch (linearity: the psum of the shards'
    partials is the full table, consumed for its zero-cell pattern only,
    like ``sketch_chunks_local``'s). Per chunk bit-identical to the full
    path's math."""
    from commefficient_tpu.ops.topk import resolve_threshold

    if _trace_state_clean():
        _check_fused_epilogue_once(eager=True)
    Tn = est3.shape[0]
    with jax.named_scope("fed_server_topk"):
        p = resolve_threshold(est3, k, interpret=interpret,
                              axis_name=axis_name)
    with jax.named_scope("fed_server_resketch"):
        q_cols, w_cols = _local_shift_cols(cs.shift_q, cs.shift_w, t0, Tn)
        upd, ext = _fused_epilogue_pallas(
            est3, q_cols, w_cols, cs.sign_keys,
            jnp.asarray(t0, jnp.int32).reshape(1), p.reshape(1),
            S=cs.sublanes, T=Tn, interpret=interpret)
        return upd, _fold_ext_table(cs, ext)


def check_fused_epilogue_kernel(cs: CountSketch, k: int = 5_000,
                                interpret: bool = False) -> None:
    """``_fused_epilogue_pallas`` == the composed ``topk_dense_nd`` +
    ``sketch_chunks`` pair (update bits and re-sketch values), plus the
    sharded local variant (t0 ≠ 0, pre-sliced shifts) == the composed
    local pair on the same slice — outside a shard_map there is no psum'd
    threshold, so the reference is slice-local, not the full update."""
    from commefficient_tpu.ops.topk import _topk_threshold_1d

    def topk_ref(x):  # the pure-XLA descent; shape-agnostic despite its name
        return _topk_threshold_1d(x, k)

    est = cs.chunk_layout.mask_tail(
        _estimates_chunks_jax(cs, _check_table(cs, 5)))
    upd_f, tbl_f = fused_epilogue_chunks(cs, est, k, interpret=interpret)
    upd_c = topk_ref(est)
    require_equal(upd_f, upd_c, "fused epilogue (update)")
    require_equal(tbl_f, _sketch_chunks_jax(cs, upd_c),
                  "fused epilogue (re-sketch)")
    Tn = -(-cs.T // 2)
    est_p = jnp.pad(est, ((0, 2 * Tn - cs.T), (0, 0), (0, 0)))
    sl = est_p[Tn:2 * Tn]
    u_l, t_l = fused_epilogue_chunks_local(cs, sl, jnp.int32(Tn), k,
                                           interpret=interpret)
    u_ref = topk_ref(sl)
    require_equal(u_l, u_ref, "fused epilogue (local update)")
    require_equal(t_l, _sketch_chunks_jax(cs, u_ref, jnp.int32(Tn)),
                  "fused epilogue (local re-sketch)")


_FUSED_EPILOGUE_CHECKED = False


def _check_fused_epilogue_once(eager: bool = False) -> None:
    """One-time on-TPU self-check of the fused epilogue megakernel before
    first use. UNLIKE the accumulate/query checks this is NOT triggered
    from ``make_sketch``: those kernels run unconditionally, while the
    megakernel is opt-in (--fused_epilogue), and a d=450k sketch build +
    Mosaic compile at every TPU ``make_sketch`` would tax processes that
    never use it. Triggers: ``rounds.build_round_step`` when the server
    config opts in (always eager host-side setup), and an eager first call
    of ``fused_epilogue_chunks``/``_local`` for direct users."""
    global _FUSED_EPILOGUE_CHECKED
    if _FUSED_EPILOGUE_CHECKED:
        return
    if fused_epilogue_mode() != "kernel":
        # nothing to verify: the interpreter path IS the reference math,
        # and 'off' must never compile a disabled kernel
        return
    if not eager and not _trace_state_clean():
        return
    _FUSED_EPILOGUE_CHECKED = True
    check_fused_epilogue_kernel(_check_geometry())


def l2estimate(table: jax.Array) -> jax.Array:
    """Median-of-rows estimate of the sketched vector's L2 norm
    (``CSVec.l2estimate``, used via reference utils.py:305-313)."""
    sq = jnp.sum(jnp.square(table), axis=1)
    return jnp.sqrt(_median_small([sq[i] for i in range(sq.shape[0])]))
