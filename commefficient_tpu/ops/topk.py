"""Magnitude top-k sparsification.

Parity with the reference's ``_topk`` (reference utils.py:232-252): keep the k
largest-magnitude coordinates of a vector (or of each row of a matrix), zero
the rest, returned as a dense masked vector.

TPU-first design: ``jax.lax.top_k`` at FetchSGD scale (k=50k over d≈6.5M) is
a full sort — ~15 ms/call on a v5e chip and the single hottest op of the
whole federated round (it sits inside ``unsketch`` on the server). Since the
callers only ever need the *dense masked* result (never the index list), the
selection reduces to finding the k-th magnitude as a scalar threshold, found
exactly by a radix-nibble descent over the **int32 bit patterns** of the
absolute values (non-negative IEEE-754 floats compare identically as
integers): 8 passes, each comparing the whole vector against the 15 (7 for
the top nibble — finite ``|float|`` patterns keep bit 31 clear and top
nibble ≤ 7) candidate extensions of the resolved prefix and keeping the
largest whose ≥-count still reaches k. That resolves 4 threshold bits per
full-vector read with pure int32 compares — no float bisection precision
cliffs at any dynamic range, no separate max pass, and ``|vec|`` is
recomputed per pass (2 VPU ops) rather than materialized. Properties:

  - invariant after every pass: count(m ≥ p) ≥ k with p a prefix of the
    k-th magnitude's bit pattern; at the end ``m ≥ p`` keeps exactly the
    top-k set, tie-inclusive: coordinates whose magnitude equals the k-th
    are all kept (``lax.top_k`` instead breaks ties by index). Ties at the
    cut are measure-zero for real gradients; the compression semantics
    tolerate the extra coordinates;
  - NaN coordinates pass through as NaN (excluded from the threshold
    search — their bit patterns exceed the inf pattern and are mapped to
    0 — then re-inserted in the output) so divergence stays visible to the
    NaN-abort in the train loop (reference cv_train.py:110-112) — silently
    dropping them would disguise a diverged round as a healthy sparse
    update.

``method="sort"`` keeps the exact ``lax.top_k`` behavior for callers that
need reference tie-breaking.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_ABS_MASK = 0x7FFFFFFF
_INF_BITS = 0x7F800000  # |pattern| above this ⇔ NaN
_LANES = 128
_SUB = 512              # count-kernel block: (512, 128) i32 = 256 KiB VMEM


# Measured crossover (v5e, 2026-08-01, by a script since deleted): the
# Pallas count-pass descent wins 37x at the FetchSGD geometry
# (d=6,568,640: 0.30 ms vs 11.10 ms XLA, outputs bit-equal) but LOSES at
# the GPT-2 geometry (d=124,444,417: 16.15 ms vs 14.57 ms) — above ~100M
# the kernel's fixed (512, 128) blocking stops tracking HBM streams (1,900
# block boundaries per pass leave no pipelining slack). Gate between the
# two measured points, nearer the win; a cell on each side of it: cell 1
# below, the GPT-2 and JoyAI cells above. The blocking is d-adaptive
# (``_sub_for``), never re-measured above the gate; the gate itself only
# moves on a ledger line (ROADMAP Queue 1 item 13).
_PALLAS_TOPK_MAX_D = 32 * 1024 * 1024


def _sub_for(d: int) -> int:
    """Count/descent-kernel block sublanes chosen from d: (512, 128) i32 =
    256 KiB blocks at FetchSGD scale (the measured 37x-win shape), 4x that
    (1 MiB blocks, still trivially double-buffered in VMEM) above the 32M
    gate where the round-5 A/B showed the fixed blocking losing the HBM
    streams — 4x fewer block boundaries for the same bytes.

    Radix width note (the other lever considered for d-scaling): widening
    a pass from 4 to 8 bits would halve the HBM reads but needs 255
    ≥-compares per element vs 15 — the measured per-pass kernel already
    runs at the VPU:HBM balance point (~32 int ops per 4-byte element at
    ~700 GB/s effective), so 8-bit passes are ~8x compute-bound and lose.
    4-bit levels + fewer/larger blocks is the d-scaling fix; the arithmetic
    is written out in docs/fused_epilogue.md."""
    return _SUB if d <= _PALLAS_TOPK_MAX_D else 4 * _SUB


def _use_pallas_topk(d: int) -> bool:
    """Pallas count-pass kernel: ON by default on TPU below the measured
    crossover size; COMMEFFICIENT_PALLAS_TOPK=0/1 forces either way."""
    import os

    from commefficient_tpu.utils import is_tpu_backend

    force = os.environ.get("COMMEFFICIENT_PALLAS_TOPK")
    if force is not None:
        return is_tpu_backend() and force == "1"
    return is_tpu_backend() and d <= _PALLAS_TOPK_MAX_D


@functools.partial(jax.jit, static_argnames=("T", "sub", "interpret"))
def _count_ge_pallas(v3, ts, *, T, sub=_SUB, interpret=False):
    """``counts[j] = sum(mag(v) >= ts[j])`` over the whole vector, one HBM
    read: blocks of the int32 bit patterns stream through VMEM while the 16
    threshold compares and their scalar reductions stay in registers/SMEM —
    the radix-descent inner pass with its memory traffic pinned to 4·d
    bytes (the pure-XLA formulation leaves the (d, 15) broadcast's fate to
    the fusion heuristics). ``ts`` must be padded to 16 with INT32_MAX
    (counts 0 there: finite-|float| patterns never reach it). ``sub`` is
    the d-adaptive block height (``_sub_for``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(ts_ref, v_ref, out_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            for j in range(16):
                out_ref[0, j] = 0

        m = v_ref[0] & _ABS_MASK
        m = jnp.where(m > _INF_BITS, 0, m)
        for j in range(16):
            out_ref[0, j] += jnp.sum((m >= ts_ref[j]).astype(jnp.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T,),
        in_specs=[pl.BlockSpec((1, sub, _LANES), lambda t, *_: (t, 0, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
    )
    # (1, 16), not (16,): under vmap (per-client top-k) the batched SMEM
    # block is then (Squeezed, 1, 16), whose last two dims equal the
    # array's — Mosaic refuses the batched 1-D block (Squeezed, 16)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, 16), jnp.int32),
        interpret=interpret,
        name="fed_topk_count",
    )(ts, v3)[0]


@functools.partial(jax.jit, static_argnames=("T", "sub", "interpret"))
def _descent_pallas(v3, kk, *, T, sub=_SUB, interpret=False):
    """The WHOLE 8-pass radix descent in one ``pallas_call``: grid
    ``(8, T)`` re-streams the vector once per pass while the resolved
    prefix and the 15 running ≥-counts live in SMEM scratch across blocks
    — one kernel launch instead of 8, and none of the tiny s32[16]
    select/sum XLA ops between passes (each a ~20 µs dispatch in the
    round-5 post-flip profile). Pass p resolves threshold bits
    ``31-4p..28-4p``; candidate j tests ``prefix + (j+1) << shift``,
    with the first pass's impossible candidates (top nibble of a finite
    |float| is ≤ 7) pinned to INT32_MAX where no magnitude can reach.
    Returns the scalar k-th-magnitude bit-pattern threshold."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(kk_ref, v_ref, out_ref, counts, prefix):
        p_id = pl.program_id(0)
        t_id = pl.program_id(1)

        @pl.when(jnp.logical_and(p_id == 0, t_id == 0))
        def _():
            prefix[0] = 0

        @pl.when(t_id == 0)
        def _():
            for j in range(15):
                counts[j] = 0

        shift = 28 - 4 * p_id
        pfx = prefix[0]
        m = v_ref[0] & _ABS_MASK
        m = jnp.where(m > _INF_BITS, 0, m)
        for j in range(15):
            ts_j = pfx + jnp.left_shift(jnp.int32(j + 1), shift)
            # pass 0: candidates 8..15 would shift into the sign bit —
            # pin to ABS_MASK (>= it is impossible for finite |float|)
            ts_j = jnp.where(jnp.logical_and(p_id == 0, j >= 7),
                             jnp.int32(_ABS_MASK), ts_j)
            counts[j] += jnp.sum((m >= ts_j).astype(jnp.int32))

        @pl.when(t_id == T - 1)
        def _():
            k = kk_ref[0]
            sel = jnp.int32(0)
            for j in range(15):
                sel += jnp.where(counts[j] >= k, 1, 0).astype(jnp.int32)
            prefix[0] = pfx + jnp.left_shift(sel, shift)

        @pl.when(jnp.logical_and(p_id == 7, t_id == T - 1))
        def _():
            out_ref[0] = prefix[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(8, T),
        in_specs=[pl.BlockSpec((1, sub, _LANES), lambda p, t, *_: (t, 0, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
        scratch_shapes=[pltpu.SMEM((15,), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
        interpret=interpret,
        name="fed_topk_descent",
    )(kk, v3)


def _blocks3(raw: jax.Array, sub: int = _SUB):
    """Pad the int32 bit patterns with +0.0 (mag 0 never reaches any
    threshold, all ≥ 1) and reshape to the kernels' ``(T, sub, _LANES)``
    block layout."""
    d = raw.shape[0]
    block = sub * _LANES
    T = -(-d // block)
    return jnp.pad(raw, (0, T * block - d)).reshape(T, sub, _LANES), T


def _apply_threshold(raw: jax.Array, vec: jax.Array, p) -> jax.Array:
    """Dense-masked result from the resolved k-th-magnitude bit pattern:
    keep mag ≥ p (tie-inclusive), re-insert NaNs (module docstring)."""
    m = raw & _ABS_MASK
    mag = jnp.where(m > _INF_BITS, 0, m)
    out = jnp.where(mag >= p, vec, jnp.zeros_like(vec))
    return jnp.where(m > _INF_BITS, vec, out)


def _topk_threshold_1d_fused(vec: jax.Array, k: int,
                             interpret: bool = False) -> jax.Array:
    """Descent via the single fused kernel; identical output to the
    per-pass paths whenever the counts agree (exact integer arithmetic).

    Block sublanes scale up 4x at GPT-2-scale d: the measured round-4
    loss above ~100M came from the fixed (512, 128) blocking — too many
    block boundaries for the HBM streams to pipeline across; fewer,
    larger blocks (1 MiB each, still trivially VMEM-resident
    double-buffered) is the candidate fix the topk_ab leg decides."""
    raw = vec.view(jnp.int32)
    p = _threshold_descent_fused(raw, k, interpret=interpret)
    return _apply_threshold(raw, vec, p)


def _threshold_descent_fused(raw: jax.Array, k: int,
                             interpret: bool = False) -> jax.Array:
    """Resolved k-th-magnitude bit pattern via the single fused descent
    kernel on the blocked flat view of ``raw`` (any shape) — shared by the
    flat and chunked-resident paths like ``_threshold_descent_pallas``."""
    flat = raw.reshape(-1)
    sub = _sub_for(flat.shape[0])
    v3, T = _blocks3(flat, sub)
    kk = jnp.asarray([k], jnp.int32)
    return _descent_pallas(v3, kk, T=T, sub=sub, interpret=interpret)[0]


def _threshold_descent_pallas(raw: jax.Array, k: int,
                              interpret: bool = False,
                              axis_name=None) -> jax.Array:
    """Resolved k-th-largest-magnitude bit pattern via the per-pass Pallas
    count kernel on the blocked flat view of ``raw`` (any shape) — the one
    descent loop both the flat and chunked-resident top-k paths share, so
    a blocking/kernel change cannot silently diverge them.

    ``axis_name`` is the sharded-server threshold exchange
    (docs/sharded_server.md): each shard counts over its LOCAL slice and
    the 16 per-candidate counts are psum'd — 16 ints per pass instead of
    materializing the full vector per chip. Counts are exact integers, so
    the resolved threshold is identical to the unsharded descent's."""
    flat = raw.reshape(-1)
    sub = _sub_for(flat.shape[0])
    v3, T = _blocks3(flat, sub)
    p = jnp.int32(0)
    for shift in range(28, -1, -4):
        hi_nib = 8 if shift == 28 else 16
        ts = p + (jnp.arange(1, hi_nib, dtype=jnp.int32) << shift)
        ts = jnp.pad(ts, (0, 16 - (hi_nib - 1)),
                     constant_values=jnp.int32(_ABS_MASK))
        counts = _count_ge_pallas(v3, ts, T=T, sub=sub, interpret=interpret)
        if axis_name is not None:
            counts = jax.lax.psum(counts, axis_name)
        sel = jnp.sum(counts >= k).astype(jnp.int32)
        p = p + (sel << shift)
    return p


def _topk_threshold_1d_pallas(vec: jax.Array, k: int,
                              interpret: bool = False) -> jax.Array:
    """Same radix descent as ``_topk_threshold_1d``, counts from the Pallas
    kernel. Identical output: the descent is exact integer arithmetic, so
    the two paths agree bit-for-bit whenever the counts do."""
    raw = vec.view(jnp.int32)
    p = _threshold_descent_pallas(raw, k, interpret=interpret)
    return _apply_threshold(raw, vec, p)


class KernelMismatch(RuntimeError):
    """A compiled Pallas kernel disagreed with its ``jnp`` reference."""


def require_equal(got, want, what: str) -> None:
    """Raise ``KernelMismatch`` unless ``got == want`` elementwise (``==``:
    the documented ±0.0 sign deviation of the running-table / fused sketch
    kernels is allowed, value deviations are not)."""
    import numpy as np

    if not np.array_equal(np.asarray(got), np.asarray(want)):
        raise KernelMismatch(f"{what}: kernel output != jnp reference")


def _check_vec(d: int) -> jax.Array:
    import numpy as np

    # heavy-tailed magnitudes so the descent resolves several nibble levels
    return jnp.asarray(np.random.RandomState(9).randn(d) ** 3, jnp.float32)


def check_count_descent_kernel(d: int, k: int,
                               interpret: bool = False) -> None:
    """Per-pass Pallas count descent == the pure-XLA descent (part of the
    kernel self-check family of ops/sketch.py; run by chip_smoke.py) —
    alone, and vmapped over rows as the per-client top-k of
    ``--mode local_topk`` / ``--topk_down`` batches it."""
    vec = _check_vec(d)
    require_equal(_topk_threshold_1d_pallas(vec, k, interpret=interpret),
                  _topk_threshold_1d(vec, k), "count-pass descent")
    rows = jnp.stack([vec, vec[::-1]])
    require_equal(
        jax.vmap(lambda v: _topk_threshold_1d_pallas(
            v, k, interpret=interpret))(rows),
        jax.vmap(lambda v: _topk_threshold_1d(v, k))(rows),
        "count-pass descent (vmapped)")


def check_fused_descent_kernel(d: int, k: int,
                               interpret: bool = False) -> None:
    """Whole-descent Pallas kernel == the pure-XLA descent."""
    vec = _check_vec(d)
    require_equal(_topk_threshold_1d_fused(vec, k, interpret=interpret),
                  _topk_threshold_1d(vec, k), "fused descent")


def _select_threshold_impl(d: int):
    """Pick the threshold-descent implementation for this geometry.

    The fused whole-descent kernel is default OFF: never measured on the
    chip (ROADMAP D2), so nothing says it beats the per-pass kernel —
    the same gate-then-flip playbook as the count-pass kernel. The opt-in
    flag deliberately bypasses the d ≤ 32M crossover gate: the fused
    kernel's large-d blocking is exactly what the A/B needs to test at
    GPT-2 scale."""
    import os

    from commefficient_tpu.utils import is_tpu_backend

    if os.environ.get("COMMEFFICIENT_PALLAS_TOPK") == "0":
        return _topk_threshold_1d  # explicit kill-switch beats everything
    if (os.environ.get("COMMEFFICIENT_PALLAS_TOPK_FUSED") == "1"
            and is_tpu_backend()):
        return _topk_threshold_1d_fused
    if _use_pallas_topk(d):
        return _topk_threshold_1d_pallas
    return _topk_threshold_1d


def _topk_sort_1d(vec: jax.Array, k: int) -> jax.Array:
    # clamp so both methods accept k > d (threshold handles it naturally)
    _, idx = jax.lax.top_k(jnp.abs(vec), min(k, vec.shape[0]))
    return jnp.zeros_like(vec).at[idx].set(vec[idx])


def _threshold_descent_xla(raw: jax.Array, k: int,
                           axis_name=None) -> jax.Array:
    """Resolved k-th-largest-magnitude bit pattern over ALL elements of
    ``raw`` (any shape — the counts are full-array reductions, so the same
    descent serves the flat ``(d,)`` vector and the chunked-resident
    ``(T, S, 128)`` layout without a reshape). With ``axis_name`` the
    counts additionally psum over that mesh axis — the sharded-server
    threshold exchange (see ``_threshold_descent_pallas``): integer-exact,
    so the threshold matches the unsharded descent's over the
    concatenation of the shards' slices."""

    def mag(r):
        # |pattern| as int (abs, not the reference's square, utils.py:246:
        # squares underflow below |v|≈1e-19 and overflow above ≈2e19; bit
        # patterns are exact at every representable magnitude); NaN → 0 so
        # divergence never wins the threshold race
        m = r & _ABS_MASK
        return jnp.where(m > _INF_BITS, 0, m)

    # Radix descent: after each pass p is the resolved high-nibble prefix of
    # the k-th largest magnitude's bit pattern, maintaining
    # count(m ≥ p) ≥ k. Unrolled: 8 static passes, thresholds are ints.
    p = jnp.int32(0)
    for shift in range(28, -1, -4):
        hi_nib = 8 if shift == 28 else 16
        ts = p + (jnp.arange(1, hi_nib, dtype=jnp.int32) << shift)
        m = mag(raw)
        counts = jnp.sum(m[..., None] >= ts, axis=tuple(range(m.ndim)))
        if axis_name is not None:
            counts = jax.lax.psum(counts, axis_name)
        # counts are non-increasing in the threshold, so the chosen nibble
        # is just the number of candidates whose count still reaches k
        sel = jnp.sum(counts >= k).astype(jnp.int32)
        p = p + (sel << shift)
    return p


def _topk_threshold_1d(vec: jax.Array, k: int) -> jax.Array:
    raw = vec.view(jnp.int32)
    p = _threshold_descent_xla(raw, k)
    # p == 0 ⇔ fewer than k nonzero magnitudes: m ≥ 0 keeps everything,
    # and zero-magnitude coordinates contribute value 0 anyway — the same
    # dense-masked result lax.top_k pads with zeros
    return _apply_threshold(raw, vec, p)


def resolve_threshold(vec: jax.Array, k: int, interpret: bool = False,
                      axis_name=None) -> jax.Array:
    """THE k-th-largest-magnitude bit-pattern resolver (scalar int32 p) for
    an arbitrary-shape float32 array — the one dispatch point every caller
    that needs the top-k threshold without the mask shares:
    ``topk_dense_nd`` below, and the fused server epilogue
    (ops/sketch.fused_epilogue_chunks, docs/fused_epilogue.md), whose
    megakernel takes p precomputed so its single sweep can mask, emit the
    update, and re-sketch in one pass.

    Precedence (mirrors ``_select_threshold_impl``): kill-switch
    (COMMEFFICIENT_PALLAS_TOPK=0) beats everything, then the fused
    whole-descent kernel A/B opt-in (COMMEFFICIENT_PALLAS_TOPK_FUSED=1 —
    deliberately bypasses the crossover gate: GPT-2-scale d is what the
    A/B tests), then the per-pass kernel below the measured gate, then
    pure XLA. Every implementation resolves exact integer counts, so they
    agree bit-for-bit.

    ``axis_name`` (sharded server, docs/sharded_server.md): ``vec`` is one
    shard's slice inside a ``shard_map``; the per-pass counts psum over
    the axis so p is the GLOBAL k-th magnitude. The fused whole-descent
    kernel cannot psum between its in-kernel passes, so the sharded path
    always uses the per-pass kernel or pure XLA."""
    import os

    from commefficient_tpu.utils import is_tpu_backend

    raw = vec.view(jnp.int32)
    if os.environ.get("COMMEFFICIENT_PALLAS_TOPK") == "0":
        return _threshold_descent_xla(raw, k, axis_name=axis_name)
    if (os.environ.get("COMMEFFICIENT_PALLAS_TOPK_FUSED") == "1"
            and is_tpu_backend() and axis_name is None):
        return _threshold_descent_fused(raw, k, interpret=interpret)
    if _use_pallas_topk(vec.size) or interpret:
        return _threshold_descent_pallas(raw, k, interpret=interpret,
                                         axis_name=axis_name)
    return _threshold_descent_xla(raw, k, axis_name=axis_name)


def topk_dense_nd(vec: jax.Array, k: int, interpret: bool = False,
                  axis_name=None) -> jax.Array:
    """Shape-preserving global magnitude top-k over EVERY element of an
    arbitrary-shape array — the chunked-resident round's entry point: the
    ``(T, S, 128)`` estimate chunks are thresholded in place, so no
    flat-layout materialization enters the steady-state server phase.

    Tie-inclusive threshold semantics identical to ``topk(method=
    "threshold")`` on the flattened input: the descent's counts are
    full-array reductions, so the resolved k-th-magnitude bit pattern (and
    therefore the kept set) matches the 1-D path's exactly. Zero-valued
    positions (e.g. a chunked layout's masked tail) can never win a nonzero
    threshold, and when fewer than k nonzeros exist they are kept with
    value 0 — the invariant-preserving dense-masked result. On TPU below
    the measured Pallas crossover the count passes run through the fused
    count kernel on a blocked flat view (the one remaining reshape rides
    the same path the flat round always paid; above the crossover the
    descent is reshape-free). Threshold dispatch precedence lives in
    ``resolve_threshold``."""
    raw = vec.view(jnp.int32)
    p = resolve_threshold(vec, k, interpret=interpret, axis_name=axis_name)
    return _apply_threshold(raw, vec, p)


def topk(vec: jax.Array, k: int, method: str = "threshold") -> jax.Array:
    """Dense vector with only the k largest-magnitude entries kept.

    Accepts 1-D ``(d,)`` or 2-D ``(rows, d)`` input (row-wise top-k), mirroring
    reference utils.py:246-252.
    """
    if method == "threshold":
        f = _select_threshold_impl(vec.shape[-1])
    elif method == "sort":
        f = _topk_sort_1d
    else:
        raise ValueError(f"unknown topk method {method!r}")
    if vec.ndim == 1:
        return f(vec, k)
    if vec.ndim == 2:
        return jax.vmap(lambda v: f(v, k))(vec)
    raise ValueError(f"topk supports 1-D or 2-D input, got ndim={vec.ndim}")
