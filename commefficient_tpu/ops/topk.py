"""Magnitude top-k sparsification.

Parity with the reference's ``_topk`` (reference utils.py:232-252): keep the k
largest-magnitude coordinates of a vector (or of each row of a matrix), zero
the rest, returned as a dense masked vector.

TPU-first design: ``jax.lax.top_k`` at FetchSGD scale (k=50k over d≈6.5M) is
a full sort — ~15 ms/call on a v5e chip. The callers only ever need the
*dense masked* result (never the index list), so the selection reduces to
the k-th magnitude as a scalar threshold, found exactly by a radix-nibble
descent over the **int32 bit patterns** of the absolute values (non-negative
IEEE-754 floats compare identically as integers): 8 passes, each comparing
every element against the 15 (7 for the top nibble — finite ``|float|``
patterns keep bit 31 clear) candidate extensions of the resolved prefix and
keeping the largest whose ≥-count still reaches k: 4 threshold bits per
read, pure int32 compares, no float bisection precision cliffs at any
dynamic range, ``|vec|`` recomputed per pass (2 VPU ops). Above
``_PALLAS_TOPK_MAX_D`` elements the passes do not sweep the plane: only k of
its d/128 granules can hold the cut, and the descent runs over those
(``_threshold_descent_pruned``), to the same pattern bit for bit. Properties:

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
# below, the GPT-2 and JoyAI cells above, where no whole-plane descent runs
# on one chip any more: the plane is pruned to k granules first
# (``_threshold_path``) and the descent left is below-gate sized again.
_PALLAS_TOPK_MAX_D = 32 * 1024 * 1024
# Pruning granule: one lane row of the (T, S, 128) chunk view, so a
# granule's maximum is a minor-axis reduce and its gather a 512-byte row.
_GRANULE = _LANES
# Prune only a plane of at least this many granules per kept coordinate:
# the second descent reads k granules, so at 4 it reads a quarter of the
# plane at most and the max sweep and gather are paid back; nearer 1 the
# candidates are the plane again (the cells have 19x and 65x).
_PRUNE_MIN_GRANULES_PER_K = 4


def _sub_for(d: int) -> int:
    """Count/descent-kernel block sublanes chosen from d: (512, 128) i32 =
    256 KiB blocks at FetchSGD scale (the measured 37x-win shape), 4x that
    (1 MiB, still double-buffered in VMEM) above the 32M gate. The default
    path prunes first there (``_threshold_path``): only the opt-in fused
    kernel, or the per-pass kernel forced onto a sharded slice, gets the 4x.

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


def _mag(raw: jax.Array) -> jax.Array:
    """|pattern| as int (abs, not the reference's square, utils.py:246:
    squares underflow below |v|≈1e-19 and overflow above ≈2e19; bit
    patterns are exact at every representable magnitude); NaN → 0 so
    divergence never wins the threshold race."""
    m = raw & _ABS_MASK
    return jnp.where(m > _INF_BITS, 0, m)


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


def check_pruned_descent(d: int, k: int, sublanes: int,
                         interpret: bool = False) -> None:
    """Granule-pruned descent == the whole-plane XLA descent, on the flat
    vector and on its ``(T, sublanes, 128)`` chunk view with a zero tail:
    chip_smoke.py runs it at the GPT-2 geometry, above the gate, where
    the CPU tests cannot reach."""
    vec = _check_vec(d)
    want = _threshold_descent_xla(vec.view(jnp.int32), k)
    descend = functools.partial(resolve_threshold, interpret=interpret)
    require_equal(_threshold_descent_pruned(vec, k, descend), want,
                  "pruned descent (flat)")
    chunk = sublanes * _LANES
    v3 = jnp.pad(vec, (0, -d % chunk)).reshape(-1, sublanes, _LANES)
    require_equal(_threshold_descent_pruned(v3, k, descend), want,
                  "pruned descent (chunk view)")


def _threshold_path(size: int, k: int, interpret: bool = False,
                    axis_name=None) -> str:
    """THE rule that picks how the k-th magnitude of ``size`` elements is
    resolved, from what the call can see (``resolve_threshold``, ``topk``
    and the run header's ``topk_plan`` all ask here):

      - ``"fused"``: the whole-descent kernel, opt-in only
        (COMMEFFICIENT_PALLAS_TOPK_FUSED=1 on a TPU): never measured on the
        chip (ROADMAP D2). It cannot psum between its in-kernel passes, so
        never on a sharded slice;
      - ``"pruned"``: above the gate, on one chip's whole plane
        (``axis_name is None``), with at least
        ``_PRUNE_MIN_GRANULES_PER_K`` granules per kept coordinate:
        ``_threshold_descent_pruned``, whose descent over the candidates
        comes back here at its own, below-gate size. A sharded slice keeps the
        whole-slice descent with psum'd counts (ROADMAP D3);
      - ``"pallas"``: the per-pass count kernel, on a TPU below the gate
        (or forced, or interpreted);
      - ``"xla"``: everywhere else, and whole under the kill-switch
        COMMEFFICIENT_PALLAS_TOPK=0, which beats the kernels (pruning is
        no kernel: it stays, with an XLA descent inside)."""
    import os

    from commefficient_tpu.utils import is_tpu_backend

    killed = os.environ.get("COMMEFFICIENT_PALLAS_TOPK") == "0"
    if (not killed and axis_name is None and is_tpu_backend()
            and os.environ.get("COMMEFFICIENT_PALLAS_TOPK_FUSED") == "1"):
        return "fused"
    if (axis_name is None and size > _PALLAS_TOPK_MAX_D
            and -(-size // _GRANULE) >= _PRUNE_MIN_GRANULES_PER_K * k):
        return "pruned"
    if not killed and (_use_pallas_topk(size) or interpret):
        return "pallas"
    return "xla"


def topk_plan(size: int, k: int, sharded: bool = False) -> dict:
    """How a run resolves its top-k threshold, for the run header
    (telemetry ``run_start.topk_plan``, docs/observability.md): the path is
    static for a run, so this line and ``topk_ms`` say how often pruning
    engages. ``candidates`` is what the descent then reads, ``share``
    that over the plane."""
    path = _threshold_path(size, k, axis_name="shard" if sharded else None)
    plan = {"path": path}
    if path == "pruned":
        plan.update(granule=_GRANULE, granules=-(-size // _GRANULE),
                    candidates=k * _GRANULE, share=k * _GRANULE / size)
    return plan


def _select_threshold_impl(d: int, k: int):
    """The dense-masked 1-D top-k for this geometry (``_threshold_path``
    has the rule)."""
    return {"fused": _topk_threshold_1d_fused,
            "pruned": topk_dense_nd,
            "pallas": _topk_threshold_1d_pallas,
            "xla": _topk_threshold_1d}[_threshold_path(d, k)]


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

    # Radix descent: after each pass p is the resolved high-nibble prefix of
    # the k-th largest magnitude's bit pattern, maintaining
    # count(m ≥ p) ≥ k. Unrolled: 8 static passes, thresholds are ints.
    p = jnp.int32(0)
    for shift in range(28, -1, -4):
        hi_nib = 8 if shift == 28 else 16
        ts = p + (jnp.arange(1, hi_nib, dtype=jnp.int32) << shift)
        m = _mag(raw)
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


def _bits(x: jax.Array) -> jax.Array:
    """The int32 bit patterns of a float32 array (or the array, if it is
    them already)."""
    return x if x.dtype == jnp.int32 else x.view(jnp.int32)


def _granule_rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """Magnitudes of granules ``idx`` of ``x``, ``[len(idx), _GRANULE]``,
    gathered from the array as given (``_granule_maxima`` numbers them):
    the float plane itself, not its int32 view, which XLA would write out
    whole as the gather's operand."""
    if x.ndim > 1 and x.shape[-1] == _GRANULE:
        lead = jnp.unravel_index(idx, x.shape[:-1])
        return _mag(_bits(x[lead]))
    flat = x.reshape(-1)
    # dynamic_slice clamps the last, partial granule's window back inside
    # the vector: blank what it then repeats of the granule before it
    start = jnp.minimum(idx * _GRANULE, flat.shape[0] - _GRANULE)
    rows = jax.vmap(lambda s: jax.lax.dynamic_slice(
        flat, (s,), (_GRANULE,)))(start)
    pos = start[:, None] + jnp.arange(_GRANULE)
    return jnp.where(pos >= idx[:, None] * _GRANULE, _mag(_bits(rows)), 0)


def _granule_maxima(x: jax.Array) -> jax.Array:
    """Largest magnitude of every granule of ``_GRANULE`` consecutive
    elements, flat ``(n,)``, reduced from the view as given: a chunk
    view's lane rows, or a flat vector's full granules and its tail — no
    pad and no copy of the plane."""
    if x.ndim > 1 and x.shape[-1] == _GRANULE:
        return jnp.max(_mag(_bits(x)), axis=-1).reshape(-1)
    flat = _bits(x).reshape(-1)
    full = flat.shape[0] // _GRANULE
    gmax = jnp.max(_mag(flat[:full * _GRANULE]).reshape(full, _GRANULE),
                   axis=-1)
    if full * _GRANULE == flat.shape[0]:
        return gmax
    return jnp.append(gmax, jnp.max(_mag(flat[full * _GRANULE:])))


def _threshold_descent_pruned(x: jax.Array, k: int, descend) -> jax.Array:
    """The k-th-largest-magnitude bit pattern of ``x`` (any shape; float32
    or its int32 view), equal to ``_threshold_descent_xla``'s, from the k
    granules that can hold it. ``descend(raw, k)`` resolves the gathered
    candidates, a below-gate array.

    Let q be the k-th largest granule maximum and p the pattern sought.
    The pick is **every granule whose maximum is above q, the remaining
    slots filled with granules whose maximum equals q** (``lax.top_k`` of
    the maxima is exactly that). Proof that the k-th largest of the picked
    elements is p: k maxima are ≥ q, so p ≥ q. A granule not picked holds
    nothing above q, so for every threshold above q the picked elements
    count what the plane counts: if p > q they resolve p. If p = q no
    threshold above q reaches k on either, and each of the k slots holds
    an element ≥ q: they resolve q. Ties, NaNs (magnitude 0) and fewer
    than k nonzeros (p = 0) are cases of the same two lines."""
    gmax = _granule_maxima(x)
    if gmax.shape[0] < k:
        raise ValueError(f"{gmax.shape[0]} granules cannot hold k={k}")
    _, idx = jax.lax.top_k(gmax, k)
    return descend(_granule_rows(x, idx), k)


def resolve_threshold(vec: jax.Array, k: int, interpret: bool = False,
                      axis_name=None) -> jax.Array:
    """THE k-th-largest-magnitude bit-pattern resolver (scalar int32 p) for
    an arbitrary-shape float32 array — the one dispatch point every caller
    that needs the top-k threshold without the mask shares:
    ``topk_dense_nd`` below, and the fused server epilogue
    (ops/sketch.fused_epilogue_chunks, docs/fused_epilogue.md), whose
    megakernel takes p precomputed so its single sweep can mask, emit the
    update, and re-sketch in one pass. ``_threshold_path`` picks the
    implementation; every one resolves exact integer counts, so they agree
    bit-for-bit.

    ``axis_name`` (sharded server, docs/sharded_server.md): ``vec`` is one
    shard's slice inside a ``shard_map``; the per-pass counts psum over
    the axis so p is the GLOBAL k-th magnitude."""
    path = _threshold_path(vec.size, k, interpret, axis_name)
    if path == "pruned":
        # magnitudes are their own bit patterns: the candidates come back
        # here, below the gate
        return _threshold_descent_pruned(
            vec, k, functools.partial(resolve_threshold, interpret=interpret))
    raw = _bits(vec)
    if path == "fused":
        return _threshold_descent_fused(raw, k, interpret=interpret)
    if path == "pallas":
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
    plane is read in place, once for the granule maxima and once for the
    mask). ``_threshold_path`` has the dispatch rule."""
    raw = vec.view(jnp.int32)
    p = resolve_threshold(vec, k, interpret=interpret, axis_name=axis_name)
    return _apply_threshold(raw, vec, p)


def topk(vec: jax.Array, k: int, method: str = "threshold") -> jax.Array:
    """Dense vector with only the k largest-magnitude entries kept.

    Accepts 1-D ``(d,)`` or 2-D ``(rows, d)`` input (row-wise top-k), mirroring
    reference utils.py:246-252.
    """
    if method == "threshold":
        f = _select_threshold_impl(vec.shape[-1], k)
    elif method == "sort":
        f = _topk_sort_1d
    else:
        raise ValueError(f"unknown topk method {method!r}")
    if vec.ndim == 1:
        return f(vec, k)
    if vec.ndim == 2:
        return jax.vmap(lambda v: f(v, k))(vec)
    raise ValueError(f"topk supports 1-D or 2-D input, got ndim={vec.ndim}")
