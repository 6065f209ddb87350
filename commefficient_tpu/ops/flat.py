"""Flat-parameter-vector plumbing and the chunked resident layout.

The reference keeps the authoritative model as a flat float vector and
scatters/gathers it into the torch module per step (``get_param_vec`` /
``set_param_vec``, reference utils.py:281-297). In JAX the idiomatic
equivalent is ``jax.flatten_util.ravel_pytree``: ravel once at init to obtain
the flat vector and a closed-over ``unravel`` function; the forward pass
unravels under jit, where XLA turns the reshape/slice into free views.

``ChunkLayout`` is the **chunked resident layout** for sketch-mode rounds:
the lane-aligned ``(T, S, 128)`` chunk/sublane/lane shape the count-sketch
kernels consume (ops/sketch.py). The GPT-2 per-op profile
(v5e, 2026-08-01, capture since deleted) showed ~7 ms/round of pure layout
churn converting the d=124M flat vector to and from this shape
(``pad.6``/``reshape.950``/``reshape.2197``) plus the flat ravel concat
(``concatenate.35``); keeping PS state resident in the chunked shape
end-to-end makes those per-round conversions disappear — the flat view is
materialized only at the model (pytree) boundary. Invariant: a resident
chunked array carries **zeros in its padded tail** (coordinates ≥ d); every
linear op preserves it, and the one nonlinear producer (sketch ``estimates``,
whose tail cells are hash noise) is masked by ``mask_tail`` before re-entering
the resident data plane.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree as _ravel_pytree

LANES = 128


def ravel_pytree(params: Any) -> Tuple[jax.Array, Callable[[jax.Array], Any]]:
    """Flatten a parameter pytree into a float32 vector + unravel closure."""
    flat, unravel = _ravel_pytree(params)
    return flat.astype(jnp.float32), unravel


class LeafSegment(NamedTuple):
    """One pytree leaf's place in ``ravel_pytree``'s flat layout."""

    path: str    # '/'-joined lowercase param path (rounds._flat_scale form)
    offset: int  # global flat element offset of the leaf's first element
    size: int    # number of elements (C-order ravel of the leaf)


def leaf_segments(tree: Any) -> Tuple[LeafSegment, ...]:
    """Per-leaf ``(path, offset, size)`` of ``ravel_pytree``'s flat layout:
    leaves in ``tree_flatten`` order, each raveled C-order, offsets the
    running cumulative size — THE offset map the sketch cells' client phase
    (docs/stream_sketch.md) uses to sketch each gradient leaf at its global
    coordinate base instead of materializing the concatenated d-vector,
    and the one the tp/ep flat grad-rescale masks are built from
    (rounds._flat_scale), so the two layouts cannot drift. ``tree`` may be
    real arrays or ``jax.eval_shape`` structs (only shapes are read)."""
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    segs = []
    start = 0
    for path, leaf in leaves:
        keys = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path).lower()
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        segs.append(LeafSegment(path=keys, offset=start, size=n))
        start += n
    return tuple(segs)


class SegmentGroup(NamedTuple):
    """A contiguous run of ``leaf_segments`` leaves coalesced into ONE
    sketch-accumulate launch (docs/stream_sketch.md). Because ``leaf_segments`` offsets are the
    running cumulative size, the run covers one contiguous flat span
    ``[offset, offset + size)`` whose covering chunk range is
    ``[t_a, t_b)`` — the range the kernel keeps the table row block
    VMEM-resident across."""

    start: int   # index of the first leaf in the group (into segs)
    stop: int    # one past the last leaf index
    offset: int  # flat element offset of the group's first element
    size: int    # total elements (the leaves are contiguous)
    t_a: int     # first covering chunk
    t_b: int     # one past the last covering chunk (== t_a when size == 0)


def coalesce_segments(segs: Sequence[LeafSegment], vmem_budget: int, *,
                      chunk_elems: int) -> Tuple[SegmentGroup, ...]:
    """Greedy in-order grouping of adjacent ``leaf_segments`` leaves into
    covering chunk-range groups under a static byte budget — the planner
    of the client phase's sketch (docs/stream_sketch.md). A group is
    extended while its covering chunk range ``[t_a, t_b)`` stays within
    ``vmem_budget`` bytes of f32 chunks (``chunk_elems`` = the sketch's
    ``c_pad``); the accumulate kernel then pays ONE table row-block
    read + write per group instead of per leaf.

    Rules (pinned in tests/test_sketch_coalesce.py):

    - groups PARTITION the leaves in order (every leaf in exactly one
      group; flat spans are contiguous by the ``leaf_segments`` layout);
    - zero-size leaves never open or close a group on their own — they
      ride whichever group is current (their covering range is empty);
    - a single leaf whose covering range alone exceeds the budget cannot
      be split (splitting would only ADD launches): it forms its own
      group — one launch for that leaf, already optimal (a GPT-2-scale embedding leaf under the auto
      budget is the normal case, so an oversized leaf alone is silent);
    - when the budget is smaller than EVERY adjacency — no multi-leaf
      group forms at all and the plan degenerates to a launch per leaf
      (e.g. a budget below one chunk) — ONE warning per plan says so.

    Host-side and deterministic; called once per round-step build, never
    under jit.
    """
    segs = tuple(segs)
    if not segs:
        return ()
    ce = int(chunk_elems)
    budget = int(vmem_budget)
    assert ce > 0, ce
    assert budget > 0, budget
    for a, b in zip(segs[:-1], segs[1:]):
        # the single-span group math relies on the leaf_segments layout:
        # each leaf starts exactly where the previous one ends
        assert b.offset == a.offset + a.size, (a, b)

    def span_bytes(e0: int, e1: int) -> int:
        if e1 <= e0:
            return 0
        return (-(-e1 // ce) - e0 // ce) * ce * 4

    def mk(start: int, stop: int) -> SegmentGroup:
        e0 = segs[start].offset
        e1 = segs[stop - 1].offset + segs[stop - 1].size
        size = e1 - e0
        t_a = e0 // ce
        t_b = -(-e1 // ce) if size else t_a
        return SegmentGroup(start=start, stop=stop, offset=e0, size=size,
                            t_a=t_a, t_b=t_b)

    groups = []
    start = 0
    g_e0 = segs[0].offset
    cur_size = segs[0].size
    for i in range(1, len(segs)):
        s = segs[i]
        end = s.offset + s.size
        if (span_bytes(g_e0, end) <= budget or cur_size == 0
                or s.size == 0):
            # fits; or the group holds only zero-size leaves so far (an
            # oversized leaf joining them still yields one launch); or
            # the leaf itself is zero-size (adds no span)
            cur_size += s.size
            continue
        groups.append(mk(start, i))
        start, g_e0, cur_size = i, s.offset, s.size
    groups.append(mk(start, len(segs)))

    n_nonzero = sum(1 for s in segs if s.size)
    multi = any(sum(1 for s in segs[g.start:g.stop] if s.size) > 1
                for g in groups)
    if n_nonzero > 1 and not multi:
        # there WAS something to coalesce (>= 2 nonzero leaves) and the
        # plan coalesced nothing — every adjacency (and possibly every
        # single leaf) exceeds the budget, so grouping buys
        # zero benefit: the degenerate misconfiguration worth one
        # warning. (An oversized leaf INSIDE an otherwise-coalesced plan
        # is normal — GPT-2's embedding under the auto budget — and its
        # single launch is already optimal, so it stays silent.)
        worst = max((g for g in groups if g.size),
                    key=lambda g: g.t_b - g.t_a)
        big = next(segs[i] for i in range(worst.start, worst.stop)
                   if segs[i].size)
        warnings.warn(
            f"coalesce_segments: budget {budget} B is smaller than every "
            f"leaf adjacency's covering chunk range (largest single leaf "
            f"{big.path!r}: {worst.t_b - worst.t_a} chunks "
            f"= {(worst.t_b - worst.t_a) * ce * 4} B); no adjacent "
            f"leaves coalesced — the plan degenerates to one per-leaf "
            f"launch each", RuntimeWarning)
    return tuple(groups)


def chunked_unravel(layout: "ChunkLayout",
                    template: Any) -> Callable[[jax.Array], Any]:
    """Parameter pytree directly from the ``(T, S, 128)`` resident layout
    with NO d-sized flatten: each leaf slices only its covering chunk rows
    (a pure slice), flattens that block (≤ leaf size + 2 chunks), and
    reshapes to the leaf shape. Bitwise the same values as
    ``unravel(layout.unchunk(c3))`` for the matching ``ravel_pytree``
    layout — the leaf-group client phase's model boundary
    (docs/stream_sketch.md), run once a round.
    ``template`` may be real arrays or ``jax.eval_shape`` structs."""
    segs = leaf_segments(template)
    flat_leaves, treedef = jax.tree_util.tree_flatten(template)
    shapes = [l.shape for l in flat_leaves]
    dtypes = [l.dtype for l in flat_leaves]
    ce = layout.S * LANES  # elements per chunk

    def unravel_chunks(c3: jax.Array) -> Any:
        assert c3.shape == layout.shape, (c3.shape, layout.shape)
        leaves = []
        for seg, shp, dt in zip(segs, shapes, dtypes):
            t0 = seg.offset // ce
            t1 = -(-(seg.offset + seg.size) // ce)
            block = c3[t0:t1].reshape((t1 - t0) * ce)
            lo = seg.offset - t0 * ce
            x = jax.lax.slice_in_dim(block, lo, lo + seg.size)
            leaves.append(x.reshape(shp).astype(dt))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return unravel_chunks


@dataclass(frozen=True)
class ChunkLayout:
    """Geometry of the ``(T, S, 128)`` chunked resident layout of a
    ``(d,)`` vector: T chunks of S sublanes x 128 lanes, zero-padded tail."""

    d: int
    T: int
    S: int

    @property
    def shape(self) -> Tuple[int, int, int]:
        return (self.T, self.S, LANES)

    @property
    def padded_size(self) -> int:
        return self.T * self.S * LANES

    def chunk(self, v: jax.Array) -> jax.Array:
        """``(d,)`` → ``(T, S, 128)`` with a zero tail (dtype-preserving —
        the resident plane also carries bool/int32 accounting arrays)."""
        assert v.shape == (self.d,), (v.shape, self.d)
        v = jnp.asarray(v)
        v_p = jnp.pad(v, (0, self.padded_size - self.d))
        return v_p.reshape(self.shape)

    def unchunk(self, c3: jax.Array) -> jax.Array:
        """``(T, S, 128)`` → ``(d,)`` (drops the padded tail)."""
        assert c3.shape == self.shape, (c3.shape, self.shape)
        return c3.reshape(self.padded_size)[: self.d]

    def mask_tail(self, c3: jax.Array) -> jax.Array:
        """Zero the padded-tail positions (coordinates ≥ d) — restores the
        resident-layout invariant after a nonlinear producer."""
        if self.padded_size == self.d:
            return c3
        idx = self.flat_index()
        return jnp.where(idx < self.d, c3, jnp.zeros((), c3.dtype))

    def flat_index(self) -> jax.Array:
        """int32 ``(T, S, 128)`` array holding each position's flat
        coordinate index (tail positions hold indices ≥ d)."""
        chunk_elems = self.S * LANES
        return (
            jax.lax.broadcasted_iota(jnp.int32, self.shape, 0) * chunk_elems
            + jax.lax.broadcasted_iota(jnp.int32, self.shape, 1) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, self.shape, 2))

