"""The group plan of the client phase's sketch (docs/stream_sketch.md).

Contracts pinned on the forced-8-device CPU mesh:

1. planner (``ops/flat.coalesce_segments``): groups partition the leaves
   in order under the byte budget — zero-size leaves ride their
   neighbors, a leaf straddling many chunk boundaries coalesces or falls
   back cleanly, a budget covering the padded plane yields ONE group,
   and a budget smaller than one leaf falls back to a launch per leaf
   with ONE warning;
2. op level: ``ops/sketch.sketch_segments_accum`` (one launch per group)
   equals the leaf-by-leaf fold and the flat ``sketch_vec`` (``==``:
   all-zero cells may differ in zero sign), on the pure path and the
   Pallas kernel through the interpreter;
3. tree level: ``worker.sketch_grad_tree`` under a coarse plan equals
   the one-launch-per-leaf plan bit-for-bit, per-leaf tp/ep scales
   included;
4. round level: fp32 trajectories are BIT-IDENTICAL whatever the plan
   (a launch per leaf, two groups, one group) across
   replicated/``--server_shard`` × composed/``--fused_epilogue``;
5. structure: with COMMEFFICIENT_PALLAS_SKETCH=interpret the jitted
   client phase's sketch-accumulate ``pallas_call`` count EQUALS the
   plan's group count, ONCE a round (none inside the microbatch scan),
   whatever the count of scan steps;
6. a build pinned to the flat route ignores the budget.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from commefficient_tpu.federated.worker import sketch_grad_tree
from commefficient_tpu.ops.flat import (
    LeafSegment,
    SegmentGroup,
    coalesce_segments,
    leaf_segments,
    ravel_pytree,
)
from commefficient_tpu.ops import sketch as sk
from commefficient_tpu.ops.sketch import (
    coalesce_vmem_budget,
    make_sketch,
    sketch_segments_accum,
    sketch_vec,
)
from tests.test_stream_sketch import (
    _batch,
    _build,
    _max_scan_carry,
    _mlp_params,
    _run_rounds,
    _tree,
    _walk_eqns,
)

CE = 512  # chunk elements used by the planner-only tests


def _segs(*sizes, names=None):
    """A contiguous LeafSegment layout from leaf sizes (incl. zeros)."""
    out, off = [], 0
    for i, n in enumerate(sizes):
        name = names[i] if names else f"leaf{i}"
        out.append(LeafSegment(path=name, offset=off, size=n))
        off += n
    return tuple(out)


def _span_bytes(g: SegmentGroup) -> int:
    return (g.t_b - g.t_a) * CE * 4


# ---- 1. planner: partition / budget / edge cases -------------------------

class TestCoalescePlanner:
    def _check_partition(self, segs, groups):
        assert groups[0].start == 0 and groups[-1].stop == len(segs)
        for a, b in zip(groups[:-1], groups[1:]):
            assert a.stop == b.start
        for g in groups:
            assert g.offset == segs[g.start].offset
            assert g.size == sum(s.size for s in segs[g.start:g.stop])
            if g.size:
                assert g.t_a == g.offset // CE
                assert g.t_b == -(-(g.offset + g.size) // CE)

    def test_gpt2_like_layout_groups_fewer_than_leaves(self):
        """A GPT-2-shaped layout — one embedding-scale leaf followed by
        many small ln/bias/attn leaves — must coalesce to strictly fewer
        launches than leaves under a mid budget."""
        sizes = [10 * CE + 37]  # 'wte': straddles 11 chunk boundaries
        for _ in range(12):
            sizes += [CE // 2, 64, 0, 3 * CE + 5, 64]  # blocks w/ empties
        segs = _segs(*sizes)
        budget = 6 * CE * 4
        groups = coalesce_segments(segs, budget, chunk_elems=CE)
        self._check_partition(segs, groups)
        nonzero = sum(1 for s in segs if s.size)
        assert len(groups) < nonzero, (len(groups), nonzero)
        for g in groups:
            # only single-nonzero-leaf groups may exceed the budget
            if _span_bytes(g) > budget:
                assert sum(1 for s in segs[g.start:g.stop] if s.size) == 1

    def test_zero_size_leaves_ride_neighbors(self):
        """Zero-size leaves never form their own group — leading,
        embedded, and trailing empties all attach."""
        segs = _segs(0, 0, 100, 0, 200, 0, 0)
        groups = coalesce_segments(segs, 10 * CE * 4, chunk_elems=CE)
        self._check_partition(segs, groups)
        assert len(groups) == 1
        assert groups[0].size == 300

    def test_single_group_covers_whole_layout(self):
        segs = _segs(137, 1, CE, 3 * CE + 11, 40)
        total = segs[-1].offset + segs[-1].size
        padded_bytes = -(-total // CE) * CE * 4
        groups = coalesce_segments(segs, padded_bytes, chunk_elems=CE)
        self._check_partition(segs, groups)
        assert len(groups) == 1
        assert groups[0] == SegmentGroup(0, len(segs), 0, total, 0,
                                         -(-total // CE))

    def test_budget_smaller_than_leaf_falls_back_per_leaf_one_warning(self):
        """Every leaf's covering range exceeds a sub-chunk budget: the
        plan degenerates to one group per nonzero leaf (zero-size leaves
        still ride), with exactly ONE warning for the whole plan."""
        segs = _segs(CE, 0, 2 * CE, CE // 2, 0)
        with pytest.warns(RuntimeWarning,
                          match="covering chunk range") as rec:
            groups = coalesce_segments(segs, 100, chunk_elems=CE)
        assert len([w for w in rec
                    if issubclass(w.category, RuntimeWarning)]) == 1
        self._check_partition(segs, groups)
        assert len(groups) == 3  # one per nonzero leaf
        for g in groups:
            assert sum(1 for s in segs[g.start:g.stop] if s.size) == 1

    def test_degenerate_plan_warns_even_when_each_leaf_fits(self):
        """Leaves that each fit the budget alone but where NO adjacency
        does: the plan is fully per-leaf — zero benefit from the flag —
        and must warn, even though no single leaf is oversized."""
        segs = _segs(2 * CE, 2 * CE, 2 * CE)
        with pytest.warns(RuntimeWarning, match="no adjacent leaves "
                          "coalesced"):
            groups = coalesce_segments(segs, 2 * CE * 4, chunk_elems=CE)
        self._check_partition(segs, groups)
        assert len(groups) == 3

    def test_single_leaf_layout_is_silent(self):
        """One leaf = nothing to coalesce; a one-group plan is not a
        misconfiguration and must not warn."""
        segs = _segs(3 * CE)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            groups = coalesce_segments(segs, 100, chunk_elems=CE)
        assert len(groups) == 1

    def test_budget_respected_under_fit(self):
        """When no single leaf is oversized, every group's covering range
        fits the budget."""
        segs = _segs(*([CE // 4] * 40))
        budget = 3 * CE * 4
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # no fallback warning allowed
            groups = coalesce_segments(segs, budget, chunk_elems=CE)
        self._check_partition(segs, groups)
        assert 1 < len(groups) < 40
        for g in groups:
            assert _span_bytes(g) <= budget

    def test_empty_layout(self):
        assert coalesce_segments((), 1024, chunk_elems=CE) == ()

    def test_auto_budget_sane(self):
        cs = make_sketch(5000, 512, 3, seed=1, num_blocks=1)
        b = coalesce_vmem_budget(cs)
        # at least one chunk, at most the 32 MiB staging ceiling
        assert cs.c_pad * 4 <= b <= 32 * 1024 * 1024


class TestLeafSegmentsEdges:
    """ops/flat.leaf_segments edge cases the coalescer leans on: empty
    leaves occupy zero width (their neighbors stay contiguous) and scalar
    leaves occupy one slot — offsets always match the ravel layout."""

    def test_zero_size_and_scalar_leaves(self):
        tree = {
            "a": jnp.zeros((3, 4)),
            "empty": jnp.zeros((0, 7)),
            "s": jnp.asarray(2.5),
            "z": jnp.zeros((5,)),
        }
        segs = leaf_segments(tree)
        sizes = {s.path: s.size for s in segs}
        assert sizes["empty"] == 0
        assert sizes["s"] == 1
        # contiguity incl. across the empty leaf
        for a, b in zip(segs[:-1], segs[1:]):
            assert b.offset == a.offset + a.size
        flat, _ = ravel_pytree(tree)
        assert segs[-1].offset + segs[-1].size == int(flat.size)
        for s in segs:
            if s.path == "s":
                np.testing.assert_array_equal(
                    np.asarray(flat[s.offset]), np.float32(2.5))


# ---- 2. op level: grouped accumulate == per-leaf fold == composed --------

class TestSegmentsAccum:
    # (d, c, r, leaf boundaries) — unaligned cuts, 1-element leaves, a
    # leaf straddling many chunk boundaries, zero-size leaves
    CASES = [
        (5000, 512, 3, (0, 137, 138, 512, 512, 4000, 5000)),
        (5000, 512, 3, (0, 5000)),
        (3000, 128, 2, (0, 1, 2, 129, 129, 2900, 3000)),
    ]

    @staticmethod
    def _cuts(bounds):
        cuts = sorted(set(bounds))
        return list(zip(cuts[:-1], cuts[1:]))

    @pytest.mark.parametrize("d,c,r,bounds", CASES,
                             ids=[f"d{d}-{len(b)}cuts" for d, c, r, b
                                  in CASES])
    @pytest.mark.parametrize("interpret", [False, True],
                             ids=["pure", "interpret"])
    def test_grouped_equals_perleaf_and_composed(self, d, c, r, bounds,
                                                 interpret):
        cs = make_sketch(d, c, r, seed=7, num_blocks=2)
        v = jnp.asarray(np.random.RandomState(3).randn(d), jnp.float32)
        cuts = self._cuts(bounds)
        # leaf-by-leaf reference fold
        ref = jnp.zeros(cs.table_shape, jnp.float32)
        for a, b in cuts:
            ref = sketch_segments_accum(cs, ref, [v[a:b]], a,
                                        interpret=interpret)
        # grouped: split the leaves into two groups at an arbitrary point
        mid = max(1, len(cuts) // 2)
        tbl = jnp.zeros(cs.table_shape, jnp.float32)
        for grp in (cuts[:mid], cuts[mid:]):
            if not grp:
                continue
            tbl = sketch_segments_accum(cs, tbl,
                                        [v[a:b] for a, b in grp],
                                        grp[0][0], interpret=interpret)
        want = sketch_vec(cs, v)
        np.testing.assert_array_equal(np.asarray(tbl), np.asarray(ref))
        np.testing.assert_array_equal(np.asarray(tbl), np.asarray(want))

    def test_zero_size_segments_inside_group(self):
        cs = make_sketch(2000, 256, 3, seed=2, num_blocks=2)
        v = jnp.asarray(np.random.RandomState(9).randn(2000), jnp.float32)
        t = jnp.zeros(cs.table_shape, jnp.float32)
        got = sketch_segments_accum(
            cs, t, [v[0:0], v[:700], jnp.zeros(0), v[700:2000]], 0)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(sketch_vec(cs, v)))

    @pytest.mark.parametrize("interpret", [False, True],
                             ids=["pure", "interpret"])
    def test_single_segment_onto_running_table(self, interpret):
        """One unaligned segment onto a running table == the pure fold of
        its covering chunks (what the kernel's on-TPU self-check holds)."""
        cs = make_sketch(2000, 256, 3, seed=4, num_blocks=2)
        v = jnp.asarray(np.random.RandomState(1).randn(900), jnp.float32)
        base = jnp.asarray(
            np.random.RandomState(2).randn(*cs.table_shape), jnp.float32)
        got = sketch_segments_accum(cs, base, [v], 613, interpret=interpret)
        seg3, t_a = sk._segment_chunks(cs, v, 613)
        want = sk._sketch_accum_chunks_jax(cs, base, seg3, t_a)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_empty_group_and_bounds(self):
        cs = make_sketch(1000, 128, 2, seed=3, num_blocks=1)
        t = jnp.zeros(cs.table_shape, jnp.float32)
        out = sketch_segments_accum(cs, t, [jnp.zeros(0), jnp.zeros(0)],
                                    500)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(t))
        with pytest.raises(AssertionError):
            sketch_segments_accum(cs, t, [jnp.zeros(10)], 995)  # past d


# ---- 3. tree level: sketch_grad_tree(groups=) == per-leaf ----------------

def _per_leaf(segs, cs):
    """The plan of one launch a nonzero leaf (a budget under one chunk;
    the planner says so once)."""
    with pytest.warns(RuntimeWarning, match="no adjacent leaves"):
        return coalesce_segments(segs, 1, chunk_elems=cs.c_pad)


class TestGradTreeCoalesced:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_groups_equal_perleaf(self, dtype):
        tree = _tree(dtype=dtype, seed=4)
        flat, _ = ravel_pytree(tree)
        d = int(flat.size)
        segs = leaf_segments(tree)
        cs = make_sketch(d, 128, 3, seed=11, num_blocks=1)
        groups = coalesce_segments(segs, 4 * 128 * 4,
                                   chunk_elems=cs.c_pad)
        assert 1 < len(groups) < len(segs)
        zero = jnp.zeros(cs.table_shape, jnp.float32)
        got = sketch_grad_tree(cs, zero, tree, segs, groups)
        want = sketch_grad_tree(cs, zero, tree, segs, _per_leaf(segs, cs))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(sketch_vec(cs, flat)))

    def test_per_leaf_scales_with_groups(self):
        tree = _tree(seed=6)
        flat, _ = ravel_pytree(tree)
        d = int(flat.size)
        segs = leaf_segments(tree)
        scales = tuple(1.0 if i % 2 else 0.5 for i in range(len(segs)))
        cs = make_sketch(d, 128, 3, seed=12, num_blocks=1)
        groups = coalesce_segments(segs, 4 * 128 * 4,
                                   chunk_elems=cs.c_pad)
        zero = jnp.zeros(cs.table_shape, jnp.float32)
        got = sketch_grad_tree(cs, zero, tree, segs, groups, scales=scales)
        want = sketch_grad_tree(cs, zero, tree, segs, _per_leaf(segs, cs),
                                scales=scales)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_groups_must_partition(self):
        tree = _tree(seed=7)
        segs = leaf_segments(tree)
        d = segs[-1].offset + segs[-1].size
        cs = make_sketch(d, 128, 3, seed=13, num_blocks=1)
        groups = coalesce_segments(segs, 4 * 128 * 4,
                                   chunk_elems=cs.c_pad)
        assert len(groups) >= 2
        zero = jnp.zeros(cs.table_shape, jnp.float32)
        with pytest.raises(AssertionError, match="partition"):
            sketch_grad_tree(cs, zero, tree, segs, groups[:-1])


# ---- 4./5./6. round level on the 8-device mesh ---------------------------

# a budget that coalesces the MLP's 6 leaves (d=4141, c_pad=128, T=33)
# into 2 groups — fewer launches than leaves, more than one group
BUDGET = 32 * 128 * 4
WHOLE = 33 * 128 * 4  # the padded plane: one group
PER_LEAF = 1          # under one chunk: a launch per leaf (one warning)


def _plan(budget=BUDGET, d=4141):
    """The plan a ``budget`` build uses (same inputs as
    build_round_step's: the leaf offset map + the sketch's c_pad)."""
    tpl = jax.eval_shape(_mlp_params)
    segs = leaf_segments(tpl)
    cs_geo = make_sketch(d, 16, 3, seed=0, num_blocks=1)
    return segs, coalesce_segments(segs, budget, chunk_elems=cs_geo.c_pad)


@pytest.mark.filterwarnings("ignore:coalesce_segments:RuntimeWarning")
class TestPlanRoundBitIdentity:
    """Acceptance criterion: fp32 trajectories are bit-identical whatever
    the group plan, across both server planes and both epilogues: a
    coarser plan replays the finer plan's per-cell add order exactly.
    (One scan step and no decay: XLA:CPU contracts ``g + coef · w`` into
    a fused multiply-add in some plans' staging fusions and not in
    others', and compiles the MLP's backward pass differently from
    program to program; tests/test_stream_sketch.py holds both to ``==``
    on ``_int_loss``.)"""

    @pytest.mark.parametrize("shard", [False, True],
                             ids=["replicated", "server_shard"])
    @pytest.mark.parametrize("fused", [False, True],
                             ids=["composed", "fused_epilogue"])
    def test_trajectory_bit_identical(self, shard, fused, monkeypatch):
        if fused:
            monkeypatch.setenv("COMMEFFICIENT_FUSED_EPILOGUE", "interpret")
        kw = dict(server_shard=shard, fused=fused)
        runs = [_run_rounds(*_build(budget=b, **kw)[:4])
                for b in (PER_LEAF, BUDGET, WHOLE)]
        a, ssa, _ = runs[0]
        for b, ssb, _ in runs[1:]:
            for rnd, (x, y) in enumerate(zip(a, b)):
                np.testing.assert_array_equal(
                    x, y,
                    err_msg=f"shard={shard} fused={fused} round {rnd} ps")
            for name in ("velocity", "error"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(ssa, name)),
                    np.asarray(getattr(ssb, name)), err_msg=name)

    def test_flat_build_ignores_the_budget(self):
        """A build pinned to the flat route must not half-enable anything:
        its client phase is the flat one (scan carry is d-sized, no plan),
        and the trajectory matches the budget-less flat build's
        bit-for-bit."""
        steps_c, ps_c, ss_c, cs_c, d = _build(False, budget=BUDGET)
        assert (steps_c.client_sketch_path,
                steps_c.client_sketch_launches) == ("flat", 0)
        args = (ps_c, cs_c, {}, _batch(0), 0.1, jax.random.key(0))
        carry = _max_scan_carry(steps_c.client_step, *args)
        assert carry >= d, \
            f"flat carry {carry} should be d-sized (d={d})"
        a, _, _ = _run_rounds(*_build(False)[:4])
        b, _, _ = _run_rounds(*_build(False, budget=BUDGET)[:4])
        for rnd, (x, y) in enumerate(zip(a, b)):
            np.testing.assert_array_equal(x, y, err_msg=f"round {rnd}")


# ---- structural assert: launch count == group count, once a round --------

def _count_accum_launches(fn, *args):
    """``pallas_call`` eqns in the jaxpr, (outside, inside) any scan —
    with COMMEFFICIENT_PALLAS_SKETCH=interpret the client phase's only
    Pallas calls are the sketch-accumulate launches, so ``outside`` IS the
    client phase's launch count a round and ``inside`` what would run once
    a scan step."""
    count = [0, 0]

    def visit(eqn, in_scan):
        if eqn.primitive.name == "pallas_call":
            count[in_scan] += 1

    _walk_eqns(fn, args, visit)
    return tuple(count)


@pytest.mark.filterwarnings("ignore:coalesce_segments:RuntimeWarning")
class TestPlanStructure:
    """Acceptance criterion: the client phase launches exactly ONE
    sketch-accumulate kernel per plan group, once a round: none of them
    inside the microbatch scan, whatever the count of scan steps."""

    def _launches(self, **kw):
        steps, ps, _, cstates, _ = _build(**kw)
        return steps.client_sketch_launches, _count_accum_launches(
            steps.client_step, ps, cstates, {}, _batch(0), 0.1,
            jax.random.key(0))

    @pytest.mark.parametrize("micro", [-1, 2, 1],
                             ids=["scan1", "scan2", "scan4"])
    def test_launches_equal_group_count(self, micro, monkeypatch):
        monkeypatch.setenv("COMMEFFICIENT_PALLAS_SKETCH", "interpret")
        segs, groups = _plan()
        n_leaves = sum(1 for s in segs if s.size)
        assert 1 < len(groups) < n_leaves, \
            "test layout must coalesce to fewer groups than leaves"
        said, (outside, inside) = self._launches(budget=BUDGET, micro=micro,
                                                 wd=5e-4)
        assert said == outside == len(groups), (said, outside, len(groups))
        assert inside == 0, f"{inside} accumulate launches a scan step"

    def test_plan_under_one_chunk_launches_per_leaf(self, monkeypatch):
        """The detector counts what runs: the degenerate plan shows one
        launch a leaf, the one-group plan one."""
        monkeypatch.setenv("COMMEFFICIENT_PALLAS_SKETCH", "interpret")
        segs, groups = _plan()
        n_leaves = sum(1 for s in segs if s.size)
        said, (outside, inside) = self._launches(budget=PER_LEAF, micro=2)
        assert said == outside == n_leaves > len(groups) and inside == 0
        said, (outside, inside) = self._launches(budget=WHOLE, micro=2)
        assert said == outside == 1 and inside == 0

    def test_flat_route_has_no_accumulate_launch(self, monkeypatch):
        monkeypatch.setenv("COMMEFFICIENT_PALLAS_SKETCH", "interpret")
        assert self._launches(leaf=False, micro=2) == (0, (0, 0))
