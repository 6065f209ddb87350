"""The sketch cells' client phase: leaf groups (docs/stream_sketch.md).

Contracts pinned on the forced-8-device CPU mesh:

1. op level: accumulating a vector through ``sketch_segments_accum`` calls
   in offset order — any segmentation, any (mis)alignment, bf16 or f32
   segments — equals the flat ``sketch_vec`` of the whole vector (``==``:
   all-zero cells may differ in zero sign), on both the pure path and the
   Pallas accumulate kernel through the interpreter;
2. tree level: ``worker.sketch_grad_tree`` over a gradient pytree with
   the ``ops/flat.leaf_segments`` offset map equals
   ``sketch_vec(ravel_pytree(tree))`` across leaf-count/dtype mixes
   (bf16 grads, fp32 table), per-leaf scales and the weight decay read
   from the plane in the staging pass included, and ``ops/flat.chunked_unravel`` rebuilds the pytree
   from the resident chunk plane bit-exactly;
3. round level: the leaf-group route's table ``==`` the flat route's for
   scan steps ∈ {1, 2, 4} × weight decay ∈ {0, 5e-4} × replicated /
   ``--server_shard``, and fp32 trajectories and server state are
   BIT-IDENTICAL across replicated/``--server_shard`` × composed /
   ``--fused_epilogue`` epilogues;
4. structure: the jitted leaf-group client phase contains NO d-sized
   concatenate/pad/reshape (HLO inspection) and its scan carries a tree
   of leaf shapes (jaxpr walk) — while the flat build demonstrably trips
   both detectors, so the asserts are not vacuous;
5. selection: sketch mode inside the fused + sketch-after-sum + chunked
   window takes the leaf groups; ``uncompressed``, ``true_topk`` and
   per-client state take the flat route; no flag or environment variable
   selects.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from commefficient_tpu.federated.rounds import (
    RoundConfig,
    build_round_step,
    init_client_states,
)
from commefficient_tpu.federated.server import (
    ServerConfig,
    init_server_state,
)
from commefficient_tpu.federated.worker import WorkerConfig, sketch_grad_tree
from commefficient_tpu.ops.flat import (
    chunked_unravel,
    coalesce_segments,
    leaf_segments,
    ravel_pytree,
)
from commefficient_tpu.ops.sketch import (
    make_sketch,
    sketch_segments_accum,
    sketch_vec,
)
from tests.test_sharded_server import N, _mesh


# ---- 1. op-level: segment streaming == composed sketch ------------------

class TestSegmentAccum:
    # (d, c, r, segment boundaries) — unaligned cuts, single-element
    # segments, cuts ON chunk/lane boundaries, one-segment degenerate
    CASES = [
        (5000, 512, 3, (0, 137, 138, 512, 129, 4000, 5000)),
        (5000, 512, 3, (0, 5000)),
        (1200, 128, 2, (0, 1, 2, 129, 128 * 4, 1200)),
    ]

    @staticmethod
    def _cuts(bounds):
        cuts = sorted(set(bounds))
        return list(zip(cuts[:-1], cuts[1:]))

    @pytest.mark.parametrize("d,c,r,bounds", CASES,
                             ids=[f"d{d}-{len(b)}segs" for d, c, r, b
                                  in CASES])
    @pytest.mark.parametrize("interpret", [False, True],
                             ids=["pure", "interpret"])
    def test_streams_equal_composed(self, d, c, r, bounds, interpret):
        cs = make_sketch(d, c, r, seed=7, num_blocks=2)
        v = jnp.asarray(np.random.RandomState(3).randn(d), jnp.float32)
        table = jnp.zeros(cs.table_shape, jnp.float32)
        for a, b in self._cuts(bounds):
            table = sketch_segments_accum(cs, table, [v[a:b]], a,
                                          interpret=interpret)
        want = sketch_vec(cs, v)
        np.testing.assert_array_equal(np.asarray(table), np.asarray(want))

    def test_bf16_segments_equal_f32_cast(self):
        """bf16 grads, fp32 table: per-element bf16→f32 casts are exact,
        so streaming bf16 segments equals sketching the f32-cast vector."""
        cs = make_sketch(3000, 256, 3, seed=1, num_blocks=2)
        v16 = jnp.asarray(np.random.RandomState(5).randn(3000),
                          jnp.bfloat16)
        table = jnp.zeros(cs.table_shape, jnp.float32)
        for a, b in self._cuts((0, 300, 301, 2000, 3000)):
            table = sketch_segments_accum(cs, table, [v16[a:b]], a)
        want = sketch_vec(cs, v16.astype(jnp.float32))
        np.testing.assert_array_equal(np.asarray(table), np.asarray(want))

    def test_running_table_continues_fold(self):
        """A full-range group onto a running table: accumulating v onto
        sketch(u) in one launch == in two, per cell."""
        cs = make_sketch(2000, 256, 3, seed=2, num_blocks=2)
        rng = np.random.RandomState(9)
        u = jnp.asarray(rng.randn(2000), jnp.float32)
        v = jnp.asarray(rng.randn(2000), jnp.float32)
        base = sketch_vec(cs, u)
        got = sketch_segments_accum(cs, base, [v], 0)
        want = sketch_segments_accum(cs, base, [v[:700]], 0)
        want = sketch_segments_accum(cs, want, [v[700:]], 700)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert not np.array_equal(np.asarray(got), np.asarray(base))

    def test_empty_and_bounds(self):
        cs = make_sketch(1000, 128, 2, seed=3, num_blocks=1)
        t = jnp.zeros(cs.table_shape, jnp.float32)
        out = sketch_segments_accum(cs, t, [jnp.zeros(0)], 500)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(t))
        with pytest.raises(AssertionError):
            sketch_segments_accum(cs, t, [jnp.zeros(10)], 995)  # past d


# ---- 2. tree level: sketch_grad_tree + the offset map -------------------

def _tree(dtype=jnp.float32, seed=0):
    r = np.random.RandomState(seed)
    return {
        "block": {"w": jnp.asarray(r.randn(13, 31), dtype),
                  "b": jnp.asarray(r.randn(31), dtype)},
        "head": [jnp.asarray(r.randn(31, 7), dtype),
                 jnp.asarray(r.randn(1), dtype)],
        "scalar": jnp.asarray(r.randn(), dtype),
    }


def _plan(segs, cs, chunks=4):
    """A group plan of ``chunks``-chunk groups over ``segs``."""
    return coalesce_segments(segs, chunks * cs.c_pad * 4,
                             chunk_elems=cs.c_pad)


class TestTreeStreaming:
    def test_leaf_segments_match_ravel_layout(self):
        tree = _tree()
        flat, _ = ravel_pytree(tree)
        segs = leaf_segments(tree)
        assert segs[-1].offset + segs[-1].size == int(flat.size)
        leaves = jax.tree_util.tree_leaves(tree)
        for leaf, seg in zip(leaves, segs):
            np.testing.assert_array_equal(
                np.asarray(flat[seg.offset:seg.offset + seg.size]),
                np.asarray(leaf, np.float32).reshape(-1),
                err_msg=f"segment {seg.path} misplaced")

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_tree_stream_equals_ravel_sketch(self, dtype):
        tree = _tree(dtype=dtype, seed=4)
        flat, _ = ravel_pytree(tree)  # casts to f32 like the worker path
        d = int(flat.size)
        cs = make_sketch(d, 128, 3, seed=11, num_blocks=1)
        segs = leaf_segments(tree)
        table = sketch_grad_tree(cs, jnp.zeros(cs.table_shape, jnp.float32),
                                 tree, segs, _plan(segs, cs))
        want = sketch_vec(cs, flat)
        np.testing.assert_array_equal(np.asarray(table), np.asarray(want))

    def test_per_leaf_scales(self):
        """Per-leaf scalar rescales (the tp/ep constants) applied before
        sketching equal scaling the flat vector with the segment mask —
        exact for power-of-two factors."""
        tree = _tree(seed=6)
        flat, _ = ravel_pytree(tree)
        d = int(flat.size)
        segs = leaf_segments(tree)
        scales = tuple(1.0 if i % 2 else 0.5 for i in range(len(segs)))
        cs = make_sketch(d, 128, 3, seed=12, num_blocks=1)
        got = sketch_grad_tree(cs, jnp.zeros(cs.table_shape, jnp.float32),
                               tree, segs, _plan(segs, cs), scales=scales)
        mask = np.zeros(d, np.float32)
        for seg, sc in zip(segs, scales):
            mask[seg.offset:seg.offset + seg.size] = sc
        want = sketch_vec(cs, flat * jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("scaled", [False, True],
                             ids=["decay", "scales_then_decay"])
    def test_staged_decay_equals_flat_decay(self, scaled):
        """``decay=(coef, plane)`` adds ``coef · w`` in each group's staging
        pass, read from the resident plane's own rows, after the rescale:
        the flat route's ``g · mask + coef · w`` element for element, with
        no pass of its own; the boundary chunks' neighbours are masked."""
        g_tree, w_tree = _tree(seed=6), _tree(seed=7)
        g, _ = ravel_pytree(g_tree)
        w, _ = ravel_pytree(w_tree)
        d = int(g.size)
        segs = leaf_segments(g_tree)
        scales = tuple(1.0 if i % 2 else 0.5 for i in range(len(segs))) \
            if scaled else None
        coef = jnp.float32(5e-4 / 8) * jnp.float32(32.0)
        cs = make_sketch(d, 128, 3, seed=12, num_blocks=1)
        plan = _plan(segs, cs)
        assert len(plan) > 1 and any(g_.offset % cs.c_pad for g_ in plan)
        got = sketch_grad_tree(cs, jnp.zeros(cs.table_shape, jnp.float32),
                               g_tree, segs, plan, scales=scales,
                               decay=(coef, cs.chunk_layout.chunk(w)))
        mask = np.ones(d, np.float32)
        for seg, sc in zip(segs, scales or ()):
            mask[seg.offset:seg.offset + seg.size] = sc
        want = sketch_vec(cs, g * jnp.asarray(mask) + coef * w)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_chunked_unravel_bit_exact(self):
        """ops/flat.chunked_unravel == unravel(unchunk(·)) bitwise, with
        every leaf sliced from its covering chunk rows (no d-sized op)."""
        tree = _tree(seed=8)
        flat, unravel = ravel_pytree(tree)
        d = int(flat.size)
        cs = make_sketch(d, 128, 3, seed=13, num_blocks=1)
        layout = cs.chunk_layout
        c3 = layout.chunk(flat)
        tpl = jax.eval_shape(unravel,
                             jax.ShapeDtypeStruct((d,), jnp.float32))
        got = chunked_unravel(layout, tpl)(c3)
        want = unravel(layout.unchunk(c3))
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            got, want)


# ---- 3./4./5. round level on the 8-device mesh --------------------------

IN, H = 6, 60  # 3-layer MLP: 6 leaves, d=4141, offsets straddle chunks


def _mlp_params():
    r = np.random.RandomState(0)
    return {"w1": jnp.asarray(r.randn(IN, H) * 0.1, jnp.float32),
            "b1": jnp.zeros(H),
            "w2": jnp.asarray(r.randn(H, H) * 0.1, jnp.float32),
            "b2": jnp.zeros(H),
            "w3": jnp.asarray(r.randn(H, 1) * 0.1, jnp.float32),
            "b3": jnp.zeros(1)}


def _mlp_loss(params, model_state, batch, rng, train):
    # pytree-native loss: no param ravel inside (raveling here would
    # reintroduce the flat d-vector the streaming path deletes)
    h = jnp.tanh(batch["inputs"] @ params["w1"] + params["b1"])
    h = jnp.tanh(h @ params["w2"] + params["b2"])
    pred = (h @ params["w3"] + params["b3"])[..., 0]
    err = pred - batch["targets"]
    m = batch["mask"]
    return jnp.sum(0.5 * err ** 2 * m), (jnp.sum(jnp.abs(err) * m),), \
        jnp.sum(m), model_state


def _int_loss(params, model_state, batch, rng, train):
    """A loss whose gradient is integer-valued whatever the backend does:
    every leaf's gradient is a small integer pattern times an integer
    feature of the example, so sums over examples, clients and scan steps
    are exact in float32 in any order. XLA:CPU compiles the MLP's backward
    pass differently in two programs (its batched products reorder their
    sums with the consumer), which says nothing about the two routes; on
    this loss ``==`` tests what the routes themselves do: which
    microbatches are added, where the decay lands, where each element is
    sketched."""
    z = jnp.round(2.0 * batch["inputs"])  # (mb, IN) small integers
    m = batch["mask"]
    total = 0.0
    for i, (name, leaf) in enumerate(sorted(params.items())):
        pat = (jnp.arange(leaf.size) % 7 - 3.0).reshape(leaf.shape)
        total = total + jnp.sum(z[:, i] * m) * jnp.sum(leaf * pat)
    return total, (jnp.sum(jnp.abs(z[:, 0]) * m),), jnp.sum(m), model_state


def _batch(seed=0, B=4):
    r = np.random.RandomState(100 + seed)
    return {"inputs": jnp.asarray(r.randn(N, B, IN), jnp.float32),
            "targets": jnp.asarray(r.randn(N, B), jnp.float32),
            "mask": jnp.ones((N, B), jnp.float32),
            "client_ids": jnp.arange(N, dtype=jnp.int32),
            "worker_mask": jnp.ones(N, jnp.float32)}


def _build(leaf=None, server_shard=False, fused=False, micro=-1, wd=0.0,
           budget=0, mode="sketch", error_type="virtual", loss=_mlp_loss,
           mesh=None, model_axis=None):
    """A placed round on the 8-device mesh over the multi-leaf MLP (T=33
    chunks at c_pad=128, leaf offsets straddling chunk and lane
    boundaries). ``leaf`` is ``RoundConfig.sketch_leaf_groups`` (None: the
    build decides, False pins the flat route); ``micro`` the microbatch
    (4 examples a client: -1 is one scan step, 2 two, 1 four)."""
    mesh = mesh or _mesh()
    rep = NamedSharding(mesh, P())
    params = _mlp_params()
    flat, unravel = ravel_pytree(params)
    d = int(flat.size)

    def ravel(tree):
        return ravel_pytree(tree)[0]

    wcfg = WorkerConfig(mode=mode, error_type=error_type, k=5,
                        num_workers=N, microbatch_size=micro,
                        weight_decay=wd, model_axis=model_axis)
    scfg = ServerConfig(mode=mode, error_type=error_type, k=5,
                        grad_size=d,
                        virtual_momentum=0.0 if error_type == "local"
                        else 0.9, fused_epilogue=fused)
    cs_geo = make_sketch(d, 16, 3, seed=0, num_blocks=1) \
        if mode == "sketch" else None
    cfg = RoundConfig(worker=wcfg, server=scfg, grad_size=d,
                      server_shard=server_shard, sketch_leaf_groups=leaf,
                      sketch_coalesce_budget=budget,
                      # every leaf replicated over the model axis: each of
                      # its shards computes the whole gradient
                      tp_sliced=(lambda path: False) if model_axis else None)
    steps = build_round_step(loss, loss, unravel, ravel, cfg,
                             sketch=cs_geo, mesh=mesh)
    ss = init_server_state(scfg, cs_geo)
    ss = jax.tree_util.tree_map(lambda a: jax.device_put(a, rep), ss)
    ps = jax.device_put(
        steps.layout.chunk(flat) if steps.layout is not None else flat, rep)
    cstates = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, rep),
        init_client_states(16, d, wcfg, init_weights=flat, sketch=cs_geo))
    return steps, ps, ss, cstates, d


def _run_rounds(steps, ps, ss, cstates, rounds=3, lr=0.1):
    traj = []
    for rnd in range(rounds):
        ps, ss, cstates, _, _ = steps.train_step(
            ps, ss, cstates, {}, _batch(seed=rnd), lr, jax.random.key(rnd))
        traj.append(np.asarray(steps.layout.unchunk(ps)))
    return traj, ss, cstates


class TestLeafGroupRoundBitIdentity:
    """Acceptance criterion: the leaf-group route's table equals the flat
    route's under ``==`` for any count of scan steps and any weight decay
    on one mesh axis, and fp32 trajectories are bit-identical across both
    server planes and both epilogues."""

    @staticmethod
    def _tables(loss, micro, wd, shard):
        out = []
        for leaf in (False, None):
            steps, ps, _, cstates, _ = _build(leaf, shard, micro=micro,
                                              wd=wd, loss=loss)
            ctx, _, metrics = steps.client_step(
                ps, cstates, {}, _batch(0), 0.1, jax.random.key(0))
            out.append((np.asarray(ctx.gradient),
                        [np.asarray(m) for m in metrics]))
        (flat_t, flat_m), (leaf_t, leaf_m) = out
        assert np.any(flat_t != 0)
        return flat_t, leaf_t, flat_m, leaf_m

    @pytest.mark.parametrize("shard", [False, True],
                             ids=["replicated", "server_shard"])
    @pytest.mark.parametrize("wd", [0.0, 5e-4], ids=["wd0", "wd5e-4"])
    @pytest.mark.parametrize("micro", [-1, 2, 1],
                             ids=["scan1", "scan2", "scan4"])
    def test_table_equals_flat_routes(self, micro, wd, shard):
        """``==`` for every count of scan steps and weight decay, on a
        gradient whose arithmetic no backend can reorder (``_int_loss``)."""
        flat_t, leaf_t, _, _ = self._tables(_int_loss, micro, wd, shard)
        np.testing.assert_array_equal(leaf_t, flat_t)

    @pytest.mark.parametrize("shard", [False, True],
                             ids=["replicated", "server_shard"])
    @pytest.mark.parametrize("micro,wd", [(-1, 0.0), (-1, 5e-4),
                                          (2, 5e-4), (1, 5e-4)],
                             ids=["scan1-wd0", "scan1-wd5e-4",
                                  "scan2-wd5e-4", "scan4-wd5e-4"])
    def test_table_on_the_mlp(self, micro, wd, shard):
        """The tanh MLP: ``==`` at one scan step (the two programs hold
        the same backward pass there), and to rounding at more, where
        XLA:CPU sums the batched products of the two programs' backward
        passes in different orders (a single step's gradient already
        differs between them there, with no scan and no sketch)."""
        flat_t, leaf_t, flat_m, leaf_m = self._tables(_mlp_loss, micro, wd,
                                                      shard)
        if micro == -1:
            np.testing.assert_array_equal(leaf_t, flat_t)
        else:
            np.testing.assert_allclose(leaf_t, flat_t, rtol=0,
                                       atol=2e-6 * np.abs(flat_t).max())
        for a, b in zip(leaf_m, flat_m):
            np.testing.assert_allclose(a, b, rtol=1e-6)

    @pytest.mark.parametrize("wd", [0.0, 5e-4], ids=["wd0", "wd5e-4"])
    def test_second_mesh_axis_rides_the_table(self, wd):
        """A model axis beside the clients axis: the leaf-group route
        rescales per leaf, adds the decay on ONE shard of the axis and
        psums the table, where the flat route psums the gradient, rescales
        by the mask and decays after. Equal to float32 rounding (the sums
        reorder); a decay counted once a shard would be off by 1e-4."""
        from commefficient_tpu.parallel.mesh import make_mesh

        out = []
        for leaf in (False, None):
            steps, ps, _, cstates, _ = _build(
                leaf, micro=2, wd=wd, model_axis="model",
                mesh=make_mesh([("clients", 2), ("model", 2)],
                               devices=jax.devices()[:4]))
            ctx, _, _ = steps.client_step(
                ps, cstates, {}, _batch(0), 0.1, jax.random.key(0))
            out.append(np.asarray(ctx.gradient))
        flat_t, leaf_t = out
        np.testing.assert_allclose(leaf_t, flat_t, rtol=0,
                                   atol=2e-6 * np.abs(flat_t).max())

    @pytest.mark.parametrize("shard", [False, True],
                             ids=["replicated", "server_shard"])
    @pytest.mark.parametrize("fused", [False, True],
                             ids=["composed", "fused_epilogue"])
    def test_trajectory_bit_identical(self, shard, fused, monkeypatch):
        if fused:
            # megakernel through the Pallas interpreter (the CPU suite's
            # kernel path, bit-identical math — test_fused_epilogue.py)
            monkeypatch.setenv("COMMEFFICIENT_FUSED_EPILOGUE", "interpret")
        kw = dict(server_shard=shard, fused=fused, wd=5e-4)
        a, ssa, csa = _run_rounds(*_build(False, **kw)[:4])
        b, ssb, csb = _run_rounds(*_build(None, **kw)[:4])
        for rnd, (x, y) in enumerate(zip(a, b)):
            np.testing.assert_array_equal(
                x, y,
                err_msg=f"shard={shard} fused={fused} round {rnd} ps")
        for name in ("velocity", "error"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ssa, name)),
                np.asarray(getattr(ssb, name)), err_msg=name)


# ---- selection: what the build can observe, and nothing else ------------

class TestRouteSelection:
    @pytest.mark.parametrize("kw,path", [
        (dict(), "leaf_groups"),
        (dict(server_shard=True), "leaf_groups"),
        (dict(micro=1, wd=5e-4), "leaf_groups"),
        (dict(mode="uncompressed", error_type="none"), "flat"),
        (dict(mode="true_topk"), "flat"),
        (dict(error_type="local"), "flat"),  # per-client sketch tables
        (dict(leaf=False), "flat"),
    ], ids=["sketch", "sketch-server_shard", "sketch-scan4-wd",
            "uncompressed", "true_topk", "per_client_state", "pinned_flat"])
    def test_route_follows_the_window(self, kw, path):
        """Sketch mode inside the fused + sketch-after-sum + chunked
        window takes the leaf groups; modes that need the flat gradient
        itself and per-client paths take the flat route."""
        steps = _build(**kw)[0]
        assert steps.client_sketch_path == path
        if path == "flat":
            assert steps.client_sketch_launches == 0
        else:
            segs = leaf_segments(jax.eval_shape(_mlp_params))
            n_leaves = sum(1 for s in segs if s.size)
            assert 1 <= steps.client_sketch_launches <= n_leaves

    def test_forcing_outside_the_window_is_refused(self):
        with pytest.raises(AssertionError, match="outside its window"):
            _build(True, mode="uncompressed", error_type="none")

    def test_pinned_flat_build_holds_d_sized_movement(self):
        """``sketch_leaf_groups=False`` must build the flat client phase:
        the d-sized movement ops reappear in the lowered HLO (structural
        evidence, not just equal numbers)."""
        steps, ps, ss, cstates, d = _build(False)
        hits = _big_movement_ops(_client_hlo(steps, ps, cstates), d)
        assert hits, "flat build should contain d-sized movement"

    @pytest.mark.parametrize("flag", ["--stream_sketch",
                                      "--sketch_coalesce"])
    def test_the_flags_are_gone(self, flag):
        from commefficient_tpu.config import parse_args

        with pytest.raises(SystemExit):
            parse_args(argv=["--mode", "sketch", flag])

    @pytest.mark.parametrize("name", ["COMMEFFICIENT_STREAM_SKETCH",
                                      "COMMEFFICIENT_SKETCH_COALESCE"])
    def test_no_environment_switch_selects(self, name, monkeypatch):
        monkeypatch.setenv(name, "0")
        assert _build()[0].client_sketch_path == "leaf_groups"


# ---- structural asserts: no d-sized movement, a tree-shaped carry -------

_SHAPE_RE = re.compile(
    r"tensor<([0-9]+(?:x[0-9]+)*)x(?:f32|f64|bf16|f16|i32|ui32|i8|i1)>")


def _client_hlo(steps, ps, cstates, seed=0):
    return steps.client_step.lower(
        ps, cstates, {}, _batch(seed), 0.1, jax.random.key(seed)).as_text()


def _big_movement_ops(hlo_text, threshold):
    """Lines lowering to stablehlo concatenate/pad/reshape whose largest
    tensor reaches ``threshold`` elements."""
    hits = []
    for line in hlo_text.splitlines():
        m = re.search(r"stablehlo\.(concatenate|pad|reshape)", line)
        if not m:
            continue
        sizes = [int(np.prod([int(x) for x in s.split("x")]))
                 for s in _SHAPE_RE.findall(line)]
        if sizes and max(sizes) >= threshold:
            hits.append((m.group(1), max(sizes)))
    return hits


def _walk_eqns(fn, args, visit):
    """``visit(eqn, in_scan)`` for every equation of ``fn``'s jaxpr,
    descending into pjit/shard_map/scan sub-jaxprs; ``in_scan`` says
    whether the equation lies inside some scan's body."""
    def walk(jx, in_scan):
        for eqn in jx.eqns:
            visit(eqn, in_scan)
            inner = in_scan or eqn.primitive.name == "scan"
            for val in eqn.params.values():
                for j in (val if isinstance(val, (list, tuple)) else [val]):
                    if hasattr(j, "eqns"):
                        walk(j, inner)
                    elif hasattr(j, "jaxpr") and hasattr(j.jaxpr, "eqns"):
                        walk(j.jaxpr, inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)


def _scan_carries(fn, *args):
    """The shapes of every scan carry anywhere in the jaxpr."""
    shapes = []

    def visit(eqn, in_scan):
        if eqn.primitive.name == "scan":
            inner = eqn.params["jaxpr"].jaxpr
            nc = eqn.params["num_carry"]
            ncons = eqn.params["num_consts"]
            shapes.extend(tuple(v.aval.shape) for v in
                          inner.invars[ncons:ncons + nc])

    _walk_eqns(fn, args, visit)
    return shapes


def _max_scan_carry(fn, *args):
    """Largest scan-carry aval (elements) anywhere in the jaxpr."""
    return max([int(np.prod(s)) for s in _scan_carries(fn, *args)] + [0])


class TestLeafGroupStructure:
    """Acceptance criterion: the jitted leaf-group client phase contains
    no d-sized concatenate/pad/reshape — in the scan body or anywhere —
    and its scan carries a tree of leaf shapes; asserted against the
    lowered HLO/jaxpr, with the flat build proving the detectors fire."""

    def test_no_d_sized_movement_and_tree_carry(self):
        kw = dict(micro=2, wd=5e-4)  # a real scan: two steps
        steps_c, ps, ss, cstates, d = _build(False, **kw)
        args_c = (ps, cstates, {}, _batch(0), 0.1, jax.random.key(0))
        flat_hits = _big_movement_ops(_client_hlo(steps_c, ps, cstates), d)
        assert flat_hits, \
            "detector is vacuous: flat build shows no d-sized movement"
        flat_carry = _max_scan_carry(steps_c.client_step, *args_c)
        assert flat_carry >= d, \
            f"flat carry {flat_carry} should be d-sized (d={d})"

        steps_s, ps_s, ss_s, cstates_s, _ = _build(None, **kw)
        leaf_hits = _big_movement_ops(
            _client_hlo(steps_s, ps_s, cstates_s), d)
        assert not leaf_hits, \
            f"leaf-group client phase has d-sized movement ops: {leaf_hits}"
        carries = _scan_carries(
            steps_s.client_step, ps_s, cstates_s, {}, _batch(0), 0.1,
            jax.random.key(0))
        leaf_shapes = [tuple(x.shape) for x in
                       jax.tree_util.tree_leaves(_mlp_params())]
        # the gradient accumulator is the parameter tree, leaf for leaf
        for shp in leaf_shapes:
            assert shp in carries, (shp, carries)
        biggest = max(int(np.prod(s)) for s in carries)
        assert biggest == max(int(np.prod(s)) for s in leaf_shapes) < d


# ---- CLI e2e: the entrypoint path, both routes --------------------------

class TestCLIEndToEnd:
    def test_cv_train_leaf_groups_match_flat(self, tmp_path, monkeypatch):
        """The real cv_train CLI at its default weight decay and two scan
        steps reproduces the flat route's epoch summary EXACTLY (the
        summary's loss/acc means are pure functions of the round
        trajectory). The flat side is pinned from the test: the program
        has no option for it."""
        import cv_train
        from commefficient_tpu.federated import aggregator

        monkeypatch.setenv("COMMEFFICIENT_TINY_MODEL", "1")
        monkeypatch.setenv("COMMEFFICIENT_SYNTHETIC_PER_CLASS", "24")
        seen = []
        build = aggregator.build_round_step

        def spy(*a, **kw):
            steps = build(*a, **kw)
            seen.append(steps.client_sketch_path)
            return steps

        monkeypatch.setattr(aggregator, "build_round_step", spy)

        def run(subdir):
            argv = [
                "--dataset_name", "CIFAR10",
                "--dataset_dir", str(tmp_path / subdir),
                "--num_epochs", "1",
                "--num_workers", "2",
                "--local_batch_size", "4",
                "--microbatch_size", "2",
                "--valid_batch_size", "8",
                "--lr_scale", "0.01",
                "--pivot_epoch", "0.5",
                "--seed", "0",
                "--iid", "--num_clients", "4",
                "--mode", "sketch", "--error_type", "virtual",
                "--local_momentum", "0", "--virtual_momentum", "0.9",
                "--k", "500", "--num_cols", "2048", "--num_rows", "3",
                "--num_blocks", "2",
            ]
            return cv_train.main(argv)

        b = run("a")
        with monkeypatch.context() as m:
            m.setattr(aggregator, "RoundConfig", functools.partial(
                aggregator.RoundConfig, sketch_leaf_groups=False))
            a = run("a")  # same synthetic data dir
        assert seen == ["leaf_groups", "flat"]
        for key in ("train_loss", "train_acc", "test_loss", "test_acc"):
            assert a[key] == b[key], \
                f"{key}: flat {a[key]!r} != leaf groups {b[key]!r}"


# ---- engine invariant: the route adds no host syncs ---------------------

class TestLeafGroupNoHostSyncs:
    def test_dispatch_loop_zero_syncs(self):
        from commefficient_tpu.profiling import host_sync_monitor

        steps, ps, ss, cstates, _ = _build()
        assert steps.client_sketch_path == "leaf_groups"
        out = steps.train_step(ps, ss, cstates, {}, _batch(0), 0.1,
                               jax.random.key(0))
        jax.block_until_ready(out[0])
        state = out[:4]
        with host_sync_monitor() as counter:
            for rnd in range(1, 3):
                out = steps.train_step(*state, _batch(rnd), 0.1,
                                       jax.random.key(rnd))
                state = out[:4]
        jax.block_until_ready(state[0])
        assert counter.count == 0, \
            f"leaf-group round dispatched {counter.count} blocking fetches"
