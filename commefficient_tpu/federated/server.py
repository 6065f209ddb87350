"""Server-side update rules: the five compression modes, error feedback and
virtual momentum, as pure jittable functions.

Functional re-design of the reference's ``get_server_update`` +
``_server_helper_{fedavg,uncompressed,true_topk,local_topk,sketched}``
(reference fed_aggregator.py:469-613). State that the reference mutates in
place (``Vvelocity``, ``Verror``) is threaded explicitly as ``ServerState``;
the torch aliasing trick for sketch-mode local error (``Verror = Vvelocity``,
reference fed_aggregator.py:580 — after masking, both names point at the same
masked tensor) is reproduced by returning the same masked array for both.

Legality matrix (enforced at config time, mirroring the reference's runtime
asserts — fed_aggregator.py:484-486, 512, 545, 573-576):

  mode          error_type          notes
  fedavg        none                local_momentum == 0, lr applied on-worker
  uncompressed  any (ignored)       optional server DP noise
  true_topk     virtual (required)  server-side client-velocity masking
  local_topk    local | none
  sketch        local | virtual     local → virtual_momentum == 0,
                                    virtual → local_momentum == 0

Documented deviation: in the reference, ``mode=sketch`` with
``error_type=none`` silently unsketches an all-zero error table and produces a
zero update (fed_aggregator.py:578-590 — ``Verror`` is never written on that
path). We instead unsketch the momentum-accumulated gradient, which is the
evident intent; the combination is still discouraged.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from commefficient_tpu.ops.flat import ChunkLayout
from commefficient_tpu.ops.sketch import (
    CountSketch,
    estimates_chunks,
    estimates_chunks_local,
    fused_epilogue_chunks,
    fused_epilogue_chunks_local,
    fused_epilogue_mode,
    sketch_chunks,
    sketch_chunks_local,
)
from commefficient_tpu.ops.topk import topk, topk_dense_nd

MODES = ("sketch", "true_topk", "local_topk", "fedavg", "uncompressed")
ERROR_TYPES = ("none", "local", "virtual")


@dataclass(frozen=True)
class ServerConfig:
    """Static server config — hashable, closed over by jit."""

    mode: str
    error_type: str = "none"
    k: int = 0
    grad_size: int = 0
    virtual_momentum: float = 0.0
    local_momentum: float = 0.0
    do_dp: bool = False
    dp_mode: str = "worker"
    noise_multiplier: float = 0.0
    # Fused server epilogue (--fused_epilogue, docs/fused_epilogue.md):
    # sketch mode's threshold-mask + update-emit + re-sketch run as one
    # Pallas megakernel over the chunk plane instead of the composed
    # topk_dense_nd + sketch_chunks sweeps. Sketch-mode + chunked-resident
    # only; silently composed elsewhere (and under the
    # COMMEFFICIENT_FUSED_EPILOGUE=0 kill-switch / VMEM guard — see
    # ops/sketch.fused_epilogue_mode). fp32 results are bit-identical to
    # the composed path (pinned in tests/test_fused_epilogue.py).
    fused_epilogue: bool = False

    def __post_init__(self):
        assert self.mode in MODES, self.mode
        assert self.error_type in ERROR_TYPES, self.error_type
        if self.mode == "fedavg":
            assert self.error_type == "none", "fedavg requires error_type=none"
            assert self.local_momentum == 0, "fedavg requires local_momentum=0"
        if self.mode == "true_topk":
            assert self.error_type == "virtual", "true_topk requires virtual error"
        if self.mode == "local_topk":
            assert self.error_type in ("local", "none")
        if self.mode == "sketch":
            if self.error_type == "local":
                assert self.virtual_momentum == 0, \
                    "sketch + local error carries momentum locally: set " \
                    "--virtual_momentum 0"
            if self.error_type == "virtual":
                assert self.local_momentum == 0, \
                    "sketch + virtual error carries momentum on the " \
                    "server: set --local_momentum 0 (the CLI default 0.9 " \
                    "mirrors the reference and must be overridden for " \
                    "the FetchSGD recipe)"


class ServerState(NamedTuple):
    """(velocity, error) — shape (num_rows, num_cols) for sketch mode, else
    (grad_size,) (reference fed_aggregator.py:399-409).

    Sharded server data plane (``--server_shard``, docs/sharded_server.md):
    dense-mode velocity/error become ``(d_pad,)`` (grad_size padded to a
    multiple of the shard count), row-sharded over the worker axis — each
    chip stores and updates only its ``d_pad/n`` slice. Sketch-mode tables
    stay replicated (they are the already-small transmit).

    Compressed-collective carries (docs/compressed_collectives.md; both
    are error-feedback residuals, zero-initialized and safe to restart
    from zero):

    - ``qres`` exists when the UPLINK leg (dense transmit reduce or
      sketch-table exchange) of the collective plan is quantized: each
      chip's un-transmitted quantization remainder from the block-scaled
      transmit collective (ops/collectives.py), shape
      ``(n, *transmit_shape)`` sharded over dim 0 — added back into the
      chip's next contribution before quantization, so the quantized
      reduce is compensated, not lossy.
    - ``dres`` exists when the DOWNLINK leg (the update all-gather) is
      quantized: each chip's un-transmitted remainder of its own update
      tile, in the gathered layout sharded over dim 0 — sketch mode
      ``(n·⌈T/n⌉, S, 128)`` chunk rows, dense ``(d_pad,)`` — folded into
      the chip's next-round emitted update tile before quantization, so
      the downlink error telescopes exactly as ``qres`` telescopes the
      uplink.

    Per-mesh-axis plans (docs/multihost.md): when a leg lowers
    hierarchically (``ops.collectives.resolve_leg_lowering`` returned an
    ``((axis, dtype), ...)`` tuple), the matching carry generalizes to a
    TUPLE of per-axis slots aligned with the lowering — slot j is axis
    j's error-feedback residual (None at a float32 level). Uplink slot j
    is the stacked ``(n, *level_j_input_shape)`` array sharded over dim 0
    (the level input's dim-0 tile shrinks by each reduced axis's size);
    downlink slot j keeps the FULL gathered shape globally but lives
    sharded over axes 0..j only (replicated over the axes already
    gathered when level j runs — see
    ``ops.collectives.hierarchical_all_gather``). Flat plans keep the
    single-array spelling unchanged (checkpoint and shard-spec compat)."""

    velocity: jax.Array
    error: jax.Array
    qres: Optional[jax.Array] = None
    dres: Optional[jax.Array] = None


def init_server_state(cfg: ServerConfig, sketch: Optional[CountSketch] = None,
                      shard_n: int = 0,
                      quantized: bool = False,
                      plan=None, lowering=None,
                      axis_sizes=None) -> ServerState:
    """``shard_n`` > 0 selects the sharded-server residency (see
    ServerState). ``plan`` (a ``CollectivePlan``,
    docs/compressed_collectives.md) decides which error-feedback carries
    exist: ``qres`` when the mode's uplink leg (dense transmit / sketch
    table) is quantized, ``dres`` when the downlink all-gather is.
    ``quantized`` is the legacy ``--reduce_dtype int8`` spelling — the
    all-int8 plan (every leg quantized). ``lowering``
    (``{leg: resolve_leg_lowering(...)}``) selects the per-mesh-axis
    residency: a leg whose lowering is an ``((axis, dtype), ...)`` tuple
    gets a TUPLE of per-axis carry slots (see ServerState); plain-dtype
    lowerings (and ``lowering=None``) keep the single-array carries.
    ``axis_sizes`` (``{axis_name: size}``, required with a hierarchical
    lowering) sizes the per-level dense uplink slots — the level input
    shrinks by each already-reduced axis."""
    from commefficient_tpu.ops.collectives import plan_from_reduce_dtype

    if plan is None:
        plan = plan_from_reduce_dtype("int8" if quantized else "float32")
    if lowering is None:
        lowering = {"uplink": plan.uplink, "table": plan.table,
                    "downlink": plan.downlink}
        assert not any(":" in v for v in lowering.values()), \
            "per-axis collective plans must pass lowering= (the " \
            "resolve_leg_lowering dict) — the leg strings alone do not " \
            "size the per-axis carry slots"
    if cfg.mode == "sketch":
        assert sketch is not None
        shape = sketch.table_shape
    else:
        d = cfg.grad_size
        shape = (-(-d // shard_n) * shard_n,) if shard_n else (d,)
    up_low = lowering["table"] if cfg.mode == "sketch" \
        else lowering["uplink"]
    down_low = lowering["downlink"]
    qres = None
    if isinstance(up_low, tuple):
        assert shard_n > 0, \
            "quantized collective legs require --server_shard"
        # per-axis slots: level j's input tile is the transmit divided by
        # the sizes of the axes already reduced (dense); the table leg's
        # all-reduce preserves shape at every level
        assert axis_sizes is not None, \
            "hierarchical lowering needs axis_sizes={axis: size}"
        slots = []
        seen = 1
        for ax, dt in up_low:
            if dt == "float32":
                slots.append(None)
            elif cfg.mode == "sketch":
                slots.append(jnp.zeros((shard_n,) + shape, jnp.float32))
            else:
                slots.append(jnp.zeros((shard_n, shape[0] // seen),
                                       jnp.float32))
            seen *= int(axis_sizes[ax])
        qres = tuple(slots)
    elif up_low != "float32":
        assert shard_n > 0, \
            "quantized collective legs require --server_shard"
        qres = jnp.zeros((shard_n,) + shape if cfg.mode == "sketch"
                         else (shard_n, shape[0]), jnp.float32)
    dres = None
    if isinstance(down_low, tuple):
        assert shard_n > 0, \
            "quantized collective legs require --server_shard"
        # every downlink slot keeps the full gathered shape globally
        # (shardings differ per slot — place_server_state); the sketch
        # layout pads T to the shard multiple like the flat carry
        if cfg.mode == "sketch":
            Tn = -(-sketch.T // shard_n)
            full = (Tn * shard_n, sketch.sublanes, 128)
        else:
            full = shape
        dres = tuple(None if dt == "float32"
                     else jnp.zeros(full, jnp.float32)
                     for _, dt in down_low)
    elif down_low != "float32":
        assert shard_n > 0, \
            "quantized collective legs require --server_shard"
        if cfg.mode == "sketch":
            # the gathered update layout: each chip owns ceil(T/n) chunk
            # rows of (S, 128), padded to the shard multiple
            Tn = -(-sketch.T // shard_n)
            dres = jnp.zeros((Tn * shard_n, sketch.sublanes, 128),
                             jnp.float32)
        else:
            dres = jnp.zeros(shape, jnp.float32)  # (d_pad,), dim-0 sharded
    # Separate zeros computations, NOT one shared array: the round step
    # donates server_state (rounds.build_round_step), and donating a pytree
    # whose two leaves share one buffer is an execute-time error
    # ("attempt to donate the same buffer twice").
    return ServerState(velocity=jnp.zeros(shape, jnp.float32),
                       error=jnp.zeros(shape, jnp.float32),
                       qres=qres, dres=dres)


def place_server_state(state: ServerState, mesh, mode: str,
                       server_shard: bool, put=None,
                       axis=None) -> ServerState:
    """THE sharded-server residency rule, in one place (callers: FedModel,
    the multichip dry-run): sketch tables replicated (they are
    the already-small transmit), dense velocity/error dim-0-sharded over
    the worker axis, the qres/dres carries always dim-0-sharded. Committing
    fresh state to these shardings up front keeps round 1 on the jit
    cache and donation safe (see aggregator._place_replicated). ``put``
    overrides plain ``jax.device_put`` for multi-process global arrays
    (``__graft_entry__.run_tiny_sketched_round``). ``axis`` is the server
    reduce axis (name or ordered tuple, ``mesh.server_reduce_axes``;
    None = the legacy clients axis): per-axis dres slot j lives sharded
    over axes 0..j only (replicated over the already-gathered rest —
    ServerState docstring)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from commefficient_tpu.parallel.mesh import (
        CLIENTS_AXIS,
        replicated_sharding,
        server_shard_sharding,
    )

    if mesh is None:
        return state
    if put is None:
        def put(x, sharding):
            return jax.device_put(x, sharding)

    if axis is None:
        axis = CLIENTS_AXIS
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    rep = replicated_sharding(mesh)
    sh0 = server_shard_sharding(mesh, axis)
    state_sh = sh0 if (server_shard and mode != "sketch") else rep

    def put_qres(q):
        if q is None:
            return None
        if isinstance(q, tuple):  # per-axis slots: all stacked over dim 0
            return tuple(None if s is None else put(s, sh0) for s in q)
        return put(q, sh0)

    def put_dres(d):
        if d is None:
            return None
        if isinstance(d, tuple):
            return tuple(
                None if s is None
                else put(s, NamedSharding(mesh, P(tuple(axes[:j + 1]))))
                for j, s in enumerate(d))
        return put(d, sh0)

    return state._replace(
        velocity=put(state.velocity, state_sh),
        error=put(state.error, state_sh),
        qres=put_qres(state.qres),
        dres=put_dres(state.dres))


def round_health(transmit, new_ps, max_abs: float = 0.0):
    """Scalar health verdict of one round's server transition
    (docs/fault_tolerance.md): True iff the aggregated transmit AND the
    candidate updated PS weights are all finite (and, when ``max_abs`` > 0,
    every updated weight is within the magnitude ceiling).

    Both reductions ride the jitted round step — a few scalar ``isfinite``
    sweeps over planes the epilogue already reads — and the verdict stays on
    device in the round handle, so the engine's zero-blocking-fetch
    invariant holds with guards on (pinned in tests/test_engine.py). With
    error feedback a single non-finite contribution telescopes into
    (velocity, error) forever, which is why the check gates the WHOLE state
    transition (rounds.server_step), not just the weight write."""
    ok = jnp.all(jnp.isfinite(transmit)) & jnp.all(jnp.isfinite(new_ps))
    if max_abs > 0:
        ok = ok & (jnp.max(jnp.abs(new_ps)) <= max_abs)
    return ok


def server_update(
    gradient: jax.Array,
    state: ServerState,
    cfg: ServerConfig,
    lr,
    sketch: Optional[CountSketch] = None,
    rng: Optional[jax.Array] = None,
    layout: Optional[ChunkLayout] = None,
) -> Tuple[jax.Array, ServerState]:
    """One server step: aggregated (possibly compressed) round gradient →
    (dense weight update, new state).

    ``gradient`` is the data-weighted round average: a dense ``(d,)`` vector
    for uncompressed/true_topk/fedavg, a k-sparse-by-construction dense vector
    for local_topk, or an ``(r, c)`` sketch table for sketch mode.
    ``lr`` may be a scalar or a per-coordinate ``(d,)`` vector (per-param-group
    LRs, reference fed_aggregator.py:411-427).

    ``layout`` (sketch mode only) selects the **chunked-resident** server
    phase: the returned update is in the ``(T, S, 128)`` chunk layout —
    unsketch/top-k/re-sketch run without a flat-layout materialization
    (docs/round_engine.md). A vector ``lr`` must then be in the same chunked
    layout (zero tail). Values are identical to the flat path.
    """
    helper = {
        "fedavg": _fedavg,
        "uncompressed": _uncompressed,
        "true_topk": _true_topk,
        "local_topk": _local_topk,
        "sketch": _sketched,
    }[cfg.mode]
    if cfg.mode == "sketch":
        return helper(gradient, state, cfg, lr, sketch, layout)
    assert layout is None, "chunked-resident layout is sketch-mode only"
    if cfg.mode == "uncompressed":
        return helper(gradient, state, cfg, lr, rng)
    return helper(gradient, state, cfg, lr)


@jax.named_scope("fed_server_apply")
def _fedavg(avg_update, state, cfg, lr):
    # lr already applied on-worker; server asserts lr == 1
    # (reference fed_aggregator.py:483-495).
    velocity = avg_update + cfg.virtual_momentum * state.velocity
    return velocity, ServerState(velocity, state.error)


@jax.named_scope("fed_server_apply")
def _uncompressed(gradient, state, cfg, lr, rng):
    velocity = gradient + cfg.virtual_momentum * state.velocity
    update = velocity
    if cfg.do_dp and cfg.dp_mode == "server":
        assert rng is not None, "server DP needs an rng key"
        update = update + cfg.noise_multiplier * jax.random.normal(
            rng, update.shape, update.dtype
        )
    return update * lr, ServerState(velocity, state.error)


def _true_topk(gradient, state, cfg, lr):
    with jax.named_scope("fed_server_apply"):
        velocity = gradient + cfg.virtual_momentum * state.velocity
        error = state.error + velocity
    with jax.named_scope("fed_server_topk"):
        update = topk(error, cfg.k)
    with jax.named_scope("fed_server_apply"):
        nz = update != 0
        # error feedback + momentum factor masking at the chosen
        # coordinates (reference fed_aggregator.py:536-540)
        error = jnp.where(nz, 0.0, error)
        velocity = jnp.where(nz, 0.0, velocity)
        return update * lr, ServerState(velocity, error)


@jax.named_scope("fed_server_apply")
def _local_topk(local_topk_grad, state, cfg, lr):
    # no virtual error, no masking (rationale: reference
    # fed_aggregator.py:559-563)
    velocity = local_topk_grad + cfg.virtual_momentum * state.velocity
    return velocity * lr, ServerState(velocity, state.error)


def sharded_server_update(
    transmit_local: jax.Array,
    state: ServerState,
    cfg: ServerConfig,
    lr,
    count,
    *,
    axis: str,
    n_shard: int,
    sketch: Optional[CountSketch] = None,
    layout: Optional[ChunkLayout] = None,
    rng: Optional[jax.Array] = None,
    reduce_dtype: str = "float32",
    plan=None,
    lowering=None,
) -> Tuple[jax.Array, ServerState, Optional[jax.Array]]:
    """The sharded server data plane's per-shard step — MUST run inside a
    ``shard_map`` over mesh axis ``axis`` (rounds.build_round_step wraps
    it). Replaces ``psum → replicated server_update`` with
    reduce-scatter → per-shard update → all-gather (Xu et al.,
    arXiv:2004.13336):

    - ``transmit_local`` is this chip's UNREDUCED transmit sum (the
      ``(r, c_pad)`` sketch table, or the flat dense ``(d,)`` sum); the
      round average's ``/count`` division happens here, AFTER the reduce,
      so the summed values are bit-identical to the replicated path's.
    - dense modes reduce-scatter the transmit over a ``d_pad = n·⌈d/n⌉``
      zero-padded flat view and run velocity/error/masking on the local
      ``d_pad/n`` slice (``state`` arrives as local slices); sketch mode
      psums the (small) table, keeps the table algebra replicated, and
      shards the d-sized chunk plane: ``estimates_chunks_local`` /
      ``topk_dense_nd(axis_name=...)`` / ``sketch_chunks_local`` over
      this shard's ``⌈T/n⌉`` chunks.
    - the one genuinely global quantity — the top-k threshold — comes
      from the radix descent's per-candidate counts psum'd over the axis
      (ops/topk.py): 16 ints per pass instead of a per-chip full vector.
    - only the RESULT is all-gathered: the update slice (exact f32 data
      movement), then scaled by ``lr`` replicated — so fp32 trajectories
      are bit-identical to ``server_update``'s (pinned in
      tests/test_sharded_server.py).
    - the per-leg ``plan`` (``CollectivePlan``,
      docs/compressed_collectives.md) swaps individual wire legs for the
      block-scaled stochastic-rounding collectives (ops/collectives.py):
      a quantized uplink/table leg folds the carry ``state.qres`` (this
      chip's row) into the contribution before quantization; a quantized
      DOWNLINK leg quantizes each chip's update tile before the
      all-gather, with the un-transmitted remainder carried per chip in
      ``state.dres`` and folded into the next round's emitted tile —
      error feedback for both wire directions. ``reduce_dtype`` is the
      legacy alias (int8 = every leg int8) used when ``plan`` is None.
      The exact-update byproducts (re-sketch cells, top-k masking, DP
      noise) are computed from the EXACT update — what the quantized
      gather did not deliver this round is exactly what ``dres`` delivers
      later, so the server's own EF accounting stays in update units.
    - ``lowering`` (``{leg: resolve_leg_lowering(...)}``,
      docs/multihost.md) selects the per-mesh-axis forms: a leg resolved
      to an ``((axis, dtype), ...)`` tuple runs the hierarchical
      collectives level by level over ``axis`` (which is then the ordered
      reduce-axis TUPLE — ICI first, DCN last) with the matching carry a
      tuple of per-axis slots. None derives flat single-dtype lowerings
      from ``plan`` — every pre-existing path bit for bit.

    Returns ``(lr-scaled full update, new local state, re-sketched update
    table or None)`` — the table is sketch mode's cell-masking byproduct
    (psum of the shards' partial re-sketches), reused by the round's
    client-state masking so it is not recomputed.
    """
    from commefficient_tpu.ops.collectives import (
        all_gather_tiled,
        hierarchical_all_gather,
        hierarchical_psum,
        hierarchical_psum_scatter,
        plan_from_reduce_dtype,
        quantized_all_gather,
        quantized_psum,
        quantized_psum_scatter,
        reduce_scatter_sum,
    )

    if plan is None:
        plan = plan_from_reduce_dtype(reduce_dtype)
    if lowering is None:
        lowering = {"uplink": plan.uplink, "table": plan.table,
                    "downlink": plan.downlink}
        assert not any(":" in v for v in lowering.values()), \
            "per-axis collective plans must pass lowering= " \
            "(resolve_leg_lowering per leg)"
    up_low = lowering["table"] if cfg.mode == "sketch" \
        else lowering["uplink"]
    down_low = lowering["downlink"]
    # a hierarchical lowering always mixes dtypes (all-equal collapses to
    # the flat path in resolve_leg_lowering), so it is always quantized
    up_q = isinstance(up_low, tuple) or up_low != "float32"
    down_q = isinstance(down_low, tuple) or down_low != "float32"

    qres_local = state.qres  # (1, *transmit_shape) local row(s), or None
    dres_local = state.dres  # this chip's update-tile residual(s), or None
    if up_q:
        assert qres_local is not None, \
            "quantized uplink/table leg needs the qres carry " \
            "(init_server_state plan=)"
    if down_q:
        assert dres_local is not None, \
            "quantized downlink leg needs the dres carry " \
            "(init_server_state plan=)"
    # one SR stream per quantized leg; when only one leg is quantized the
    # raw key is used directly, so a plan that quantizes exactly the legs
    # --reduce_dtype int8 used to reproduces the PR-2 draws
    rng_up = rng_down = rng
    if up_q and down_q:
        rng_up, rng_down = jax.random.split(rng)

    scope = jax.named_scope
    if cfg.mode == "sketch":
        assert sketch is not None and layout is not None
        with scope("fed_server_apply"):
            if isinstance(up_low, tuple):
                # per-axis table exchange: level-by-level all-reduce, each
                # quantized level folding ITS carry slot's local row
                table, new_slots = hierarchical_psum(
                    transmit_local, up_low, rng_up,
                    residuals=[None if q is None else q[0]
                               for q in qres_local],
                    block=sketch.c_pad)
                new_qres = tuple(None if r is None else r[None]
                                 for r in new_slots)
            elif up_q:
                # block = one table row (c_pad = S·128 lanes) per scale
                table, new_qres = quantized_psum(
                    transmit_local, axis, rng_up, residual=qres_local[0],
                    block=sketch.c_pad, dtype=up_low)
                new_qres = new_qres[None]
            else:
                table = jax.lax.psum(transmit_local, axis)
                new_qres = qres_local
            table = table / count
            velocity = table + cfg.virtual_momentum * state.velocity
            if cfg.error_type == "virtual":
                error = state.error + velocity
            else:  # "local" and the documented "none" deviation alike
                error = velocity

        Tn = -(-sketch.T // n_shard)
        with scope("fed_server_estimate"):
            t0 = jax.lax.axis_index(axis) * Tn
            est_local = estimates_chunks_local(sketch, error, t0, Tn)
        fe_mode = fused_epilogue_mode(sketch) if cfg.fused_epilogue else "off"
        if fe_mode != "off":
            # per-shard one-sweep epilogue: the threshold comes from the
            # psum'd count exchange exactly like topk_dense_nd's, the
            # kernel emits this shard's update slice and PARTIAL re-sketch
            # (bit-identical per chunk to sketch_chunks_local's), and the
            # psum of partials replaces the composed psum — same table up
            # to the summation order the sharded plane already documents
            upd_local, part = fused_epilogue_chunks_local(
                sketch, est_local, t0, cfg.k, axis_name=axis,
                interpret=(fe_mode == "interpret"))
            with scope("fed_server_resketch"):
                resketched = jax.lax.psum(part, axis)
        else:
            with scope("fed_server_topk"):
                upd_local = topk_dense_nd(est_local, cfg.k, axis_name=axis)
            with scope("fed_server_resketch"):
                resketched = jax.lax.psum(
                    sketch_chunks_local(sketch, upd_local, t0), axis)
        with scope("fed_server_apply"):
            cell_nz = resketched != 0
            if cfg.error_type == "virtual":
                error = jnp.where(cell_nz, 0.0, error)
            velocity = jnp.where(cell_nz, 0.0, velocity)
            if cfg.error_type == "local":
                # torch aliasing parity (see _sketched)
                error = velocity
            if isinstance(down_low, tuple):
                # per-axis downlink: gather level by level in reverse reduce
                # order; slot j's local view IS level j's input tile
                full, new_dres = hierarchical_all_gather(
                    upd_local, down_low, rng_down, residuals=dres_local,
                    block=sketch.sublanes * 128)
                update = full[: sketch.T]
            elif down_q:
                # downlink leg: quantize this shard's update chunks (one scale
                # per (S, 128) resident chunk) before the gather; the
                # remainder telescopes through dres like qres on the uplink
                full, new_dres = quantized_all_gather(
                    upd_local, axis, rng_down, residual=dres_local,
                    block=sketch.sublanes * 128, dtype=down_low)
                update = full[: sketch.T]
            else:
                update = all_gather_tiled(upd_local, axis)[: sketch.T]
                new_dres = dres_local
            return (update * lr,
                    ServerState(velocity, error, new_qres, new_dres),
                    resketched)

    # ---- dense modes: flat (d,) transmit, state as local slices --------
    with scope("fed_server_apply"):
        d = cfg.grad_size
        d_pad = -(-d // n_shard) * n_shard
        x = jnp.pad(transmit_local, (0, d_pad - d))
        if isinstance(up_low, tuple):
            tile, new_slots = hierarchical_psum_scatter(
                x, up_low, rng_up,
                residuals=[None if q is None else q[0] for q in qres_local])
            new_qres = tuple(None if r is None else r[None] for r in new_slots)
        elif up_q:
            tile, new_qres = quantized_psum_scatter(x, axis, rng_up,
                                                    residual=qres_local[0],
                                                    dtype=up_low)
            new_qres = new_qres[None]
        else:
            tile = reduce_scatter_sum(x, axis)
            new_qres = qres_local
        grad = tile / count

        velocity = grad + cfg.virtual_momentum * state.velocity
        error = state.error
    if cfg.mode == "true_topk":
        with scope("fed_server_apply"):
            error = error + velocity
        with scope("fed_server_topk"):
            upd_local = topk_dense_nd(error, cfg.k, axis_name=axis)
        with scope("fed_server_apply"):
            nz = upd_local != 0
            error = jnp.where(nz, 0.0, error)
            velocity = jnp.where(nz, 0.0, velocity)
    else:  # uncompressed / local_topk / fedavg: update IS the velocity
        with scope("fed_server_apply"):
            upd_local = velocity
            if cfg.mode == "uncompressed" and cfg.do_dp \
                    and cfg.dp_mode == "server":
                assert rng is not None, "server DP needs an rng key"
                # one replicated (d_pad,)-stream draw, locally sliced, so every
                # shard agrees on the full noise vector (the stream differs
                # from the replicated path's (d,)-shaped draw — documented in
                # docs/sharded_server.md). Under a quantized plan the raw key
                # (or its split children) already feeds the collectives' SR
                # draws — fold to a distinct stream so the DP noise stays
                # statistically independent of the quantization dither; the
                # fp32 plan keeps the pre-plan draw bit for bit.
                noise_rng = rng
                if up_q or down_q:
                    noise_rng = jax.random.fold_in(rng, 2)
                noise = jax.random.normal(noise_rng, (d_pad,), upd_local.dtype)
                per = d_pad // n_shard
                upd_local = upd_local + cfg.noise_multiplier * \
                    jax.lax.dynamic_slice_in_dim(
                        noise, jax.lax.axis_index(axis) * per, per)

    with scope("fed_server_apply"):
        if isinstance(down_low, tuple):
            full, new_dres = hierarchical_all_gather(
                upd_local, down_low, rng_down, residuals=dres_local)
            update = full[:d]
        elif down_q:
            full, new_dres = quantized_all_gather(
                upd_local, axis, rng_down, residual=dres_local,
                dtype=down_low)
            update = full[:d]
        else:
            update = all_gather_tiled(upd_local, axis)[:d]
            new_dres = dres_local
        return (update * lr, ServerState(velocity, error, new_qres, new_dres),
                None)


def _sketched(sketched_grad, state, cfg, lr, sketch: CountSketch,
              layout: Optional[ChunkLayout] = None):
    scope = jax.named_scope
    with scope("fed_server_apply"):
        velocity = sketched_grad + cfg.virtual_momentum * state.velocity
        if cfg.error_type == "local":
            error = velocity
        elif cfg.error_type == "virtual":
            error = state.error + velocity
        else:  # "none": deviation — unsketch the velocity (module docstring)
            error = velocity

    # chunked-resident: top-k'd estimates stay in the (T, S, 128) layout and
    # re-sketch without the pad/reshape round trip; same values as the flat
    # path (the chunking is pure layout, the threshold descent counts over
    # the same coordinates)
    if layout is not None:
        fe_mode = fused_epilogue_mode(sketch) if cfg.fused_epilogue else "off"
        if fe_mode != "off":
            # one-sweep epilogue (docs/fused_epilogue.md): estimates are
            # materialized once (the threshold descent reads them 8x, so
            # re-deriving them from table windows per pass would cost more),
            # then ONE kernel masks at the precomputed threshold, emits the
            # update, and accumulates its re-sketch — the composed path's
            # separate compare_select and sketch_chunks d-plane sweeps
            # collapse into it. Bit-identical values by construction.
            with scope("fed_server_estimate"):
                est = estimates_chunks(sketch, error)
            # (threshold and kernel carry their own stage scopes)
            update, sketched_update = fused_epilogue_chunks(
                sketch, est, cfg.k, interpret=(fe_mode == "interpret"))
        else:
            update, sketched_update = _unsketch_resketch(sketch, error,
                                                         cfg.k)
    else:
        # flat caller: ONE shared (T, S, 128) view end-to-end. The old
        # formulation (unsketch → flat update → sketch_vec) flattened the
        # estimate chunks and then re-padded the SAME flat plane for the
        # re-sketch — the twin d-sized pad/reshape pairs of the GPT-2
        # profile (~3.1 ms/round, v5e, 2026-08-01, capture since deleted).
        # Thresholding the chunked estimates in place and re-sketching the
        # chunked update keeps the one flat materialization at the return
        # boundary; values are identical (pure layout + the same
        # threshold-descent counts). The nonzero cells of the re-sketch
        # are where error feedback and momentum masking happen (reference
        # fed_aggregator.py:592-611).
        upd3, sketched_update = _unsketch_resketch(sketch, error, cfg.k)
        with scope("fed_server_apply"):
            update = sketch.chunk_layout.unchunk(upd3)
    with scope("fed_server_apply"):
        cell_nz = sketched_update != 0
        if cfg.error_type == "virtual":
            error = jnp.where(cell_nz, 0.0, error)
        velocity = jnp.where(cell_nz, 0.0, velocity)
        if cfg.error_type == "local":
            # torch aliasing: Verror and Vvelocity are the same tensor after
            # fed_aggregator.py:580, so masking velocity also masks error
            error = velocity
        return update * lr, ServerState(velocity, error)


def _unsketch_resketch(sketch: CountSketch, table, k: int):
    """The composed epilogue, one named stage each: estimates of every
    coordinate, the top-k of them (``ops.sketch.unsketch_chunks`` is these
    two), and the re-sketch of that update. Returns ``(update chunks,
    re-sketched table)``."""
    with jax.named_scope("fed_server_estimate"):
        est = estimates_chunks(sketch, table)
    with jax.named_scope("fed_server_topk"):
        update = topk_dense_nd(est, k)
    with jax.named_scope("fed_server_resketch"):
        return update, sketch_chunks(sketch, update)

