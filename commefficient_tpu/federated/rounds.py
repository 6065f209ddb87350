"""The federated round as jitted SPMD programs.

This module replaces the reference's entire L0 distributed substrate —
process spawn + mp.Queue scatter + shared-memory state + NCCL reduce
(reference fed_aggregator.py:94-164, 301-332; fed_worker.py:14-138) — with
compiled steps over a ``jax.sharding.Mesh``:

  - the round's W sampled clients are lanes of a ``vmap``, sharded W/n per
    device via ``shard_map`` over the ``clients`` mesh axis (the reference's
    "one worker process per GPU looping over its chunk of clients");
  - the one collective in the whole system — the sum-reduce of per-client
    (possibly sketched) contributions (reference fed_worker.py:136-138 ↔
    fed_aggregator.py:327-330) — is a ``lax.psum`` over ICI. Sketch tables
    are fixed-shape and linear, which is exactly why they psum cleanly;
  - per-client persistent state (velocities/errors, reference
    fed_aggregator.py:116-129) lives in device-resident ``(num_clients, d)``
    arrays; participating rows are gathered before the shard_map and
    scatter-updated afterwards with an add-of-deltas (safe w.r.t. padded
    duplicate slots);
  - the server update runs replicated on the round gradient, and
    ``ps_weights`` never leaves HBM (deliberate improvement over the
    reference's host-resident PS weights, fed_worker.py:41 /
    fed_aggregator.py:455).

Two entry granularities are built from the same pieces:

  - ``client_step`` / ``server_step`` — the reference's two-phase API
    (``model(batch)`` computes and combines gradients; ``opt.step()`` applies
    the server rule, reference cv_train.py:221-229), used by
    FedModel/FedOptimizer;
  - ``train_step`` — the fused single-dispatch round used by benchmarks and
    the multichip dry-run.

Train metrics come back per client slot; the host aggregates. ``worker_mask``
zeroes contributions of padded slots (rounds where fewer than W clients
remain), replacing the reference's modulo re-dispatch (and its
double-counting bug, SURVEY.md §2.5).
"""

from __future__ import annotations

from dataclasses import dataclass, replace as dc_replace
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from commefficient_tpu.compat import shard_map

from commefficient_tpu.federated.server import (
    ServerConfig,
    ServerState,
    round_health,
    server_update,
)
from commefficient_tpu.federated.worker import (
    WorkerConfig,
    fedavg_local,
    forward_grad,
    get_new_worker_weights,
    local_step,
    microbatch_plan,
    next_rng,
    probe_n_metrics,
    sketch_grad_tree,
    split_microbatches,
)
from commefficient_tpu.ops.flat import (
    chunked_unravel,
    coalesce_segments,
    leaf_segments,
)
from commefficient_tpu.ops.sketch import (
    CountSketch,
    coalesce_vmem_budget,
    sketch_chunks,
    sketch_vec,
)


class ClientStates(NamedTuple):
    """Per-client persistent state; members are None when the config doesn't
    need them (matching the reference's conditional allocation,
    fed_aggregator.py:105-129).

    For ``mode="sketch"`` the velocity/error state lives in **sketch space**:
    ``(num_clients, r, c_pad)`` tables instead of ``(num_clients, d)`` dense
    rows — the reference's allocation shape (fed_aggregator.py:116-120) and
    *the* memory trick that makes EMNIST-scale per-client state feasible
    (3500 clients × 6M dense floats ≈ 84 GB vs ≈35 GB sketched)."""

    velocities: Optional[jax.Array]  # (num_clients, d) | (num_clients, r, c)
    errors: Optional[jax.Array]      # (num_clients, d) | (num_clients, r, c)
    weights: Optional[jax.Array]     # (num_clients, d) iff do_topk_down


class RoundContext(NamedTuple):
    """Client-phase outputs the server phase needs (the functional stand-in
    for the reference's cross-phase module globals, fed_aggregator.py:37-44).

    With the sharded server plane (``RoundConfig.server_shard``)
    ``gradient`` is the UNREDUCED stack of per-shard transmit sums —
    ``(n, ...)`` sharded over the worker axis, no data movement between
    the phases — and ``count`` carries the round's datum count so the
    data-weighted division happens AFTER the server's reduce (keeping the
    summed values bit-identical to the replicated path's psum)."""

    gradient: jax.Array
    ids: jax.Array
    wmask: jax.Array  # (W,) 1 for participating slots, 0 for padding
    vel_rows: jax.Array
    err_rows: jax.Array
    stale_rows: jax.Array
    new_vel: jax.Array
    new_err: jax.Array
    count: Optional[jax.Array] = None


def init_client_states(num_clients: int, grad_size: int, wcfg: WorkerConfig,
                       init_weights: Optional[jax.Array] = None,
                       sharding=None,
                       sketch: Optional[CountSketch] = None) -> ClientStates:
    def alloc(shape):
        z = jnp.zeros(shape, jnp.float32)
        return jax.device_put(z, sharding) if sharding is not None else z

    # sketch mode stores velocity/error per client as (r, c_pad) tables
    # (reference fed_aggregator.py:116-120)
    if wcfg.mode == "sketch" and (wcfg.has_velocity or wcfg.has_error):
        assert sketch is not None, \
            "sketch-mode client state needs the sketch geometry"
        state_shape = (num_clients,) + sketch.table_shape
    else:
        state_shape = (num_clients, grad_size)
    velocities = alloc(state_shape) if wcfg.has_velocity else None
    errors = alloc(state_shape) if wcfg.has_error else None
    weights = None
    if wcfg.do_topk_down:
        assert init_weights is not None
        weights = jnp.tile(init_weights[None, :], (num_clients, 1))
        if sharding is not None:
            weights = jax.device_put(weights, sharding)
    return ClientStates(velocities, errors, weights)


@dataclass(frozen=True)
class RoundConfig:
    worker: WorkerConfig
    server: ServerConfig
    grad_size: int
    do_test: bool = False
    # Batch keys whose LAST dimension is the (globally ordered) sequence,
    # sharded over the worker's ``seq_axis`` when sequence parallelism is on.
    # All other batch leaves are replicated across seq shards.
    seq_sharded_keys: Tuple[str, ...] = ("input_ids", "token_type_ids",
                                         "lm_labels_shifted")
    # Fused-gradient client phase: None = auto (on whenever legal — see
    # ``build_round_step``), True/False forces it (tests use False to pin the
    # per-client-gradient path for parity checks).
    fuse_gradients: Optional[bool] = None
    # Tensor parallelism: predicate over '/'-joined lowercase param paths,
    # True for weights whose gradient is slice-local per model shard (e.g.
    # models.gpt2.tp_sliced_param). Required when worker.model_axis is set;
    # used to build the flat grad-rescale mask (1 sliced, 1/nm replicated).
    tp_sliced: Optional[Callable[[str], bool]] = None
    # Expert parallelism: same contract for the `expert` axis (e.g.
    # parallel.moe.ep_sliced_param — 1 on expert-stacked MoE weights,
    # 1/ne on the router and every dense param). Required when
    # worker.expert_axis is set.
    ep_sliced: Optional[Callable[[str], bool]] = None
    # Chunked-resident data plane: None = auto (on for sketch mode without
    # topk-down stale weights), True/False forces it. When on, the round
    # step's ps_weights argument/result live in the sketch's (T, S, 128)
    # chunk layout (ops/flat.ChunkLayout, exposed as FederatedSteps.layout)
    # so the sketch kernels consume PS state with no per-round pad/reshape
    # churn; per-param pytrees materialize only at the model boundary.
    chunked_resident: Optional[bool] = None
    # Buffer donation through the jitted steps (ps_weights, client states,
    # and — where the server rule cannot alias two outputs to one buffer —
    # the server velocity/error). False pins the copying path; the
    # donation-parity test uses it to show results are bit-identical.
    donate: bool = True
    # Sharded server data plane (--server_shard, docs/sharded_server.md):
    # reduce-scatter the transmit over the worker mesh axis, run the
    # server rule per-shard (threshold via a psum'd count exchange), and
    # all-gather only the resulting update. fp32 trajectories are
    # bit-identical to the replicated path. Requires a mesh; incompatible
    # with --topk_down (its stale-weight math lives on dense client rows).
    server_shard: bool = False
    # Transmit-collective element type (--reduce_dtype): "int8" swaps the
    # fp32 reduce for the block-scaled stochastic-rounding collective
    # (ops/collectives.py) with its residual carried in ServerState.qres.
    # Opt-in; requires server_shard. LEGACY alias — since the per-leg
    # collective plan landed it means "every leg int8"; prefer
    # collective_plan below.
    reduce_dtype: str = "float32"
    # Per-leg collective plan (--collective_plan,
    # docs/compressed_collectives.md): an ops.collectives.CollectivePlan
    # choosing the wire dtype of each leg — uplink (dense transmit
    # reduce), table (sketch-table exchange), downlink (update
    # all-gather) — from {float32, int8, fp8_e4m3, int4}. None derives
    # the plan from reduce_dtype. Quantized legs require server_shard;
    # their error-feedback residuals ride ServerState.qres (uplink/table)
    # and ServerState.dres (downlink). The fp32 plan is bit-identical to
    # the pre-plan code paths (pinned in
    # tests/test_compressed_collectives.py).
    collective_plan: Optional[Any] = None
    # The sketch cells' client phase (docs/stream_sketch.md): inside the
    # fused-gradient + sketch-after-sum + chunked-resident window the
    # gradient never becomes a flat vector. The parameter tree is sliced
    # out of the resident plane once a round, the backward pass
    # differentiates by that tree, the microbatch scan adds the leaves'
    # gradients leaf by leaf, and after the scan the leaves are sketched
    # once at their flat offsets, one accumulate launch per
    # ``ops/flat.coalesce_segments`` group (weight decay added in the
    # group's staging pass, read from the plane's own rows). The
    # table equals the flat route's under ``==`` (zero cells may differ in
    # sign). None = decide (on in that window, the only rule), False pins
    # the flat route (tests build both sides), True asserts the window.
    sketch_leaf_groups: Optional[bool] = None
    # Group-sizing budget in bytes (the covering chunk-range staging
    # buffer of one launch); 0 = auto from the sketch geometry
    # (ops/sketch.coalesce_vmem_budget).
    sketch_coalesce_budget: int = 0
    # On-device health guards (--guards, docs/fault_tolerance.md): the
    # server phase computes a scalar finiteness/magnitude verdict
    # (server.round_health) and gates the WHOLE state transition on it —
    # a tripped round leaves ps_weights, server (velocity, error, qres)
    # and the client-state scatter untouched (the poisoned contribution is
    # discarded, NOT absorbed into the error-feedback carry). When on,
    # server_step/train_step return the verdict as one extra device scalar
    # (drained with the batched metrics; zero extra host syncs).
    guards: bool = False
    # Magnitude ceiling for the guard (0 = finiteness-only).
    guard_max_abs: float = 0.0
    # Zero-sync telemetry plane (--telemetry, docs/observability.md): the
    # server phase additionally returns one fixed-schema
    # (len(telemetry.METRIC_FIELDS),) f32 device vector of round metrics
    # (transmit/update/carry norms, resolved top-k threshold, guard
    # detail — telemetry.device_round_metrics). Pure reductions over
    # planes the epilogue already reads: the state transition is
    # untouched, so fp32 trajectories are bit-identical with telemetry on
    # or off (pinned in tests/test_telemetry.py on both server planes),
    # and the vector rides the round handle to the batched drain exactly
    # like the guard verdict (zero extra host syncs).
    telemetry: bool = False
    # Schema-v3 histogram block (--telemetry_hist, the default with
    # telemetry on; docs/observability.md): append the fixed-K
    # log-magnitude histograms of the emitted update and the post-round
    # error carry (telemetry.log_magnitude_histogram) to the metrics
    # vector — online threshold-drift / estimation-fidelity visibility.
    # Same non-perturbation contract as the scalar block (pure
    # reductions; fp32 trajectories bit-identical on/off, pinned in
    # tests/test_watch.py on both server planes).
    telemetry_hist: bool = False


class FederatedSteps(NamedTuple):
    """With ``RoundConfig.guards`` on, ``server_step`` returns one extra
    trailing element (the device health-verdict scalar of
    server.round_health), and with ``RoundConfig.telemetry`` on, one more
    (the fixed-schema round-metrics device vector of
    telemetry.device_round_metrics) — always in that order, guard before
    telemetry; ``train_step`` appends the same trailing elements. Callers
    that enable the flags unpack the extras; the arity is unchanged
    otherwise."""

    train_step: Callable   # fused round
    client_step: Callable  # phase 1: gradients + client state rows
    server_step: Callable  # phase 2: server rule + state scatter
    val_step: Callable
    # ops/flat.ChunkLayout of the resident ps_weights when the chunked data
    # plane is on, else None (callers convert flat vectors at this boundary)
    layout: Optional[Any] = None
    # the gradient's route to the sketch table in the client phase
    # ("leaf_groups" | "flat"; docs/stream_sketch.md) and the accumulate
    # launches of its group plan (0 on the flat route) — the run's start
    # event carries both (docs/observability.md §Names)
    client_sketch_path: str = "flat"
    client_sketch_launches: int = 0


def build_round_step(
    compute_loss_train: Callable,
    compute_loss_val: Callable,
    unravel: Callable,
    ravel: Callable,
    cfg: RoundConfig,
    sketch: Optional[CountSketch] = None,
    mesh: Optional[Mesh] = None,
    axis="clients",
) -> FederatedSteps:
    """``axis`` is the server reduce axis: one mesh axis name, or — on a
    2D (clients × shard) mesh — the ORDERED axis tuple
    ``mesh.server_reduce_axes`` (ICI axis first, the DCN-spanning axis
    last; docs/multihost.md). Client slots shard and the server plane
    reduces over the whole tuple; per-mesh-axis collective-plan legs
    lower hierarchically along it."""
    wcfg, scfg = cfg.worker, cfg.server

    # Sharded server data plane (docs/sharded_server.md): legality checks
    # up front, mirroring the chunked_resident ones below.
    server_shard = bool(cfg.server_shard)
    assert cfg.reduce_dtype in ("float32", "int8"), cfg.reduce_dtype
    # resolve the per-leg collective plan (docs/compressed_collectives.md):
    # an explicit plan wins; otherwise the legacy --reduce_dtype alias
    # (int8 = every leg int8, float32 = the exact fp32 plan)
    from commefficient_tpu.ops.collectives import (
        PLAN_LEGS,
        CollectivePlan,
        plan_from_reduce_dtype,
        resolve_leg_lowering,
    )

    plan = cfg.collective_plan
    if plan is None:
        plan = plan_from_reduce_dtype(cfg.reduce_dtype)
    assert isinstance(plan, CollectivePlan), plan
    if plan.quantized:
        assert server_shard, \
            "quantized collective legs (--collective_plan / " \
            "--reduce_dtype int8) require --server_shard"
    axis_names = (axis,) if isinstance(axis, str) else tuple(axis)
    if server_shard:
        assert mesh is not None and all(a in mesh.axis_names
                                        for a in axis_names), \
            "--server_shard needs a mesh with the worker axis/axes"
        assert not wcfg.do_topk_down, \
            "--server_shard is incompatible with --topk_down (stale-" \
            "weight reconstruction lives on dense per-client rows)"
    n_shard = 1
    if server_shard:
        for _a in axis_names:
            n_shard *= int(mesh.shape[_a])
    # per-mesh-axis plan legs resolve against THIS mesh (docs/multihost.md):
    # ici/dcn aliases bind to the axes' fabric placement, all-equal legs
    # collapse back to the flat single-dtype collectives (bit-identity),
    # and an entry naming an axis this mesh lacks fails here — at build
    # time — with the axis list
    lowering = None
    if server_shard and plan.per_axis:
        from commefficient_tpu.parallel.mesh import mesh_axis_placement

        placement = mesh_axis_placement(mesh)
        lowering = {leg: resolve_leg_lowering(getattr(plan, leg), axis,
                                              placement)
                    for leg in PLAN_LEGS}

    # Chunked-resident data plane: ps_weights (and every dense (d,)-shaped
    # value of the server phase — unsketched update, per-coordinate lr) stay
    # in the sketch's lane-aligned (T, S, 128) chunk layout across rounds, so
    # sketch_chunks/estimates_chunks consume and produce PS state directly
    # and the per-round flat↔chunk conversions (the pad/reshape/concatenate
    # data movement measured at ~7 ms/round busy on GPT-2,
    # v5e, 2026-08-01, capture deleted) drop out of the steady state.
    # The flat view materializes only inside `unravel_res` at the model
    # (pytree) boundary. topk-down is excluded: its stale-weight
    # reconstruction math lives on (num_clients, d) dense rows.
    chunked = cfg.chunked_resident
    if chunked is None:
        chunked = (wcfg.mode == "sketch" and sketch is not None
                   and not wcfg.do_topk_down)
    if chunked:
        assert wcfg.mode == "sketch" and sketch is not None, \
            "chunked_resident requires sketch mode (the layout is the " \
            "sketch kernels' chunk geometry)"
        assert not wcfg.do_topk_down, \
            "chunked_resident is incompatible with --topk_down stale weights"
    layout = sketch.chunk_layout if chunked else None
    if scfg.fused_epilogue and wcfg.mode == "sketch" and chunked:
        # one-time on-TPU self-check of the fused epilogue megakernel,
        # triggered here (always eager host-side setup, and the one place
        # that knows the config actually opted in) rather than from
        # make_sketch — processes that never use the megakernel must not
        # pay its compile+compare at every sketch build
        from commefficient_tpu.ops.sketch import _check_fused_epilogue_once

        _check_fused_epilogue_once(eager=True)
    if server_shard and wcfg.mode == "sketch":
        # the sharded sketch server produces its update in the chunk
        # layout (estimates/top-k/re-sketch slices are chunk-aligned)
        assert chunked, "--server_shard sketch mode requires the " \
            "chunked-resident data plane (don't force chunked_resident=False)"

    def unravel_res(w):
        """Resident weights → parameter pytree (the one flat materialization
        of a chunked round, at the model boundary)."""
        return unravel(layout.unchunk(w)) if chunked else unravel(w)

    def _to_resident(w):
        """Normalize ps_weights to the step's resident layout. A chunked
        round accepts a legacy flat ``(d,)`` vector too (tests, bench, and
        scripts that predate the chunked data plane): the conversion is pure
        layout, so results are identical — but a flat caller pays the
        per-round chunk/unchunk churn the resident path exists to avoid.
        Shape is static under jit, so the branch retraces, never re-checks."""
        return layout.chunk(w) if (chunked and w.ndim == 1) else w

    # Sketch-after-sum fusion: count-sketches are linear, so when nothing
    # nonlinear touches the per-client table — no sketch-space client state
    # (velocity/error), no sketch-space max_grad_norm clip — the sum of
    # per-client sketches equals one sketch of the dense per-shard gradient
    # sum. Workers then transmit dense gradients within the shard and the
    # shard sketches once before the psum: identical result (up to float
    # summation order), ~W× fewer sketch kernels per round. The transmitted
    # quantity over the mesh is still the (r, c_pad) table, so the
    # communication accounting and server math are untouched (reference
    # upload semantics, fed_aggregator.py:291-299).
    sketch_after_sum = (wcfg.mode == "sketch" and not wcfg.has_velocity
                        and not wcfg.has_error
                        and wcfg.max_grad_norm is None and not cfg.do_test)
    inner_wcfg = (dc_replace(wcfg, mode="uncompressed") if sketch_after_sum
                  else wcfg)

    # Fused-gradient client phase: every client in the round holds identical
    # weights, and when nothing nonlinear or stateful touches the per-client
    # gradient — no local momentum/error, no per-client clip/DP/topk, no
    # stale topk-down weights — the sum of per-client transmits IS the
    # gradient of the slot-masked sum of per-client losses:
    #   Σ_i mask_i · count_i · mean_grad_i = ∇_w Σ_i mask_i · loss_sum_i .
    # So the shard computes ONE d-sized gradient of a summed loss instead of
    # W separate ones: the backward pass writes one parameter-gradient
    # buffer (vs W at 124M params each for GPT-2), and the per-client
    # forward/backward batches into one big MXU program. Per-client metrics
    # and model_state still come from the vmapped loss evaluations, and the
    # microbatch scan + per-client dropout rng streams are mirrored from
    # worker._microbatch_grads, so the result matches the per-client path up
    # to float summation order.
    fused_grad = (
        not cfg.do_test
        and wcfg.mode in ("uncompressed", "true_topk", "sketch")
        and not wcfg.has_velocity and not wcfg.has_error
        and not wcfg.do_dp and not wcfg.do_topk_down
        and wcfg.max_grad_norm is None
    )
    if cfg.fuse_gradients is not None:
        assert not (cfg.fuse_gradients and not fused_grad), \
            "fuse_gradients=True forced on a config where it is not legal"
        fused_grad = cfg.fuse_gradients
    # fused sketch mode only ever rides the sketch-after-sum path
    assert not (fused_grad and wcfg.mode == "sketch" and not sketch_after_sum)

    # The gradient's route to the table (docs/stream_sketch.md): inside the
    # fused-gradient + sketch-after-sum + chunked-resident window (one
    # gradient per shard, nothing nonlinear between the backward pass and
    # the table) the leaves are sketched in groups at their flat offsets;
    # everywhere else the flat gradient itself is needed.
    leaf_groups = fused_grad and sketch_after_sum and chunked
    if cfg.sketch_leaf_groups is not None:
        assert not (cfg.sketch_leaf_groups and not leaf_groups), \
            "sketch_leaf_groups=True forced on a config outside its window"
        leaf_groups = cfg.sketch_leaf_groups

    # Tensor/expert parallelism: flat grad-rescale masks built once,
    # host-side — 1.0 on segments whose weights the model computes
    # slice-locally per shard of the axis, 1/n where every shard computed
    # the identical full grad (see worker.WorkerConfig.model_axis /
    # .expert_axis).
    # the template pytree of the flat layout (eval_shape: no device
    # allocation at GPT-2 scale) and its per-leaf offset map — computed
    # once per build, shared by the tp/ep rescale masks and the leaf
    # groups' per-leaf scales and offsets, so the layouts cannot drift
    # (ops/flat.leaf_segments)
    _layout_cache = {}

    def _template():
        if "tpl" not in _layout_cache:
            _layout_cache["tpl"] = jax.eval_shape(
                unravel, jax.ShapeDtypeStruct((cfg.grad_size,), jnp.float32))
        return _layout_cache["tpl"]

    def _segs():
        if "segs" not in _layout_cache:
            _layout_cache["segs"] = leaf_segments(_template())
        return _layout_cache["segs"]

    def _leaf_scale_vals(axis_name, sliced_pred, pred_attr):
        """Per-leaf rescale values (1.0 on slice-local segments, 1/n on
        replicated ones) in ravel order."""
        assert mesh is not None and axis_name in mesh.axis_names, \
            f"axis {axis_name!r} not in mesh axes"
        assert sliced_pred is not None, \
            f"worker axis {axis_name!r} set but RoundConfig.{pred_attr} " \
            f"is missing"
        n = mesh.shape[axis_name]
        return tuple(1.0 if sliced_pred(s.path) else 1.0 / n
                     for s in _segs())

    def _flat_scale(axis_name, sliced_pred, pred_attr):
        vals = _leaf_scale_vals(axis_name, sliced_pred, pred_attr)
        scale = jnp.concatenate([
            jnp.full(s.size, v, jnp.float32)
            for s, v in zip(_segs(), vals)])
        assert scale.size == cfg.grad_size, \
            f"{pred_attr} scale layout does not match the flat vector"
        return scale

    # The leaf-group route never touches the d-sized masks (its per-leaf
    # constants come from _leaf_scale_vals below) — materializing them
    # anyway would park ~2×d f32 of dead mask in HBM at GPT-2 scale.
    tp_scale = None
    if wcfg.model_axis is not None and not leaf_groups:
        tp_scale = _flat_scale(wcfg.model_axis, cfg.tp_sliced, "tp_sliced")
    ep_scale = None
    if wcfg.expert_axis is not None and not leaf_groups:
        # composes with every other axis, each on its own mesh dimension:
        # seq (token-partial grads, scale 1), model (orthogonal param
        # sets: each axis's scale mask marks the other's params
        # replicated), and stage (MoE layers live inside their owning
        # stage's blocks; the stage psum sums disjoint segments before
        # the expert psum x ep_scale reconciles the expert slices)
        ep_scale = _flat_scale(wcfg.expert_axis, cfg.ep_sliced, "ep_sliced")

    # fused-path copies of the rescale masks in the resident layout (the
    # fused gradient sum is chunked there; the per-client worker path keeps
    # the flat masks). Zero tail x zero gradient tail stays zero.
    tp_scale_res = layout.chunk(tp_scale) if (chunked and tp_scale is not None) \
        else tp_scale
    ep_scale_res = layout.chunk(ep_scale) if (chunked and ep_scale is not None) \
        else ep_scale

    # Leaf-group machinery, built once, host-side: the leaf offset map of
    # the flat layout, a model-boundary unravel that reads the leaves
    # straight out of the (T, S, 128) resident plane (no d-sized flatten),
    # the per-leaf tp×ep rescale constants (the flat masks are per-leaf
    # constants; the reorder past the psum is exact for power-of-two mesh
    # axes — docs/stream_sketch.md) and the group plan, one accumulate
    # launch a group, sized from the sketch's geometry.
    leaf_segs = leaf_unravel = leaf_scales = leaf_plan = None
    if leaf_groups:
        leaf_segs = _segs()
        assert leaf_segs[-1].offset + leaf_segs[-1].size \
            == cfg.grad_size, "leaf layout does not cover the flat vector"
        leaf_unravel = chunked_unravel(layout, _template())
        vals = [1.0] * len(leaf_segs)
        if wcfg.model_axis is not None:
            tp_vals = _leaf_scale_vals(wcfg.model_axis, cfg.tp_sliced,
                                       "tp_sliced")
            vals = [a * b for a, b in zip(vals, tp_vals)]
        if wcfg.expert_axis is not None:
            ep_vals = _leaf_scale_vals(wcfg.expert_axis, cfg.ep_sliced,
                                       "ep_sliced")
            vals = [a * b for a, b in zip(vals, ep_vals)]
        leaf_scales = tuple(vals) if any(v != 1.0 for v in vals) else None
        leaf_plan = coalesce_segments(
            leaf_segs,
            int(cfg.sketch_coalesce_budget) or coalesce_vmem_budget(sketch),
            chunk_elems=sketch.c_pad)

    # Pipeline parallelism (parallel/pipeline.py): the loss callbacks carry
    # the GPipe schedule; the round only needs the one-gradient psum over
    # the stage axis (see worker.WorkerConfig.pp_axis). Composes with seq
    # (the pipelined loss computes token-partial stage-local grads; the
    # stage and seq psums both run at scale 1 on orthogonal axes), with
    # model (stage psum + model psum x tp_scale), and with expert (above).
    if wcfg.pp_axis is not None:
        assert mesh is not None and wcfg.pp_axis in mesh.axis_names, \
            f"pp_axis {wcfg.pp_axis!r} not in mesh axes"

    # The round's stages carry ``jax.named_scope`` names on the device
    # (profiling.DEVICE_STAGES; docs/observability.md lists who reads each).
    # Scopes never nest: an operation's path holds exactly one stage.
    scope = jax.named_scope

    def clients_loss(params, mstates, micro, subs):
        """The loss of every client slot of a microbatch: a vmap over the
        clients axis, or the loss's own ``over_clients`` where it batches
        that axis itself (one token axis for an expert layer's routing)."""
        over = getattr(compute_loss_train, "over_clients", None)
        if over is not None:
            return over(params, mstates, micro, subs)
        return jax.vmap(
            lambda ms, b, r: compute_loss_train(params, ms, b, r, True))(
                mstates, micro, subs)

    def fused_clients(ps_weights, model_state, batch, rng_keys, worker_mask):
        """One-gradient client phase for a shard's W client slots. Returns
        (the shard's transmit, stacked per-client model_state, per-client
        metrics) — drop-in for the vmap path's (Σ transmit, new_ms,
        metrics). The transmit is the dense gradient sum incl. weight decay
        and the seq/model/pp/expert psums, or — on the leaf-group route
        (docs/stream_sketch.md) — already the shard's (r, c_pad) table.

        The leaf-group route differentiates by the parameter TREE, sliced
        out of the resident plane once a round, so the backward pass's
        transpose never writes a (d,) vector and the scan carries a tree
        of float32 leaves; after the scan the leaves are rescaled, staged
        in groups (the decay read from the plane's own rows there) and
        sketched once at their flat offsets (worker.sketch_grad_tree).
        The microbatches and the decay are added before the sketch, in the
        flat route's own elementwise order, so on the clients axis alone
        the table equals ``sketch_chunks`` of the flat route's sum under
        ``==`` for any count of scan steps and any weight decay (zero
        cells may differ in sign); seq/model/pp/expert psums ride the
        small table (sketch linearity) and reorder float32 sums."""
        W = worker_mask.shape[0]
        B = batch["mask"].shape[1]
        mb, n_iters, pad = microbatch_plan(B, wcfg.microbatch_size)
        # (n_iters, W, mb, ...) — client axis inside the scan axis
        stacked = split_microbatches(batch, mb, n_iters, pad, example_dim=1)
        mstates0 = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (W,) + x.shape), model_state)

        if leaf_groups:
            # the ONE model boundary of the round: leaves sliced straight
            # from the resident plane, every op smaller than d
            with scope("fed_client_grad"):
                wrt = leaf_unravel(ps_weights)
            to_params = lambda p: p  # noqa: E731
        else:
            wrt, to_params = ps_weights, unravel_res

        def step_loss(w, mstates, micro, subs):
            loss_sums, msums, counts, new_ms = clients_loss(
                to_params(w), mstates, micro, subs)
            total = jnp.sum(loss_sums * worker_mask)
            return total, (loss_sums, msums, counts, new_ms)

        grad_fn = jax.value_and_grad(step_loss, has_aux=True)

        n_metrics = probe_n_metrics(
            compute_loss_train, to_params(wrt), model_state,
            jax.tree_util.tree_map(lambda x: x[0, 0], stacked))

        def body(carry, micro):
            g_acc, loss_acc, m_acc, n_acc, mstates, keys = carry
            # the per-client scan's rng protocol, one lane per client
            keys2, subs = jax.vmap(next_rng)(keys)
            (_, (loss_sums, msums, counts, new_ms)), g = grad_fn(
                wrt, mstates, micro, subs)
            m_acc = tuple(a + m for a, m in zip(m_acc, msums))
            # one array on the flat route, the tree's leaves otherwise
            return (jax.tree_util.tree_map(jnp.add, g_acc, g),
                    loss_acc + loss_sums, m_acc, n_acc + counts,
                    new_ms, keys2), None

        with scope("fed_client_grad"):
            init = (jax.tree_util.tree_map(
                        lambda x: jnp.zeros(x.shape, jnp.float32), wrt),
                    jnp.zeros(W),
                    tuple(jnp.zeros(W) for _ in range(n_metrics)),
                    jnp.zeros(W), mstates0, rng_keys)
            (g_sum, loss_sums, m_sums, counts, new_ms, _), _ = jax.lax.scan(
                body, init, stacked)
            denom = jnp.maximum(counts, 1.0)
            metrics = (loss_sums / denom,) \
                + tuple(m / denom for m in m_sums) + (counts,)

        def decay_coef():
            """Per-client (wd/num_workers)·w scaled by the client's datum
            count (worker.forward_grad + local_step ×count)."""
            return (wcfg.weight_decay / wcfg.num_workers) * \
                jnp.sum(worker_mask * counts)

        if leaf_groups:
            axes = [ax for ax in (wcfg.seq_axis, wcfg.model_axis,
                                  wcfg.pp_axis, wcfg.expert_axis)
                    if ax is not None]
            with scope("fed_client_compress"):
                decay = None
                if wcfg.weight_decay != 0:
                    coef = decay_coef()
                    # the weights are replicated over the axes whose psums
                    # ride the table below: one shard of each adds the
                    # decay (g + 0·w is g on the others)
                    for ax in axes:
                        coef = jnp.where(jax.lax.axis_index(ax) == 0,
                                         coef, 0.0)
                    decay = (coef, ps_weights)
                table = sketch_grad_tree(
                    sketch, jnp.zeros(sketch.table_shape, jnp.float32),
                    g_sum, leaf_segs, leaf_plan, scales=leaf_scales,
                    decay=decay)
                for ax in axes:
                    table = jax.lax.psum(table, ax)
            return table, new_ms, metrics

        with scope("fed_client_compress"):
            if wcfg.seq_axis is not None:
                # shards backpropagated their local sequence slice (linear,
                # so one psum of the sum replaces the per-client psums)
                g_sum = jax.lax.psum(g_sum, wcfg.seq_axis)
            if wcfg.model_axis is not None:
                # reconcile sliced/replicated segments (worker.forward_grad)
                g_sum = jax.lax.psum(g_sum, wcfg.model_axis) * tp_scale_res
            if wcfg.pp_axis is not None:
                # disjoint stage-local gradient segments -> full gradient
                g_sum = jax.lax.psum(g_sum, wcfg.pp_axis)
            if wcfg.expert_axis is not None:
                # expert-sliced/replicated reconciliation
                # (see worker.forward_grad)
                g_sum = jax.lax.psum(g_sum, wcfg.expert_axis) * ep_scale_res
            if wcfg.weight_decay != 0:
                g_sum = g_sum + decay_coef() * ps_weights
        return g_sum, new_ms, metrics

    def one_client(ps_weights, vel_row, err_row, stale_row, model_state,
                   batch_row, lr, rng, slot_mask):
        # choose weights (topk-down stale path, fed_worker.py:150-159)
        if wcfg.do_topk_down:
            with scope("fed_client_compress"):
                weights_used = get_new_worker_weights(ps_weights, stale_row,
                                                      wcfg.k, True)
        else:
            weights_used = ps_weights

        if cfg.do_test:
            # smoke mode: skip fwd/bwd, all-ones transmit
            # (reference fed_worker.py:117-122); the fake metrics tuple must
            # match the workload's real (loss, *metrics, count) arity — CV
            # has an accuracy metric, GPT-2 none
            shape = sketch.table_shape if wcfg.mode == "sketch" else \
                (cfg.grad_size,)
            transmit = jnp.ones(shape, jnp.float32)
            n_metrics = probe_n_metrics(compute_loss_train,
                                        unravel(weights_used), model_state,
                                        batch_row)
            metrics = (jnp.ones(()),) + tuple(
                jnp.ones(()) for _ in range(n_metrics)) + \
                (batch_row["mask"].sum(),)
            new_vel, new_err, new_ms = vel_row, err_row, model_state
        elif wcfg.mode == "fedavg":
            res, new_ms = fedavg_local(compute_loss_train, weights_used,
                                       unravel, ravel, model_state, batch_row,
                                       rng, lr, wcfg, tp_scale=tp_scale,
                                       ep_scale=ep_scale)
            transmit, new_vel, new_err, metrics = (res.transmit, vel_row,
                                                   err_row, res.metrics)
        else:
            res, new_ms = local_step(compute_loss_train, weights_used,
                                     unravel, ravel, model_state, vel_row,
                                     err_row, batch_row, rng, inner_wcfg,
                                     sketch, tp_scale=tp_scale,
                                     ep_scale=ep_scale)
            transmit, new_vel, new_err, metrics = (res.transmit,
                                                   res.new_velocity,
                                                   res.new_error, res.metrics)

        # padded slots contribute nothing and keep their state
        with scope("fed_client_compress"):
            transmit = transmit * slot_mask
            if new_vel is not None:
                new_vel = jnp.where(slot_mask > 0, new_vel, vel_row)
            if new_err is not None:
                new_err = jnp.where(slot_mask > 0, new_err, err_row)
        return transmit, new_vel, new_err, new_ms, metrics

    def clients_shard(ps_weights, vel_rows, err_rows, stale_rows, model_state,
                      batch, lr, rng_keys, worker_mask):
        """Runs on one device over its W/n client slots; psums the transmit."""
        if fused_grad:
            # on the leaf-group route local_sum IS already the shard's table
            local_sum, new_ms, metrics = fused_clients(
                ps_weights, model_state, batch, rng_keys, worker_mask)
            # no per-client state on any fused-eligible config: the inert
            # placeholder rows pass through untouched
            new_vel, new_err = vel_rows, err_rows
        else:
            # per-client path: the worker math (local_step/fedavg_local)
            # runs on the flat vector; a chunked round materializes the
            # flat view once per round here (the model boundary)
            with scope("fed_client_grad"):
                ps_flat = layout.unchunk(ps_weights) if chunked \
                    else ps_weights
            f = partial(one_client, ps_flat)
            transmit, new_vel, new_err, new_ms, metrics = jax.vmap(
                f, in_axes=(0, 0, 0, None, 0, None, 0, 0),
                out_axes=(0, 0, 0, 0, 0),
            )(vel_rows, err_rows, stale_rows, model_state, batch, lr,
              rng_keys, worker_mask)
            with scope("fed_client_compress"):
                local_sum = jnp.sum(transmit, axis=0)
        with scope("fed_client_compress"):
            total = _reduce_transmit(local_sum)
        with scope("fed_client_grad"):
            new_ms = _average_model_state(new_ms, model_state, worker_mask)
        return total, new_vel, new_err, new_ms, metrics

    def _reduce_transmit(local_sum):
        """The shard's transmit sum -> the round's (fed_client_compress)."""
        if sketch_after_sum and not leaf_groups:
            # one sketch of the shard's dense gradient sum (see fusion note
            # above); the psum then rides the small (r, c_pad) table exactly
            # as the per-client path would. The fused chunked gradient is
            # already in the kernel's (T, S, 128) layout — no pad/reshape.
            # (The leaf-group route already produced the table.)
            if chunked and fused_grad:
                local_sum = sketch_chunks(sketch, local_sum)
            else:
                local_sum = sketch_vec(sketch, local_sum)
        if server_shard:
            # sharded server plane: DON'T reduce here — return this
            # shard's sum stacked under a leading axis (out_spec P(axis):
            # no data moves), so the server phase owns the reduce (and,
            # under a quantized collective plan, the quantization + the
            # qres/dres error-feedback carries)
            return local_sum[None]
        if mesh is not None:
            return jax.lax.psum(local_sum, axis)
        return local_sum

    def _average_model_state(new_ms, model_state, worker_mask):
        # model_state (e.g. BatchNorm stats): average over clients, weighted
        # by slot mask — a documented deviation; the reference lets each
        # worker process's BN stats drift independently. A shard whose slots
        # are all padding must contribute 0 to BOTH the numerator and the
        # denominator of the cross-shard mean — clamping its weight to 1
        # would shrink the averaged state every short round (BN running
        # stats halve on an 8-of-16 round, exploding later eval losses).
        wsum = worker_mask.sum()
        local_mean = jax.tree_util.tree_map(
            lambda x: jnp.einsum("c,c...->...", worker_mask, x)
            / jnp.maximum(wsum, 1.0), new_ms)
        if mesh is not None:
            total_w = jax.lax.psum(wsum, axis)
            new_ms = jax.tree_util.tree_map(
                lambda x: jax.lax.psum(x * wsum, axis)
                / jnp.maximum(total_w, 1.0), local_mean)
        else:
            total_w = wsum
            new_ms = local_mean
        # an entirely-empty round keeps the old state rather than zeroing it
        return jax.tree_util.tree_map(
            lambda new, old: jnp.where(total_w > 0, new, old),
            new_ms, model_state)

    seq_axis = wcfg.seq_axis
    if mesh is not None and seq_axis is not None:
        assert seq_axis in mesh.axis_names, \
            f"seq_axis {seq_axis!r} not in mesh axes {mesh.axis_names}"

    def _shard_clients(data_batch):
        """shard_map wrapper built at trace time so the batch's sharding
        specs can be per-leaf: every leaf is client-sharded on dim 0; leaves
        named in cfg.seq_sharded_keys are additionally sequence-sharded on
        their last dim when sequence parallelism is on."""
        if mesh is None:
            return clients_shard
        vec = P(axis)
        rep = P()
        if seq_axis is None:
            bspec: Any = vec
        else:
            bspec = {
                k: P(axis, *([None] * (v.ndim - 2)), seq_axis)
                if k in cfg.seq_sharded_keys else vec
                for k, v in data_batch.items()
            }
        return shard_map(
            clients_shard,
            mesh=mesh,
            in_specs=(rep, vec, vec, vec, rep, bspec, rep, vec, vec),
            out_specs=(vec if server_shard else rep, vec, vec, rep, vec),
            check_vma=False,
        )

    def _maybe_rows(state_arr, ids, width):
        if state_arr is None:
            return jnp.zeros((width, 1), jnp.float32)  # inert placeholder
        return state_arr[ids]

    # ---- phase 1: client gradients -------------------------------------

    def client_step(ps_weights, client_states: ClientStates, model_state,
                    batch, lr, rng):
        ps_weights = _to_resident(ps_weights)
        ids = batch["client_ids"]
        W = ids.shape[0]
        worker_mask = batch["worker_mask"]
        data_batch = {k: v for k, v in batch.items()
                      if k not in ("client_ids", "worker_mask")}

        with scope("fed_client_compress"):
            vel_rows = _maybe_rows(client_states.velocities, ids, W)
            err_rows = _maybe_rows(client_states.errors, ids, W)
            stale_rows = _maybe_rows(client_states.weights, ids, W)
        with scope("fed_client_grad"):
            rngs = jax.random.split(rng, W)

        total, new_vel, new_err, new_model_state, metrics = _shard_clients(
            data_batch)(
            ps_weights, vel_rows, err_rows, stale_rows,
            model_state, data_batch, lr, rngs, worker_mask)

        # data-weighted average (reference fed_aggregator.py:332)
        with scope("fed_client_compress"):
            total_count = jnp.maximum(batch["mask"].sum(), 1.0)
            if server_shard:
                # keep the per-shard sums raw: the division happens after
                # the server phase's reduce, so Σ then ÷ matches the
                # replicated path's psum-then-÷ bit-for-bit
                gradient, count = total, total_count
            else:
                gradient, count = total / total_count, None

        ctx = RoundContext(gradient, ids, worker_mask, vel_rows, err_rows,
                           stale_rows, new_vel, new_err, count)
        return ctx, new_model_state, metrics

    # ---- phase 2: server update + state scatter ------------------------

    # Sharded server plane: one shard_map over the worker axis owns the
    # transmit reduce (fp32 psum/psum_scatter, or the int8 EF collective),
    # the per-shard server rule, and the update all-gather
    # (server.sharded_server_update). State specs: dense velocity/error
    # are dim-0-sharded slices; sketch tables are replicated (already
    # transmit-sized); the qres carry is per-chip (dim-0-sharded).
    _sharded_server = None
    if server_shard:
        from commefficient_tpu.federated.server import sharded_server_update

        _vec = P(axis)
        # per-axis carries (docs/multihost.md): a hierarchically lowered
        # leg's carry is a TUPLE of per-axis slots — uplink slots all
        # stacked over dim 0 (P(axis)); downlink slot j sharded over axes
        # 0..j only (replicated over the axes already gathered when its
        # level runs). None slots (fp32 levels) are empty pytree nodes on
        # both sides, so the spec trees match the state trees.
        _qres_spec, _dres_spec = _vec, _vec
        if lowering is not None:
            up_low = lowering["table"] if scfg.mode == "sketch" \
                else lowering["uplink"]
            if isinstance(up_low, tuple):
                _qres_spec = tuple(_vec if dt != "float32" else None
                                   for _, dt in up_low)
            if isinstance(lowering["downlink"], tuple):
                _dres_spec = tuple(
                    P(tuple(axis_names[: j + 1])) if dt != "float32"
                    else None
                    for j, (_, dt) in enumerate(lowering["downlink"]))
        _state_spec = ServerState(
            velocity=P() if scfg.mode == "sketch" else _vec,
            error=P() if scfg.mode == "sketch" else _vec,
            qres=_qres_spec, dres=_dres_spec)

        def _sharded_inner(g, st, lr_, rng_, count_):
            return sharded_server_update(
                g[0], st, scfg, lr_, count_, axis=axis, n_shard=n_shard,
                sketch=sketch, layout=layout, rng=rng_, plan=plan,
                lowering=lowering)

        def _sharded_server(grad_stacked, server_state, lr_, rng_, count_):
            return shard_map(
                _sharded_inner, mesh=mesh,
                in_specs=(_vec, _state_spec, P(), P(), P()),
                out_specs=(P(), _state_spec, P()),
                check_vma=False,
            )(grad_stacked, server_state, jnp.asarray(lr_), rng_, count_)

    def _manual(f, in_specs, out_specs=P()):
        """``f`` inside a shard_map over the whole mesh. The server phase
        dispatches to Pallas kernels on TPU, and XLA's SPMD partitioner
        refuses a Mosaic custom call outside a manual region ("Mosaic
        kernels cannot be automatically partitioned") — so on a
        multi-device mesh every kernel call site of the phase is a manual
        region: per device on replicated data (what the partitioner does
        with the XLA path anyway), or on the device's own client rows."""
        if mesh is None or mesh.size == 1:
            return f
        return shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def _on_every_device(f, *args):
        return _manual(f, (P(),) * len(args))(*args)

    def server_step(ps_weights, server_state: ServerState,
                    client_states: ClientStates, ctx: RoundContext, lr, rng):
        flat_caller = chunked and ps_weights.ndim == 1
        ps_weights = _to_resident(ps_weights)
        if chunked and jnp.ndim(lr) == 1:
            # per-coordinate LR from a legacy flat caller rides the resident
            # layout like every other (d,)-shaped server value
            lr = layout.chunk(lr)
        # fedavg applies lr on-worker; server sees lr=1
        # (reference fed_aggregator.py:441-451)
        eff_lr = 1.0 if wcfg.mode == "fedavg" else lr
        resketched = None
        if server_shard:
            update, new_server_state, resketched = _sharded_server(
                ctx.gradient, server_state, eff_lr, rng, ctx.count)
        else:
            update, new_server_state = _on_every_device(
                lambda g, st, lr_, rng_: server_update(
                    g, st, scfg, lr_, sketch=sketch, rng=rng_,
                    layout=layout),
                ctx.gradient, server_state, jnp.asarray(eff_lr), rng)
        with scope("fed_server_apply"):
            new_ps = ps_weights - update

            # On-device health guard (--guards, docs/fault_tolerance.md): one
            # scalar verdict gates the WHOLE state transition. A select against
            # the pre-round state (never arithmetic like `update * ok` — a NaN
            # times zero is still NaN) makes a tripped round a no-op: weights,
            # server (velocity, error, qres) and every client-state scatter
            # below keep their pre-round values, so the poisoned contribution
            # is discarded rather than telescoped through error feedback.
            guard_ok = None
            if cfg.guards:
                guard_ok = round_health(ctx.gradient, new_ps,
                                        cfg.guard_max_abs)
                new_ps = jnp.where(guard_ok, new_ps, ps_weights)
                new_server_state = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(guard_ok, new, old),
                    new_server_state, server_state)

        ids = ctx.ids

        # Server-side masking of client state, fused into the scatter:
        # - true_topk: momentum factor masking of local velocities at the
        #   global top-k coords (reference fed_aggregator.py:525-533);
        # - sketch: error feedback and momentum masking of the participating
        #   clients' *sketch-space* state tables at the nonzero cells of the
        #   re-sketched update — the sketch-space analogue of the server's
        #   own Verror/Vvelocity cell masking (reference
        #   fed_aggregator.py:592-611). The reference allocates table-shaped
        #   per-client state (fed_aggregator.py:116-120) but its worker
        #   asserts leave the path dead (fed_worker.py:228-236); this is the
        #   working completion of that design.
        keep_vel = keep_err = None
        if wcfg.mode == "true_topk" and wcfg.local_momentum > 0:
            with scope("fed_server_apply"):
                keep_vel = (update == 0).astype(jnp.float32)[None, :]
        elif wcfg.mode == "sketch" and (wcfg.has_velocity or wcfg.has_error):
            if resketched is not None and jnp.ndim(eff_lr) == 0:
                # sharded server: the psum'd partial re-sketch (of the
                # UNSCALED update) is already in hand; sketches are linear,
                # so scaling it by the scalar lr equals re-sketching the
                # scaled update — no replicated d-sized re-sketch. A
                # per-coordinate lr vector scales before the sketch, so
                # that case recomputes below.
                with scope("fed_server_apply"):
                    sketched_update = resketched * eff_lr
            else:
                resketch = sketch_chunks if chunked else sketch_vec
                with scope("fed_server_resketch"):
                    sketched_update = _on_every_device(
                        lambda u: resketch(sketch, u), update)
            with scope("fed_server_apply"):
                cell_keep = (sketched_update == 0).astype(
                    jnp.float32)[None]
            keep_vel = keep_err = cell_keep

        # One delta-scatter per state array writes the masked new rows for
        # *participating* slots only. Padded slots carry a duplicate client
        # id (the loader pads with id 0) but have wmask 0, so they add delta
        # 0 while a real slot for the same id still lands its full value.
        with scope("fed_server_apply"):
            def scatter(state_arr, old_rows, new_rows, keep):
                if state_arr is None:
                    return None
                final = new_rows if keep is None else new_rows * keep
                w = ctx.wmask.reshape((-1,) + (1,) * (old_rows.ndim - 1))
                delta = (final - old_rows) * w
                if guard_ok is not None:
                    # quarantined round: every participating row keeps its
                    # pre-round state (select, not multiply — NaN rows)
                    delta = jnp.where(guard_ok, delta, jnp.zeros_like(delta))
                return state_arr.at[ids].add(delta)

            cs = ClientStates(
                velocities=scatter(client_states.velocities, ctx.vel_rows,
                                   ctx.new_vel, keep_vel),
                errors=scatter(client_states.errors, ctx.err_rows, ctx.new_err,
                               keep_err),
                weights=client_states.weights,
            )
            # topk-down: participating clients' stale weights advance to the
            # weights they actually used this round. wmask gates the delta like
            # the velocity/error scatters above: a padded slot (the loader pads
            # with client id 0, wmask 0) or a --client_dropout-zeroed slot must
            # not advance its client's stale weights — and a padded slot
            # duplicating a real slot's id would otherwise land the SAME delta
            # twice (2*used - stale instead of used).
            if wcfg.do_topk_down and cs.weights is not None:
                used = _manual(
                    lambda w, rows: jax.vmap(lambda s: get_new_worker_weights(
                        w, s, wcfg.k, True))(rows),
                    (P(), P(axis)), P(axis))(ps_weights, ctx.stale_rows)
                w = ctx.wmask.reshape(-1, 1)
                stale_delta = (used - ctx.stale_rows) * w
                if guard_ok is not None:
                    # a quarantined round is discarded end to end — its clients'
                    # stale weights must not advance either
                    stale_delta = jnp.where(guard_ok, stale_delta,
                                            jnp.zeros_like(stale_delta))
                cs = cs._replace(weights=cs.weights.at[ids].add(stale_delta))
        # Zero-sync telemetry (cfg.telemetry, docs/observability.md): one
        # fixed-schema device vector of round metrics, computed AFTER the
        # guard select so a quarantined round's metrics show exactly what
        # tripped (non-finite transmit/update norms) while the carried
        # state norms show the preserved pre-round values. Reductions
        # only — the state transition above is untouched.
        tel = None
        if cfg.telemetry:
            from commefficient_tpu.telemetry import device_round_metrics

            tel = device_round_metrics(ctx.gradient, update, new_ps,
                                       new_server_state, guard_ok=guard_ok,
                                       hists=cfg.telemetry_hist)
        if flat_caller:
            with scope("fed_server_apply"):
                new_ps = layout.unchunk(new_ps)
        ret = (new_ps, new_server_state, cs)
        if cfg.guards:
            ret += (guard_ok,)
        if cfg.telemetry:
            ret += (tel,)
        return ret

    # ---- fused round (bench / dry-run path) ----------------------------

    def train_step(ps_weights, server_state, client_states, model_state,
                   batch, lr, rng):
        flat_caller = chunked and ps_weights.ndim == 1
        ps_weights = _to_resident(ps_weights)
        rng, sub = jax.random.split(rng)
        ctx, new_model_state, metrics = client_step(ps_weights, client_states,
                                                    model_state, batch, lr,
                                                    rng)
        out = server_step(ps_weights, server_state, client_states, ctx, lr,
                          sub)
        new_ps, new_server_state, cs = out[:3]
        if flat_caller:
            new_ps = layout.unchunk(new_ps)
        # guard verdict and/or telemetry vector ride along as trailing
        # elements in server_step's order (guard first, then telemetry)
        return (new_ps, new_server_state, cs, new_model_state,
                metrics) + tuple(out[3:])

    @jax.named_scope("fed_val")
    def val_step(ps_weights, model_state, batch):
        def _val(w, ms, b):
            w_flat = layout.unchunk(w) if (chunked and w.ndim != 1) else w
            _, metrics, _, _ = forward_grad(
                compute_loss_val, w_flat, unravel, ravel, ms, b,
                jax.random.key(0), wcfg, sketch, compute_grad=False)
            return metrics

        if mesh is not None and seq_axis is not None:
            # val batches are flat (no client axis); shard the sequence dim
            # over the seq axis and replicate everything else. The loss psums
            # its token sums over seq, so the metrics come back replicated.
            bspec = {
                k: P(*([None] * (v.ndim - 1)), seq_axis)
                if k in cfg.seq_sharded_keys else P()
                for k, v in batch.items()
            }
            sharded = shard_map(_val, mesh=mesh, in_specs=(P(), P(), bspec),
                                out_specs=P(), check_vma=False)
            return sharded(ps_weights, model_state, batch)
        if mesh is not None and (wcfg.model_axis is not None
                                 or wcfg.pp_axis is not None
                                 or wcfg.expert_axis is not None):
            # tensor-/pipeline-/expert-parallel model: the apply must run
            # inside a shard_map that binds the axis; everything is
            # replicated, the internal psums make the outputs replicated too
            sharded = shard_map(_val, mesh=mesh, in_specs=(P(), P(), P()),
                                out_specs=P(), check_vma=False)
            return sharded(ps_weights, model_state, batch)
        return _val(ps_weights, model_state, batch)

    # Donation keeps PS state in place across rounds instead of copying the
    # d-sized (124M-element on GPT-2) buffers every round:
    #   - ps_weights and the (num_clients, ·) client velocity/error/weight
    #     arrays are donated in the fused step — uniquely owned by the
    #     caller and rebound immediately;
    #   - the server (velocity, error) state is donated whenever the server
    #     rule cannot return two outputs backed by ONE buffer. Sketch mode
    #     with LOCAL error reassigns error = velocity AFTER the cell_nz
    #     masking (the torch aliasing of reference fed_aggregator.py:580) —
    #     two outputs aliasing a single buffer while two donated inputs
    #     stand by is an execute-time error, so that config keeps the
    #     copying path. error_type "none" is safe: its returned error is
    #     the PRE-mask velocity, a distinct value from the masked one;
    #   - ctx is never donated (same identical-outputs hazard on the
    #     passthrough rows), and ps_weights in the two-phase server_step is
    #     kept because the aggregator's download accounting holds references
    #     to past weight snapshots (fed_aggregator.py:178-194 semantics).
    # cfg.donate=False disables all of it — the donation-parity test pins
    # bit-identical results between the two.
    donate_ss = cfg.donate and not (
        scfg.mode == "sketch" and scfg.error_type == "local")
    train_donate = ((0, 1, 2) if donate_ss else (0, 2)) if cfg.donate else ()
    server_donate = ((1, 2) if donate_ss else (2,)) if cfg.donate else ()
    return FederatedSteps(
        train_step=jax.jit(train_step, donate_argnums=train_donate),
        client_step=jax.jit(client_step),
        server_step=jax.jit(server_step, donate_argnums=server_donate),
        val_step=jax.jit(val_step),
        layout=layout,
        client_sketch_path="leaf_groups" if leaf_groups else "flat",
        client_sketch_launches=len(leaf_plan) if leaf_groups else 0,
    )
