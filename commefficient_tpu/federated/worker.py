"""Client-side (worker) computation as pure, vmappable functions.

Functional re-design of the reference worker runtime (reference
fed_worker.py:14-335). Where the reference runs one OS process per GPU, each
looping over client batches with shared-memory state slices, here a client is
one lane of a ``vmap`` inside a ``shard_map`` shard — per-client state rows
are gathered/scattered by the round step (federated/rounds.py).

Semantics preserved (reference anchors):
- per-example-mean gradient × local batch size (fed_worker.py:184-190), so
  the cross-client sum is data-weighted;
- weight decay folded in as ``wd / num_workers × weights``
  (reference utils.py:254-259);
- local momentum ``v = g + m·v`` on the client's state row
  (fed_worker.py:193-195); local error ``e += v``, transmit ``e``
  (fed_worker.py:197-202);
- local_topk: transmit top-k, zero error and velocity at the transmitted
  coordinates (fed_worker.py:204-216);
- sketch mode transmits the count-sketch table of the weighted gradient
  (fed_worker.py:311-320). Local momentum and local error for sketch mode are
  carried **in sketch space**: the client's velocity/error rows are
  ``(r, c_pad)`` tables and the momentum/error recurrences below apply
  unchanged (sketches are linear, so ``v = g + m·v`` and ``e += v`` commute
  with sketching). This is the working completion of the reference's design —
  it allocates table-shaped per-client state for exactly this
  (fed_aggregator.py:116-120) but trailing asserts leave the path dead
  (fed_worker.py:228-236); the matching server-side cell masking lives in
  rounds.server_step;
- DP: clip to ``l2_norm_clip`` then add N(0, noise_multiplier²)·√num_workers
  noise in worker mode (fed_worker.py:304-309);
- ``max_grad_norm`` clipping, skipped in dense space for sketch mode where it
  is applied in sketch space via ``l2estimate`` (fed_worker.py:289-292,
  317-319);
- fedavg: ``num_fedavg_epochs`` of local SGD over ``fedavg_batch_size``
  chunks with per-step decay, transmitting (w₀ − w_final)·|client dataset|
  (fed_worker.py:61-113);
- microbatched gradient accumulation (fed_worker.py:256-270) via
  ``lax.scan``. Documented deviation: the reference's accumulated microbatch
  gradient is the *sum* of per-microbatch means (an inflation by num_iters
  that its clip compensates, fed_worker.py:266-292); we compute the exact
  per-example mean, which matches the reference whenever microbatching is
  off (its default).

The loss callback contract is
``compute_loss(params, model_state, microbatch, rng, train) ->
(loss_sum, metric_sums: tuple, count, new_model_state)`` where sums run over
*valid* (mask=1) examples only.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from commefficient_tpu.ops.clip import clip_by_l2
from commefficient_tpu.ops.sketch import (
    CountSketch,
    l2estimate,
    sketch_segments_accum,
    sketch_vec,
)
from commefficient_tpu.ops.topk import topk


@dataclass(frozen=True)
class WorkerConfig:
    mode: str
    error_type: str = "none"
    k: int = 0
    num_workers: int = 1
    weight_decay: float = 0.0
    local_momentum: float = 0.0
    microbatch_size: int = -1
    max_grad_norm: Optional[float] = None
    do_dp: bool = False
    dp_mode: str = "worker"
    l2_norm_clip: float = 1.0
    noise_multiplier: float = 0.0
    num_fedavg_epochs: int = 1
    fedavg_batch_size: int = -1
    fedavg_lr_decay: float = 1.0
    do_topk_down: bool = False
    # Sequence-parallel mesh axis (long-context extension; no reference
    # equivalent). When set, the round runs inside a shard_map whose mesh
    # has this axis, activations are sequence-sharded, and forward_grad
    # psums the dense gradient over it BEFORE any nonlinear transform
    # (clip/DP/topk/sketch/momentum), so every compression mode sees the
    # full gradient, replicated across seq shards.
    seq_axis: Optional[str] = None
    # Tensor-parallel mesh axis (Megatron-style, GPT-2 only; no reference
    # equivalent). Transformer blocks compute 1/nm of heads/hidden per
    # shard; the per-shard backward then yields slice-local gradients for
    # the sliced weights and replicated (identical) gradients for
    # everything else, so forward_grad reconciles with one psum followed
    # by a flat rescale mask (1 on sliced segments, 1/nm elsewhere) before
    # any nonlinear transform — every compression mode again sees the
    # full gradient, replicated across model shards.
    model_axis: Optional[str] = None
    # Pipeline-parallel mesh axis (GPipe-style, GPT-2 only; no reference
    # equivalent — parallel/pipeline.py). Each stage shard backpropagates
    # only its own layer range (plus embeddings on stage 0, heads on the
    # last stage), producing zero gradient segments elsewhere, so
    # forward_grad reconciles with ONE psum and no rescale — again before
    # any nonlinear transform, so every compression mode sees the full
    # gradient, replicated across stage shards.
    pp_axis: Optional[str] = None
    # Expert-parallel mesh axis (GShard/Switch-style MoE, GPT-2 only; no
    # reference equivalent — parallel/moe.py). Each shard computes only
    # its E/ne experts, so expert-sliced params get slice-local grads
    # (zero outside the slice) while the router and all dense params get
    # identical replicated grads; forward_grad reconciles with one psum +
    # a flat rescale mask (1 on expert segments, 1/ne elsewhere), exactly
    # the model_axis scheme.
    expert_axis: Optional[str] = None

    @property
    def has_velocity(self) -> bool:
        # client_velocities allocated iff local_momentum > 0
        # (reference fed_aggregator.py:127-129)
        return self.local_momentum > 0

    @property
    def has_error(self) -> bool:
        # client_errors allocated iff error_type == "local"
        # (reference fed_aggregator.py:116-126)
        return self.error_type == "local"


class ClientResult(NamedTuple):
    transmit: jax.Array  # (d,) dense or (r, c) table — weighted by batch count
    new_velocity: Optional[jax.Array]
    new_error: Optional[jax.Array]
    metrics: Tuple[jax.Array, ...]  # (loss_mean, *metric_means, count)


def microbatch_plan(B: int, microbatch_size: int):
    """``(mb, n_iters, pad)`` for splitting a B-example batch into equal
    microbatch slices (reference fed_worker.py:256-270 sizing; ≤ 0 means
    whole-batch)."""
    mb = B if microbatch_size <= 0 else min(microbatch_size, B)
    n_iters = -(-B // mb)
    return mb, n_iters, n_iters * mb - B


def split_microbatches(batch, mb: int, n_iters: int, pad: int,
                       example_dim: int = 0):
    """Reshape every batch leaf's example axis into ``(n_iters, mb)``
    zero-padded microbatch slices, with the scan axis moved to the front.
    Shared by the per-client scan (example_dim 0) and the fused-gradient
    round path (example_dim 1, leading client axis) so the two paths cannot
    drift."""
    def split(x):
        if pad:
            cfg = [(0, 0)] * x.ndim
            cfg[example_dim] = (0, pad)
            x = jnp.pad(x, cfg)
        x = x.reshape(x.shape[:example_dim] + (n_iters, mb)
                      + x.shape[example_dim + 1:])
        return jnp.moveaxis(x, example_dim, 0)

    return {k: split(v) for k, v in batch.items()}


def next_rng(key):
    """The per-microbatch rng protocol (``r, sub = split(r)``) — one shared
    definition so the fused path's vmapped streams stay bitwise-identical to
    the per-client scan's."""
    ks = jax.random.split(key)
    return ks[0], ks[1]


def probe_n_metrics(compute_loss, params, model_state, example_batch) -> int:
    """Number of auxiliary metric sums the loss returns (eval_shape: no
    FLOPs)."""
    probe = jax.eval_shape(
        lambda: compute_loss(params, model_state, example_batch,
                             jax.random.key(0), True))
    return len(probe[1])


def sketch_grad_tree(sketch: CountSketch, table, grad_tree, segments, groups,
                     scales=None, decay=None):
    """Accumulate a gradient PYTREE into a running count-sketch table —
    the sketch cells' replacement for ``sketch_vec(sketch,
    ravel(grad_tree))`` (docs/stream_sketch.md): every leaf lands at its
    global flat offset (ops/flat.leaf_segments), so the concatenated
    d-vector is never materialized. ``groups`` (an
    ``ops/flat.coalesce_segments`` plan partitioning the leaves) makes each
    run of adjacent leaves ONE accumulate launch
    (ops/sketch.sketch_segments_accum): one table row-block read + write
    per group, staging per group. Groups run in offset order, so per table
    cell the f32 adds continue the flat route's chunk-ordered fold —
    equal under ``==``, up to the sign of all-zero cells.

    Per leaf, before the group's staging: the cast to float32 (exact for
    bf16 leaves, matching the flat route's pad/convert), then ``scales``
    (optional, one float per leaf), the tp/ep grad-rescale value, a
    per-leaf constant of the flat rescale masks and exact under the psum
    reorder for power-of-two mesh axes. ``decay`` (optional, ``(coef,
    plane)`` with the resident ``(T, S, 128)`` weights) is added in each
    group's staging pass, ``g + coef · w`` read from the plane where it
    lies (ops/sketch.sketch_segments_accum) — the flat route's ``g_sum +
    coef · ps_weights`` element for element, so weight decay costs no
    pass of its own and keeps no leaf of the weights alive."""
    leaves = jax.tree_util.tree_leaves(grad_tree)
    assert len(leaves) == len(segments), (len(leaves), len(segments))
    assert scales is None or len(scales) == len(segments)

    def leaf_flat(i):
        leaf, seg = leaves[i], segments[i]
        assert int(leaf.size) == seg.size, (leaf.shape, seg)
        x = leaf.reshape(-1).astype(jnp.float32)
        if scales is not None and float(scales[i]) != 1.0:
            x = x * jnp.float32(scales[i])
        return x

    assert groups[0].start == 0 and groups[-1].stop == len(segments) \
        and all(a.stop == b.start for a, b in zip(groups[:-1], groups[1:])), \
        "groups must partition the leaf segments in order"
    for grp in groups:
        table = sketch_segments_accum(
            sketch, table, [leaf_flat(i) for i in range(grp.start, grp.stop)],
            grp.offset, decay=decay)
    return table


@jax.named_scope("fed_client_grad")
def _microbatch_grads(compute_loss, params, model_state, batch, rng,
                      cfg: WorkerConfig):
    """Per-example-mean gradient over the masked batch, accumulated over
    microbatches with ``lax.scan``. Returns (grad_pytree_mean, loss_mean,
    metric_means, count, new_model_state)."""
    B = batch["mask"].shape[0]
    mb, n_iters, pad = microbatch_plan(B, cfg.microbatch_size)
    stacked = split_microbatches(batch, mb, n_iters, pad)

    def loss_for_grad(p, mstate, micro, r):
        loss_sum, msums, count, new_state = compute_loss(p, mstate, micro, r,
                                                         True)
        return loss_sum, (msums, count, new_state)

    grad_fn = jax.value_and_grad(loss_for_grad, has_aux=True)

    def body(carry, micro):
        g_acc, loss_acc, m_acc, n_acc, mstate, r = carry
        r, sub = next_rng(r)
        (loss_sum, (msums, count, new_state)), g = grad_fn(params, mstate,
                                                           micro, sub)
        g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
        m_acc = tuple(a + m for a, m in zip(m_acc, msums))
        return (g_acc, loss_acc + loss_sum, m_acc, n_acc + count, new_state,
                r), None

    zeros_g = jax.tree_util.tree_map(jnp.zeros_like, params)
    n_metrics = probe_n_metrics(
        compute_loss, params, model_state,
        jax.tree_util.tree_map(lambda x: x[0], stacked))
    init = (zeros_g, jnp.zeros(()), tuple(jnp.zeros(()) for _ in range(n_metrics)),
            jnp.zeros(()), model_state, rng)
    (g_sum, loss_sum, m_sums, count, new_state, _), _ = jax.lax.scan(
        body, init, stacked)

    denom = jnp.maximum(count, 1.0)
    g_mean = jax.tree_util.tree_map(lambda x: x / denom, g_sum)
    return (g_mean, loss_sum / denom, tuple(m / denom for m in m_sums), count,
            new_state)


def _compress_grad(g_mean_tree, params_flat, ravel, rng, cfg: WorkerConfig,
                   sketch, tp_scale, ep_scale):
    """forward_grad's second half (the fed_client_compress stage): the
    mean-gradient pytree flattened, reconciled across the parallel axes,
    decayed, clipped, noised and — in sketch mode — sketched. Returns
    ``(dense grad, transmit)``."""
    grad = ravel(g_mean_tree)
    if cfg.seq_axis is not None:
        # per-shard partial gradients (each shard backpropagated its local
        # slice of the sequence) → full gradient, replicated over seq
        grad = jax.lax.psum(grad, cfg.seq_axis)
    if cfg.model_axis is not None:
        # sliced-weight segments: each shard holds its slice's grad, zero
        # elsewhere → psum reconstitutes; replicated segments: every shard
        # holds the full identical grad → psum overcounts by nm, fixed by
        # the 1/nm entries of tp_scale (see WorkerConfig.model_axis)
        grad = jax.lax.psum(grad, cfg.model_axis) * tp_scale
    if cfg.pp_axis is not None:
        # pipeline stages hold disjoint gradient segments (zero elsewhere);
        # one psum reassembles the full gradient (see WorkerConfig.pp_axis)
        grad = jax.lax.psum(grad, cfg.pp_axis)
    if cfg.expert_axis is not None:
        # expert-sliced segments assemble across shards; the replicated
        # rest is overcounted by ne, fixed by the 1/ne entries of ep_scale
        # (see WorkerConfig.expert_axis)
        grad = jax.lax.psum(grad, cfg.expert_axis) * ep_scale
    # weight decay (reference utils.py:254-259)
    if cfg.weight_decay != 0:
        grad = grad + (cfg.weight_decay / cfg.num_workers) * params_flat
    # dense-space max_grad_norm clip, not for sketch (fed_worker.py:289-292)
    if cfg.max_grad_norm is not None and cfg.mode != "sketch":
        grad = clip_by_l2(grad, cfg.max_grad_norm)
    # DP (fed_worker.py:304-309)
    if cfg.do_dp:
        grad = clip_by_l2(grad, cfg.l2_norm_clip)
        if cfg.dp_mode == "worker":
            rng, sub = jax.random.split(rng)
            noise = cfg.noise_multiplier * jax.random.normal(
                sub, grad.shape) * jnp.sqrt(float(cfg.num_workers))
            grad = grad + noise

    if cfg.mode == "sketch":
        table = sketch_vec(sketch, grad)
        if cfg.max_grad_norm is not None:
            # sketch-space clipping via l2estimate (fed_worker.py:317-319,
            # utils.py:305-313)
            table = clip_by_l2(table, cfg.max_grad_norm,
                               norm=l2estimate(table))
        g = table
    else:
        g = grad

    return grad, g


def forward_grad(compute_loss, params_flat, unravel, ravel, model_state,
                 batch, rng, cfg: WorkerConfig, sketch: Optional[CountSketch],
                 compute_grad: bool = True, tp_scale=None, ep_scale=None):
    """reference fed_worker.py:249-335 as a pure function.

    Returns (transmit_or_None, (loss_mean, *metric_means, count),
    new_model_state, dense_mean_grad)."""
    if not compute_grad:
        # validation: the caller's scope (rounds.val_step, fed_val) names it
        loss_sum, msums, count, new_state = compute_loss(
            unravel(params_flat), model_state, batch, rng, False)
        denom = jnp.maximum(count, 1.0)
        metrics = (loss_sum / denom,) + tuple(m / denom for m in msums) + (count,)
        return None, metrics, new_state, None

    with jax.named_scope("fed_client_grad"):
        params = unravel(params_flat)
    g_mean_tree, loss_mean, metric_means, count, new_state = _microbatch_grads(
        compute_loss, params, model_state, batch, rng, cfg)
    with jax.named_scope("fed_client_compress"):
        grad, g = _compress_grad(g_mean_tree, params_flat, ravel, rng, cfg,
                                 sketch, tp_scale, ep_scale)
    metrics = (loss_mean,) + metric_means + (count,)
    return g, metrics, new_state, grad


def local_step(compute_loss, params_flat, unravel, ravel, model_state,
               velocity, error, batch, rng, cfg: WorkerConfig,
               sketch: Optional[CountSketch],
               tp_scale=None, ep_scale=None) -> Tuple[ClientResult, Any]:
    """One client's training contribution (reference fed_worker.py:184-230)."""
    g, metrics, new_state, _ = forward_grad(
        compute_loss, params_flat, unravel, ravel, model_state, batch, rng,
        cfg, sketch, tp_scale=tp_scale, ep_scale=ep_scale)
    with jax.named_scope("fed_client_compress"):
        count = metrics[-1]
        # sum-of-example-gradients scaling (fed_worker.py:190); linear, so it
        # applies to sketch tables too
        g = g * count

        new_velocity, new_error = velocity, error
        if cfg.has_velocity:
            new_velocity = g + cfg.local_momentum * velocity
            carrier = new_velocity
        else:
            carrier = g
        if cfg.has_error:
            new_error = error + carrier
            to_transmit = new_error
        else:
            to_transmit = carrier

        if cfg.mode == "local_topk":
            to_transmit = topk(to_transmit, cfg.k)
            nz = to_transmit != 0
            if cfg.has_error:
                new_error = jnp.where(nz, 0.0, new_error)
            if cfg.has_velocity:
                new_velocity = jnp.where(nz, 0.0, new_velocity)

    return ClientResult(to_transmit, new_velocity, new_error, metrics), new_state


def fedavg_local(compute_loss, params_flat, unravel, ravel, model_state,
                 batch, rng, lr, cfg: WorkerConfig,
                 tp_scale=None, ep_scale=None) -> Tuple[ClientResult, Any]:
    """FedAvg local training (reference fed_worker.py:61-113): local SGD over
    chunked whole-client batch, transmit (w₀ − w_final)·dataset_size."""
    B = batch["mask"].shape[0]
    fbs, n_chunks, pad = microbatch_plan(B, cfg.fedavg_batch_size)
    chunks = split_microbatches(batch, fbs, n_chunks, pad)

    def grad_of(p_flat, mstate, chunk, r):
        def loss_fn(p, ms):
            loss_sum, msums, count, new_ms = compute_loss(unravel(p), ms,
                                                          chunk, r, True)
            return loss_sum, (msums, count, new_ms)

        (loss_sum, (msums, count, new_ms)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(p_flat, mstate)
        if cfg.seq_axis is not None:
            # each seq shard backpropagated its slice of the sequence
            g = jax.lax.psum(g, cfg.seq_axis)
        if cfg.model_axis is not None:
            # reconcile sliced/replicated grads (see forward_grad) so the
            # local SGD weights stay replicated across model shards
            g = jax.lax.psum(g, cfg.model_axis) * tp_scale
        if cfg.pp_axis is not None:
            # disjoint stage-local gradient segments -> full gradient
            g = jax.lax.psum(g, cfg.pp_axis)
        if cfg.expert_axis is not None:
            # expert-sliced/replicated reconciliation (see forward_grad)
            g = jax.lax.psum(g, cfg.expert_axis) * ep_scale
        return g, loss_sum, msums, count, new_ms

    n_metrics = probe_n_metrics(
        compute_loss, unravel(params_flat), model_state,
        jax.tree_util.tree_map(lambda x: x[0], chunks))

    def body(carry, chunk):
        w, mstate, r, step, loss_acc, m_acc, n_steps = carry
        r, sub = next_rng(r)
        g, loss_sum, msums, count, new_ms = grad_of(w, mstate, chunk, sub)
        # average gradient over the chunk (fed_worker.py:96-98)
        g_mean = g / jnp.maximum(count, 1.0)
        decay = cfg.fedavg_lr_decay ** step
        # skip empty (all-padding) chunks
        valid = (count > 0).astype(jnp.float32)
        w = w - valid * g_mean * lr * decay
        denom = jnp.maximum(count, 1.0)
        m_acc = tuple(a + valid * m / denom for a, m in zip(m_acc, msums))
        return (w, new_ms, r, step + valid, loss_acc + valid * loss_sum / denom,
                m_acc, n_steps + valid), None

    init = (params_flat, model_state, rng, jnp.zeros(()), jnp.zeros(()),
            tuple(jnp.zeros(()) for _ in range(n_metrics)), jnp.zeros(()))
    with jax.named_scope("fed_client_grad"):
        for _ in range(cfg.num_fedavg_epochs):
            (w, mstate, rng, step, loss_acc, m_acc, n_steps), _ = \
                jax.lax.scan(body, init, chunks)
            init = (w, mstate, rng, step, loss_acc, m_acc, n_steps)
    w, mstate, _, _, loss_acc, m_acc, n_steps = init

    count = batch["mask"].sum()
    # weight the delta by client dataset size (fed_worker.py:104-108)
    with jax.named_scope("fed_client_compress"):
        transmit = (params_flat - w) * count
    denom = jnp.maximum(n_steps, 1.0)
    metrics = (loss_acc / denom,) + tuple(m / denom for m in m_acc) + (count,)
    return ClientResult(transmit, None, None, metrics), mstate


def get_new_worker_weights(ps_weights, worker_weights, k, do_topk_down):
    """topk-down stale-weight reconstruction (reference fed_worker.py:232-247)."""
    diff = ps_weights - worker_weights
    update = topk(diff, k) if do_topk_down else diff
    return worker_weights + update
