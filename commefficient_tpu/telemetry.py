"""Zero-sync telemetry plane: on-device round metrics, the structured run
event log, and round-lifecycle spans (docs/observability.md).

The engine pipelines, shards, fuses, and quarantines rounds (PRs 1-5), but
until this module the only windows into a *running* federation were offline
XLA profile captures and whatever a timing script printed — guard verdicts,
error-feedback carry norms, compression behavior, and per-collective wire
bytes were invisible at runtime. That is exactly the gap the FL
practicality survey (arXiv:2405.20431) flags for real deployments with
stragglers and dropout, and the prerequisite for the per-leg
{dtype x collective} auto-tuner (ROADMAP item 3 — the tuner needs measured
bytes per leg, in the spirit of Konecny's uplink/downlink accounting,
arXiv:1610.05492).

The hard constraint is PR 1's invariant: ZERO blocking device-to-host
fetches per steady-state round. Telemetry therefore has three strictly
separated layers:

1. **On-device metrics** (``device_round_metrics``): a fixed-schema vector
   of f32 scalars computed INSIDE the jitted server phase
   (``rounds.server_step`` under ``RoundConfig.telemetry``) — norms of the
   aggregated transmit, the emitted update, and the post-round server
   carries (velocity / error / qres), the resolved top-k threshold, and
   the guard verdict detail. All are cheap reductions over planes the
   epilogue already reads; the result is ONE ``(len(METRIC_FIELDS),)``
   device array that rides the round handle exactly like
   ``RoundHandle.guard`` does (attached by ``seal_round``) and
   materializes with the engine's batched drain. The fp32 trajectory is
   bit-identical with telemetry on or off, pinned in
   tests/test_telemetry.py on both server planes.

2. **Host-side spans** (``RunTelemetry``): the durations of the program's
   own ``profiling.annotate`` spans — ``fed_round`` (dispatch),
   ``fed_window_wait``, ``fed_h2d``, ``fed_input_wait``, ``fed_drain`` —
   plus in-flight-window occupancy at dispatch. The recorder stamps no
   clock of its own: the engine hands it each span as it closes, and the
   spans of other modules are read as differences of
   ``profiling.SPAN_TOTALS``. Buffered in memory per round; nothing is
   written until the round drains, so the dispatch path stays
   allocation-cheap and fetch-free. These are the host numbers of a run
   whose profiler was never on.

3. **The JSONL event log**: one line per drained round (spans + metrics +
   loss + guard verdict), plus immediate lines for run_start / setup /
   program / val / guard_trip / rollback / guard_fatal / checkpoint /
   epoch / drain / run_end (``setup``, ``program``, ``val`` and the
   memory samples of ``drain`` and ``run_end`` are the run's record of its
   own start-up and device memory, profiling.py).
   ``scripts/obs_report.py`` renders a run summary (timeline, compression
   ledger, guard/rollback history) and a machine-readable tail from the
   log alone.

``collective_ledger`` is the static half of the byte accounting: the
per-round payload of every wire leg (transmit reduce, update all-gather,
threshold exchange, per-client uplink), computed from the config the same
way ``ops/collectives.py`` shapes its payloads — logged once in the
run_start event so obs_report can price a run without re-deriving collective
internals.

The CONTINUOUS half (docs/observability.md: "what is happening", not
"what happened") rides the same three layers: schema v3 appends fixed-K
log-magnitude histograms of the emitted update and the error carry to
the jitted metrics vector (``log_magnitude_histogram``, gated by
``RoundConfig.telemetry_hist``), and ``WatchEngine`` evaluates
declarative threshold + EWMA-drift rules over each DRAINED round record
(``RunTelemetry.on_drained``) — host arithmetic on already-materialized
values, zero extra syncs — emitting immediate ``watch_alert`` events
with a log / trace-next-N-rounds (``profiling.RoundTracer``) /
force-checkpoint reaction ladder.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from typing import (
    Any, Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple,
)

import jax
import jax.numpy as jnp

from commefficient_tpu.profiling import (
    PROGRAM_LOG_S,
    SPAN_TOTALS,
    annotate,
    memory_sample,
    program_summary,
    span_totals,
    subscribe_programs,
    unsubscribe_programs,
)

__all__ = [
    "METRIC_FIELDS",
    "HIST_BINS",
    "HIST_LO",
    "HIST_STEP",
    "metric_schema",
    "log_magnitude_histogram",
    "device_round_metrics",
    "collective_ledger",
    "RunTelemetry",
    "attach_run_telemetry",
    "read_events",
    "WatchRule",
    "WatchEngine",
    "parse_watch_rules",
    "DEFAULT_WATCH_RULES",
]


# The fixed on-device metric schema, in stack order. Fixed so the drained
# vector's meaning never depends on mode/config branches: fields that do
# not apply to a config (e.g. qres_norm without --reduce_dtype int8) are
# 0.0, never absent.
#
#   transmit_norm / transmit_max_abs — l2 / max|.| of the aggregated round
#     contribution the server consumed (the sketch table, or the dense
#     flat sum; under --server_shard the stacked pre-reduce shard sums,
#     the same view the health guard checks). A NaN/Inf here is the guard
#     verdict's "what tripped" detail.
#   update_norm / update_nnz — l2 and nonzero count of the emitted
#     (lr-scaled) weight update. For sketch/true_topk modes, update_nnz is
#     the RESOLVED k (radix-descent thresholds are >= k by ties).
#   topk_threshold — min nonzero |update|: the effective (lr-scaled)
#     magnitude threshold the round's top-k resolved to; 0 when the update
#     is all-zero (e.g. a quarantined round).
#   velocity_norm / error_norm — post-round server carries. error_norm IS
#     the sketch-estimation residual: the accumulated estimate energy the
#     threshold did not emit, carried forward by error feedback.
#   qres_norm — the quantized UPLINK collective's un-transmitted
#     quantization remainder (a quantized uplink/table plan leg, incl. the
#     legacy --reduce_dtype int8 alias; 0 otherwise).
#   ps_norm / ps_max_abs — the post-round weights (ps_max_abs is the
#     magnitude-guard quantity).
#   guard_ok — the round-health verdict as 1.0/0.0 (1.0 when --guards is
#     off: an unguarded round is presumed healthy).
#   dres_norm — the quantized DOWNLINK gather's un-transmitted remainder
#     (ServerState.dres, docs/compressed_collectives.md; 0 otherwise):
#     per-round visibility of compressed-downlink drift with zero new
#     host syncs. SCHEMA v2: appended as the LAST slot so v1 logs (11
#     fields) and v2 logs (12) disagree only in the tail — readers
#     (obs_report.py, aggregator.finish_round's zip) key fields by the
#     run_start schema list, so both versions parse.
#   update_hist_* / error_hist_* — SCHEMA v3 (the continuous-observability
#     PR): fixed-K log-magnitude histograms of the emitted update and the
#     post-round error carry, appended AFTER dres_norm so v1 (11-field)
#     and v2 (12-field) logs disagree only in the tail, exactly like the
#     v1→v2 append. Bin i of log_magnitude_histogram counts elements with
#     |x| in [10^(HIST_LO + i·HIST_STEP), 10^(HIST_LO + (i+1)·HIST_STEP))
#     — zeros excluded (update_nnz already carries them), underflow/
#     overflow clamped into the edge bins, non-finite values counted in
#     the LAST bin (a poisoned round's histogram shows its mass at the
#     top). Scalar norms cannot show threshold drift (the emitted-update
#     mass sliding toward the threshold bin) or sketch-estimation fidelity
#     decay (error-carry mass climbing bins); the histograms can, online,
#     and they are still pure reductions riding the same batched drain.
HIST_BINS = 8
HIST_LO = -12.0   # log10 of the first finite bin's lower edge
HIST_STEP = 2.0   # decades per bin: bins span 1e-12 .. 1e4
METRIC_FIELDS = (
    "transmit_norm",
    "transmit_max_abs",
    "update_norm",
    "update_nnz",
    "topk_threshold",
    "velocity_norm",
    "error_norm",
    "qres_norm",
    "ps_norm",
    "ps_max_abs",
    "guard_ok",
    "dres_norm",
) + tuple(f"update_hist_{i}" for i in range(HIST_BINS)) \
  + tuple(f"error_hist_{i}" for i in range(HIST_BINS))

# the scalar (pre-histogram) prefix — v2's schema, and the vector length
# when the histogram block is disabled (--no_telemetry_hist)
N_SCALAR_FIELDS = 12


def metric_schema(hists: bool = True) -> Tuple[str, ...]:
    """The ACTIVE metric schema of a run: the full v3 field tuple with the
    histogram block on, the 12-field v2 prefix without. run_start records
    this list verbatim and every reader keys metrics by name, which is the
    whole cross-version parse contract (v1/v2/v3 logs all render)."""
    return METRIC_FIELDS if hists else METRIC_FIELDS[:N_SCALAR_FIELDS]


def log_magnitude_histogram(x):
    """``(HIST_BINS,)`` f32 counts of ``|x|`` over fixed log10-magnitude
    bins (edges ``10**(HIST_LO + i*HIST_STEP)``). Zeros are excluded,
    under/overflow clamp into the edge bins, and non-finite elements land
    in the last bin. Pure device reductions: the counts are eight masked
    int32 sums, fused with the binning into one pass over ``x`` (nothing of
    ``x``'s size is written; a count is exact up to 2**31 elements and
    rounds to float32 only on the way out) — nothing feeds back into the
    state transition."""
    ax = jnp.abs(x.astype(jnp.float32)).reshape(-1)
    # != 0 (the update_nnz idiom), NOT > 0: NaN compares false under >
    # and a poisoned round's NaN elements must land in the last bin, not
    # silently vanish from the distribution
    nz = ax != 0
    # log10 of zeros would be -inf; substitute 1.0 (bin of it is discarded
    # by the nz mask below)
    e = (jnp.log10(jnp.where(nz, ax, 1.0)) - HIST_LO) / HIST_STEP
    idx = jnp.clip(jnp.floor(e), 0, HIST_BINS - 1).astype(jnp.int32)
    # non-finite |x| (a poisoned round): clip/floor of NaN is NaN and its
    # int cast is undefined — pin those elements to the last bin instead
    idx = jnp.where(jnp.isfinite(ax), idx, HIST_BINS - 1)
    # ONE reduction with eight int32 accumulators, not eight jnp.sums: the
    # TPU compiler fuses sibling sums into one pass by itself, the CPU's
    # writes idx and eight d-sized masks out first
    masks = [((idx == i) & nz).astype(jnp.int32) for i in range(HIST_BINS)]
    counts = jax.lax.reduce(
        masks, [jnp.int32(0)] * HIST_BINS,
        lambda acc, m: tuple(a + b for a, b in zip(acc, m)), (0,))
    return jnp.stack(counts).astype(jnp.float32)


@jax.named_scope("fed_telemetry_metrics")
def device_round_metrics(transmit, update, new_ps, state, guard_ok=None,
                         hists: bool = False):
    """The jit-side half: one ``(len(metric_schema(hists)),)`` f32 device
    vector from arrays the server phase already holds. Pure reductions —
    nothing here feeds back into the state transition, which is what makes
    the telemetry-on trajectory bit-identical to telemetry-off
    (tests/test_telemetry.py pins it on both server planes; the v3
    histogram block rides the same contract, tests/test_watch.py).

    ``hists`` appends the schema-v3 log-magnitude histogram block (the
    emitted update's and the post-round error carry's
    ``log_magnitude_histogram``) — online visibility into threshold drift
    and sketch-estimation fidelity that scalar norms cannot show."""

    def l2(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    def l2_carry(x):
        # EF carries may be per-mesh-axis TUPLES of level slots
        # (docs/multihost.md); one combined norm keeps the metric schema
        # fixed — and reduces to the old scalar on flat carries
        if x is None:
            return jnp.float32(0.0)
        if isinstance(x, tuple):
            sq = jnp.float32(0.0)
            for s in x:
                if s is not None:
                    sq = sq + jnp.sum(jnp.square(s.astype(jnp.float32)))
            return jnp.sqrt(sq)
        return l2(x)

    abs_u = jnp.abs(update.astype(jnp.float32))
    nz = abs_u != 0
    thr = jnp.min(jnp.where(nz, abs_u, jnp.inf))
    thr = jnp.where(jnp.isfinite(thr), thr, 0.0)
    vals = (
        l2(transmit),
        jnp.max(jnp.abs(transmit.astype(jnp.float32))),
        l2(update),
        jnp.sum(nz).astype(jnp.float32),
        thr,
        l2(state.velocity),
        l2(state.error),
        l2_carry(state.qres),
        l2(new_ps),
        jnp.max(jnp.abs(new_ps.astype(jnp.float32))),
        (guard_ok.astype(jnp.float32) if guard_ok is not None
         else jnp.float32(1.0)),
        l2_carry(state.dres),
    )
    out = jnp.stack([jnp.asarray(v, jnp.float32).reshape(()) for v in vals])
    if hists:
        out = jnp.concatenate([out, log_magnitude_histogram(update),
                               log_magnitude_histogram(state.error)])
    assert out.shape == (len(metric_schema(hists)),)
    return out


def collective_ledger(mode: str, grad_size: int, *,
                      sketch=None, n_shard: int = 0,
                      reduce_dtype: str = "float32",
                      k: int = 0, plan=None,
                      lowering=None, axis_sizes=None,
                      axis_placement=None) -> Dict[str, Dict[str, Any]]:
    """Static per-round wire-byte ledger, one entry per collective leg.

    Bytes are LOGICAL payload per chip per round, priced by THE one
    formula the collectives themselves implement
    (``ops.collectives.payload_bytes``: element payload at the leg's wire
    dtype + per-block f32 scales, nibble packing for int4) — so the
    accounting and the collectives can never disagree on any dtype's wire
    cost. Ring/all-to-all topology factors are deliberately excluded so
    the numbers compare across mesh sizes. The runtime-dependent half of
    the accounting (per-client download bytes, which depend on staleness)
    stays in the aggregator's device-resident accounting and is reported
    per round by the training loops; this ledger prices the fixed legs,
    Konecny-style (arXiv:1610.05492: uplink and downlink accounted
    separately).

    ``plan`` (an ``ops.collectives.CollectivePlan``) prices each leg at
    its planned wire dtype — the exact blocks the collectives use at
    runtime (table: one scale per (c_pad,) row; downlink sketch: one per
    (S, 128) chunk; dense: DEFAULT_QUANT_BLOCK). ``reduce_dtype`` is the
    legacy alias used when ``plan`` is None.

    ``lowering`` (``{leg: resolve_leg_lowering(...)}``, docs/multihost.md)
    splits a per-MESH-AXIS leg's bytes per level: the entry gains a
    ``bytes_per_axis`` map ({axis: {dtype, elements, bytes_per_round,
    placement}}) priced by the same ``payload_bytes`` formula at each
    level's real input size — the hierarchical scatter/gather levels
    shrink/grow by each already-reduced axis (``axis_sizes``), the table
    all-reduce keeps the full table at every level. ``axis_placement``
    (``mesh_axis_placement(mesh)``) labels each axis ici/dcn so
    obs_report can render the cross-host vs intra-host wire split.
    """
    from commefficient_tpu.ops.collectives import (
        DEFAULT_QUANT_BLOCK,
        payload_bytes,
        plan_from_reduce_dtype,
    )

    if plan is None:
        plan = plan_from_reduce_dtype(reduce_dtype)
    d = int(grad_size)
    ledger: Dict[str, Dict[str, Any]] = {}

    def leg(name, collective, elems, dtype, block=DEFAULT_QUANT_BLOCK):
        if dtype != "float32":
            collective = f"{collective} ({dtype}+scales)"
        ledger[name] = {"collective": collective, "elements": int(elems),
                        "dtype": dtype,
                        "bytes_per_round": int(payload_bytes(int(elems),
                                                             dtype, block))}

    def leg_low(name):
        # the leg's per-axis lowering tuple, or None for flat legs
        key = {"transmit_reduce": "table" if mode == "sketch" else "uplink",
               "update_all_gather": "downlink"}[name]
        low = (lowering or {}).get(key)
        return low if isinstance(low, tuple) else None

    def per_axis_leg(name, collective, elems, low,
                     block=DEFAULT_QUANT_BLOCK, shrink=False):
        # one hierarchical collective = one wire level per mesh axis, in
        # reduce order; ``shrink`` models the scatter/gather level sizes
        # (level j moves the tile already divided by the earlier axes),
        # the table all-reduce moves the full table at every level
        per_axis = {}
        total, seen = 0, 1
        for ax, dt in low:
            lvl = int(elems) // seen if shrink else int(elems)
            b = int(payload_bytes(lvl, dt, block))
            per_axis[ax] = {
                "dtype": dt, "elements": lvl, "bytes_per_round": b,
                "placement": (axis_placement or {}).get(ax, "ici")}
            total += b
            if shrink:
                assert axis_sizes is not None, \
                    "per-axis ledger needs axis_sizes={axis: size}"
                seen *= int(axis_sizes[ax])
        ledger[name] = {
            "collective": f"{collective} (per-axis)",
            "elements": int(elems),
            "dtype": "/".join(f"{ax}:{dt}" for ax, dt in low),
            "bytes_per_round": total,
            "bytes_per_axis": per_axis}

    # per-client uplink: what one participating client logically transmits
    # (mirrors aggregator._account_bytes_deferred's upload accounting)
    if mode == "sketch":
        table_elems = sketch.r * sketch.c_pad if sketch is not None else 0
        c_pad = sketch.c_pad if sketch is not None else None
        leg("client_uplink", "transmit", table_elems, "float32")
        if leg_low("transmit_reduce") is not None:
            per_axis_leg("transmit_reduce", "hierarchical_psum",
                         table_elems, leg_low("transmit_reduce"),
                         block=c_pad)
        elif plan.table != "float32":
            leg("transmit_reduce", "quantized_psum", table_elems,
                plan.table, block=c_pad)
        else:
            leg("transmit_reduce", "psum", table_elems, "float32")
    else:
        per_client = k if mode == "local_topk" else d
        leg("client_uplink", "transmit", per_client, "float32")
        d_pad = -(-d // n_shard) * n_shard if n_shard else d
        if n_shard and leg_low("transmit_reduce") is not None:
            per_axis_leg("transmit_reduce", "hierarchical_psum_scatter",
                         d_pad, leg_low("transmit_reduce"), shrink=True)
        elif n_shard and plan.uplink != "float32":
            leg("transmit_reduce", "quantized_psum_scatter", d_pad,
                plan.uplink)
        elif n_shard:
            leg("transmit_reduce", "psum_scatter", d_pad, "float32")
        else:
            leg("transmit_reduce", "psum", d, "float32")

    if n_shard:
        # downlink half of the sharded plane: the update all-gather
        # (Konecny's other direction — quantized per the plan's downlink
        # leg, with the remainder carried in ServerState.dres;
        # docs/compressed_collectives.md)
        if mode == "sketch" and sketch is not None:
            # the sharded sketch server gathers update CHUNKS: ceil(T/n)
            # chunks per shard x n shards of (S, 128) each
            up_elems = (-(-sketch.T // n_shard) * n_shard
                        * sketch.sublanes * 128)
            down_block = sketch.sublanes * 128
        else:
            up_elems = -(-d // n_shard) * n_shard
            down_block = DEFAULT_QUANT_BLOCK
        if leg_low("update_all_gather") is not None:
            per_axis_leg("update_all_gather", "hierarchical_all_gather",
                         up_elems, leg_low("update_all_gather"),
                         block=down_block, shrink=True)
        elif plan.downlink != "float32":
            leg("update_all_gather", "quantized_all_gather", up_elems,
                plan.downlink, block=down_block)
        else:
            leg("update_all_gather", "all_gather", up_elems, "float32")
        if mode in ("sketch", "true_topk"):
            # the radix descent's psum'd count exchange: 16 s32 candidates
            # per pass, ~8 passes (ops/topk.py) — negligible (and not a
            # payload_bytes wire dtype), listed so the ledger is complete
            ledger["threshold_exchange"] = {
                "collective": "psum (count exchange)",
                "elements": 16 * 8, "dtype": "int32",
                "bytes_per_round": 4 * 16 * 8}
    return ledger


def _json_safe(x):
    """Non-finite floats as the strings ``'nan'``/``'inf'``/``'-inf'``
    (``float()`` round-trips them), recursively. A poisoned round's NaN
    norms are real data the log must carry, but ``json.dumps`` would emit
    them as bare ``NaN`` tokens — not RFC-8259 JSON, rejected by jq and
    every strict consumer the JSONL format exists for."""
    if isinstance(x, float) and not math.isfinite(x):
        return repr(x)
    if isinstance(x, dict):
        return {k: _json_safe(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_json_safe(v) for v in x]
    return x


# --------------------------------------------------------------------------
# Watch / alert rule engine (--watch, docs/observability.md §watch plane)
# --------------------------------------------------------------------------

class WatchRule(NamedTuple):
    """One declarative watch rule over the drained metric stream.

    Spec grammar (one rule; rules join with ','):

        METRIC OP BOUND [@N] [->ACTION]

    - ``METRIC``: a metric-schema field name, a round-record span key
      (``loss``, ``occupancy``, ``dispatch_ms``, ``compute_ms``,
      ``drain_fetch_ms``), or a derived stream quantity
      (``rounds_per_sec`` from successive dispatch stamps,
      ``prefetch_miss`` — 1.0 when the round's offload span records a
      prefetch miss).
    - ``OP``: ``>`` or ``<``.
    - ``BOUND``: a float threshold, or ``ewma*F`` — F times the rule's own
      exponentially weighted moving average of the metric's history
      (drift detection; armed only after ``WATCH_WARMUP`` observations).
    - ``@N``: require N CONSECUTIVE violating rounds before firing
      (default 1) — slow divergence is a streak, one noisy round is not.
    - ``->ACTION``: the reaction ladder — ``log`` (default; the
      ``watch_alert`` JSONL event every alert emits), ``trace[:R]``
      (additionally request a windowed trace capture of the next R rounds
      — default WATCH_TRACE_ROUNDS — through the attached
      profiling.RoundTracer), or ``checkpoint`` (additionally request a
      run-state checkpoint; the training loop services it at the next
      round boundary).

    A non-finite observed value violates ANY rule on its metric (NaN/Inf
    is never healthy; NaN compares false against every bound, so this is
    explicit)."""

    metric: str
    op: str                      # '>' | '<'
    bound: float                 # absolute threshold (ewma_factor == 0)
    ewma_factor: float           # > 0: bound = factor * EWMA(history)
    consecutive: int
    action: str                  # 'log' | 'trace' | 'checkpoint'
    trace_rounds: int
    spec: str                    # the source text, logged verbatim


WATCH_WARMUP = 5          # observations before an EWMA bound arms
WATCH_EWMA_ALPHA = 0.25   # EWMA update weight of the newest observation
WATCH_COOLDOWN = 8        # rounds a fired rule stays silent
WATCH_TRACE_ROUNDS = 3    # default trace-reaction window length

# The default rule set — the runtime failure modes the continuous-
# observability PR names (docs/observability.md): loss divergence, the
# what-tripped transmit blowup, EF-carry blowup (error/qres/dres),
# resolved-k (threshold) collapse, in-flight occupancy drop, prefetch
# miss storms, and host rounds/sec regression. Absolute budgets (e.g. a
# rounds/sec floor) go in --watch_rules. The io_* /
# worker_queue_age rules are the storage-fault ladder's watch rungs
# (docs/fault_tolerance.md §storage faults): a retry storm logs, an
# exhausted op (= a row quarantine or the terminal rung approaching)
# forces the drain-first resumable checkpoint, a queue-age blowup traces
# the rounds where the disk fell behind.
DEFAULT_WATCH_RULES = (
    "loss>ewma*4@2->trace",
    "transmit_norm>ewma*10->trace",
    "error_norm>ewma*8@3",
    "qres_norm>ewma*8@3",
    "dres_norm>ewma*8@3",
    "update_nnz<ewma*0.25@2",
    "occupancy<ewma*0.5@4",
    "prefetch_miss>0.5@8",
    "rounds_per_sec<ewma*0.5@4",
    "io_retry>ewma*8@3",
    "io_error>0.5->checkpoint",
    "worker_queue_age>ewma*8@4->trace",
    # integrity-plane rungs (docs/fault_tolerance.md §silent corruption):
    # a gather-detected checksum mismatch was already repaired-or-
    # quarantined in line — log it; a SCRUB-found mismatch means
    # corruption is accumulating in cold rows, so force the drain-first
    # resumable checkpoint — the next snapshot must be taken from
    # repaired state, never inherit the rot
    "io_corrupt>0.5",
    "scrub_mismatch>0.5->checkpoint",
)


# every name a watch rule may observe: the full v3 metric schema, the
# round-record span keys, and the derived stream quantities — enumerable
# at parse time, so a typo'd metric fails AT STARTUP instead of silently
# never firing for the whole run. The io_retry/io_error/worker_queue_age
# trio reads the offload span's storage-fault counters (per-round deltas
# attached by the aggregator, docs/fault_tolerance.md §storage faults).
WATCH_METRIC_NAMES = frozenset(METRIC_FIELDS) | {
    "loss", "occupancy", "dispatch_ms", "compute_ms", "drain_fetch_ms",
    "dispatch_to_drain_ms", "rounds_per_sec", "prefetch_miss",
    "io_retry", "io_error", "worker_queue_age",
    "io_corrupt", "scrub_mismatch",
}

# watch-rule name -> the offload-span key carrying its per-round value
_IO_WATCH_KEYS = {"io_retry": "io_retries", "io_error": "io_errors",
                  "worker_queue_age": "queue_age_ms",
                  "io_corrupt": "io_corrupt",
                  "scrub_mismatch": "scrub_mismatch"}


def parse_watch_rules(spec: str) -> List[WatchRule]:
    """Parse a ','-joined rule spec (see WatchRule). Empty/whitespace
    entries are skipped; a malformed entry — including an unknown metric
    name — raises at parse time: config errors must fail at startup, not
    rounds into a run."""
    rules = []
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        body, action, trace_rounds = part, "log", WATCH_TRACE_ROUNDS
        if "->" in body:
            body, act = body.split("->", 1)
            act = act.strip()
            if act.startswith("trace"):
                action = "trace"
                if ":" in act:
                    trace_rounds = int(act.split(":", 1)[1])
                    assert trace_rounds >= 1, part
            elif act in ("log", "checkpoint"):
                action = act
            else:
                raise ValueError(
                    f"watch rule {part!r}: unknown action {act!r}; use "
                    "log | trace[:N] | checkpoint")
        consecutive = 1
        if "@" in body:
            body, n = body.rsplit("@", 1)
            consecutive = int(n)
            assert consecutive >= 1, part
        op = ">" if ">" in body else ("<" if "<" in body else None)
        if op is None:
            raise ValueError(
                f"watch rule {part!r}: expected METRIC>BOUND or "
                "METRIC<BOUND (BOUND a float or ewma*F)")
        metric, bound_s = (s.strip() for s in body.split(op, 1))
        assert metric, f"watch rule {part!r}: empty metric name"
        if metric not in WATCH_METRIC_NAMES:
            raise ValueError(
                f"watch rule {part!r}: unknown metric {metric!r}; known "
                f"names: {', '.join(sorted(WATCH_METRIC_NAMES))}")
        bound, factor = 0.0, 0.0
        if bound_s.startswith("ewma"):
            factor = (float(bound_s.split("*", 1)[1])
                      if "*" in bound_s else 1.0)
            assert factor > 0, f"watch rule {part!r}: ewma factor <= 0"
        else:
            bound = float(bound_s)
        rules.append(WatchRule(metric=metric, op=op, bound=bound,
                               ewma_factor=factor, consecutive=consecutive,
                               action=action, trace_rounds=trace_rounds,
                               spec=part))
    return rules


class _RuleState:
    __slots__ = ("ewma", "n", "consec", "cooldown_until", "fired")

    def __init__(self):
        self.ewma = 0.0
        self.n = 0
        self.consec = 0
        self.cooldown_until = -1
        self.fired = 0


class WatchEngine:
    """Evaluate watch rules over the drained metric stream, at ZERO extra
    host syncs: every value it reads is host data the batched drain
    already materialized (``RunTelemetry.on_drained`` calls ``observe``
    with the round record before JSON encoding). Alerts land as immediate
    ``watch_alert`` JSONL events; the trace reaction requests a windowed
    round-aligned capture through the attached ``profiling.RoundTracer``,
    the checkpoint reaction raises ``checkpoint_pending`` for the training
    loop — the same escalation design as the guard ladder
    (docs/fault_tolerance.md), but for SLOW failure modes the binary
    finiteness guard cannot see."""

    def __init__(self, rules: Sequence[WatchRule], telemetry=None,
                 tracer=None):
        self.rules = list(rules)
        self._rt = telemetry
        self.tracer = tracer
        self._state = [_RuleState() for _ in self.rules]
        self._last_dispatch_t: Optional[float] = None
        self.alerts = 0
        self.fired: List[Tuple[int, str]] = []   # (round, rule spec)
        self.checkpoint_pending = False

    def pop_checkpoint(self) -> bool:
        """True once per pending checkpoint request (the training loop
        polls this at round boundaries and forces a run-state save)."""
        pending, self.checkpoint_pending = self.checkpoint_pending, False
        return pending

    # -- the per-round evaluation ----------------------------------------

    def _value(self, rec: Dict[str, Any], name: str):
        metrics = rec.get("metrics") or {}
        if name in metrics:
            return metrics[name]
        if name in ("loss", "occupancy", "dispatch_ms", "compute_ms",
                    "drain_fetch_ms", "dispatch_to_drain_ms"):
            return rec.get(name)
        if name == "prefetch_miss":
            off = rec.get("offload")
            if not off or "prefetch" not in off:
                return None
            return 1.0 if off["prefetch"] == "miss" else 0.0
        if name in _IO_WATCH_KEYS:
            off = rec.get("offload")
            if not off:
                return None
            return off.get(_IO_WATCH_KEYS[name])
        if name == "rounds_per_sec":
            return rec.get("_rounds_per_sec")
        return None

    def observe(self, rec: Dict[str, Any]) -> None:
        """Evaluate every rule against one drained round record."""
        round_no = rec.get("round", -1)
        # derived stream quantity: host rounds/sec from successive
        # dispatch wall stamps (batched drains deliver per-round stamps)
        t_disp = rec.get("t_dispatch")
        if t_disp is not None:
            if self._last_dispatch_t is not None \
                    and t_disp > self._last_dispatch_t:
                rec["_rounds_per_sec"] = 1.0 / (t_disp
                                                - self._last_dispatch_t)
            self._last_dispatch_t = t_disp
        for rule, st in zip(self.rules, self._state):
            raw = self._value(rec, rule.metric)
            if raw is None or isinstance(raw, bool):
                continue
            try:
                v = float(raw)
            except (TypeError, ValueError):
                continue
            finite = math.isfinite(v)
            if rule.ewma_factor > 0:
                armed = st.n >= WATCH_WARMUP
                bound = rule.ewma_factor * st.ewma
                if finite:
                    st.ewma = (v if st.n == 0 else
                               (1 - WATCH_EWMA_ALPHA) * st.ewma
                               + WATCH_EWMA_ALPHA * v)
                    st.n += 1
                if not armed:
                    continue
            else:
                bound = rule.bound
            violated = (not finite) or (v > bound if rule.op == ">"
                                        else v < bound)
            if round_no <= st.cooldown_until:
                continue
            if not violated:
                st.consec = 0
                continue
            st.consec += 1
            if st.consec < rule.consecutive:
                continue
            self._fire(rule, st, round_no, v, bound)
        rec.pop("_rounds_per_sec", None)

    def _fire(self, rule: WatchRule, st: _RuleState, round_no: int,
              value: float, bound: float) -> None:
        st.consec = 0
        st.cooldown_until = round_no + WATCH_COOLDOWN
        st.fired += 1
        self.alerts += 1
        self.fired.append((round_no, rule.spec))
        traced = False
        if rule.action == "trace" and self.tracer is not None:
            # round-aligned reaction: capture the next N submitted rounds
            # (profiling.RoundTracer names the dir by the actual global
            # round_no it starts at)
            traced = self.tracer.request(rule.trace_rounds)
        if rule.action == "checkpoint":
            self.checkpoint_pending = True
        if self._rt is not None:
            self._rt.event(
                "watch_alert", round=round_no, rule=rule.spec,
                metric=rule.metric, value=value, bound=bound,
                fire=st.fired, action=rule.action,
                **({"trace_requested": traced}
                   if rule.action == "trace" else {}))
        print(f"WATCH alert at round {round_no}: {rule.spec} "
              f"(value {value:g}, bound {bound:g}, action {rule.action})")


class RunTelemetry:
    """The host-side recorder: buffers per-round spans in memory and writes
    one JSONL line per drained round (plus immediate lines for lifecycle
    events). Nothing here touches a device array — the one metric fetch per
    round happens inside ``FedModel.finish_round`` through the counted
    ``profiling.materialize`` seam, at drain time, which is why the
    engine's zero-blocking-fetch invariant survives with telemetry on
    (pinned in tests/test_telemetry.py with ``host_sync_monitor``).

    Every line is flushed as written so a SIGKILL'd run leaves a usable
    log — obs_report on a crashed run is a design goal, not a corner case.
    """

    def __init__(self, path: str, run_info: Optional[dict] = None,
                 schema: Optional[Sequence[str]] = None):
        self.path = path
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        self._f = open(path, "a")
        self._spans: Dict[int, Dict[str, Any]] = {}
        # SPAN_TOTALS is process-wide: start from what earlier runs of
        # this process left there
        self._seen_ns: Dict[str, int] = {
            name: tot[1] for name, tot in SPAN_TOTALS.items()}
        self.rounds = 0
        self.events = 0
        self._closed = False
        # program builds arrive on whatever thread built them
        # (profiling.subscribe_programs, from ``setup`` on)
        self._write_lock = threading.RLock()
        self._small_builds: Optional[Dict[str, Any]] = None
        self._builds_sink = None
        self._dispatched = 0      # rounds dispatched so far: a build's
        # ``round``
        # the watch/alert rule engine, when attached
        # (attach_run_telemetry): evaluated over each drained round record
        # in on_drained — host arithmetic on already-materialized values,
        # zero extra syncs
        self.watch: Optional[WatchEngine] = None
        # `schema` is THE active metric schema of this run (v2's 12-field
        # prefix without the histogram block, the full v3 list with it) —
        # recorded verbatim so readers key fields by name across versions
        self.event("run_start",
                   schema=list(schema if schema is not None
                               else METRIC_FIELDS),
                   **(run_info or {}))

    # -- immediate events --------------------------------------------------

    def event(self, ev: str, **fields) -> None:
        if self._closed:
            return
        with annotate("fed_telemetry_host", round=fields.get("round", -1)):
            rec = {"ev": ev, "t": time.time()}
            rec.update(fields)
            self._write(rec)

    def _emit(self, rec: Dict[str, Any]) -> None:
        self._f.write(json.dumps(_json_safe(rec), allow_nan=False) + "\n")
        self.events += 1

    def _flush_small_builds(self) -> None:
        small, self._small_builds = self._small_builds, None
        if small is not None:
            self._emit(small)

    def _write(self, rec: Dict[str, Any]) -> None:
        with self._write_lock:
            # first the builds too small for a line of their own since the
            # last line, summed: the log's sums stay whole
            self._flush_small_builds()
            self._emit(rec)
            self._f.flush()

    # -- start-up and the programs built (profiling.py) --------------------

    def setup(self, phases: Sequence[dict], t0: Optional[float]) -> None:
        """The entry point's set-up is done: write its phases once
        (``[{phase, start_s, seconds, programs, build_s, memory}]``,
        ``start_s`` since the process started at ``t0``; ``t0`` None where
        the OS gave no start time, ``start_s`` then counts from the import
        of profiling.py), then every program built so far, and from here
        on each build as it closes."""
        self.event("setup", t0=t0, phases=list(phases))
        if self._builds_sink is None:
            self._builds_sink = self._on_build
            for build in subscribe_programs(self._builds_sink):
                self._on_build(build, past=True)

    def _on_build(self, build: dict, past: bool = False) -> None:
        """One program traced, lowered, compiled or loaded. A line of its
        own (``program``) where it took ``PROGRAM_LOG_S`` in all or was
        written to the persistent cache (a cold compile the next start is
        spared); the small ones are summed and written before the next
        line of any kind. ``round``: the rounds dispatched by then, so the
        round whose dispatch built it (a build past the first drain is a
        program built in steady state); None for the builds of set-up."""
        if self._closed:
            return
        secs = build["trace_s"] + build["lower_s"] + build["backend_s"]
        rnd = None if past else self._dispatched
        if secs >= PROGRAM_LOG_S or build.get("stored"):
            rec = {"ev": "program", "round": rnd}
            rec.update(build)
            for key in ("trace_s", "lower_s", "backend_s", "load_s"):
                if key in rec:
                    rec[key] = round(rec[key], 4)
            self._write(rec)
            return
        with self._write_lock:
            acc = self._small_builds
            if acc is None or acc["phase"] != build["phase"]:
                acc = {"ev": "program", "name": "other", "round": rnd,
                       "phase": build["phase"], "builds": 0, "trace_s": 0.0,
                       "lower_s": 0.0, "backend_s": 0.0, "hits": 0,
                       "misses": 0}
                self._flush_small_builds()   # another phase's sum
                self._small_builds = acc
            acc["t"] = build["t"]
            acc["builds"] += 1
            acc["hits"] += build.get("cache") == "hit"
            acc["misses"] += build.get("cache") == "miss"
            for key in ("trace_s", "lower_s", "backend_s"):
                acc[key] = round(acc[key] + build[key], 4)

    # -- round-lifecycle spans (buffered; written at drain) ----------------

    def _since_last(self, name: str) -> float:
        """Milliseconds the spans called ``name`` took since this was last
        asked: how the recorder reads spans that other modules open
        (``fed_h2d`` in ``FedModel.begin_round``, ``fed_input_wait`` in
        ``PrefetchLoader``) without being handed them."""
        ns = SPAN_TOTALS.get(name, (0, 0))[1]
        ms = (ns - self._seen_ns.get(name, 0)) / 1e6
        self._seen_ns[name] = ns
        return ms

    def on_dispatch(self, round_no: int, span, occupancy: int) -> None:
        """Called by the engine after seal with the round's closed
        ``fed_round`` span (LR step + client dispatch, the batch's
        host-to-device copy included + server dispatch + seal);
        ``occupancy`` is the in-flight window depth including this round.
        ``h2d_ms`` is the ``fed_h2d`` time of this dispatch,
        ``input_wait_ms`` the ``fed_input_wait`` time since the previous
        dispatch: what the loop waited for this round's batch (a
        validation pass's waits land on the round after it)."""
        self._dispatched = round_no + 1
        with annotate("fed_telemetry_host", round=round_no):
            self._spans[round_no] = {
                "t_wall": time.time(),
                "start_ns": span.start_ns,
                "sealed_ns": span.end_ns,
                "dispatch_ms": span.ms,
                "h2d_ms": self._since_last("fed_h2d"),
                "input_wait_ms": self._since_last("fed_input_wait"),
                "occupancy": occupancy,
            }

    def on_complete(self, round_no: int, span) -> None:
        """The engine's ``fed_window_wait`` span for this round just
        closed: its device computation is complete (a completion wait, not
        a fetch). ``window_wait_ms`` is how long the host stood in that
        wait; ``compute_ms`` runs from the round's seal to the wait's
        return — an upper bound of the round's device time (the wait
        starts ``window`` submits after the seal), not a measurement of
        it."""
        rec = self._spans.get(round_no)
        if rec is not None and "compute_ms" not in rec:
            rec["window_wait_ms"] = span.ms
            rec["compute_ms"] = (span.end_ns - rec["sealed_ns"]) / 1e6

    def on_metrics(self, round_no: int, metrics: Optional[Dict[str, float]],
                   loss: Optional[float] = None,
                   guard_ok: Optional[bool] = None,
                   cohort: Optional[Dict[str, Any]] = None,
                   offload: Optional[Dict[str, Any]] = None,
                   model: Optional[Dict[str, float]] = None) -> None:
        """Called by ``FedModel.finish_round`` with the drained (host)
        metric values; ``cohort`` carries the host-side participation/
        staleness summary (participants, slots, staleness_mean/max when
        the accounting regime tracks per-client participation, and the
        async buffer record on the ``--async_buffer`` plane);
        ``offload`` the host-offload data-plane record (placement tier,
        gather/scatter ms, prefetch hit/miss — docs/host_offload.md).
        ``model`` the round's sums of the loss's named metric sums (a
        routed-expert model's pair counts, the model configuration's
        ``metric_names``).
        ``metrics`` is None for async BUFFERED dispatches — the server
        phase (whose jitted vector the metrics are) runs only on folds."""
        span = self._spans.setdefault(round_no, {})
        if metrics is not None:
            span["metrics"] = metrics
        if loss is not None:
            span["loss"] = loss
        if guard_ok is not None:
            span["guard_ok"] = guard_ok
        if cohort:
            span["cohort"] = cohort
        if offload:
            span["offload"] = offload
        if model:
            span["model"] = model

    def on_drained(self, round_no: int, span) -> None:
        """The round's batched drain finished (``span`` is its closed
        ``fed_drain``): derive the span fields and write the one ``round``
        line."""
        with annotate("fed_telemetry_host", round=round_no):
            self._write_round(round_no, span)

    def _write_round(self, round_no: int, span) -> None:
        buf = self._spans.pop(round_no, {})
        rec: Dict[str, Any] = {"ev": "round", "round": round_no,
                               "t": time.time()}
        if "t_wall" in buf:
            rec["t_dispatch"] = buf["t_wall"]
            rec["dispatch_ms"] = round(buf["dispatch_ms"], 3)
            rec["h2d_ms"] = round(buf["h2d_ms"], 3)
            rec["input_wait_ms"] = round(buf["input_wait_ms"], 3)
            rec["dispatch_to_drain_ms"] = round(
                (span.end_ns - buf["start_ns"]) / 1e6, 3)
            rec["occupancy"] = buf["occupancy"]
        if "compute_ms" in buf:
            rec["window_wait_ms"] = round(buf["window_wait_ms"], 3)
            rec["compute_ms"] = round(buf["compute_ms"], 3)
        rec["drain_fetch_ms"] = round(span.ms, 3)
        for key in ("loss", "guard_ok", "cohort", "offload", "model",
                    "metrics"):
            if key in buf:
                rec[key] = buf[key]
        self._write(rec)
        self.rounds += 1
        if self.watch is not None:
            # the watch plane evaluates AFTER the round line lands, so its
            # watch_alert events follow the round they describe in the log
            # (obs_report --follow renders them in that order); rec still
            # holds raw floats here — non-finite values reach the rules as
            # real NaN/Inf, not the JSON string encoding
            self.watch.observe(rec)

    def close(self, **totals) -> None:
        if self._closed:
            return
        # dispatched-but-never-drained rounds (e.g. the in-flight window at
        # a fatal guard escalation): flush their partial spans as their own
        # event kind so crash forensics sees them without obs_report
        # counting them as drained rounds
        for round_no in sorted(self._spans):
            span = self._spans[round_no]
            rec = {"round": round_no}
            for key in ("dispatch_ms", "h2d_ms", "input_wait_ms",
                        "occupancy", "window_wait_ms", "compute_ms", "loss",
                        "guard_ok", "cohort", "offload", "metrics"):
                if key in span:
                    rec[key] = span[key]
            self.event("round_partial", **rec)
        self._spans.clear()
        if self._builds_sink is not None:
            unsubscribe_programs(self._builds_sink)
        # every program span of the process, by name: the host's side of
        # the run with the profiler off (scripts/obs_report.py prints it);
        # every program built, by name; the device's memory at the end
        self.event("run_end", rounds=self.rounds, spans=span_totals(),
                   programs=program_summary(),
                   memory=memory_sample("run_end"), **totals)
        self._closed = True
        self._f.close()


def attach_run_telemetry(args, fed_model, log_dir: str,
                         entrypoint: str) -> Optional[RunTelemetry]:
    """Entrypoint hook (cv_train/gpt2_train): build the per-run recorder,
    log the static collective ledger in run_start, and hand the recorder to
    the model (``FedModel.finish_round`` records drained metrics through
    it; the engine picks it up via ``model.telemetry`` for spans). Also
    attaches the round-scoped trace capturer (``--trace_rounds`` and
    ``--profile`` windows, plus the watch plane's trace reaction — ``model.tracer``, picked up by
    the engine) and the watch/alert rule engine (``--watch``, default ON;
    rules from ``--watch_rules`` or DEFAULT_WATCH_RULES). Returns None
    when ``--no_telemetry`` (the tracer still attaches: a profiler window
    is independent of the event log)."""
    from commefficient_tpu.profiling import RoundTracer, parse_trace_rounds

    trace_spec = (getattr(args, "trace_rounds", "") or "").strip()
    watch_on = bool(getattr(args, "watch", False))
    windows = parse_trace_rounds(trace_spec)
    if getattr(args, "do_profile", False):
        # --profile: rounds 2 … 2+N-1 (past the compiling rounds), into
        # --profile_dir — one more window of the one tracer
        windows.append((2, int(args.profile_steps), args.profile_dir))
        print(f"profile: rounds 2-{1 + int(args.profile_steps)} -> "
              f"{args.profile_dir}")
    tracer = None
    if windows or (watch_on and getattr(args, "telemetry", False)):
        # the watch plane's trace reaction needs a tracer even with no
        # static windows; an idle tracer is one integer compare per
        # submitted round
        tracer = RoundTracer(log_dir, windows=windows)
        fed_model.tracer = tracer
        if trace_spec:
            print(f"trace_rounds: windowed round-aligned capture(s) "
                  f"{trace_spec} -> {log_dir}/trace_round_* "
                  "(docs/observability.md)")
    if not getattr(args, "telemetry", False):
        return None
    hists = bool(getattr(args, "telemetry_hist", False))
    path = os.path.join(log_dir, "telemetry.jsonl")
    # the RESOLVED per-leg plan (explicit spec, the auto-tune probe's
    # pick, or the legacy --reduce_dtype alias — aggregator._resolve_plan)
    # prices the ledger and is recorded verbatim, so obs_report shows the
    # real per-leg wire bytes and an 'auto' run's chosen plan is auditable
    # from the log alone (docs/compressed_collectives.md)
    plan = getattr(fed_model, "collective_plan", None)
    mesh = getattr(fed_model, "mesh", None)
    placement = None
    if mesh is not None:
        from commefficient_tpu.parallel.mesh import mesh_axis_placement

        placement = mesh_axis_placement(mesh)
    ledger = collective_ledger(
        args.mode, fed_model.grad_size, sketch=fed_model.sketch,
        n_shard=fed_model._n_shard,
        reduce_dtype=getattr(args, "reduce_dtype", "float32") or "float32",
        k=args.k, plan=plan,
        lowering=getattr(fed_model, "_plan_lowering", None),
        axis_sizes=getattr(fed_model, "_axis_sizes", None),
        axis_placement=placement)
    run_info = {
        "entrypoint": entrypoint,
        "mode": args.mode,
        "grad_size": fed_model.grad_size,
        "num_workers": args.num_workers,
        "num_clients": fed_model.num_clients,
        "server_shard": bool(getattr(args, "server_shard", False)),
        "reduce_dtype": getattr(args, "reduce_dtype", "float32"),
        "guards": bool(getattr(args, "guards", False)),
        # where the download accounting's mark and count run: inside the
        # round's jitted server step (federated/rounds.account_download)
        "download_accounting": "server_step",
        "seed": args.seed,
        "backend": jax.default_backend(),
        "ledger": ledger,
    }
    # Multi-tenant run packing (scripts/orchestrate.py, docs/packing.md):
    # an orchestrated tenant records its fleet identity + pinned run dir
    # in its OWN run header, so a tenant telemetry log found on disk says
    # which fleet slot produced it without consulting the fleet JSONL.
    tenant_id = os.environ.get("COMMEFFICIENT_TENANT_ID")
    if tenant_id is not None:
        run_info["tenant"] = tenant_id
        run_info["run_dir_pinned"] = bool(
            os.environ.get("COMMEFFICIENT_RUN_DIR"))
    if mesh is not None:
        # mesh topology (docs/multihost.md): which axes exist, their
        # sizes, and their ici/dcn placement — with process_count, the
        # run log alone says whether a leg's bytes crossed hosts
        run_info["mesh"] = {
            "process_count": int(jax.process_count()),
            "axes": [{"name": n, "size": int(mesh.shape[n]),
                      "placement": placement[n]}
                     for n in mesh.axis_names]}
    if args.mode in ("sketch", "true_topk", "local_topk"):
        # how this run resolves its top-k threshold (static for a run:
        # ops/topk.topk_plan has the rule). Sketch mode thresholds the
        # chunk view of the estimates, the others the flat vector.
        from commefficient_tpu.ops.topk import topk_plan

        cs = fed_model.sketch
        run_info["topk_plan"] = topk_plan(
            cs.chunk_layout.padded_size if cs is not None
            else fed_model.grad_size,
            args.k, sharded=bool(fed_model._n_shard))
    if args.mode == "sketch":
        # the gradient's route to the table in the client phase (static
        # for a run: rounds.build_round_step has the rule) and the
        # accumulate launches of its group plan (docs/stream_sketch.md)
        run_info["client_sketch_path"] = fed_model.steps.client_sketch_path
        run_info["client_sketch_launches"] = \
            fed_model.steps.client_sketch_launches
    cfg = getattr(getattr(fed_model, "model", None), "cfg", None)
    if hasattr(cfg, "kda_core_path"):
        # a model with a delta-rule mixer: the path its chunked core takes
        # (static for a run: ops/linear_attention.kda_core_path has the rule)
        run_info["kda_core_path"] = cfg.kda_core_path(args.max_seq_len)
    # Participation-layer config (--participation / --inject_client_fault,
    # federated/participation.py): recorded in the run header so a logged
    # run is reproducible from the log alone — the fault schedule is
    # SEEDED, so spec + seed IS the schedule (the same auditability
    # contract --collective_plan already has).
    run_info["participation"] = (getattr(args, "participation", "")
                                 or "1.0")
    run_info["participation_sampling"] = getattr(
        args, "participation_sampling", "uniform")
    run_info["staleness_decay"] = float(getattr(args, "staleness_decay",
                                                0.5))
    fault_spec = (getattr(args, "inject_client_fault", "") or "").strip()
    if fault_spec:
        from commefficient_tpu.federated.participation import (
            parse_client_fault,
        )

        sched = parse_client_fault(fault_spec)
        run_info["client_fault"] = {
            "spec": sched.spec(), "drop": sched.drop, "slow": sched.slow,
            "corrupt": sched.corrupt, "delay": sched.delay,
            "seed": sched.seed,
            "quarantine_after": sched.quarantine_after}
    else:
        run_info["client_fault"] = None
    # Open-world population churn (--churn, docs/service.md): the seeded
    # schedule in the run header — spec + seed IS the whole population
    # trajectory, so the obs_report Churn section reproduces it from the
    # log alone (same auditability contract as the fault schedule)
    churn_spec = (getattr(args, "churn", "") or "").strip()
    if churn_spec:
        from commefficient_tpu.federated.participation import parse_churn

        csched = parse_churn(churn_spec)
        run_info["churn"] = {
            "spec": csched.spec(), "join": csched.join,
            "depart": csched.depart, "init": csched.init,
            "seed": csched.seed, "compact": csched.compact}
    else:
        run_info["churn"] = None
    # Async buffered federation (--async_buffer, docs/async.md): the
    # fold threshold + decay in the run header, so a logged async run's
    # buffer/staleness story reproduces from the log alone (obs_report's
    # Async section) — same auditability contract as the fault schedule
    async_k = int(getattr(args, "async_buffer", 0) or 0)
    run_info["async"] = ({"buffer": async_k,
                          "staleness_decay": float(
                              getattr(args, "staleness_decay", 0.5))}
                         if async_k else None)
    # Host-offload data plane (docs/host_offload.md): the resolved
    # placement tier + per-round streamed-row geometry, so the obs_report
    # "Host offload" section reproduces the data-plane story from the log
    # alone (same auditability contract as the participation config above)
    mem_plan = getattr(fed_model, "memory_plan", None)
    if mem_plan is not None and getattr(fed_model, "streaming", False):
        run_info["state_placement"] = mem_plan.placement
        run_info["state_row_bytes"] = int(mem_plan.row_bytes)
        # ALL members' bytes for one client slot (members can differ in
        # row size — aggregator computes it from the plan total)
        run_info["state_slot_bytes"] = int(
            getattr(fed_model, "_slot_bytes", mem_plan.row_bytes))
        run_info["state_rows_per_round"] = int(args.num_workers)
    elif mem_plan is not None and mem_plan.total_bytes:
        run_info["state_placement"] = mem_plan.placement
    # Storage-fault plane (docs/fault_tolerance.md §storage faults): the
    # disk tier's resolved I/O config — queue bound, retry ladder,
    # watchdog deadline, and any seeded injection schedule — so a logged
    # run's storage-fault story (and the injected drill that produced
    # it) reproduces from the header alone, like the client-fault config
    store = getattr(fed_model, "_row_store", None)
    if store is not None:
        run_info["state_io"] = {
            "queue_bound": int(store.queue_bound),
            "retries": int(store.io_retries),
            "backoff_ms": float(store.io_backoff_ms),
            "deadline_ms": float(store.io_deadline_ms),
            "quarantine_after": int(store.quarantine_after),
            # integrity plane (docs/fault_tolerance.md §silent
            # corruption): resolved checksum state + scrub budget, so a
            # logged run's detection/repair story is auditable from the
            # header like the injection schedule
            "checksums": bool(getattr(store, "checksums", False)),
            "scrub_rows": int(getattr(store, "scrub_rows", 0)),
            "inject": (store.inject.schedule.spec()
                       if store.inject is not None else None),
        }
    if plan is not None:
        run_info["collective_plan"] = plan.spec()
    if getattr(fed_model, "plan_report", None):
        # the auto-tune probe's per-{leg x dtype} rel_err/probe_ms/bytes
        run_info["collective_plan_probe"] = fed_model.plan_report
    # continuous-observability config (docs/observability.md): the active
    # metric schema version, the resolved watch rules, and any static
    # trace windows — same reproducible-from-the-header contract as the
    # participation/collective-plan configs above
    run_info["telemetry_hist"] = hists
    rule_spec = (getattr(args, "watch_rules", "") or "").strip()
    rules = (parse_watch_rules(rule_spec) if rule_spec
             else parse_watch_rules(",".join(DEFAULT_WATCH_RULES)))
    run_info["watch"] = ([r.spec for r in rules] if watch_on else None)
    if trace_spec:
        run_info["trace_rounds"] = trace_spec
    rt = RunTelemetry(path, run_info=run_info, schema=metric_schema(hists))
    if watch_on:
        rt.watch = WatchEngine(rules, telemetry=rt, tracer=tracer)
    fed_model.telemetry = rt
    print(f"telemetry: run event log -> {path} "
          "(docs/observability.md; --no_telemetry disables"
          + (f"; watch plane ON, {len(rules)} rules — --no_watch disables"
             if watch_on else "") + ")")
    return rt


def read_events(path: str) -> Iterator[dict]:
    """Yield the JSONL events of a run log, skipping a torn trailing line
    (a SIGKILL mid-write must not make the whole log unreadable)."""
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                yield json.loads(line)
            except json.JSONDecodeError:
                return
