"""Pipelined round engine: host-sync-free steady-state federated rounds.

A GPT-2 per-op profile (v5e, 2026-08-01, capture since deleted) measured
337 ms wall per round against 69 ms of device-busy time — ~80% of every
round was host dispatch and blocking scalar drains, because the reference
loop shape (cv_train.py / gpt2_train.py)

    lr_scheduler.step(); loss, ... = model(batch); opt.step()

forces a device→host fetch of every round's metrics before the next round
may be dispatched. Nothing in the round's *math* requires that: round t+1
consumes round t's device arrays (weights, momentum, error), never its
fetched values. This engine restructures the loop around that fact:

- ``submit(batch)`` dispatches one full round (LR step, client phase,
  server phase) with ZERO blocking host transfers — the per-round metrics
  and the deferred download accounting stay on device inside a
  ``RoundHandle`` (aggregator.begin_round);
- dispatched-but-unfetched handles accumulate in a device-side buffer that
  is drained every ``drain_every`` rounds (or on ``drain()``/``close()``):
  one batched materialization instead of one sync per round. Drained
  values are identical to per-round fetching — pinned by
  tests/test_engine.py;
- host run-ahead is bounded by ``window``: before dispatching round t the
  engine waits for round ``t - window``'s COMPUTATION to complete
  (``jax.block_until_ready`` — a completion wait, not a transfer, so it
  does not count as a host sync). Without the bound the host can enqueue
  unboundedly far ahead of the device. On the async buffered plane
  (``--async_buffer``, docs/async.md) this window IS the concurrency
  limit, not a round barrier: buffered dispatches skip the server phase
  entirely, so nothing downstream of a slow contribution ever waits for
  it — the server folds whenever K contributions have landed and the
  engine keeps dispatching at window depth throughout.

The zero-syncs-per-round invariant is auditable: wrap the submit loop in
``profiling.host_sync_monitor`` and assert ``counter.count == 0`` (the
engine's own drains go through the counted ``profiling.materialize``
seam). tests/test_engine.py holds it to zero.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Deque, List, NamedTuple, Optional, Tuple

import numpy as np

import jax

from commefficient_tpu.profiling import Heartbeat, annotate, memory_sample

__all__ = ["RoundResult", "PipelinedRoundEngine", "cohort_lookahead"]


def cohort_lookahead(loader, model):
    """Batch iterator with one-round cohort lookahead for the host-offload
    prefetcher (host_state.CohortPrefetcher, docs/host_offload.md).

    Yields the loader's batches unchanged. After the caller finishes round
    t's loop body (``engine.submit``), the NEXT batch is drawn and its
    ``client_ids`` handed to ``model.prefetch_cohort`` BEFORE it is
    yielded — so round t+1's row gather dispatches while round t (and the
    rest of the engine's in-flight window) still computes on device.

    Ordering is deliberately identical to the plain ``for batch in
    loader`` loop: batch t+1 is drawn only AFTER round t's body ran, so
    the sampler/augmentation RNG order — and the participation layer's
    requeue/quarantine mutations, which must land before the next draw
    (config.validate_args's --train_dataloader_workers 0 constraint) —
    are untouched. Prefetch on/off therefore changes WHEN rows are read,
    never which batches (or rows) a trajectory sees.

    A no-op wrapper for models without row streaming (``prefetch_cohort``
    returns immediately), so both entrypoints use it unconditionally."""
    it = iter(loader)
    prefetch = getattr(model, "prefetch_cohort", None)
    try:
        batch = next(it)
    except StopIteration:
        return
    while True:
        yield batch
        try:
            nxt = next(it)
        except StopIteration:
            return
        if prefetch is not None:
            prefetch(nxt)
        batch = nxt


class RoundResult(NamedTuple):
    """One finished round: ``index`` is the submit order (0-based within
    the engine's lifetime), ``values`` the reference-shaped result list
    ``[loss_arr(, acc_arr, ...), download_bytes, upload_bytes]`` that
    ``model(batch)`` used to return synchronously."""

    index: int
    values: List[Any]


class PipelinedRoundEngine:
    """Drives ``FedModel`` + ``FedOptimizer`` (+ optional LR scheduler)
    with round pipelining and batched metric drains.

    One ``submit(batch)`` replaces the reference loop body
    ``lr_scheduler.step(); model(batch); opt.step()`` and returns the list
    of rounds drained by this call — empty most rounds, ``drain_every``
    results at once on drain rounds, always in submit order. Call
    ``drain()`` after the loop (and before reading ``model.params`` for
    checkpoints — dispatched rounds are already part of the device-side
    weights, so this is only about collecting their metrics).

    ``drain_every=1`` degenerates to the reference's per-round fetching,
    which is what the parity test pins against.
    """

    def __init__(self, model, opt, lr_scheduler=None, window: int = 2,
                 drain_every: int = 8, telemetry=None,
                 heartbeat: Optional[Heartbeat] = None, tracer=None):
        assert window >= 1, "in-flight window must be at least 1"
        assert drain_every >= 1, "drain_every must be at least 1"
        self.model = model
        self.opt = opt
        self.lr_scheduler = lr_scheduler
        self.window = window
        self.drain_every = drain_every
        self._pending: Deque[Tuple[int, Any]] = deque()
        self._next_index = 0
        self.rounds_submitted = 0
        self.drains = 0
        # Telemetry plane (docs/observability.md): the engine hands the
        # recorder its own spans as they close — fed_round (dispatch),
        # fed_window_wait, fed_drain — and the in-flight occupancy; the
        # recorder reads their durations, it stamps no clock of its own.
        # Span data buffers in memory and is written
        # only when the round drains, so the dispatch path stays fetch-free
        # (the zero-syncs audit covers telemetry-on runs,
        # tests/test_telemetry.py). Defaults to the model's attached
        # recorder (telemetry.attach_run_telemetry).
        self.telemetry = (telemetry if telemetry is not None
                          else getattr(model, "telemetry", None))
        # Engine-owned liveness heartbeat (scripts/crash_matrix.py,
        # docs/fault_tolerance.md): one flushed stderr line per DRAINED
        # round, carrying the telemetry round index — the model's global
        # dispatch counter (RoundHandle.round_no), monotonic across epochs
        # and engine instances, so an external supervisor can target an
        # absolute round without counting lines. Armed by
        # COMMEFFICIENT_HEARTBEAT=1 (a no-op otherwise).
        self.heartbeat = heartbeat if heartbeat is not None else Heartbeat()
        # Round-scoped trace capture (profiling.RoundTracer,
        # docs/observability.md): the engine drives the tracer in the
        # global round_no timeline — maybe-start before a round's
        # dispatch, maybe-stop when the window's last round drains — so a
        # capture is aimable at an absolute round (--trace_rounds, or the
        # watch plane's trace reaction). Defaults to the model's attached
        # tracer (telemetry.attach_run_telemetry).
        self.tracer = (tracer if tracer is not None
                       else getattr(model, "tracer", None))

    def submit(self, batch) -> List[RoundResult]:
        """Dispatch one training round; no blocking host transfer happens
        here unless this is a drain round (every ``drain_every``-th)."""
        # the round_no this dispatch will get (the model's global counter;
        # models without one fall back to the engine-local index)
        rn_next = getattr(self.model, "rounds_dispatched",
                          self._next_index)
        if self.tracer is not None:
            # may start a windowed jax.profiler capture BEFORE dispatch,
            # so this round's dispatch + device compute land in the trace
            self.tracer.on_submit(rn_next)
        # the step span marks the round on the profiler timeline (_r and
        # step_num are what jax.profiler.StepTraceAnnotation sets), keyed
        # by the global round_no like every other program span
        with annotate("fed_round", _r=1, step_num=rn_next,
                      round=rn_next) as round_span:
            if self.lr_scheduler is not None:
                self.lr_scheduler.step()
            handle = self.model.begin_round(batch)
            self.opt.step()
            seal = getattr(self.model, "seal_round", None)
            if seal is not None:
                # attach the server phase's on-device health verdict
                # (--guards, docs/fault_tolerance.md) and telemetry
                # metrics vector (--telemetry) to the handle they belong
                # to; still device arrays — they drain with the batched
                # metrics
                handle = seal(handle)
        self._pending.append((self._next_index, handle))
        self._next_index += 1
        self.rounds_submitted += 1
        if self.telemetry is not None:
            self.telemetry.on_dispatch(
                self._round_no(handle, self._next_index - 1), round_span,
                occupancy=len(self._pending))

        if len(self._pending) > self.window:
            # bound host run-ahead: wait for the computation of the round
            # `window` back — completion only, its values stay on device.
            # The one place where the host waits for the device and the
            # device's idle time is NOT the host's doing.
            oidx, old = self._pending[-1 - self.window]
            waited = self._round_no(old, oidx)
            with annotate("fed_window_wait", round=waited) as wait_span:
                jax.block_until_ready(old.metrics)
            if self.telemetry is not None:
                # the wait doubles as the round's device-completion stamp
                self.telemetry.on_complete(waited, wait_span)

        if len(self._pending) >= self.drain_every:
            return self.drain()
        return []

    @staticmethod
    def _round_no(handle, fallback: int) -> int:
        """The handle's global dispatch index (RoundHandle.round_no); falls
        back to the engine-local index for handle types that predate it."""
        rn = getattr(handle, "round_no", -1)
        return rn if rn >= 0 else fallback

    def drain(self) -> List[RoundResult]:
        """Materialize every dispatched-but-unfetched round, oldest first —
        the batched host sync. Safe to call with nothing pending."""
        results = []
        while self._pending:
            idx, handle = self._pending.popleft()
            rn = self._round_no(handle, idx)
            with annotate("fed_drain", round=rn) as drain_span:
                results.append(RoundResult(idx,
                                           self.model.finish_round(handle)))
            if self.heartbeat.enabled:
                # minimal live monitor even with telemetry off: the
                # drained round's mean loss + guard verdict ride the
                # heartbeat line (host math on already-fetched values)
                vals = results[-1].values
                loss_arr = vals[0] if len(vals) >= 3 else None
                hb_loss = (float(np.mean(loss_arr))
                           if loss_arr is not None
                           and getattr(loss_arr, "size", 0) else None)
                # async buffered federation (--async_buffer,
                # docs/async.md): buffer depth + oldest un-folded
                # contribution age ride the line, so hang detection stays
                # meaningful when rounds no longer tick uniformly — a
                # full-but-never-folding buffer must not read as a
                # healthy heartbeat (scripts/supervise.py --max-stale).
                # All host bookkeeping; None (and absent from the line)
                # on the synchronous path.
                hb_buf = hb_stale = None
                part = getattr(self.model, "_participation", None)
                if part is not None and getattr(part, "async_k", 0):
                    hb_buf = len(part.buffer)
                    hb_stale = part.oldest_age(
                        getattr(self.model, "rounds_dispatched",
                                self._next_index))
                # open-world churn (--churn, docs/service.md): the live
                # population rides the line so a supervisor sees the
                # churn trajectory without the telemetry log; None (and
                # absent) for a closed population
                pop = getattr(self.model, "_population", None)
                hb_pop = pop.population if pop is not None else None
                self.heartbeat.round(
                    rn, loss=hb_loss,
                    guard_ok=getattr(self.model, "last_guard_ok", None),
                    buffer=hb_buf, stale=hb_stale, population=hb_pop)
            if self.telemetry is not None:
                self.telemetry.on_drained(rn, drain_span)
            if self.tracer is not None:
                # stop an active capture once its window's last round has
                # drained (device compute provably complete), and log the
                # round-aligned capture record
                cap = self.tracer.on_drained(rn)
                if cap is not None and self.telemetry is not None:
                    self.telemetry.event("trace_captured", **cap)
        if results:
            self.drains += 1
            if self.telemetry is not None:
                # the device's memory once a drain (a host call into the
                # runtime, no fetch), under a span of its own so that its
                # cost shows in run_end.spans; every drained round's
                # computation is complete here, so with nothing in flight
                # ``bytes_in_use`` is the run at rest
                with annotate("fed_memory_sample", round=rn):
                    memory = memory_sample("drain")
                self.telemetry.event("drain", round=rn, rounds=len(results),
                                     inflight=len(self._pending),
                                     memory=memory)
        return results

    def close(self) -> List[RoundResult]:
        """Final drain (the docstring's ``close()``): materialize every
        in-flight round and return the results. A convenience alias of
        ``drain()`` for callers that drive the engine to completion —
        NOTE it does NOT expire pending straggler cohorts or the async
        contribution buffer (federated/participation.py): stragglers may
        legally land — and buffered contributions fold — in a later
        epoch's engine instance, so the end-of-run expiry audit
        (``expire_pending`` + ``expire_buffer``, with the
        ``straggler_expired``/``async_expired`` run events) belongs to
        the entrypoints, which own the run lifetime. Nothing is silently
        dropped: tests/test_async.py pins the conservation count."""
        return self.drain()

    @property
    def pending(self) -> int:
        return len(self._pending)
