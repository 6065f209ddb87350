"""One federated run: what ``cv_train.py`` and ``gpt2_train.py`` share once
each has built its model, its loaders and its ``FedModel``.

- ``attach_planes``: the ops planes' wiring (participation, churn, the
  telemetry recorder, ``--resume``), in the order both entry points need;
- ``finish_setup``: the phase table printed, the ``setup`` event and the
  programs built so far written (profiling.py's record of start-up);
- ``val_pass``: the span and the ``val`` event around a validation pass;
- ``run_rounds``: one training epoch's round loop over a
  ``PipelinedRoundEngine`` the caller constructed — dispatch, batched metric
  drains, the ``--checkpoint_every_rounds`` and watch-forced saves;
- ``close_run``: the run's close-out (expiry and conservation audits, the
  tracer, the row store's counters, the recorder), ending in
  ``FedModel.finalize()``.

What differs between the two entry points stays with them and comes in as
arguments: what a drained round's metrics mean (``consume``), what a
mid-epoch save carries (``extras``), which batches a ``--test`` run skips.
Each entry point still constructs the engine and the model from the names in
its OWN module (``cv_train.PipelinedRoundEngine``, ``gpt2_train.FedModel``,
...): the benchmark and ``chip_smoke.py`` substitute those names.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, NamedTuple, Optional

from commefficient_tpu import profiling
from commefficient_tpu.federated.checkpoint import (
    resume_run,
    save_round_state,
)
from commefficient_tpu.federated.engine import cohort_lookahead
from commefficient_tpu.federated.participation import (
    attach_churn,
    attach_participation,
)
from commefficient_tpu.telemetry import attach_run_telemetry


class Planes(NamedTuple):
    """What ``close_run`` needs of a run ``attach_planes`` wired."""
    fed_model: Any
    participation: Any   # ParticipationController or None
    population: Any      # PopulationManager or None
    telemetry: Any       # RunTelemetry or None


def attach_planes(args, fed_model, opt, lr_scheduler, train_loader, log_dir,
                  entrypoint: str):
    """Wire the ops planes to a freshly built ``fed_model`` and resume.
    Returns ``(planes, start_epoch, totals, resume_mid)``: the handle for
    ``close_run`` and where ``--resume`` says training re-enters."""
    sampler = getattr(train_loader, "sampler", None)
    # straggler-/dropout-tolerant participation layer (--participation /
    # --inject_client_fault, docs/fault_tolerance.md): partial cohorts
    # through the sampler, seeded client faults, staleness-weighted late
    # landing
    pc = attach_participation(args, fed_model, sampler=sampler)
    # open-world population churn (--churn, docs/service.md): clients
    # register/depart mid-run; the sampler draws from the live population
    # and the disk-tier row store allocates/retires/compacts rows
    pm = attach_churn(args, fed_model, sampler=sampler)
    # zero-sync telemetry plane (--telemetry, on by default): per-round
    # device metrics + the structured run event log under log_dir
    # (docs/observability.md; render with scripts/obs_report.py)
    rt = attach_run_telemetry(args, fed_model, log_dir, entrypoint)
    start_epoch, totals, resume_mid = resume_run(args, fed_model, opt,
                                                 lr_scheduler)
    if rt is not None and (start_epoch or resume_mid is not None):
        rt.event("resume", start_epoch=start_epoch,
                 mid_epoch=resume_mid is not None)
    return Planes(fed_model, pc, pm, rt), start_epoch, totals, resume_mid


def finish_setup(planes: Optional[Planes]) -> None:
    """The entry point's set-up is done (its last ``profiling.phase`` has
    closed): print the phases and hand them to the event log, which from
    here on also writes every program built (``RunTelemetry.setup``).
    ``planes`` is None where the run attached none (gpt2_train's eval-only
    ``--finetune``)."""
    print(profiling.phase_table(), flush=True)
    if planes is not None and planes.telemetry is not None:
        planes.telemetry.setup(profiling.PHASES, profiling.PROCESS_START_T)


@contextlib.contextmanager
def val_pass(model):
    """Around one validation pass (``run_batches(training=False)``'s loop,
    whose every batch is fetched, so the device is done when it ends):
    the span ``fed_val_pass`` and a ``val`` event with its seconds and the
    device's memory before and after."""
    rt = getattr(model, "telemetry", None)
    before = profiling.memory_sample("val_start") if rt is not None else None
    with profiling.annotate("fed_val_pass") as span:
        yield
    if rt is not None:
        rt.event("val", round=getattr(model, "rounds_dispatched", None),
                 seconds=round(span.ms / 1e3, 4), memory_start=before,
                 memory_end=profiling.memory_sample("val_end"))


def run_rounds(engine, loader, args, *, epoch: int, i0: int, spe: int,
               epoch_fraction, totals, consume: Callable[[list], Any],
               extras: Callable[[], dict],
               skip: Optional[Callable[[int], Any]] = None,
               submitted: Optional[Callable[[int], None]] = None,
               stop_after_first: bool = False) -> bool:
    """One training epoch's rounds through ``engine``, from round ``i0`` of
    ``spe`` (a resumed epoch re-enters at ``i0 > 0``) up to
    ``epoch_fraction`` of it.

    Each iteration dispatches a round without blocking on its results
    (federated/engine.py); metrics arrive in batches of
    ``--metrics_drain_every`` and go to ``consume(results)``, which returns
    true to abandon the epoch (cv_train's NaN abort, which therefore fires
    at drain time, up to drain_every-1 rounds after the NaN round:
    docs/round_engine.md). Returns False if ``consume`` abandoned it, else
    True once the window has drained.

    ``submitted(rounds_done)`` runs after each dispatch, before its drained
    results are consumed; ``skip(i)`` drops the loader's i-th batch of this
    call undispatched; ``extras()`` is the caller's partial-epoch
    accumulators for a mid-epoch save, read after the drain."""
    model = engine.model
    save_every = int(getattr(args, "checkpoint_every_rounds", 0) or 0)
    # watch plane (telemetry.WatchEngine, docs/observability.md): its
    # checkpoint reaction is serviced HERE, at a round boundary, the way
    # the save_every path is
    watch = getattr(getattr(model, "telemetry", None), "watch", None)
    # cohort_lookahead peeks batch t+1 AFTER round t submits and hands its
    # client_ids to the host-offload prefetcher — the next round's row
    # gather overlaps this round's device compute (no-op without row
    # streaming; docs/host_offload.md)
    for i, batch in enumerate(cohort_lookahead(loader, model)):
        if skip is not None and skip(i):
            continue
        if i0 + i > spe * epoch_fraction:
            break
        rounds_done = i0 + i + 1
        done = engine.submit(batch)
        if submitted is not None:
            submitted(rounds_done)
        if consume(done):
            return False
        do_save = bool(save_every and rounds_done % save_every == 0)
        forced = False
        if watch is not None and watch.pop_checkpoint():
            # the watch checkpoint reaction: force a run-state save at this
            # round boundary (a resumable save needs the no-prefetch-thread
            # constraint, like --checkpoint_every_rounds — validate_args
            # noted it)
            if args.train_dataloader_workers == 0:
                do_save = forced = True
            else:
                print("watch: checkpoint reaction skipped (needs "
                      "--train_dataloader_workers 0 for a "
                      "resumable save)")
        if do_save:
            # drain the in-flight window first: the saved sampler / RNG
            # position must describe exactly the rounds whose state AND
            # metrics are folded into the checkpoint
            if consume(engine.drain()):
                return False
            save_round_state(
                args, epoch, rounds_done, loader.sampler.get_state(), model,
                engine.opt, engine.lr_scheduler, totals,
                extras=extras())
            if getattr(model, "telemetry", None) is not None:
                # `round` is the GLOBAL round_no the round/guard events
                # share (the window just drained, so the last dispatched
                # round is the last covered); the epoch-local save
                # position rides separately
                model.telemetry.event(
                    "checkpoint", epoch=epoch,
                    round=model.rounds_dispatched - 1,
                    round_in_epoch=rounds_done,
                    **({"forced_by_watch": True} if forced else {}))
        if stop_after_first:
            break
    return not consume(engine.drain())


def population_emptied(model, losses) -> bool:
    """The open-world end state (--churn, docs/service.md): the live
    population emptied before this epoch produced a single cohort and no
    joiner can ever refill it — a clean end of training (the caller returns
    None as its loss), not a NaN trajectory."""
    return not losses and getattr(model, "_population", None) is not None


def close_run(planes: Planes) -> None:
    """The run's close-out, on EVERY exit path of the entry point's training
    call (its ``finally:``)."""
    fed_model, pc, pm, rt = planes
    if pc is not None:
        # end-of-run expiry audit (owned HERE, not engine.close() — cohorts
        # legally land across engine instances): stragglers whose due round
        # will never dispatch AND async contributions that landed but never
        # reached a K-fold are counted, never silent (the obs_report
        # participation/async sections and the run log both carry the
        # numbers; tests/test_async.py pins the conservation count)
        expired = pc.expire_pending()
        if expired and rt is not None:
            rt.event("straggler_expired", count=expired)
        a_expired = pc.expire_buffer() if pc.async_k else 0
        if a_expired and rt is not None:
            rt.event("async_expired", count=a_expired)
    if pm is not None:
        # open-world conservation audit (docs/service.md): every client
        # that ever registered is exactly one of active / departed /
        # quarantined — cross-checked against the live mask AND the running
        # counters, recorded so the whole churn story reproduces from the
        # JSONL log alone
        audit = pm.audit()
        if rt is not None:
            # churn records drawn after the last dispatched round (e.g. the
            # departure that emptied the pool) have no begin_round left to
            # relay them — flush here so the event totals match the audit's
            # counters
            for ev in pm.pop_events():
                rt.event(ev.pop("kind"), **ev)
            rt.event("churn_audit", **audit)
        if not audit["ok"]:
            print(f"CHURN AUDIT FAILED: {audit}")
    tracer = getattr(fed_model, "tracer", None)
    if tracer is not None:
        # a capture window left open at run end stops here; its (partial)
        # record still lands in the event log
        cap = tracer.close()
        if cap is not None and rt is not None:
            rt.event("trace_captured", **cap)
    store = getattr(fed_model, "_row_store", None)
    if store is not None and rt is not None:
        if store.fatal_error is not None:
            # the storage-fault terminal rung (docs/fault_tolerance.md
            # §storage faults): the one actionable error, recorded so the
            # whole ladder reproduces from the JSONL log alone
            rt.event("io_fatal", error=str(store.fatal_error))
        # run-total I/O + integrity counters (incl. the realized
        # injected-fault counts) — the last word the log needs for the
        # detected-vs-injected silent-corruption audit
        rt.event("io_counters", **store.io_counters())
    if rt is not None:
        rt.close()
    # EVERY exit path — including the storage-fault terminal rung — drains
    # and joins the row store's I/O worker (bounded; MemmapRowStore.close
    # reports instead of abandoning a daemon thread mid-write)
    fed_model.finalize()
