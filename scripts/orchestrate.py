"""Multi-tenant run packing: fair-share orchestrator + shared compile cache.

A federation simulator's real unit of work is rarely one run — it's a
sweep (K×decay rungs, scenario × algorithm grids). Today each run owns
the whole device pool and the fleet cost is N sequential cold-compile
runs. This orchestrator (docs/packing.md, ROADMAP item 3(c)) packs N
tenant runs onto one machine/chip:

- **One ladder, N tenants.** Each tenant is a ``supervise.ChildRun`` —
  the exact crash/hang/restart/backoff/poison ladder of the single-run
  supervisor (PR 15), ticked non-blockingly, so a dead tenant restarts
  with ``--resume auto`` without its neighbors noticing. Backoff is a
  deadline, not a sleep: one tenant waiting out a restart never stalls
  the fleet loop.
- **Per-tenant namespace.** Every tenant gets its own dir under the
  fleet dir (``t<i>/ckpt`` checkpoint+state root, ``t<i>/run`` run dir)
  — the orchestrator appends ``--checkpoint_path``/``--state_dir`` when
  the tenant argv doesn't carry them (so ``--resume auto`` after a crash
  finds the tenant's OWN checkpoints, never a neighbor's) and pins the
  run dir through the ``COMMEFFICIENT_RUN_DIR`` env seam
  (``utils.make_logdir``), so two tenants' telemetry JSONLs and
  ``trace_round_*`` profiler captures can never collide (JAX allows one
  profiler session per process; namespacing keeps their outputs apart).
- **One shared compile cache.** All tenants point at a single FRESH
  per-orchestrator ``JAX_COMPILATION_CACHE_DIR``: identical configs
  compile once across the fleet. Fresh-per-fleet is the guard against
  the known jax 0.4.37 donation-from-cache hazard (README
  Troubleshooting): a stale entry from an earlier build can poison
  bit-exactness, and a torn entry from a SIGKILLed run deserializes
  without validation — a cache no older than the orchestrator can hold
  neither. Deleted on exit unless ``--keep-cache``.
- **Cache-warmup admission.** The FIRST admitted tenant holds an
  exclusive slot until its first heartbeat (compile done, cache entries
  written) — only then are further tenants admitted, so they compile
  *warm* instead of racing the cold compile N times. This is where the
  packed-fleet speedup comes from even on a single core (not measured
  on the chip); ``--no-warm-admission`` disables.
- **Fair-share interleave.** Admission is bounded (``--max-concurrent``)
  and least-progress-first (heartbeat count, ties by tenant id — the
  admission order is deterministic). Optionally ``--max-lead R``
  SIGSTOPs a tenant that runs R rounds ahead of the slowest live tenant
  until the laggard catches up (liveness clocks are suspended while
  paused), so a straggler is never starved of the core by its faster
  neighbors.
- **Fleet JSONL.** Every decision lands in one flushed event log
  (``fleet_start`` / ``tenant_admit`` / ``tenant_start`` /
  ``tenant_progress`` / ``tenant_exit`` / ``tenant_restart`` /
  ``tenant_poison`` / ``tenant_throttle`` / ``tenant_unthrottle`` /
  ``tenant_giveup`` / ``tenant_finish`` / ``fleet_done``) that
  ``scripts/obs_report.py --fleet`` renders into a per-tenant round
  table + aggregate rounds/sec from the log alone. Conservation:
  admitted == finished + gave_up at ``fleet_done``.

Usage:
    python scripts/orchestrate.py --fleet-dir runs/fleet_x \\
        --max-concurrent 3 \\
        --tenant "cv_train.py --mode sketch --seed 0 ..." \\
        --tenant "cv_train.py --mode sketch --seed 1 ..." \\
        --tenant "cv_train.py --mode sketch --seed 2 ..."

Each ``--tenant`` is one shlex-split child command (a leading ``*.py``
gets ``sys.executable`` prepended, same as supervise.py). The supervisor
ladder knobs (``--heartbeat-timeout``, ``--startup-grace``,
``--max-restarts``, ``--backoff``, ``--backoff-max``, ``--max-stale``)
apply per tenant.
"""

from __future__ import annotations

import argparse
import os
import shlex
import shutil
import sys
import time

_SCRIPTS = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_SCRIPTS)
for _p in (_REPO, _SCRIPTS):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from supervise import ChildRun, EventLog  # noqa: E402


def _normalize(argv) -> list:
    argv = list(argv)
    if argv and argv[0].endswith(".py"):
        argv = [sys.executable] + argv
    return argv


def orchestrate(tenants, *, fleet_dir: str, labels=None,
                max_concurrent: int = 0, warm_admission: bool = True,
                share_cache: bool = True, keep_cache: bool = False,
                namespace_args: bool = True, max_lead: int = 0,
                progress_every: int = 1, heartbeat_timeout: float = 120.0,
                startup_grace: float = 900.0, max_restarts: int = 5,
                backoff: float = 2.0, backoff_max: float = 60.0,
                max_stale: int = 200, events_path: str = "",
                poll: float = 0.1, out=None) -> int:
    """Run every tenant argv to completion under the packed-fleet policy
    (module docstring); returns 0 iff every tenant finished, else 1.
    ``tenants`` is a list of argv lists; ``max_concurrent`` 0 means all
    at once (after the warm-admission gate). Programmatic entry for
    tests."""
    out = out if out is not None else sys.stdout
    n = len(tenants)
    if n == 0:
        raise ValueError("no tenants")
    mc = max_concurrent if max_concurrent and max_concurrent > 0 else n
    labels = list(labels) if labels else [f"t{i}" for i in range(n)]
    os.makedirs(fleet_dir, exist_ok=True)
    events_path = events_path or os.path.join(fleet_dir,
                                              "fleet_events.jsonl")
    cache_dir = ""
    cache_created = False
    if share_cache:
        # FRESH per-orchestrator cache dir: the 0.4.37 donation-from-
        # cache guard (module docstring). Never reuse a pre-existing
        # cache — not even a previous fleet's.
        cache_dir = os.path.join(fleet_dir, "jax_cache")
        if os.path.isdir(cache_dir):
            shutil.rmtree(cache_dir)
        os.makedirs(cache_dir)
        cache_created = True

    log = EventLog(events_path)
    t0 = time.time()
    log.event("fleet_start", tenants=n, max_concurrent=mc,
              fleet_dir=fleet_dir, cache_dir=cache_dir or None,
              warm_admission=bool(warm_admission and share_cache),
              max_lead=max_lead, labels=labels)

    runs: list = [None] * n
    admitted_order: list = []
    last_emit = [-1] * n     # last round a tenant_progress was emitted for
    warm_open = not (warm_admission and share_cache)
    throttled = [False] * n

    def _mk_handler(i):
        _map = {"launch": "tenant_start", "done": "tenant_finish"}

        def handler(ev, **fields):
            name = _map.get(ev, "tenant_" + ev)
            if ev == "done" and runs[i] is not None:
                fields.setdefault("rounds", runs[i].beats_total)
            log.event(name, tenant=i, label=labels[i], **fields)
        return handler

    def _admit(i) -> None:
        tdir = os.path.join(fleet_dir, f"t{i}")
        run_dir = os.path.join(tdir, "run")
        os.makedirs(run_dir, exist_ok=True)
        argv = _normalize(tenants[i])
        if namespace_args:
            # per-tenant checkpoint/state namespace: --resume auto after
            # a crash must find THIS tenant's checkpoints, never a
            # neighbor's (the isolation boundary, docs/packing.md)
            if "--checkpoint_path" not in argv:
                argv += ["--checkpoint_path", os.path.join(tdir, "ckpt")]
            if "--state_dir" not in argv:
                argv += ["--state_dir", os.path.join(tdir, "state")]
        env_extra = {
            "COMMEFFICIENT_RUN_DIR": run_dir,
            "COMMEFFICIENT_TENANT_ID": str(i),
        }
        if share_cache:
            env_extra["JAX_COMPILATION_CACHE_DIR"] = cache_dir
            if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" \
                    not in os.environ:
                env_extra["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] \
                    = "1"
        log.event("tenant_admit", tenant=i, label=labels[i],
                  argv=argv, run_dir=run_dir)
        runs[i] = ChildRun(
            argv, heartbeat_timeout=heartbeat_timeout,
            startup_grace=startup_grace, max_restarts=max_restarts,
            backoff=backoff, backoff_max=backoff_max, max_stale=max_stale,
            env_extra=env_extra, out=out,
            tag=f"[orchestrate t{i}]", on_event=_mk_handler(i))
        admitted_order.append(i)

    try:
        while True:
            for i, r in enumerate(runs):
                if r is None or r.terminal:
                    continue
                r.tick()
                if r.last_round > last_emit[i] and \
                        r.last_round - last_emit[i] >= progress_every:
                    log.event("tenant_progress", tenant=i,
                              label=labels[i], round=r.last_round,
                              beats=r.beats_total)
                    last_emit[i] = r.last_round
            if max_lead > 0:
                _apply_throttle(runs, throttled, max_lead, log, labels)
            # admission AFTER the tick pass, so the heartbeat that
            # opened the warm gate is already in the log when the
            # follower admissions land (the JSONL reads causally)
            active = sum(1 for r in runs if r is not None
                         and not r.terminal)
            # warm-admission gate: open once any admitted tenant has
            # heartbeat (cache written) or gone terminal (don't wedge
            # the fleet behind a tenant that can never beat)
            if not warm_open:
                warm_open = any(
                    r is not None and (r.beats_total > 0 or r.terminal)
                    for r in runs)
                if warm_open and len(admitted_order) < n:
                    log.event("fleet_warm",
                              warmed_by=admitted_order[0]
                              if admitted_order else None)
            pending = [i for i in range(n) if runs[i] is None]
            slots = mc - active
            if pending and slots > 0:
                if not admitted_order:
                    _admit(pending[0])   # first tenant: the cache warmer
                elif warm_open:
                    # never-admitted tenants all sit at zero progress,
                    # so least-progress-first degenerates to tenant-id
                    # order — deterministic, and the max_lead throttle
                    # above is what keeps the share fair AFTER admission
                    for i in pending[:slots]:
                        _admit(i)
            if all(r is not None and r.terminal for r in runs):
                break
            time.sleep(poll)
    except BaseException:
        for r in runs:
            if r is not None and not r.terminal:
                r.kill()
        raise
    finally:
        wall = time.time() - t0
        finished = sum(1 for r in runs
                       if r is not None and r.state == ChildRun.DONE)
        gave_up = sum(1 for r in runs
                      if r is not None and r.state == ChildRun.GAVE_UP)
        total_rounds = sum(r.beats_total for r in runs if r is not None)
        restarts = sum(r.restarts for r in runs if r is not None)
        log.event("fleet_done", admitted=len(admitted_order),
                  finished=finished, gave_up=gave_up, restarts=restarts,
                  total_rounds=total_rounds, wall_s=round(wall, 3),
                  rounds_per_sec=round(total_rounds / wall, 4)
                  if wall > 0 else None)
        log.close()
        if cache_created and not keep_cache:
            shutil.rmtree(cache_dir, ignore_errors=True)
    return 0 if all(r is not None and r.state == ChildRun.DONE
                    for r in runs) else 1


def _apply_throttle(runs, throttled, max_lead, log, labels) -> None:
    """SIGSTOP tenants more than ``max_lead`` rounds ahead of the
    slowest live tenant; SIGCONT them once the gap closes. The slowest
    tenant itself is never throttled (gap 0), so the fleet cannot
    deadlock."""
    live = [r for r in runs if r is not None and not r.terminal
            and r.beats_total > 0]
    if len(live) < 2:
        floor_round = None
    else:
        floor_round = min(r.last_round for r in live)
    for i, r in enumerate(runs):
        if r is None or r.terminal or r.beats_total == 0:
            continue
        lead = (r.last_round - floor_round
                if floor_round is not None else 0)
        if not throttled[i] and lead > max_lead \
                and r.state == ChildRun.RUNNING:
            r.pause()
            throttled[i] = True
            log.event("tenant_throttle", tenant=i, label=labels[i],
                      round=r.last_round, lead=lead)
        elif throttled[i] and lead <= max_lead:
            r.unpause()
            throttled[i] = False
            log.event("tenant_unthrottle", tenant=i, label=labels[i],
                      round=r.last_round, lead=lead)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
        usage="orchestrate.py [options] --tenant 'CMD...' "
              "[--tenant 'CMD...' ...]")
    ap.add_argument("--tenant", action="append", default=[],
                    help="one tenant child command (shlex-split; "
                         "repeatable)")
    ap.add_argument("--fleet-dir", default="",
                    help="fleet root (default runs/fleet_<timestamp>); "
                         "tenant t<i> gets <fleet>/t<i>/{ckpt,state,run}")
    ap.add_argument("--events", default="",
                    help="fleet JSONL path (default "
                         "<fleet-dir>/fleet_events.jsonl; render with "
                         "obs_report.py --fleet)")
    ap.add_argument("--max-concurrent", type=int, default=0,
                    help="bounded tenant concurrency (0 = all tenants "
                         "at once, after the warm-admission gate)")
    ap.add_argument("--max-lead", type=int, default=0,
                    help="fair-share throttle: SIGSTOP a tenant this "
                         "many rounds ahead of the slowest live tenant "
                         "until it catches up (0 disables)")
    ap.add_argument("--progress-every", type=int, default=1,
                    help="emit tenant_progress every N rounds")
    ap.add_argument("--no-shared-cache", action="store_true",
                    help="give tenants no shared compile cache (each "
                         "inherits the ambient env instead)")
    ap.add_argument("--no-warm-admission", action="store_true",
                    help="admit all tenants immediately instead of "
                         "letting the first warm the shared cache")
    ap.add_argument("--keep-cache", action="store_true",
                    help="keep the fleet's shared compile cache dir on "
                         "exit (default: deleted — the fresh-per-fleet "
                         "0.4.37 donation-from-cache guard)")
    ap.add_argument("--no-namespace-args", action="store_true",
                    help="don't append per-tenant --checkpoint_path/"
                         "--state_dir to tenant argvs")
    ap.add_argument("--heartbeat-timeout", type=float, default=120.0)
    ap.add_argument("--startup-grace", type=float, default=900.0)
    ap.add_argument("--max-restarts", type=int, default=5)
    ap.add_argument("--backoff", type=float, default=2.0)
    ap.add_argument("--backoff-max", type=float, default=60.0)
    ap.add_argument("--max-stale", type=int, default=200)
    args = ap.parse_args(argv)
    if not args.tenant:
        ap.error("no tenants given (repeat --tenant 'CMD ...')")
    tenants = [shlex.split(t) for t in args.tenant]
    fleet_dir = args.fleet_dir or os.path.join(
        "runs", f"fleet_{time.strftime('%Y%m%d_%H%M%S')}")
    labels = [os.path.basename(t[0]) if t else f"t{i}"
              for i, t in enumerate(tenants)]
    rc = orchestrate(
        tenants, fleet_dir=fleet_dir, labels=labels,
        max_concurrent=args.max_concurrent,
        warm_admission=not args.no_warm_admission,
        share_cache=not args.no_shared_cache,
        keep_cache=args.keep_cache,
        namespace_args=not args.no_namespace_args,
        max_lead=args.max_lead, progress_every=args.progress_every,
        heartbeat_timeout=args.heartbeat_timeout,
        startup_grace=args.startup_grace,
        max_restarts=args.max_restarts, backoff=args.backoff,
        backoff_max=args.backoff_max, max_stale=args.max_stale,
        events_path=args.events)
    events = args.events or os.path.join(fleet_dir, "fleet_events.jsonl")
    print(f"[orchestrate] fleet {'complete' if rc == 0 else 'DEGRADED'} "
          f"(rc {rc}); render with: python scripts/obs_report.py "
          f"--fleet {events}", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
