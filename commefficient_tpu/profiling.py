"""Profiling / tracing subsystem.

The reference has only remnants of profiling scaffolding — commented cProfile
and LineProfiler hookups (reference fed_aggregator.py:32-52,
cv_train.py:26-29, 292-305) and a manual ``Timer``. The TPU-native
replacement is ``jax.profiler``: XLA-level traces viewable in
TensorBoard/Perfetto, capturing device compute, HBM transfers, and collective
time — strictly more information than the reference's host-side cProfile.

``RoundTracer`` is the one class that starts and stops a profiler session
(``--profile``, ``--trace_rounds``, the watch plane's trace reaction: all
windows over the global round index). ``annotate`` is the one way to open
a host span: it lands on the profiler's clock beside the device operations
AND adds its duration to ``SPAN_TOTALS``, which the event log reads
(``RunTelemetry``), so a run with the profiler off still leaves the host's
waits in its run dir. ``DEVICE_STAGES`` / ``KERNEL_NAMES`` are the names
the jitted round and its Pallas kernels carry on the device
(``jax.named_scope`` / ``pallas_call(name=)``); docs/observability.md lists
every name with its reader.

``phase`` / ``PHASES``, ``program_totals`` and ``memory_sample`` are the
run's record of itself outside the round loop, built on ``annotate`` and
``SPAN_TOTALS``: the stretches of start-up (``fed_setup_<name>`` spans, one
ordered entry each), every program jax traced, lowered and compiled or
loaded, by name (one set of ``jax.monitoring`` listeners,
``install_program_listener``), and the device's memory at the end of a
phase, of a batched drain, around a validation pass and at the run's end.
The event log writes them (``setup`` / ``program`` / ``drain.memory`` /
``val`` / ``run_end``), ``scripts/obs_report.py`` prints them.

``host_sync_monitor`` is the pipelined round engine's audit hook
(federated/engine.py, docs/round_engine.md): it counts blocking
device→host materializations so the steady-state zero-syncs-per-round
invariant is assertable in tests.
``jax.transfer_guard`` is the natural tool but is inert on the CPU backend
the test suite runs on (measured — "disallow" lets both array and scalar
fetches through), and ``np.asarray`` on a CPU-backed ``jax.Array`` reads
the buffer protocol directly, bypassing any Python-level wrapper. The
portable counter therefore has two layers: (1) global wraps of the scalar
conversion entry points (``float``/``int``/``bool``/``item``/``_value``,
which do route through Python), and (2) the ``materialize()`` seam every
framework-internal array fetch goes through (aggregator drains, engine).
``strict=True`` additionally arms the real transfer guard on device
backends, where it turns ANY device→host transfer into a hard error.
"""

from __future__ import annotations

import collections
import contextlib
import os
import re
import sys
import threading
import time

import jax

__all__ = ["annotate", "SPAN_TOTALS", "span_totals", "phase", "PHASES",
           "begin_setup", "phase_table", "install_program_listener",
           "program_totals", "program_summary", "subscribe_programs",
           "unsubscribe_programs", "memory_sample", "DEVICE_STAGES",
           "INNER_SCOPES", "KERNEL_NAMES", "SyncCounter", "host_sync_monitor",
           "materialize", "offpath_fetches", "Heartbeat", "RoundTracer",
           "parse_trace_rounds", "HEARTBEAT_RE", "parse_heartbeat"]

# The round's stages on the device: one ``jax.named_scope`` each, where the
# work is traced (worker.py, rounds.py, server.py, ops/sketch.py,
# telemetry.py, aggregator.py). An operation's scope path holds exactly one
# of them (tests/test_tracing.py), under whatever transform wrapped it
# (``transpose(jvp(fed_client_grad))`` for the backward pass).
DEVICE_STAGES = ("fed_client_grad", "fed_client_compress",
                 "fed_server_estimate", "fed_server_topk",
                 "fed_server_resketch", "fed_server_apply",
                 "fed_telemetry_metrics", "fed_accounting", "fed_val")
# Scopes a model opens INSIDE ``fed_client_grad`` (models/joyai.py,
# models/laguna.py, models/ouro.py, parallel/moe.py): the expert layer's
# routing (scores, top-k, grouping, gather and scatter of the held pairs),
# its grouped products, the latent attention's core, and the grouped-query
# attention's (RoPE, the core, the head gate) with the kind of its layer
# nested in it (ops/attention.py opens an attention scope again around its
# backward pass); of a stack run as a recurrence (models/ouro.py) the
# blocks of a pass (``fed_loop_body``, the attention's scopes nested in it)
# and what is read after a pass (``fed_loop_head``: final norm, head, NLL,
# exit gate). Not stages: an operation under one of them still has
# ``fed_client_grad`` as its one stage.
INNER_SCOPES = ("fed_moe_route", "fed_moe_experts", "fed_mla_attn",
                "fed_gqa_attn", "fed_gqa_attn_full", "fed_gqa_attn_window",
                "fed_loop_body", "fed_loop_head")
# ``name=`` of every pallas_call (ops/sketch.py, ops/topk.py,
# ops/attention.py). The sketch kernels keep ``sketch`` / ``estimates`` /
# ``epilogue`` in theirs and the top-k and attention kernels do not:
# benchmark/metrics/sketch_kernel_roofline.py tells them apart by those
# words.
KERNEL_NAMES = ("fed_sketch_vec", "fed_sketch_accum", "fed_estimates",
                "fed_epilogue", "fed_topk_count", "fed_topk_descent",
                "fed_mla_attn_fwd", "fed_mla_attn_bwd",
                "fed_gqa_attn_fwd", "fed_gqa_attn_bwd")


# THE heartbeat line format, one producer (Heartbeat.round) and one parser
# (parse_heartbeat) — the crash harness (scripts/crash_matrix.py) and the
# self-healing supervisor (scripts/supervise.py) both key liveness on it,
# so the format lives next to its emitter instead of as private regexes
# drifting per consumer. Supervisors key on the leading ``round=N``; the
# optional extras (epoch / loss / guard verdict) append after it.
HEARTBEAT_RE = re.compile(
    r"HEARTBEAT round=(\d+)"
    r"(?: epoch=(\d+))?"
    r"(?: loss=(\S+))?"
    r"(?: guard=(ok|TRIP))?"
    r"(?: buf=(\d+))?"
    r"(?: stale=(\d+))?"
    r"(?: population=(\d+))?"
    r"(?: serve_lag=(\d+))?")


def parse_heartbeat(line: str):
    """Parse one ``Heartbeat.round`` stderr line; None for non-heartbeat
    lines. Returns ``{"round": int}`` plus whichever optional fields the
    line carried (``epoch`` int, ``loss`` float, ``guard_ok`` bool; —
    async buffered federation, docs/async.md — ``buf`` int buffer depth
    and ``stale`` int dispatch-age of the oldest un-folded contribution;
    — always-on service, docs/service.md — ``population`` int live
    population under ``--churn`` and ``serve_lag`` int newest-minus-
    served model version from a serving replica)."""
    m = HEARTBEAT_RE.match(line.strip())
    if m is None:
        return None
    out = {"round": int(m.group(1))}
    if m.group(2) is not None:
        out["epoch"] = int(m.group(2))
    if m.group(3) is not None:
        try:
            out["loss"] = float(m.group(3))
        except ValueError:
            pass
    if m.group(4) is not None:
        out["guard_ok"] = m.group(4) == "ok"
    if m.group(5) is not None:
        out["buf"] = int(m.group(5))
    if m.group(6) is not None:
        out["stale"] = int(m.group(6))
    if m.group(7) is not None:
        out["population"] = int(m.group(7))
    if m.group(8) is not None:
        out["serve_lag"] = int(m.group(8))
    return out


class Heartbeat:
    """Per-round liveness lines for an external supervisor
    (scripts/crash_matrix.py, docs/fault_tolerance.md).

    Owned by ``PipelinedRoundEngine`` since the telemetry plane landed
    (docs/observability.md): the engine emits one line per DRAINED round
    carrying the telemetry round index — the model's global dispatch
    counter (``RoundHandle.round_no``), monotonic across epochs and engine
    instances — so a supervisor can target an absolute round by parsing
    the value instead of counting lines.

    When armed (``COMMEFFICIENT_HEARTBEAT=1``, or ``enabled=True``), each
    round emits one ``HEARTBEAT round=N`` line to stderr, flushed
    immediately — a supervisor that SIGKILLs the process at a randomized
    round still holds an exact trail of how far training got. The engine
    also passes the drained round's mean loss and (with ``--guards``) the
    guard verdict, so a ``COMMEFFICIENT_HEARTBEAT=1`` stderr tail is a
    minimal live monitor even with telemetry off. Supervisors consume
    lines through ``parse_heartbeat`` (the one parser of this format);
    the extras append after the leading ``round=N``. Disabled (the
    default) it is a no-op on the hot path."""

    def __init__(self, enabled: bool | None = None):
        if enabled is None:
            enabled = os.environ.get("COMMEFFICIENT_HEARTBEAT") == "1"
        self.enabled = bool(enabled)

    def round(self, index: int, epoch: int | None = None,
              loss: float | None = None,
              guard_ok: bool | None = None,
              buffer: int | None = None,
              stale: int | None = None,
              population: int | None = None,
              serve_lag: int | None = None) -> None:
        """``buffer``/``stale`` (async buffered federation, docs/async.md)
        carry the landed-but-unfolded buffer depth and the dispatch-age of
        the oldest un-folded contribution, so a full-but-never-folding
        buffer is visible to the supervisor's hang detection
        (scripts/supervise.py --max-stale) even while dispatch heartbeats
        keep ticking. ``population`` (--churn) is the live population;
        ``serve_lag`` (a serving replica's heartbeat, docs/service.md) is
        newest-available minus currently-served model version — a wedged
        replica beats with a growing lag instead of going silent."""
        if not self.enabled:
            return
        line = f"HEARTBEAT round={index}"
        if epoch is not None:
            line += f" epoch={epoch}"
        if loss is not None:
            line += f" loss={loss:.6g}"
        if guard_ok is not None:
            line += f" guard={'ok' if guard_ok else 'TRIP'}"
        if buffer is not None:
            line += f" buf={buffer}"
        if stale is not None:
            line += f" stale={stale}"
        if population is not None:
            line += f" population={population}"
        if serve_lag is not None:
            line += f" serve_lag={serve_lag}"
        print(line, file=sys.stderr, flush=True)


def parse_trace_rounds(spec: str) -> list:
    """``--trace_rounds`` spec → list of (start_round, count) windows.
    The spec is 'START:COUNT[,START:COUNT...]' over GLOBAL round_no
    dispatch indices; malformed specs fail here at parse time."""
    windows = []
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        try:
            start, count = (int(x) for x in part.split(":"))
        except ValueError:
            raise ValueError(
                f"--trace_rounds: bad entry {part!r}; expected "
                "START:COUNT (e.g. '10:3' or '10:3,200:5')") from None
        assert start >= 0, f"--trace_rounds: start {start} must be >= 0"
        assert count >= 1, f"--trace_rounds: count {count} must be >= 1"
        windows.append((start, count))
    return sorted(windows)


def _try_start_trace(logdir: str) -> bool:
    """Start a profiler session into ``logdir``; False (and a message)
    when one is already running. RoundTracer is the program's only
    starter and holds at most one window open, so that can only be a
    session somebody else started (a harness, a notebook): the window is
    skipped, the run goes on."""
    os.makedirs(logdir, exist_ok=True)
    try:
        jax.profiler.start_trace(logdir)
    except Exception as e:  # noqa: BLE001 — a foreign active session
        print(f"trace capture skipped: profiler unavailable ({e})")
        return False
    return True


def _stop_trace() -> None:
    with contextlib.suppress(Exception):
        jax.profiler.stop_trace()


class RoundTracer:
    """Round-scoped programmatic XLA trace capture (docs/observability.md).

    Addressed in the global round_no timeline: ``--trace_rounds
    start:count`` windows, ``--profile``'s window (rounds 2 … 2+N-1, into
    ``--profile_dir``), plus dynamic ``request(n)`` windows from the watch
    plane's trace reaction — so a capture is aimable at an absolute round
    ("trace rounds 2000-2004 where the alert fired") without hand-aiming a
    profiler session. A static window is ``(start, count)`` or ``(start,
    count, dir)``; without a directory of its own it lands in
    ``<logdir>/trace_round_<start>``.

    Driven by the engine: ``on_submit(round_no)`` BEFORE a round's
    dispatch (starts ``jax.profiler.start_trace`` into
    ``<logdir>/trace_round_<start>`` — the directory is NAMED by the
    global round_no it actually starts at); ``on_drained(round_no)`` when
    a round's batched drain lands (stops the trace once the window's last
    round has drained — its device compute is provably complete then, so
    the window's rounds are inside the capture). Returns the capture
    record for the engine to log as a ``trace_captured`` JSONL event.
    Pipelining caveat, by design: neighbors of the window that were in
    flight during it appear in the trace too; the named window is a lower
    bound, and the round-aligned ``fed_round`` step spans (``round=`` in
    their metadata) mark the exact spans inside the capture."""

    def __init__(self, logdir: str, windows=None):
        self.logdir = logdir
        self._pending = sorted(windows or [])  # static (start, count[, dir])
        self._requests = 0                    # dynamic: rounds still owed
        self._active = None                   # {start, until, dir}
        self.captures = []                    # completed capture records

    def request(self, count: int) -> bool:
        """Dynamic capture request (the watch trace reaction): trace the
        next ``count`` submitted rounds. Returns False when a capture is
        already active or pending-dynamic (no nested traces)."""
        if self._active is not None or self._requests:
            return False
        self._requests = int(count)
        return True

    def on_submit(self, round_no: int) -> None:
        """Called before round ``round_no``'s dispatch; may start a
        capture."""
        if self._active is not None:
            return
        trace_dir = os.path.join(self.logdir,
                                 f"trace_round_{round_no:06d}")
        if self._requests:
            count, self._requests = self._requests, 0
        elif self._pending and round_no >= self._pending[0][0]:
            # a static window whose start round is due (or was skipped
            # over: resumed past it, or another window was still open —
            # start now rather than never)
            _, count, *own_dir = self._pending.pop(0)
            if own_dir:
                trace_dir = own_dir[0]
        else:
            return
        if not _try_start_trace(trace_dir):
            return
        self._active = {"start": round_no,
                        "until": round_no + count - 1,
                        "dir": trace_dir}

    def on_drained(self, round_no: int):
        """Called per drained round; stops the active capture once the
        window's last round has drained. Returns the capture record (for
        the ``trace_captured`` event) or None."""
        if self._active is None or round_no < self._active["until"]:
            return None
        return self._stop()

    def close(self):
        """Stop a capture left open at run end (e.g. the run ended inside
        the window). Returns the partial capture record or None."""
        if self._active is None:
            return None
        return self._stop()

    def _stop(self):
        rec, self._active = self._active, None
        _stop_trace()
        rec = {"round_start": rec["start"], "round_until": rec["until"],
               "dir": rec["dir"]}
        self.captures.append(rec)
        print(f"trace captured: rounds {rec['round_start']}-"
              f"{rec['round_until']} -> {rec['dir']}")
        return rec


# name -> [count, ns] of every span closed in this process, whatever
# thread closed it. The event log's per-round durations are differences of
# these totals (telemetry.RunTelemetry) and ``run_end`` carries them whole:
# the host's numbers of a run whose profiler was never on.
SPAN_TOTALS: dict = {}
_span_lock = threading.Lock()


def _count_span(name: str, ns: int) -> None:
    with _span_lock:
        tot = SPAN_TOTALS.setdefault(name, [0, 0])
        tot[0] += 1
        tot[1] += ns


class annotate:
    """THE way to open a host span: ``with annotate("fed_h2d", round=n):``.

    Opens a ``jax.profiler.TraceAnnotation`` (``ids`` become the event's
    metadata), so every program span shares the device trace's clock, and
    on exit adds one count and the span's ``time.perf_counter_ns``
    duration to ``SPAN_TOTALS[name]``. ``start_ns`` / ``end_ns`` / ``ms``
    stay readable on the object after the block, for the caller that
    records this very span (the engine's round record). Near-free with the
    profiler off: two clock reads and one locked add."""

    __slots__ = ("name", "_ann", "start_ns", "end_ns")

    def __init__(self, name: str, **ids):
        self.name = name
        self._ann = jax.profiler.TraceAnnotation(name, **ids)
        self.start_ns = self.end_ns = 0

    def __enter__(self):
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _count_span(self.name, self.end_ns - self.start_ns)
        return False

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6


def span_totals() -> dict:
    """``{name: {"count", "ms"}}`` of ``SPAN_TOTALS`` — what ``run_end``
    records and ``scripts/obs_report.py`` prints."""
    with _span_lock:
        return {name: {"count": c, "ms": round(ns / 1e6, 3)}
                for name, (c, ns) in sorted(SPAN_TOTALS.items())}


# ---------------------------------------------------------------------------
# the run's record of itself outside the round loop: device memory, the
# programs built, the phases of start-up (docs/observability.md §Names)
# ---------------------------------------------------------------------------

MEMORY_FIELDS = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
                 "peak_bytes_reserved", "largest_free_block_bytes",
                 "num_allocs")


def _local_devices():
    # never the call that initialises a backend: a sample taken before the
    # process has touched its devices has nothing to read
    from jax._src import xla_bridge

    return jax.local_devices() if xla_bridge.backends_are_initialized() \
        else []


def memory_sample(at: str):
    """``memory_stats()`` of the fullest local device, reduced to
    ``MEMORY_FIELDS`` and labelled ``at``; ``None`` where the backend gives
    none (the CPU). A host call into the runtime's allocator: no
    device-to-host fetch, nothing the sync audit counts. The two peaks are
    the process's, never reset: a sample says how high each had risen by
    then."""
    best = None
    for dev in _local_devices():
        stats = dev.memory_stats()
        if stats and (best is None or stats.get("bytes_in_use", 0)
                      > best.get("bytes_in_use", 0)):
            best = stats
    if best is None:
        return None
    out = {"at": at}
    out.update((k, int(best[k])) for k in MEMORY_FIELDS if k in best)
    return out


_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_STORED = "/jax/compilation_cache/cache_misses"  # fires on a WRITE
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_BUILD_SPANS = frozenset((_TRACE, _LOWER, _BACKEND))
LISTENER_SPAN = "fed_program_listener"
# the event log gives a build a line of its own from this many seconds in
# all (telemetry.RunTelemetry); ``run_end.programs`` sums the programs
# below it under ``other``
PROGRAM_LOG_S = 0.1

# every build closed in this process, oldest first (bounded: a process
# that recompiles without end must not grow without end), its totals by
# name, and who is told of a build as it closes (the event log)
PROGRAMS: collections.deque = collections.deque(maxlen=4096)
_PROGRAM_TOTALS: dict = {}
_program_sinks: list = []
_built = [0, 0.0]            # builds closed, their seconds in all
_program_lock = threading.Lock()
_building = threading.local()
_listening = False


def _emit_build(b: dict) -> None:
    b.setdefault("trace_s", 0.0)
    b.setdefault("lower_s", 0.0)
    b.setdefault("backend_s", 0.0)
    b["phase"] = _open_phase[0]
    b["t"] = time.time()
    secs = b["trace_s"] + b["lower_s"] + b["backend_s"]
    with _program_lock:
        PROGRAMS.append(b)
        _built[0] += 1
        _built[1] += secs
        tot = _PROGRAM_TOTALS.setdefault(b["name"], {
            "builds": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "load_s": 0.0, "hits": 0, "misses": 0, "stored": 0})
        tot["builds"] += 1
        for k in ("trace_s", "lower_s", "backend_s"):
            tot[k] += b[k]
        tot["load_s"] += b.get("load_s", 0.0)
        tot["hits"] += b.get("cache") == "hit"
        tot["misses"] += b.get("cache") == "miss"
        tot["stored"] += bool(b.get("stored"))
        sinks = list(_program_sinks)
    for sink in sinks:
        sink(b)


def _span_closed(event: str, name: str, secs: float) -> None:
    """A top-level tracing, lowering or backend span closed on this thread.
    One build is a trace, then a lowering, then a backend compile (which
    contains the persistent cache's load); a trace or a lowering that
    nothing compiled (``eval_shape``, ``.lower()``) is a build of its own
    with no ``backend_s``."""
    b = getattr(_building, "build", None)
    if event == _TRACE:
        if b is not None:
            _emit_build(b)
        _building.build = {"name": name, "trace_s": secs}
    elif event == _LOWER:
        if b is not None and "lower_s" in b:
            _emit_build(b)
            b = None
        if b is None:
            b = _building.build = {}
        b.update(name=name, lower_s=secs)
    else:
        if b is not None and "lower_s" in b and b["name"] != name:
            _emit_build(b)      # lowered ahead of time, never compiled
            b = None
        b = b if b is not None else {}
        _building.build = None
        c = _building.cache
        # (jax asks its cache even where no directory is set)
        b.update(name=name, backend_s=secs,
                 cache="hit" if c["hit"] else
                 "miss" if c["requested"]
                 and jax.config.jax_compilation_cache_dir else "off")
        if c["hit"]:
            b["load_s"] = c["load_s"]
        if c["stored"]:
            b["stored"] = True
        _emit_build(b)


def _timed(fn):
    """The listener's own cost, under ``SPAN_TOTALS[LISTENER_SPAN]`` (no
    profiler annotation: it runs inside jax's compile path)."""
    def wrapped(event, *a, **kw):
        t = time.perf_counter_ns()
        try:
            fn(event, *a, **kw)
        finally:
            _count_span(LISTENER_SPAN, time.perf_counter_ns() - t)
    return wrapped


def _on_scalar(event, value, **kw):
    # jax marks the START of a tracing / lowering / backend span with a
    # scalar of the same name: spans nest (a traced function calls jitted
    # ones), and only the outermost is a build's
    if event in _BUILD_SPANS:
        stack = _building.__dict__.setdefault("stack", [])
        stack.append(event)
        if event == _BACKEND:
            _building.cache = {"requested": False, "hit": False,
                               "stored": False, "load_s": 0.0}


def _on_duration(event, secs, **kw):
    if event in _BUILD_SPANS:
        stack = _building.__dict__.get("stack")
        if not stack:
            return       # opened before the listener was registered
        stack.pop()
        if not stack:
            _span_closed(event, str(kw.get("fun_name", "?")), float(secs))
    elif event == _CACHE_LOAD:
        cache = _building.__dict__.get("cache")
        if cache is not None:
            cache["load_s"] += float(secs)


def _on_event(event, **kw):
    cache = _building.__dict__.get("cache")
    if cache is None:
        return
    if event == _CACHE_REQUEST:
        cache["requested"] = True
    elif event == _CACHE_HIT:
        cache["hit"] = True
    elif event == _CACHE_STORED:
        cache["stored"] = True


def install_program_listener() -> None:
    """Register the process's one set of ``jax.monitoring`` listeners (jax
    has no way to take one off again); ``utils.configure_compile_cache``
    calls it, which every entry point does before its first compile."""
    global _listening
    with _program_lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_scalar_listener(_timed(_on_scalar))
    jax.monitoring.register_event_duration_secs_listener(
        _timed(_on_duration))
    jax.monitoring.register_event_listener(_timed(_on_event))


def _flush_open_build() -> None:
    b = getattr(_building, "build", None)
    if b is not None and not getattr(_building, "stack", None):
        _building.build = None
        _emit_build(b)


def _rounded(tot: dict) -> dict:
    return {k: round(v, 4) if isinstance(v, float) else v
            for k, v in tot.items()}


def program_totals() -> dict:
    """``{name: {"builds", "trace_s", "lower_s", "backend_s", "load_s",
    "hits", "misses", "stored"}}`` of every program built in this process,
    like ``span_totals()``. ``backend_s`` contains ``load_s``; ``misses``
    are builds that asked the persistent cache and compiled, ``stored`` the
    ones written to it (above jax's floors)."""
    _flush_open_build()
    with _program_lock:
        return {name: _rounded(tot)
                for name, tot in sorted(_PROGRAM_TOTALS.items())}


def program_summary(floor_s: float = PROGRAM_LOG_S) -> dict:
    """``program_totals()`` for ``run_end``: programs under ``floor_s`` in
    all that never missed the cache are summed under ``other``."""
    out, other = {}, None
    for name, tot in program_totals().items():
        if (tot["trace_s"] + tot["lower_s"] + tot["backend_s"] >= floor_s
                or tot["misses"]):
            out[name] = tot
            continue
        if other is None:
            other = dict.fromkeys(tot, 0)
        for k, v in tot.items():
            other[k] += v
    if other is not None:
        out["other"] = _rounded(other)
    return out


def subscribe_programs(sink) -> list:
    """Tell ``sink(build)`` of every build from now on, as it closes (on
    the thread that built it); returns the builds closed so far, which the
    caller has to take itself."""
    _flush_open_build()
    with _program_lock:
        _program_sinks.append(sink)
        return list(PROGRAMS)


def unsubscribe_programs(sink) -> None:
    with _program_lock:
        if sink in _program_sinks:
            _program_sinks.remove(sink)


def _process_start_epoch():
    """When the OS started this process, as seconds since the epoch; None
    where it does not say (no /proc)."""
    try:
        with open("/proc/self/stat", "rb") as f:
            ticks = int(f.read().rsplit(b")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.time() - age if 0.0 <= age < 30 * 86400 else None


# The stretches of this process's life so far, oldest first:
# ``{"phase", "start_s", "seconds", "programs", "build_s", "memory"}``,
# ``start_s`` since the process started (since this module was imported,
# where the OS gives no start time). ``begin_setup`` empties it: a process
# that runs an entry point twice records each run's own.
PHASES: list = []
PROCESS_START_T = _process_start_epoch()
_ORIGIN_T = PROCESS_START_T if PROCESS_START_T is not None else time.time()
_open_phase = [None]
_import_recorded = [False]


def begin_setup() -> None:
    """An entry point's first call once its arguments are parsed and its
    devices announced: forget an earlier run's phases and builds and, on
    the process's first run, record ``import``, process start to now, where
    the OS says when the process started; never guessed."""
    PHASES.clear()
    if _import_recorded[0]:
        # a later run of this process: the builds so far were the earlier
        # runs' (the totals by name stay the process's)
        _flush_open_build()
        with _program_lock:
            PROGRAMS.clear()
        return
    _import_recorded[0] = True
    if PROCESS_START_T is None:
        return
    seconds = time.time() - PROCESS_START_T
    _count_span("fed_setup_import", int(seconds * 1e9))
    _record_phase("import", 0.0, seconds, (0, 0.0))


def _record_phase(name: str, start_s: float, seconds: float, built) -> None:
    """One entry of ``PHASES``; ``built`` is ``_built`` as the phase began."""
    with _program_lock:
        n, s = _built[0] - built[0], _built[1] - built[1]
    PHASES.append({"phase": name, "start_s": round(start_s, 3),
                   "seconds": round(seconds, 3), "programs": n,
                   "build_s": round(s, 3),
                   "memory": memory_sample("phase:" + name)})


class phase(annotate):
    """One stretch of start-up: ``with phase("data"):`` is an
    ``annotate("fed_setup_data")`` span plus one entry of ``PHASES`` with
    the programs built inside it and the device's memory at its end.
    Phases follow each other on the main thread; they do not nest."""

    __slots__ = ("phase_name", "_t", "_built")

    def __init__(self, name: str):
        super().__init__("fed_setup_" + name)
        self.phase_name = name

    def __enter__(self):
        assert _open_phase[0] is None, \
            f"phase {self.phase_name!r} inside {_open_phase[0]!r}"
        _open_phase[0] = self.phase_name
        self._t = time.time()
        with _program_lock:
            self._built = tuple(_built)
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        _flush_open_build()
        _open_phase[0] = None
        _record_phase(self.phase_name, self._t - _ORIGIN_T, self.ms / 1e3,
                      self._built)
        return False


def phase_table() -> str:
    """``PHASES`` as the table both entry points print once set-up is
    done."""
    rows = [f"{'phase':<10}{'start_s':>9}{'seconds':>9}{'programs':>9}"
            f"{'build_s':>9}{'hbm_in_use_gib':>16}"]
    for ph in PHASES:
        mem = ph.get("memory")
        rows.append(
            f"{ph['phase']:<10}{ph['start_s']:>9.2f}{ph['seconds']:>9.2f}"
            f"{ph['programs']:>9d}{ph['build_s']:>9.2f}"
            + (f"{mem['bytes_in_use'] / 2**30:>16.3f}" if mem
               else f"{'-':>16}"))
    total = sum(ph["seconds"] for ph in PHASES)
    return (f"set-up: {total:.2f} s in {len(PHASES)} phases "
            "(docs/observability.md)\n" + "\n".join(rows))


class SyncCounter:
    """Mutable tally of blocking device→host materializations observed
    while a ``host_sync_monitor`` is active."""

    def __init__(self):
        self.count = 0

    def __int__(self):
        return self.count

    def __repr__(self):
        return f"SyncCounter(count={self.count})"


# wrapper state: the patch is installed once and counts into whatever
# monitors are active (nesting-safe); _depth guards double counting when one
# conversion path calls another (__array__ -> _value).
_lock = threading.Lock()
_active: list = []
_installed = False
_depth = threading.local()


def _count_sync():
    if getattr(_depth, "n", 0) > 0:
        return
    for c in _active:
        c.count += 1


def materialize(x):
    """Blocking device→host fetch of ``x`` as a numpy array — THE seam the
    framework's own drains go through (aggregator ``finish_round``,
    engine metric drains), so ``host_sync_monitor`` can count them on CPU
    where ``np.asarray`` reads the buffer protocol and is untraceable."""
    import numpy as np

    if isinstance(x, jax.Array):
        _count_sync()
        # on device backends np.asarray dispatches to the wrapped
        # __array__/_value (no buffer protocol for device memory) — raise
        # the reentrancy depth so this ONE fetch is not counted twice
        _depth.n = getattr(_depth, "n", 0) + 1
        try:
            return np.asarray(x)
        finally:
            _depth.n -= 1
    return np.asarray(x)


@contextlib.contextmanager
def offpath_fetches():
    """Declare the dynamic extent an OFF-dispatch-path background drain.

    The zero-syncs invariant the round engine audits is about the round
    DISPATCH path: the host thread driving submit() must never stall on a
    device fetch. The disk-tier row store (host_state.MemmapRowStore)
    deliberately materializes scatter deltas on its dedicated I/O worker
    thread, overlapped with the next round's device compute — those
    fetches are the data plane working as designed, not a dispatch-path
    stall, so the worker wraps its loop body in this context and the
    ``host_sync_monitor`` tally stays an audit of the dispatch path.
    Thread-local (rides the same reentrancy depth the conversion wrappers
    use), so it never masks fetches on other threads."""
    _depth.n = getattr(_depth, "n", 0) + 1
    try:
        yield
    finally:
        _depth.n -= 1


def _install_sync_hooks():
    """Wrap the blocking scalar-conversion entry points of ``ArrayImpl``.
    The set is version-sensitive (on jax 0.4.x ``__float__`` routes through
    Python while ``np.asarray`` takes the C-level buffer protocol — see the
    module docstring), so each wrapper both counts and bumps a reentrancy
    depth — whichever entry point fires first claims the sync, nested ones
    are silent."""
    global _installed
    if _installed:
        return
    from jax._src import array as _array_mod

    cls = _array_mod.ArrayImpl

    def wrap_method(name):
        orig = getattr(cls, name, None)
        if orig is None:
            return

        def wrapper(self, *a, **kw):
            _count_sync()
            _depth.n = getattr(_depth, "n", 0) + 1
            try:
                return orig(self, *a, **kw)
            finally:
                _depth.n -= 1

        wrapper.__name__ = name
        setattr(cls, name, wrapper)

    # _value is the shared materialization property (np.asarray, bool, int,
    # tolist); the dunders cover the scalar paths that bypass it
    orig_value = cls._value

    def value_wrapper(self):
        _count_sync()
        _depth.n = getattr(_depth, "n", 0) + 1
        try:
            return orig_value.fget(self)
        finally:
            _depth.n -= 1

    cls._value = property(value_wrapper)
    for name in ("__array__", "__float__", "__int__", "__bool__",
                 "__index__", "item"):
        wrap_method(name)
    _installed = True


@contextlib.contextmanager
def host_sync_monitor(strict: bool = False):
    """Count blocking device→host materializations in the dynamic extent.

    Yields a ``SyncCounter``. ``jax.block_until_ready`` (a completion wait,
    not a transfer) and host→device ``jnp.asarray`` uploads do NOT count —
    the tally is exactly the fetches the pipelined round engine's every-N
    drain exists to batch. With ``strict=True`` on a non-CPU backend,
    ``jax.transfer_guard_device_to_host("disallow")`` is armed as well, so
    any counted sync also raises at the XLA runtime layer."""
    _install_sync_hooks()
    counter = SyncCounter()
    guard = (jax.transfer_guard_device_to_host("disallow")
             if strict and jax.default_backend() != "cpu"
             else contextlib.nullcontext())
    with _lock:
        _active.append(counter)
    try:
        with guard:
            yield counter
    finally:
        with _lock:
            _active.remove(counter)
