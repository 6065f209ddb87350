"""Schedules, loggers, and timing utilities.

Behavioral parity with the reference's utility layer (reference utils.py:14-99):
``PiecewiseLinear`` / ``Exp`` LR schedules, fixed-width console table logging,
TSV logging, and a cumulative wall-clock timer. Re-written for a JAX host loop
(no torch dependencies); schedules are also exposed as pure callables usable
inside ``optax``/jit.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Sequence

import numpy as np

__all__ = [
    "PiecewiseLinear",
    "Exp",
    "Const",
    "Logger",
    "TableLogger",
    "TSVLogger",
    "Timer",
    "make_logdir",
    "union",
    "is_tpu_backend",
    "configure_compile_cache",
    "announce_devices",
]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def is_tpu_backend() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def configure_compile_cache() -> str:
    """Place JAX's persistent compile cache; returns the directory in
    effect. Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax itself reads
    it and nothing is set in code (an empty value means no persistent
    cache, to jax and so here). Otherwise the cache is the fixed
    in-checkout ``<checkout>/.jax_cache`` (git-ignored) — never a temp
    name, pid or time: a fresh chip machine starts cold, so the only warm
    start a second process gets is a path both agree on without being
    told. Must run before the process's first compile (jax initializes
    its cache once).

    Either way the cache's key includes the programs' metadata. jax leaves
    it out by default, and the metadata is where the round's stage names
    live (``jax.named_scope``, profiling.DEVICE_STAGES): a cache filled by
    a build without a scope then serves that build's executable to one
    with it, and a capture shows the old names (seen on the v5e, PERF.md
    PR 26: the accounting programs came back without ``fed_accounting``).
    The price is that a moved line recompiles what it touches.

    Also registers the process's one set of ``jax.monitoring`` listeners
    (``profiling.install_program_listener``): every program traced,
    lowered, compiled or loaded from here on is recorded by name."""
    import jax

    from commefficient_tpu.profiling import install_program_listener

    install_program_listener()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def announce_devices() -> dict:
    """Print (flushed) the devices this process got, as JAX reports them,
    and return ``{"platform", "kind", "count"}``. Both entry points call
    it at start-up: a libtpu that failed to initialize otherwise trains
    on the CPU without a word."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"devices: platform={info['platform']} "
          f"device_kind={info['kind']!r} count={info['count']} "
          f"(jax {jax.__version__})", flush=True)
    return info


@dataclass(frozen=True)
class PiecewiseLinear:
    """Piecewise-linear schedule: value at ``t`` interpolated between knots.

    Mirrors reference utils.py:26-28 (np.interp over (knots, vals)).
    """

    knots: Sequence[float]
    vals: Sequence[float]

    def __call__(self, t):
        return np.interp([t], self.knots, self.vals)[0]


@dataclass(frozen=True)
class Exp:
    """Exponential decay ``initial * decay**t`` (reference utils.py:30-35)."""

    initial: float
    decay: float

    def __call__(self, t):
        return self.initial * (self.decay ** t)


@dataclass(frozen=True)
class Const:
    val: float

    def __call__(self, t):
        return self.val


class Logger:
    """printf-style debug logger shim (reference utils.py:14-24)."""

    def __init__(self, verbose: bool = True):
        self.verbose = verbose

    def debug(self, *args, **kwargs):
        if self.verbose:
            print(*args, **kwargs)

    info = debug


class TableLogger:
    """Fixed-width console table: header printed on first append.

    Reference utils.py:66-74. Column order is the insertion order of the first
    row's keys; floats printed with 6 significant digits.
    """

    def __init__(self):
        self.keys = None

    def append(self, row: dict):
        if self.keys is None:
            self.keys = list(row.keys())
            print(*(f"{k:>12s}" for k in self.keys))
        cells = []
        for k in self.keys:
            v = row.get(k, "")
            if isinstance(v, (float, np.floating)):
                cells.append(f"{v:12.4f}")
            else:
                cells.append(f"{str(v):>12s}")
        print(*cells)


class TSVLogger:
    """Accumulates rows, renders as TSV (reference utils.py:76-85)."""

    def __init__(self):
        self.log = [["epoch", "hours", "top1Accuracy"]]

    def append(self, row: dict):
        self.log.append(
            [
                row.get("epoch", -1),
                round(row.get("total_time", 0.0) / 3600, 6),
                row.get("test_acc", 0.0),
            ]
        )

    def __str__(self):
        return "\n".join("\t".join(str(c) for c in r) for r in self.log)


class Timer:
    """Cumulative timer: ``timer()`` returns seconds since the last call and
    (optionally) adds them to the running total (reference utils.py:89-99)."""

    def __init__(self, synch=None):
        self.synch = synch or (lambda: None)
        self.t = time.perf_counter()
        self.total_time = 0.0

    def __call__(self, include_in_total: bool = True) -> float:
        self.synch()
        now = time.perf_counter()
        dt = now - self.t
        self.t = now
        if include_in_total:
            self.total_time += dt
        return dt


def union(*dicts) -> dict:
    """One dict of all of them, later ones winning."""
    out = {}
    for d in dicts:
        out.update(d)
    return out


def make_logdir(args) -> str:
    """Run-directory name encoding the federated config + timestamp
    (reference utils.py:51-64).

    ``COMMEFFICIENT_RUN_DIR`` overrides the derived name verbatim: the
    multi-tenant orchestrator (scripts/orchestrate.py, docs/packing.md)
    pins each tenant's run dir through this seam so two tenants started
    the same second can never collide on the timestamp name — and with
    it, their telemetry.jsonl and trace_round_* profiler captures (both
    live under the run dir) stay apart."""
    pinned = os.environ.get("COMMEFFICIENT_RUN_DIR", "")
    if pinned:
        return pinned
    parts = [
        time.strftime("%Y-%m-%d-%H%M%S"),
        f"w{getattr(args, 'num_workers', 0)}",
        f"c{getattr(args, 'num_clients', 0)}",
        str(getattr(args, "mode", "?")),
    ]
    if getattr(args, "mode", None) == "sketch":
        parts.append(
            f"r{getattr(args, 'num_rows', 0)}x{getattr(args, 'num_cols', 0)}k{getattr(args, 'k', 0)}"
        )
    root = getattr(args, "logdir_root", "runs")
    return os.path.join(root, "_".join(parts))


def run_cv_recorded(argv, tag, echo=None):
    """Run ``cv_train.main(argv)`` with every TableLogger row captured.

    Shared harness for the learning-evidence scripts
    (scripts/learning_fullscale.py, scripts/femnist_ablation.py): records
    the per-epoch rows the entrypoint would print, echoing each (flushed —
    these sweeps run for hours piped to log files) with the run's ``tag``.
    Restores the real TableLogger even on failure."""
    import functools

    import cv_train

    if echo is None:
        echo = functools.partial(print, flush=True)

    rows = []

    class _Recorder:
        def append(self, row):
            rows.append(dict(row))
            echo(f"[{tag}] {row}")

    orig = cv_train.TableLogger
    cv_train.TableLogger = _Recorder
    try:
        cv_train.main(argv)
    finally:
        cv_train.TableLogger = orig
    return rows
