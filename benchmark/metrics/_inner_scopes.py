"""What the model names inside the client phase: ``jax.named_scope``s nested
under ``fed_client_grad`` (the program's ``profiling.INNER_SCOPES``: the
expert layer's routing and grouped products, the attention core) and the
routing counters the event log keeps a round (``model`` of a ``round``
record).

The stage readers of ``_program_trace`` keep an operation's outermost stage
only; these read the same device planes' scope paths once more and match an
inner name as a component of the path, under whatever transform wrapped it
(``transpose(jvp(fed_moe_experts))`` is the backward pass, ``checkpoint`` /
``rematted_computation`` the recomputation). Against a program that names
none of them every reader finds nothing and returns ``None``.
"""

import json
import os
import re

import _program_trace

_COMPONENT = re.compile(r"fed_[a-z_]+")
_PATHS: dict = {}


def _paths(ctx):
    trace_dir = os.path.join(os.environ.get("COMMEFFICIENT_RUN_DIR", ""),
                             "trace")
    if trace_dir not in _PATHS:
        try:
            tr = ctx["tr"]
            _PATHS[trace_dir] = _program_trace.device_paths(
                tr.find_xplane(trace_dir), tr)
        except (OSError, ValueError, IndexError):
            _PATHS[trace_dir] = None
    return _PATHS[trace_dir]


# XLA:TPU rewrites ``jax.lax.ragged_dot`` into custom calls whose whole scope
# path is this (``ragged-dot-none``, ``ragged-dot-metadata``): the grouped
# product loses the scope it was traced under, and keeps this name
GROUPED_PRODUCT = "ragged-dot"


def seconds(ctx, names, path_prefix=None) -> float:
    """Seconds of the window in which an operation under one of the inner
    scopes ``names`` ran, or one whose scope path starts with
    ``path_prefix`` (union of intervals, mean over the chips traced)."""
    paths = _paths(ctx)
    if not paths:
        return 0.0
    tr, names = ctx["tr"], set(names)
    per_chip = [tr.total(tr.union(tr.clip(
        [(s, e) for path, _, s, e in ops
         if names.intersection(_COMPONENT.findall(path))
         or (path_prefix and path.startswith(path_prefix))],
        ctx["lo"], ctx["hi"]))) for ops in paths.values()]
    return sum(per_chip) / len(per_chip) / 1e9


def read_ms(ctx, names, path_prefix=None):
    """ms a round under the inner scopes; None where nothing carries them."""
    s = seconds(ctx, names, path_prefix) if ctx["rounds"] else 0.0
    return s / ctx["rounds"] * 1e3 if s else None


def round_counters(ctx):
    """The ``model`` records of the window's rounds (the run's last
    ``ctx['rounds']`` round records); [] where the event log has none."""
    path = os.path.join(os.environ.get("COMMEFFICIENT_RUN_DIR", ""),
                        "telemetry.jsonl")
    try:
        with open(path) as f:
            recs = [json.loads(line) for line in f if '"model"' in line]
    except (OSError, ValueError):
        return []
    recs = [r["model"] for r in recs if r.get("ev") == "round"]
    return recs[-int(ctx["rounds"]):] if ctx["rounds"] else []
