"""The delta rule's chunked core alone (ops/linear_attention.py) at
``kimi_linear_sketch_1c``'s scan step (4 sequences x 1,024 positions x 32
heads of 128), on a TPU: the device time of one call of each path, forward
and forward + backward, and each path's worst relative error (max |diff| /
max |reference|) against the per-token recurrence (the reference file's
``Model.delta_rule`` at ``highest``), output and its five gradients.

    python scripts/kda_core_probe.py [--paths xla,pallas] [--reps 20]

``--ops N`` also traces three forward + backward calls of each path and
prints its N longest device operations, ms a call. ``--rehearse`` runs it
on the CPU at 1 x 128 x 2 x 128, kernels
interpreted (results only: its times are not the chip's).

Prints a line a path and writes ``chiprun_out/kda_core_probe.json``. The
paths run at the default matmul precision, as in the model.
"""

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark"),
                os.path.join(ROOT, "benchmark", "configs")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from commefficient_tpu.ops import linear_attention as la  # noqa: E402

PATHS = {"xla": la.kda_attention_xla, "pallas": la.kda_attention_pallas}
NAMES = ("out", "dq", "dk", "dv", "dlog_alpha", "dbeta")


def inputs(seed, S=4, T=1024, H=32, d=128):
    """Unit q and k, v normal, log alpha in [-0.5, 0) a channel, beta in
    (0, 1), and the output's cotangent."""
    r = np.random.RandomState(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    arrays = (unit(r.randn(S, T, H, d)), unit(r.randn(S, T, H, d)),
              r.randn(S, T, H, d), r.uniform(-0.5, 0.0, (S, T, H, d)),
              r.uniform(0.0, 1.0, (S, T, H)), r.randn(S, T, H, d))
    return [jnp.asarray(x, jnp.float32) for x in arrays]


def timed(fn, args, reps):
    """ms a call, the calls back to back, after one that compiles."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def both(core):
    def run(q, k, v, log_alpha, beta, d_out):
        out, vjp = jax.vjp(core, q, k, v, log_alpha, beta)
        return (out,) + vjp(d_out)
    return jax.jit(run)


def top_ops(fn, args, n, where):
    """The n operations with the most device time in three calls, ms a
    call, from a profiler trace."""
    import trace_reduce as tr

    jax.block_until_ready(fn(*args))
    with jax.profiler.trace(where):
        for _ in range(3):
            out = fn(*args)
        jax.block_until_ready(out)
    ms = {}
    for plane in jax.profiler.ProfileData.from_file(
            tr.find_xplane(where)).planes:
        if plane.name.startswith(tr.DEVICE_PLANE):
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    for ev in line.events:
                        ms[ev.name] = ms.get(ev.name, 0.0) \
                            + ev.duration_ns / 3e6
    return sorted(ms.items(), key=lambda x: -x[1])[:n]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--paths", default="xla,pallas")
    p.add_argument("--reps", type=int, default=20)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--ops", type=int, default=0)
    p.add_argument("--rehearse", action="store_true")
    a = p.parse_args(argv)
    import kimi_linear_ep32_ref as ref_file

    paths = dict(PATHS)
    if a.rehearse:
        paths["pallas"] = functools.partial(la.kda_attention_pallas,
                                            interpret=True)
    args = inputs(a.seed, *((1, 128, 2) if a.rehearse else ()))
    with jax.default_matmul_precision("highest"):
        want = both(ref_file.Model.delta_rule)(*args)
    dev = jax.devices()[0]
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "shape": list(args[0].shape), "paths": {}}
    for path in a.paths.split(","):
        core = paths[path]
        got = both(core)(*args)
        err = {n: float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
               for n, g, w in zip(NAMES, got, want)}
        row = {"fwd_ms": timed(jax.jit(core), args[:5], a.reps),
               "fwd_bwd_ms": timed(both(core), args, a.reps),
               "worst_rel_err": max(err.values()), "rel_err": err,
               "finite": all(bool(jnp.isfinite(g).all()) for g in got)}
        result["paths"][path] = row
        if a.ops:
            row["ops"] = top_ops(both(core), args, a.ops, os.path.join(
                ROOT, "chiprun_out", "kda_core_probe_trace", path))
            for name, t in row["ops"]:
                print(f"kda_core_probe: {path}:   {t:9.3f} ms  {name}")
        print(f"kda_core_probe: {path}: forward {row['fwd_ms']:.3f} ms, "
              f"forward + backward {row['fwd_bwd_ms']:.3f} ms, worst "
              f"relative error {row['worst_rel_err']:.3e} {err}")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "kda_core_probe.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    return result


if __name__ == "__main__":
    main()
