"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the FetchSGD headline recipe (CIFAR10, ResNet9 at full width,
8 workers x batch 8, sketch 5x500k, k=50k) through ``cv_train.main`` — the
entry point a user calls, with everything it turns on by default — on the TPU
JAX finds, and checks what comes out. Only the data shrinks (a seeded
synthetic split; the machine has no network). Phases, each a fresh child
process that is the sole owner of the chip while it runs (this parent never
imports jax):

  probe       what JAX sees: platform, device_kind, count, versions, whether
              the native data plane built, where the compile cache lives
  kernels     each of the seven Pallas kernels compiled (never interpreted)
              at the ResNet9 geometry and bit-compared with its jnp reference;
              the top-k's pruned descent at GPT-2's d against the whole-plane
              one; the grouped-query attention kernels at 4,096 positions
              (48 / 64 heads over 8) against their einsum oracle, to rounding
  train-cold  the recipe end to end: finite loss, >= 16 rounds + validation,
              weights moved, telemetry header says tpu, no kernel kill-switch
              flipped, every mesh device used
  train-warm  the same again in a new process on the same compile cache: must
              hit the cache and end on the same weights, bit for bit
  (>1 chip)   the recipe on one device and with --server_shard: both must
              finish and agree with the all-chip run on the loss

Without a TPU it exits non-zero before any work and prints no result; it has
no CPU mode. ``--rehearse`` is the one explicit exception, for debugging the
script itself in a sandbox: the same phases on the CPU at tiny size with the
kernels interpreted, every line labelled a rehearsal and no result line.

The last line of stdout is one JSON object with exactly these keys, the
device as JAX reports it:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
The line before it, ``chip_smoke: summary {...}``, carries the rest (phase
verdicts, cold/warm compile seconds, weight digest, ``"claim": null``).
Exit code 0 only if every phase passed. Artefacts (run dirs, dataset, phase
results) go under ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
DEADLINE_S = 1150  # the contract allows 1200 s, compilation included

# the FetchSGD headline recipe (README, reference utils.py:142-162);
# everything cv_train.py defaults on stays on, and the opt-in prefetch
# thread is added so the native batch assembly runs beside the device
RECIPE = [
    "--dataset_name", "CIFAR10", "--model", "ResNet9", "--mode", "sketch",
    "--error_type", "virtual", "--num_workers", "8",
    "--local_batch_size", "8", "--num_rows", "5", "--num_cols", "500000",
    "--k", "50000", "--num_blocks", "20", "--virtual_momentum", "0.9",
    "--local_momentum", "0", "--device", "tpu",
    "--train_dataloader_workers", "1", "--val_dataloader_workers", "1",
]
# ~20 rounds an epoch (two --metrics_drain_every 8 drains) x the default
# 24 epochs, each with its validation pass
PER_CLASS = "128"
# big_d: GPT-2's d, above ops/topk's gate, where the top-k prunes to k granules
KERNEL_GEOMETRY = {"d": 6_568_640, "c": 500_000, "r": 5, "k": 50_000,
                   "big_d": 124_444_417}

# --rehearse: CPU, tiny model, tiny sketch, interpreted kernels
REHEARSAL_RECIPE = {"--num_rows": "3", "--num_cols": "2048", "--k": "500",
                    "--device": "cpu"}
REHEARSAL_GEOMETRY = {"d": 60_000, "c": 20_000, "r": 3, "k": 500,
                      "big_d": 70_001,
                      "gqa": {"heads": (6, 8), "kv_heads": 1, "d": 16,
                              "T": 48, "window": 20, "tile": 16,
                              "ungated": ((4, 4, 48),)}}
WORKERS = 8
# the multi-round mesh-parity tolerance (tests/test_rounds.py:147,271)
LOSS_RTOL = 1e-4


def _recipe(rehearse: bool, extra=()):
    argv = list(RECIPE)
    if rehearse:
        for flag, val in REHEARSAL_RECIPE.items():
            argv[argv.index(flag) + 1] = val
    return argv + list(extra)


# --------------------------------------------------------------------------
# phases (children; each imports jax and owns the chip alone)
# --------------------------------------------------------------------------

def _say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def _require_platform(rehearse: bool) -> dict:
    from commefficient_tpu.utils import announce_devices

    dev = announce_devices()
    if not rehearse and dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found platform "
                         f"{dev['platform']!r}, not a TPU")
    return dev


def phase_probe(ns) -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    from commefficient_tpu import native
    from commefficient_tpu.utils import configure_compile_cache

    dev = _require_platform(ns.rehearse)
    cache = configure_compile_cache()
    _say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
         f"libtpu {metadata.version('libtpu')} "
         f"python {sys.version.split()[0]}")
    _say("native data plane: "
         + ("built with g++ (native/feddata.cpp)" if native.available()
            else "NOT built — numpy fallback ran"))
    _say(f"compile cache: {cache} ("
         + ("JAX_COMPILATION_CACHE_DIR" if os.environ.get(
             "JAX_COMPILATION_CACHE_DIR") else "in-checkout default") + ")")
    return {"ok": True, "device": dev, "native": native.available(),
            "cache": cache}


def phase_kernels(ns) -> dict:
    import importlib

    from commefficient_tpu.ops import attention as at
    from commefficient_tpu.ops import sketch as sk
    from commefficient_tpu.utils import configure_compile_cache

    tk = importlib.import_module("commefficient_tpu.ops.topk")
    _require_platform(ns.rehearse)
    configure_compile_cache()
    g = REHEARSAL_GEOMETRY if ns.rehearse else KERNEL_GEOMETRY
    interpret = ns.rehearse
    cs = sk.make_sketch(g["d"], c=g["c"], r=g["r"], seed=42, num_blocks=20)
    if not interpret:
        # the local-variant comparisons go through the public dispatchers:
        # with a kill-switch set they would compare jnp against jnp
        off = [name for name, on in (
            ("sketch", sk._use_pallas_sketch()),
            ("estimates", sk._use_pallas_estimates()),
            ("fused_epilogue", sk.fused_epilogue_mode(cs) == "kernel"),
            ("topk", tk._use_pallas_topk(g["d"]))) if not on]
        if off:
            raise SystemExit(f"chip_smoke: kernel dispatch is off for {off} "
                             "(kill-switch env var set?)")
    checks = [
        ("sketch_vec", lambda: sk.check_sketch_vec_kernel(cs, interpret)),
        ("estimates", lambda: sk.check_estimates_kernel(cs, interpret)),
        ("sketch_segments (the client phase's leaf groups)",
         lambda: sk.check_sketch_segments_kernel(cs, interpret)),
        ("fused_epilogue",
         lambda: sk.check_fused_epilogue_kernel(cs, g["k"], interpret)),
        ("topk count-pass descent",
         lambda: tk.check_count_descent_kernel(g["d"], g["k"], interpret)),
        ("topk fused descent",
         lambda: tk.check_fused_descent_kernel(g["d"], g["k"], interpret)),
        ("topk pruned descent",
         lambda: tk.check_pruned_descent(g["big_d"], g["k"], cs.sublanes,
                                         interpret)),
        # the grouped-query attention at models/laguna.py's published shape,
        # the turn of q and k and the heads' gates inside the kernels: gated
        # output and four gradients, full and window layer; and at
        # models/ouro.py's (16 heads over 16, no gate, 1,024 and 4,096
        # positions: output and three gradients); within rounding of its
        # oracle, not bit-equal (at.GQA_CHECK_TOL)
        ("gqa attention fwd + bwd",
         lambda: at.check_gqa_kernels(**g.get("gqa", {}),
                                      interpret=interpret)),
    ]
    how = "INTERPRETED" if interpret else "compiled (not interpreted)"
    failed = []
    for name, check in checks:
        t0 = time.monotonic()
        try:
            gaps = check()
        except Exception as e:  # noqa: BLE001 — report every kernel, then fail
            failed.append(name)
            _say(f"kernel {name}: FAILED {type(e).__name__}: {e}")
        else:
            verdict = (f"within {at.GQA_CHECK_TOL:g} of the oracle's "
                       f"largest entry (worst {max(gaps.values()):.2e}: "
                       + ", ".join(f"{k} {v:.1e}" for k, v in gaps.items())
                       if gaps else
                       f"bit-equal to reference (T={cs.T} S={cs.sublanes}")
            _say(f"kernel {name}: {how}, {verdict}, "
                 f"{time.monotonic() - t0:.1f} s)")
    return {"ok": not failed, "failed": failed}


def _digest(x) -> str:
    import numpy as np

    return hashlib.sha256(np.ascontiguousarray(np.asarray(x)).tobytes()
                          ).hexdigest()[:16]


def phase_train(ns) -> dict:
    import jax
    import numpy as np

    import cv_train

    seen = {}

    class ObservedFedModel(cv_train.FedModel):
        """cv_train's FedModel, remembering itself, its initial weights and
        where the first round's per-client losses were left."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            seen["model"] = self
            seen["init"] = _digest(self.ps_weights)

        def begin_round(self, batch):
            handle = super().begin_round(batch)
            if "loss_shards" not in seen:
                shards = handle.metrics[0].addressable_shards
                seen["loss_shards"] = sorted(
                    {(str(s.device), str(s.index)) for s in shards})
            return handle

    cv_train.FedModel = ObservedFedModel
    t0 = time.monotonic()
    summary = cv_train.main(ns.argv)
    wall = time.monotonic() - t0
    model = seen["model"]
    mesh_devices = list(model.mesh.devices.flat)

    problems = []
    if not summary:
        problems.append("cv_train.main returned no summary (NaN abort?)")
        summary = {}
    for key in ("train_loss", "test_loss", "test_acc"):
        if not np.isfinite(summary.get(key, np.nan)):
            problems.append(f"{key} is not finite: {summary.get(key)}")
    rounds = model.rounds_dispatched
    if rounds < 16:
        problems.append(f"only {rounds} training rounds (< 16)")
    final = _digest(model.ps_weights)
    if final == seen["init"]:
        problems.append("weights did not move (final digest == initial)")
    with open(os.path.join(os.environ["COMMEFFICIENT_RUN_DIR"],
                           "telemetry.jsonl")) as f:
        header = json.loads(f.readline())
    platform = "cpu" if ns.rehearse else "tpu"
    if header.get("backend") != platform:
        problems.append(f"telemetry header backend is "
                        f"{header.get('backend')!r}, not {platform!r}")
    mesh_axes = {a["name"]: a["size"] for a in header["mesh"]["axes"]}
    want = ns.expect_clients
    if mesh_axes != {"clients": want}:
        problems.append(f"mesh is {mesh_axes}, expected clients={want}")
    loss_devices = {d for d, _ in seen.get("loss_shards", ())}
    loss_slices = {i for _, i in seen.get("loss_shards", ())}
    if len(loss_devices) != want or len(loss_slices) != want:
        problems.append(f"client-phase output sits on {len(loss_devices)} "
                        f"device(s) in {len(loss_slices)} slice(s), "
                        f"expected {want}")
    flipped = {k: v for k, v in os.environ.items()
               if (k.startswith("COMMEFFICIENT_PALLAS")
                   or k == "COMMEFFICIENT_FUSED_EPILOGUE") and v == "0"}
    if flipped:
        problems.append(f"kernel kill-switch set during the run: {flipped}")
    peaks = {}
    for d in mesh_devices:
        stats = d.memory_stats()
        if stats is not None:  # the CPU backend reports none
            peaks[str(d)] = int(stats["peak_bytes_in_use"])
    if any(v <= 0 for v in peaks.values()):
        problems.append(f"a mesh device was never used: {peaks}")

    _say(f"{ns.phase}: {rounds} rounds + validation through cv_train.main in "
         f"{wall:.1f} s wall (compile included); train_loss="
         f"{summary.get('train_loss')} test_loss={summary.get('test_loss')} "
         f"test_acc={summary.get('test_acc')}")
    _say(f"{ns.phase}: mesh {mesh_axes}; client-phase output on "
         f"{len(loss_devices)} device(s); peak_bytes_in_use {peaks or 'n/a'}")
    # the program's own record of what it built (profiling.py's listener,
    # which cv_train.main registered): backend seconds, the persistent
    # cache's hits, the entries written to it
    from commefficient_tpu.profiling import program_totals

    built = program_totals().values()
    compiled = {"s": sum(t["backend_s"] for t in built),
                "hits": sum(t["hits"] for t in built),
                "writes": sum(t["stored"] for t in built)}
    _say(f"{ns.phase}: backend compile {compiled['s']:.1f} s, persistent "
         f"cache hits {compiled['hits']}, entries written "
         f"{compiled['writes']}")
    _say(f"{ns.phase}: client sketch path "
         f"{header.get('client_sketch_path')}, "
         f"{header.get('client_sketch_launches')} accumulate launches a "
         f"round")
    _say(f"{ns.phase}: weights {seen['init']} -> {final}")
    for p in problems:
        _say(f"{ns.phase}: PROBLEM {p}")
    return {"ok": not problems, "problems": problems, "rounds": rounds,
            "train_loss": float(summary.get("train_loss", np.nan)),
            "test_loss": float(summary.get("test_loss", np.nan)),
            "test_acc": float(summary.get("test_acc", np.nan)),
            "digest": final, "compile_s": round(compiled["s"], 1),
            "cache_hits": compiled["hits"],
            "cache_writes": compiled["writes"]}


PHASES = {"probe": phase_probe, "kernels": phase_kernels,
          "train": phase_train}


def child_main(ns) -> int:
    result = PHASES[ns.phase.split("-")[0]](ns)
    with open(ns.result, "w") as f:
        json.dump(result, f)
    return 0 if result["ok"] else 1


# --------------------------------------------------------------------------
# parent (never imports jax)
# --------------------------------------------------------------------------

def result_line(ok: bool, device: dict) -> str:
    """The contract's last line of stdout: these keys and no others."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


class Smoke:
    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.label = ("REHEARSAL (cpu, tiny, kernels interpreted) | "
                      if rehearse else "")
        self.t0 = time.monotonic()
        self.results = {}
        # stdout's tail is all the chip tool shows: keep the whole log too
        os.makedirs(OUT, exist_ok=True)
        self.log = open(os.path.join(OUT, "smoke.log"), "w", buffering=1)

    def say(self, msg: str) -> None:
        print(f"{self.label}{msg}", flush=True)
        self.log.write(f"{self.label}{msg}\n")

    def run(self, phase: str, argv=(), env=None, expect_clients=1) -> dict:
        """One phase in its own process group; its output streams through
        (labelled when rehearsing); it is killed at the global deadline."""
        result_path = os.path.join(OUT, f"{phase}.json")
        if os.path.exists(result_path):
            os.remove(result_path)  # never read a previous run's result
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
               "--result", result_path,
               "--expect_clients", str(expect_clients)]
        if self.rehearse:
            cmd.append("--rehearse")
        cmd += ["--", *argv]
        remaining = DEADLINE_S - (time.monotonic() - self.t0)
        if remaining <= 0:
            self.say(f"phase {phase}: SKIPPED, out of time")
            self.results[phase] = {"ok": False}
            return self.results[phase]
        t0 = time.monotonic()
        proc = subprocess.Popen(
            cmd, cwd=HERE, env={**os.environ, **(env or {})},
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            start_new_session=True)

        def kill():
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass

        timer = threading.Timer(remaining, kill)
        timer.start()
        try:
            for line in proc.stdout:
                self.say(line.rstrip("\n"))
            rc = proc.wait()
        finally:
            timer.cancel()
            kill()  # no child of the phase outlives it
        result = {"ok": False}
        if os.path.exists(result_path):
            with open(result_path) as f:
                result = json.load(f)
            result["ok"] = result["ok"] and rc == 0
        self.results[phase] = result
        self.say(f"phase {phase}: {'ok' if result['ok'] else 'FAILED'} "
                 f"(rc={rc}, {time.monotonic() - t0:.1f} s)")
        return result


def parent_main(rehearse: bool) -> int:
    missing = [p for p in ("cv_train.py", "commefficient_tpu")
               if not os.path.exists(os.path.join(HERE, p))]
    if missing:
        print(f"chip_smoke: {missing} not found beside chip_smoke.py — this "
              "script drives the fedtpu checkout it sits in",
              file=sys.stderr)
        return 2
    smoke = Smoke(rehearse)

    base_env = {"COMMEFFICIENT_SYNTHETIC_PER_CLASS": PER_CLASS}
    if rehearse:
        base_env.update({"JAX_PLATFORMS": "cpu",
                         "COMMEFFICIENT_TINY_MODEL": "1"})
    else:
        # full width: the model-shrinking switches never reach a phase
        for var in ("COMMEFFICIENT_TINY_MODEL",
                    "COMMEFFICIENT_MODEL_CHANNELS"):
            os.environ.pop(var, None)

    probe = smoke.run("probe", env=base_env)
    if not probe["ok"]:
        # no accelerator (or a broken runtime): no work, no result line
        print("chip_smoke: no usable TPU — nothing was run", file=sys.stderr)
        return 3
    device = probe["device"]
    n_dev = device["count"]
    clients = max(n for n in range(1, n_dev + 1) if WORKERS % n == 0)
    smoke.say(f"chip_smoke: running on {n_dev} device(s); the recipe's "
              f"clients mesh will be {clients}")

    smoke.run("kernels", env=base_env)

    def train(phase, extra=(), expect=clients):
        run_dir = os.path.join(OUT, f"run_{phase}")
        shutil.rmtree(run_dir, ignore_errors=True)
        env = {**base_env, "COMMEFFICIENT_RUN_DIR": run_dir}
        argv = _recipe(rehearse, [
            "--dataset_dir", os.path.join(OUT, f"data_{PER_CLASS}_per_class"),
            "--checkpoint_path", os.path.join(OUT, "checkpoint"), *extra])
        res = smoke.run(phase, argv, env, expect_clients=expect)
        # a watch-plane trace reaction can leave profiler captures far
        # larger than what the chip tool brings back: count, then drop
        traces = [t for t in (os.listdir(run_dir)
                              if os.path.isdir(run_dir) else ())
                  if t.startswith("trace_round_")]
        for t in traces:
            shutil.rmtree(os.path.join(run_dir, t), ignore_errors=True)
        if traces:
            smoke.say(f"chip_smoke: {phase} left {len(traces)} watch-plane "
                      "trace capture(s); removed")
        return res

    cold = train("train-cold")
    warm = train("train-warm")
    if cold["ok"] and warm["ok"]:
        # every executable the cold run found or stored must be found
        # again (entries are only stored above jax's 1 s compile floor, so
        # a borderline compile may be written by either run — not counted)
        held = cold["cache_hits"] + cold["cache_writes"]
        checks = {
            f"warm run hit the persistent cache for all {held} "
            "executable(s) the cold run left in it":
                warm["cache_hits"] >= max(held, 1),
            "warm final-weight digest == cold": (warm["digest"]
                                                 == cold["digest"]),
        }
        smoke.say(f"chip_smoke: compile cold {cold['compile_s']} s "
                  f"({cold['cache_hits']} hits, {cold['cache_writes']} "
                  f"written) -> warm {warm['compile_s']} s "
                  f"({warm['cache_hits']} hits, {warm['cache_writes']} "
                  f"written); digests {cold['digest']} / {warm['digest']}")
        for what, ok in checks.items():
            if not ok:
                smoke.say(f"chip_smoke: PROBLEM not true: {what}")
        smoke.results["cache-reuse"] = {"ok": all(checks.values())}

    if n_dev > 1:
        # several chips: the same recipe on one device, and on the sharded
        # server plane, must finish and agree with the all-chip run
        for phase, extra, expect in (
                ("train-one-device", ["--num_devices", "1"], 1),
                ("train-server-shard", ["--server_shard"], clients)):
            res = train(phase, extra, expect)
            if not (res["ok"] and cold["ok"]):
                continue
            rel = max(abs(res[k] - cold[k]) / max(abs(cold[k]), 1e-12)
                      for k in ("train_loss", "test_loss"))
            agree = rel <= LOSS_RTOL
            smoke.say(f"chip_smoke: {phase} vs all-chip run: train_loss "
                      f"{res['train_loss']} vs {cold['train_loss']}, "
                      f"test_loss {res['test_loss']} vs "
                      f"{cold['test_loss']}; max relative difference "
                      f"{rel:.2e} (tolerance {LOSS_RTOL:g}) — "
                      + ("agree" if agree else "DISAGREE"))
            smoke.results[f"{phase}-parity"] = {"ok": agree}

    ok = all(r["ok"] for r in smoke.results.values())
    summary = {
        "phases": {name: ("ok" if r["ok"] else "FAILED")
                   for name, r in smoke.results.items()},
        "compile_s": {"cold": cold.get("compile_s"),
                      "warm": warm.get("compile_s")},
        "digest": cold.get("digest"),
        "wall_s": round(time.monotonic() - smoke.t0, 1),
        "claim": None,
    }
    smoke.say(f"chip_smoke: summary {json.dumps(summary)}")
    if rehearse:
        # a rehearsal prints no result line
        smoke.say(f"chip_smoke: rehearsal {'passed' if ok else 'FAILED'}")
    else:
        print(result_line(ok, device), flush=True)
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny size, interpreted kernels; every line "
                         "labelled a rehearsal; prints no result line")
    ap.add_argument("--phase", help=argparse.SUPPRESS)
    ap.add_argument("--result", help=argparse.SUPPRESS)
    ap.add_argument("--expect_clients", type=int, default=1,
                    help=argparse.SUPPRESS)
    ap.add_argument("argv", nargs="*", help=argparse.SUPPRESS)
    ns = ap.parse_args()
    if ns.phase:
        return child_main(ns)
    return parent_main(ns.rehearse)


if __name__ == "__main__":
    sys.exit(main())
