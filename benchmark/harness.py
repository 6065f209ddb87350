"""The benchmark's one process: set-up, one timed call of the entry point's
own ``run_batches``, the check against the plain reference, the result line.

The entry points build everything themselves (``cv_train.main``,
``gpt2_train.train``). The harness substitutes three names in the entry
module, the way ``chip_smoke.py`` substitutes ``FedModel``:

- the epoch driver the entry point calls last -> ``Run.drive`` (warm-up call,
  validation pass, the timed call);
- ``FedModel`` -> a subclass that takes the benchmark's weights (made from
  ``--seed`` by the configuration's reference file), puts host spans around
  the round's stages and leaves the first rounds' losses, the first transmit's
  norms and the parameters' change where the comparison finds them;
- ``PipelinedRoundEngine`` -> a subclass with spans around submit and drain.

Nothing else of the program is touched; every default stays on.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import random
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, ".benchmark_out")
CHECK_ROUNDS = 3          # the reference follows the first three rounds
BIG = 1 << 40


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# -------------------------------------------------------------------------
# the loader wrapper: the window's own feed
# -------------------------------------------------------------------------

class WindowLoader:
    """Wraps the program's train loader. Re-iterates it across its epochs,
    reports an endless epoch, and stops yielding after ``rounds`` rounds or,
    with ``seconds``, at the first multiple of ``drain_every`` rounds after
    that many seconds. Counts the time the loop waits inside the inner
    loader's ``next`` and keeps the first ``keep`` batches."""

    def __init__(self, inner, drain_every, rounds=None, seconds=None,
                 keep=0, fault=None):
        self.inner = inner
        self.drain_every = int(drain_every)
        self.max_rounds = rounds
        self.seconds = seconds
        self.keep = keep
        self.fault = fault
        self.kept = []
        self.rounds = 0
        self.wait_s = 0.0
        self.epochs = 0

    def __getattr__(self, name):
        if name == "inner":
            raise AttributeError(name)
        return getattr(self.inner, name)

    def steps_per_epoch(self):
        return BIG

    def __len__(self):
        return BIG

    def _done(self, t0) -> bool:
        if self.max_rounds is not None:
            return self.rounds >= self.max_rounds
        return (self.rounds % self.drain_every == 0 and self.rounds > 0
                and time.monotonic() - t0 >= self.seconds)

    def __iter__(self):
        import jax

        t0 = time.monotonic()
        while True:
            it = iter(self.inner)
            self.epochs += 1
            try:
                while True:
                    if self._done(t0):
                        return
                    t = time.monotonic()
                    with jax.profiler.TraceAnnotation("bench_loader_wait"):
                        try:
                            batch = next(it)
                        except StopIteration:
                            break
                    self.wait_s += time.monotonic() - t
                    if len(self.kept) < self.keep:
                        self.kept.append(batch)
                    if self.fault == "half_batch":
                        batch = _drop_half(batch)
                    self.rounds += 1
                    yield batch
            finally:
                close = getattr(it, "close", None)
                if close is not None:
                    close()  # reaps the prefetch thread


def _drop_half(batch):
    """The planted fault: the second half of the round's clients left out,
    the mean taken over the rest."""
    out = dict(batch)
    wm = np.array(batch["worker_mask"], np.float32)
    wm[len(wm) // 2:] = 0.0
    out["worker_mask"] = wm
    mask = np.array(batch["mask"])
    mask[len(wm) // 2:] = 0
    out["mask"] = mask
    return out


# -------------------------------------------------------------------------
# entry points
# -------------------------------------------------------------------------

class Entry:
    """What differs between the two entry points: which names to substitute
    and how each calls its own ``run_batches``."""

    def __init__(self, name):
        self.name = name
        self.mod = importlib.import_module(name)

    def install(self, run):
        mod = self.mod
        mod.FedModel = observed_model(mod.FedModel, run)
        mod.PipelinedRoundEngine = spanned_engine(mod.PipelinedRoundEngine)
        if self.name == "cv_train":
            def train(model, opt, sched, train_loader, test_loader, args,
                      writer, **_):
                return run.drive(
                    model, train_loader, args,
                    lambda ld: mod.run_batches(model, opt, sched, ld, True,
                                               1, args),
                    lambda: mod.run_batches(model, None, None, test_loader,
                                            False, 1, args))
            mod.train = train
        elif self.name == "gpt2_train":
            def train_gpt2(model, opt, sched, train_loader, val_loader, args,
                           log_dir, writer=None, logger=None, timer=None,
                           **_):
                timer = timer or mod.Timer()
                return run.drive(
                    model, train_loader, args,
                    lambda ld: mod.run_batches(
                        model, opt, sched, ld, args, timer, training=True,
                        epoch=0, epoch_fraction=1, logger=logger),
                    lambda: mod.run_batches(
                        model, None, None, val_loader, args, timer,
                        training=False))
            mod.train_gpt2 = train_gpt2
            dropout = run.config.get("dropout")
            if dropout is not None:
                import functools

                mod.GPT2DoubleHeads = functools.partial(
                    mod.GPT2DoubleHeads, dropout=float(dropout))
        else:
            raise SystemExit(f"bench: no adapter for entry {self.name!r}")

    def main(self, argv):
        return (self.mod.main if self.name == "cv_train"
                else self.mod.train)(argv)

    @property
    def schedule_kind(self):
        """Which of reference.lr_at's schedules the entry point builds."""
        return {"cv_train": "triangle",
                "gpt2_train": "linear_decay"}[self.name]


def observed_model(base, run):
    import jax
    import jax.numpy as jnp

    span = jax.profiler.TraceAnnotation

    class ObservedFedModel(base):
        def __init__(self, model, *a, **kw):
            tree = run.ref_model.init(run.weights_seed)
            if kw.get("init_params") is not None:
                want = jax.tree_util.tree_map(jnp.shape, kw["init_params"])
                got = jax.tree_util.tree_map(jnp.shape, tree)
                if want != got:
                    raise SystemExit("bench: the reference's parameter tree "
                                     "is not the program's")
            kw["init_params"] = tree
            if kw.get("model_state") is None:
                kw["model_state"] = {}
            super().__init__(model, *a, **kw)
            del tree
            run.model = self
            # the entry point seeded numpy from its own --seed; from here on
            # the sampler's cohorts and the augmentation follow the run's
            np.random.seed(run.weights_seed % (2**32))
            random.seed(run.weights_seed)

        def begin_round(self, batch):
            rn = self.rounds_dispatched
            if run.capture and rn == CHECK_ROUNDS:
                run.program["change"] = run.change_norms(self.ps_weights)
            with span("bench_begin_round"):
                handle = super().begin_round(batch)
            if run.capture and rn == 0:
                run.program["first"] = run.first_norms(
                    self._round_ctx.gradient)
            return handle

        def _apply_server(self, server_state, lr):
            with span("bench_apply_server"):
                if run.fault == "unchanged":
                    # the planted fault: a step that returns its state
                    # unchanged
                    self._round_ctx = None
                    return server_state
                return super()._apply_server(server_state, lr)

        def finish_round(self, handle):
            with span("bench_finish_round"):
                vals = super().finish_round(handle)
            if run.capture and handle.round_no < CHECK_ROUNDS:
                run.program["client_losses"][handle.round_no] = np.asarray(
                    vals[0], np.float64)
            return vals

    return ObservedFedModel


def spanned_telemetry(recorder):
    """Host spans around the telemetry recorder's per-round calls (event log
    writes, watch rules), so that idle gaps under them carry their name."""
    import functools

    import jax

    for name in ("on_dispatch", "on_complete", "on_drained", "on_metrics",
                 "event"):
        fn = getattr(recorder, name, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, **kw):
            with jax.profiler.TraceAnnotation("bench_telemetry"):
                return _fn(*a, **kw)

        setattr(recorder, name, functools.wraps(fn)(wrapped))


def spanned_engine(base):
    import jax

    span = jax.profiler.TraceAnnotation

    class SpannedEngine(base):
        def submit(self, batch):
            with span("bench_submit"):
                return super().submit(batch)

        def drain(self):
            with span("bench_drain"):
                return super().drain()

    return SpannedEngine


# -------------------------------------------------------------------------
# one run
# -------------------------------------------------------------------------

class Run:
    def __init__(self, ns, t0):
        self.ns, self.t0 = ns, t0
        bench = load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if ns.workload not in cells:
            raise SystemExit(f"bench: no workload {ns.workload!r} in "
                             "BENCHMARK.json")
        self.bench = bench
        self.cell = cells[ns.workload]
        self.extras = load_json(HERE, "workloads", ns.workload + ".json")
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == self.cell["config"])
        self.config = load_json(ROOT, cfg_entry["file"])
        self.traffic = load_json(HERE, "traffic",
                                 self.cell["traffic"] + ".json")
        if ns.rehearse:
            self.traffic["params"].update(self.traffic.get("rehearse", {}))
            self.config.update(self.config.get("rehearse", {}))
        self.params = {**self.config.get("params", {}),
                       **self.traffic["params"]}
        if ns.variant == "bf16":
            self.params["bf16"] = True   # the program's own --bf16 path
        self.fault = ns.variant if ns.variant in ("unchanged",
                                                  "half_batch") else None
        # the program's own --seed is part of the traffic (it fixes the
        # sketch's hash tables, which the compiled programs hold as
        # constants: a seed of the run's would recompile them in every run);
        # the run's --seed makes the weights and drives the data order
        self.weights_seed = int(ns.seed)
        self.program_seed = int(self.params["seed"])
        self.env = {**self.config.get("env", {}),
                    **self.traffic.get("env", {}),
                    **(self.traffic.get("rehearse_env", {})
                       if ns.rehearse else {})}
        self.capture = False
        self.program = {"client_losses": [None] * CHECK_ROUNDS}
        self.model = None
        self.compiles = []

    # -- set-up ---------------------------------------------------------

    def environment(self):
        chips = int(self.cell["chips"])
        if self.ns.rehearse:
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
        for k, v in self.env.items():
            os.environ[k] = str(v)
        run_dir = os.path.join(OUT, "runs", self.ns.workload)
        shutil.rmtree(run_dir, ignore_errors=True)
        os.makedirs(run_dir, exist_ok=True)
        os.environ["COMMEFFICIENT_RUN_DIR"] = run_dir
        self.run_dir = run_dir
        sys.path.insert(0, ROOT)

        import jax

        from commefficient_tpu.utils import configure_compile_cache

        # the program's fixed in-checkout cache; every executable is stored,
        # so a warm start recompiles nothing (jax's floor is 1 s)
        configure_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        devs = jax.devices()
        if not self.ns.rehearse and (devs[0].platform != "tpu"
                                     or len(devs) < chips):
            raise SystemExit(
                f"bench: the cell needs {chips} TPU chip(s); JAX found "
                f"{len(devs)} x {devs[0].platform}")
        self.devices = devs[:chips]
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": chips}

        def on_duration(event, secs, **_):
            if event in ("/jax/core/compile/backend_compile_duration",
                         "/jax/compilation_cache/cache_retrieval_time_sec"):
                self.compiles.append(secs)

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def argv(self):
        # the synthetic data is sized from the environment when it is first
        # prepared: one directory for each sizing
        data_dir = os.path.join(
            OUT, "data", self.cell["config"] + "-" + "-".join(
                f"{k}{v}" for k, v in sorted(self.env.items())
                if "SYNTHETIC" in k))
        argv = []
        for key, val in self.params.items():
            if val is True:
                argv.append(f"--{key}")
            elif val is not False and val is not None:
                argv += [f"--{key}", str(val)]
        argv += ["--dataset_dir", data_dir,
                 "--num_devices", str(self.cell["chips"]),
                 "--checkpoint_path", os.path.join(self.run_dir, "ckpt")]
        if not self.ns.rehearse:
            argv += ["--device", "tpu"]
        return argv

    def reference_model(self):
        sys.path.insert(0, os.path.join(HERE, "configs"))
        mod = importlib.import_module(self.config["reference"])
        self.ref_model = mod.Model(self.config)

    # -- what the program's first rounds leave for the comparison -------

    def _flat(self, w):
        return w.reshape(-1)[: self.model.grad_size]

    def _leaf_norms(self, flat):
        """Norm of each parameter leaf of a (d,) vector, traced."""
        import jax
        import jax.numpy as jnp

        return jnp.stack([jnp.linalg.norm(x) for x in
                          jax.tree_util.tree_leaves(self.model.unravel(flat))])

    def first_norms(self, transmit):
        """Leaf norms of the transmit as the optimizer gets it: the rows of
        a sketch table, or the parameter leaves of a dense gradient."""
        import jax
        import jax.numpy as jnp

        if self.params["mode"] == "sketch":
            return jax.jit(lambda t: jnp.linalg.norm(t, axis=1))(transmit)
        return jax.jit(lambda g: self._leaf_norms(self._flat(g)))(transmit)

    def change_norms(self, w):
        """Leaf norms of the parameters' change from the seed's weights,
        which are made again inside the program, not kept on the chip."""
        import jax
        from jax.flatten_util import ravel_pytree

        @jax.jit
        def norms(w, key):
            w0 = ravel_pytree(self.ref_model.make(key))[0]
            return self._leaf_norms(self._flat(w) - w0)

        return norms(w, jax.random.key(self.weights_seed))

    # -- the epoch driver the entry point calls -------------------------

    def drive(self, model, train_loader, args, train_call, val_call):
        import jax

        ns = self.ns
        de = int(args.metrics_drain_every)
        self.steps_per_epoch = int(train_loader.steps_per_epoch())
        self.args = args

        spanned_telemetry(getattr(model, "telemetry", None))
        # two drains, unless the cell states its own count: one whose epoch
        # ends on a short cohort (a shape of its own in the program's byte
        # accounting) asks for enough rounds to pass its first epoch's end,
        # one whose rounds take a second asks for a single drain
        n_warm = int(self.extras.get("warmup_rounds", 2 * de))
        if n_warm <= CHECK_ROUNDS or n_warm % de:
            raise SystemExit("bench: warmup_rounds must be a multiple of "
                             "metrics_drain_every")
        log(f"warm-up: one call of run_batches, {n_warm} rounds, compiles "
            "every shape")
        self.capture = True
        warm = WindowLoader(train_loader, de, rounds=n_warm,
                            keep=CHECK_ROUNDS, fault=self.fault)
        out = train_call(warm)
        self.capture = False
        self.batches = warm.kept
        self.batch_shapes = {k: tuple(np.shape(v))
                             for k, v in warm.kept[0].items()}
        self.program["first"] = np.asarray(self.program["first"], np.float64)
        self.program["change"] = np.asarray(self.program["change"],
                                            np.float64)
        nan = out[0] is None or not np.isfinite(out[0])

        t = time.monotonic()
        val_call()
        jax.block_until_ready(model.ps_weights)
        self.val_ms = (time.monotonic() - t) * 1e3
        self.compile_s = float(sum(self.compiles))
        n_setup_compiles = len(self.compiles)

        seconds = float(ns.seconds)
        trace_dir = None
        if ns.trace:
            seconds = min(seconds, float(self.extras.get("trace_seconds",
                                                         seconds)))
            trace_dir = os.path.join(self.run_dir, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window = WindowLoader(train_loader, de, seconds=seconds,
                              fault=self.fault)
        gc.collect()
        self.setup_s = time.monotonic() - self.t0
        log(f"set-up {self.setup_s:.2f} s; timed call for {seconds:g} s")
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench_window"):
            out = train_call(window)
        elapsed = time.monotonic() - t0
        if trace_dir:
            jax.profiler.stop_trace()
        nan = nan or out[0] is None or not np.isfinite(out[0])
        in_window = len(self.compiles) - n_setup_compiles
        peak = 0
        for d in self.devices:
            stats = d.memory_stats()
            if stats:
                # buffers plus what the loaded programs reserve for their
                # temporaries: the activations live in the second
                peak = max(peak, int(stats["peak_bytes_in_use"])
                           + int(stats.get("peak_bytes_reserved", 0)))
                log(f"memory_stats {d}: {json.dumps(stats)}")
        self.window = {"rounds": window.rounds, "seconds": elapsed,
                       "wait_s": window.wait_s, "compiles": in_window,
                       "nan": bool(nan), "peak_bytes": peak,
                       "trace_dir": trace_dir}
        log(f"window: {window.rounds} rounds in {elapsed:.3f} s "
            f"({window.rounds / elapsed:.4f} rounds/s), loader wait "
            f"{window.wait_s:.3f} s, {in_window} compilation(s), peak "
            f"{peak / 2**30:.3f} GiB")
        return {}

    # -- after the entry point has returned -------------------------------

    def check(self):
        """The comparison with the plain reference, once the window has
        closed and the program's state is freed."""
        import jax

        import reference

        self.model = None
        gc.collect()
        jax.clear_caches()
        traffic = dict(self.params)
        traffic.update(
            program_seed=self.program_seed,
            weight_decay=float(self.args.weight_decay),
            num_workers=int(self.args.num_workers),
            schedule={"kind": self.schedule_kind,
                      "lr_scale": float(self.args.lr_scale),
                      "pivot_epoch": float(self.args.pivot_epoch),
                      "num_epochs": float(self.args.num_epochs)})
        t = time.monotonic()

        def follow(cast=None):
            return reference.follow(
                self.ref_model, traffic, self.weights_seed, self.batches,
                self.steps_per_epoch,
                int(self.extras["reference_block_clients"]), cast)

        ref = follow()
        program = self.program
        if self.ns.variant.startswith("control"):
            # the control: the reference in the program's place, its matmul
            # operands in 8 bits
            program = follow(self.ns.variant.partition(":")[2]
                             or self.extras["control"])
        verdict = reference.compare(program, ref, self.extras["limits"])
        names = ["/".join(str(k.key) for k in path) for path, _ in
                 jax.tree_util.tree_flatten_with_path(
                     self.ref_model.shapes,
                     is_leaf=lambda x: isinstance(x, tuple))[0]]
        for what, key, leaf_names in (
                ("first transmit", "first",
                 names if len(ref["first"]) == len(names) else None),
                ("change after 3", "change", names)):
            for name, p, r in reference.worst_leaves(
                    program[key], ref[key], leaf_names):
                log(f"look, {what}: {name}: program {p:.6g} "
                    f"reference {r:.6g}")
        self.check_s = time.monotonic() - t
        return verdict

    def metrics(self, verdict):
        w = self.window
        e2e = {
            "rounds_per_s": (w["rounds"] / w["seconds"], "rounds/s"),
            "peak_hbm_gib": (w["peak_bytes"] / 2**30, "GiB"),
            "setup_s": (self.setup_s, "s"),
        }
        declared = {m["name"]: m for m in self.bench["end_to_end"]}
        ok = verdict["correct"] and not w["nan"] and w["compiles"] == 0
        line = {"correct": bool(ok), "attempted": int(w["rounds"]),
                "failed": int(w["rounds"]) if w["nan"] else 0}
        device = dict(self.device, memory_peak_bytes=int(w["peak_bytes"]))
        if not self.ns.trace:
            line["metrics"] = {n: {"value": v, "unit": u}
                               for n, (v, u) in e2e.items()
                               if n in declared}
        else:
            import trace_reduce as tr

            t = time.monotonic()
            trc = tr.load(w["trace_dir"])
            lo, hi = tr.window_of(trc)
            ctx = {"trace": trc, "lo": lo, "hi": hi, "tr": tr,
                   "rounds": w["rounds"], "window_s": (hi - lo) / 1e9,
                   "host_window_s": w["seconds"], "wait_s": w["wait_s"],
                   "compile_s": self.compile_s, "val_ms": self.val_ms,
                   "config": self.config, "params": self.params,
                   "batch_shapes": self.batch_shapes, "device": self.device,
                   "ref_model": self.ref_model,
                   "grad_size": self.grad_size,
                   "peaks": load_json(HERE, "peaks.json")}
            busy = tr.busy_seconds(trc, lo, hi)
            device.update(busy_s=busy, window_s=ctx["window_s"])
            ctx["busy_s"] = busy
            log(f"traced window: {w['rounds']} rounds in "
                f"{ctx['window_s']:.3f} s, device busy {busy:.3f} s "
                f"({len(trc.device_ops)} chip(s) traced)")
            out = {}
            sys.path.insert(0, os.path.join(HERE, "metrics"))
            for m in self.bench["per_layer"]:
                if "workloads" in m and self.ns.workload not in m["workloads"]:
                    continue
                reader = importlib.import_module(m["name"])
                val = reader.read(ctx)
                if val is not None:
                    out[m["name"]] = {"value": float(val), "unit": m["unit"]}
            line["metrics"] = out
            ops = sorted(tr.op_seconds(trc, lo, hi).items(),
                         key=lambda kv: -kv[1])[:10]
            idle = sorted(tr.attribute_gaps(trc, lo, hi).items(),
                          key=lambda kv: -kv[1])[:10]
            line["breakdown"] = {
                "device_ops": [[n[:120], s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in idle]}
            if self.ns.dump_trace:
                self._dump(tr, trc, lo, hi)
            shutil.rmtree(w["trace_dir"], ignore_errors=True)
            log(f"trace read in {time.monotonic() - t:.1f} s")
        line["device"] = device
        line["compared"] = verdict["compared"]
        return line

    def _dump(self, tr, trc, lo, hi):
        """For the builder: what the trace holds, for a look by hand."""
        dst = os.path.join(ROOT, "chiprun_out", "trace_" + self.ns.workload)
        os.makedirs(dst, exist_ok=True)
        with open(os.path.join(dst, "summary.json"), "w") as f:
            json.dump({
                "planes": trc.planes,
                "ops": sorted(tr.op_seconds(trc, lo, hi).items(),
                              key=lambda kv: -kv[1])[:400],
                "spans": {n: tr.span_seconds(trc, n, lo, hi)
                          for n in tr.HOST_SPANS}}, f, indent=1)
        first = sorted(trc.device_ops)[0] if trc.device_ops else None
        sample = {"device_ops": [o for o in trc.device_ops.get(first, [])
                                 if lo <= o[1] <= hi][:6000],
                  "host_spans": [s for s in trc.host_spans
                                 if lo <= s[1] <= hi][:2000],
                  "window": [lo, hi]}
        with open(os.path.join(dst, "sample.json"), "w") as f:
            json.dump(sample, f)


def main(ns, t0) -> int:
    run = Run(ns, t0)
    if not os.path.exists(os.path.join(ROOT, "commefficient_tpu")):
        print("bench: the program is not in this directory", file=sys.stderr)
        return 2
    run.environment()
    run.reference_model()
    entry = Entry(run.config["entry"])
    entry.install(run)
    run.schedule_kind = entry.schedule_kind
    argv = run.argv()
    log(f"{ns.workload}: {run.config['entry']} {' '.join(argv)}")
    real_stdout = sys.stdout
    sys.stdout = sys.stderr   # the program's tables go beside the log
    try:
        entry.main(argv)
        run.grad_size = int(run.model.grad_size)
        verdict = run.check()
        line = run.metrics(verdict)
    finally:
        sys.stdout = real_stdout
    for name, e in line["compared"].items():
        log(f"compared {name}: {e['value']:.6g} (limit {e['limit']:g})")
    for name, v in verdict["not_compared"].items():
        log(f"not compared {name}: {v:.6g}")
    log(f"correct={line['correct']} check {run.check_s:.1f} s, "
        f"compilations in the window {run.window['compiles']}, "
        f"nan {run.window['nan']}")
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
