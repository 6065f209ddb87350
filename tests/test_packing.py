"""Multi-tenant run packing (scripts/orchestrate.py, docs/packing.md).

Pins:

- bounded fair-share admission: ``--max-concurrent`` holds, admission
  order is deterministic (tenant-id FIFO), and a waiting tenant is
  admitted only when a slot frees;
- cache-warmup admission: with a shared compile cache the FIRST tenant
  holds an exclusive slot until its first heartbeat (``fleet_warm``),
  so followers compile warm instead of racing the cold compile;
- per-tenant restart isolation: killing tenant 1 mid-fleet restarts
  ONLY tenant 1 (relaunched with ``--resume auto`` through the
  ChildRun ladder) while tenants 0/2 heartbeat uninterrupted across
  the restart — reproduced from the fleet JSONL alone;
- the per-tenant namespace env seams: ``COMMEFFICIENT_RUN_DIR`` (pinned
  run dir — ``utils.make_logdir`` returns it verbatim, keeping two
  tenants' telemetry.jsonl + trace captures apart),
  ``COMMEFFICIENT_TENANT_ID``, and the ONE shared fresh
  ``JAX_COMPILATION_CACHE_DIR``;
- fleet JSONL conservation: admitted == finished + gave_up + in_flight,
  give-ups included, and ``obs_report --fleet`` renders the whole run
  (per-tenant round table + aggregate rounds/sec) from the log alone;
- the fair-share throttle (``--max-lead``): a tenant running ahead is
  SIGSTOPped until the straggler catches up, then resumed, and both
  still finish;
- the shared-cache speedup smoke (@heavy): the second identical jax
  tenant observes a non-empty compile cache at startup.

The unit tests drive the orchestrator over FAKE tenants (tiny scripted
python children, no jax) so they stay tier-1-fast, per the
test_supervise.py precedent; the real 3-tenant cv_train packed-vs-
sequential drill with bit-identity is the @slow ``TestPackingBench``
leg.
"""

from __future__ import annotations

import json
import os
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load_script(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(__file__), "..", "scripts",
                           f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the fake tenant: beats, optional one-shot crash, env-seam dump
# ---------------------------------------------------------------------------

_TENANT = textwrap.dedent("""
    import json, os, sys, time
    out_dir = sys.argv[1]
    beats = int(sys.argv[2])
    sleep = float(sys.argv[3])
    crash_at = int(sys.argv[4]) if len(sys.argv) > 4 else -1
    tid = os.environ.get("COMMEFFICIENT_TENANT_ID", "x")
    state = os.path.join(out_dir, f"attempts_t{tid}")
    n = int(open(state).read()) if os.path.exists(state) else 0
    open(state, "w").write(str(n + 1))
    with open(state + f".attempt{n}", "w") as f:
        json.dump({"argv": sys.argv[1:],
                   "run_dir": os.environ.get("COMMEFFICIENT_RUN_DIR", ""),
                   "cache": os.environ.get(
                       "JAX_COMPILATION_CACHE_DIR", ""),
                   "tenant": tid}, f)
    if crash_at == -2:
        sys.exit(1)   # deterministic pre-beat crash, every attempt
    for i in range(beats):
        print(f"HEARTBEAT round={i}", file=sys.stderr, flush=True)
        time.sleep(sleep)
        if n == 0 and crash_at >= 0 and i == crash_at:
            sys.exit(1)   # one-shot mid-run crash (first attempt only)
    sys.exit(0)
""")


@pytest.fixture
def fleet(tmp_path):
    """Returns ``run(specs, **orchestrate_kwargs) -> (rc, events,
    dumps)`` driving scripts/orchestrate.py over scripted tenants.
    Each spec is ``(beats, sleep, crash_at)``; ``dumps`` maps
    ``(tenant, attempt) -> env-seam dict`` from the children's own
    records."""
    orch = _load_script("orchestrate")
    child_py = tmp_path / "tenant.py"
    child_py.write_text(_TENANT)
    fleet_dir = tmp_path / "fleet"
    events_path = fleet_dir / "fleet_events.jsonl"

    def run(specs, **kw):
        # crash_at is always passed explicitly so namespace args the
        # orchestrator appends land AFTER the child's own positionals
        tenants = [[sys.executable, str(child_py), str(tmp_path),
                    str(b), str(s), str(-1 if c is None else c)]
                   for b, s, c in specs]
        kw.setdefault("heartbeat_timeout", 5.0)
        kw.setdefault("startup_grace", 30.0)
        kw.setdefault("backoff", 0.05)
        kw.setdefault("max_restarts", 3)
        kw.setdefault("share_cache", False)
        kw.setdefault("warm_admission", False)
        kw.setdefault("namespace_args", False)
        kw.setdefault("poll", 0.05)
        rc = orch.orchestrate(
            tenants, fleet_dir=str(fleet_dir),
            out=open(os.devnull, "w"), **kw)
        events = [json.loads(line)
                  for line in events_path.read_text().splitlines()]
        dumps = {}
        for fn in os.listdir(tmp_path):
            if ".attempt" in fn and fn.startswith("attempts_t"):
                tid = int(fn.split(".attempt")[0][len("attempts_t"):])
                att = int(fn.split(".attempt")[1])
                dumps[(tid, att)] = json.loads(
                    (tmp_path / fn).read_text())
        return rc, events, dumps

    return run


def _evs(events, kind):
    return [e for e in events if e.get("ev") == kind]


# ---------------------------------------------------------------------------
# run-dir seam unit
# ---------------------------------------------------------------------------


def test_make_logdir_honors_run_dir_seam(monkeypatch, tmp_path):
    from commefficient_tpu.utils import make_logdir

    class A:
        num_workers, num_clients, mode, logdir_root = 2, 4, "sketch", "runs"
        num_rows, num_cols, k = 1, 8, 2

    derived = make_logdir(A())
    assert derived.startswith("runs")
    pinned = str(tmp_path / "t3" / "run")
    monkeypatch.setenv("COMMEFFICIENT_RUN_DIR", pinned)
    assert make_logdir(A()) == pinned


# ---------------------------------------------------------------------------
# admission policy
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_bounded_fifo_admission(self, fleet):
        rc, events, _ = fleet([(3, 0.1, None)] * 4, max_concurrent=2)
        assert rc == 0
        admits = _evs(events, "tenant_admit")
        assert [e["tenant"] for e in admits] == [0, 1, 2, 3]
        # the bound holds: tenants 2/3 wait for a slot, i.e. their
        # admission comes after the first finish frees one
        first_finish_t = min(e["t"] for e in _evs(events, "tenant_finish"))
        assert admits[2]["t"] >= first_finish_t - 0.01
        assert admits[3]["t"] >= first_finish_t - 0.01
        # never more than 2 in flight: reconstruct from the log
        live = 0
        peak = 0
        for e in events:
            if e["ev"] == "tenant_admit":
                live += 1
                peak = max(peak, live)
            elif e["ev"] in ("tenant_finish", "tenant_giveup"):
                live -= 1
        assert peak <= 2

    def test_warm_admission_gate(self, fleet, tmp_path):
        # shared cache on -> tenant 0 holds an exclusive slot until its
        # first heartbeat; only then are 1/2 admitted (compiling warm)
        rc, events, _ = fleet([(4, 0.05, None)] * 3,
                              share_cache=True, warm_admission=True)
        assert rc == 0
        idx = {id(e): i for i, e in enumerate(events)}
        admits = _evs(events, "tenant_admit")
        assert [e["tenant"] for e in admits] == [0, 1, 2]
        first_progress_0 = next(e for e in events
                                if e.get("ev") == "tenant_progress"
                                and e["tenant"] == 0)
        assert idx[id(admits[1])] > idx[id(first_progress_0)]
        assert idx[id(admits[2])] > idx[id(first_progress_0)]
        warm = _evs(events, "fleet_warm")
        assert len(warm) == 1 and warm[0]["warmed_by"] == 0
        # the fleet's shared cache dir is fresh-per-orchestrator and
        # cleaned up on exit (the 0.4.37 donation-from-cache guard)
        start = _evs(events, "fleet_start")[0]
        assert start["cache_dir"]
        assert not os.path.isdir(start["cache_dir"])


# ---------------------------------------------------------------------------
# restart isolation (the acceptance drill) + conservation
# ---------------------------------------------------------------------------


class TestRestartIsolation:
    def test_kill_one_tenant_neighbors_uninterrupted(self, fleet):
        # tenant 1 crashes after beat 3 on its first attempt; 0/2 just
        # run. The ladder must restart ONLY tenant 1 (--resume auto)
        # while the neighbors' heartbeats continue across the restart.
        rc, events, dumps = fleet(
            [(12, 0.15, None), (6, 0.1, 3), (12, 0.15, None)],
            backoff=0.2)
        assert rc == 0
        restarts = _evs(events, "tenant_restart")
        assert [e["tenant"] for e in restarts] == [1]
        restart_t = restarts[0]["t"]
        # only tenant 1 ran twice, and its relaunch carried --resume auto
        assert (1, 1) in dumps and (0, 1) not in dumps \
            and (2, 1) not in dumps
        assert dumps[(1, 1)]["argv"][-2:] == ["--resume", "auto"]
        assert dumps[(1, 0)]["argv"][-2:] != ["--resume", "auto"]
        # neighbors heartbeat on BOTH sides of the restart instant
        for t in (0, 2):
            prog_t = [e["t"] for e in _evs(events, "tenant_progress")
                      if e["tenant"] == t]
            assert any(pt < restart_t for pt in prog_t), \
                f"tenant {t} had no progress before the restart"
            assert any(pt > restart_t for pt in prog_t), \
                f"tenant {t} had no progress after the restart"
        # ... and the whole story reproduces from the JSONL alone
        obs = _load_script("obs_report")
        s = obs.summarize_fleet(events)
        assert s["conservation_ok"]
        assert s["tenants"]["1"]["restarts"] == 1
        assert s["tenants"]["0"]["restarts"] == 0
        assert s["tenants"]["2"]["restarts"] == 0
        assert all(s["tenants"][k]["state"] == "finished"
                   for k in ("0", "1", "2"))

    def test_conservation_with_giveup(self, fleet, capsys):
        # tenant 1 crashes pre-beat every attempt -> restart budget
        # exhausted -> gave_up; the fleet degrades but conserves:
        # admitted == finished + gave_up + in_flight (in_flight 0)
        rc, events, _ = fleet(
            [(3, 0.05, None), (0, 0.05, -2), (3, 0.05, None)],
            max_restarts=1)
        assert rc == 1
        obs = _load_script("obs_report")
        s = obs.summarize_fleet(events)
        assert s["admitted"] == 3
        assert s["finished"] == 2
        assert s["gave_up"] == 1
        assert s["in_flight"] == 0
        assert s["conservation_ok"]
        assert s["tenants"]["1"]["state"] == "gave_up"
        done = _evs(events, "fleet_done")[-1]
        assert done["admitted"] == done["finished"] + done["gave_up"]
        # the renderer reproduces the run (and the rc-2 path can't hide
        # a broken audit)
        r = obs.render_fleet(events)
        rendered = capsys.readouterr().out
        assert "## Fleet tenants" in rendered
        assert "gave_up" in rendered
        assert "-> OK" in rendered and "BROKEN" not in rendered
        assert r["conservation_ok"]

    def test_obs_report_fleet_cli(self, fleet, tmp_path, capsys):
        rc, events, _ = fleet([(2, 0.05, None)] * 2)
        assert rc == 0
        obs = _load_script("obs_report")
        rc2 = obs.main(["--fleet", str(tmp_path / "fleet")])
        out = capsys.readouterr().out
        assert rc2 == 0
        assert "## Fleet tenants" in out
        # machine-readable tail: ALWAYS the last stdout line
        tail = json.loads(out.strip().splitlines()[-1])
        assert tail["finished"] == 2 and tail["conservation_ok"]


# ---------------------------------------------------------------------------
# env-seam namespacing: run dir, tenant id, one shared cache
# ---------------------------------------------------------------------------


def test_tenant_namespace_env_seams(fleet):
    rc, events, dumps = fleet([(2, 0.05, None)] * 3, share_cache=True,
                              keep_cache=True)
    assert rc == 0
    run_dirs = {dumps[(i, 0)]["run_dir"] for i in range(3)}
    assert len(run_dirs) == 3, "tenant run dirs must never collide"
    for i in range(3):
        d = dumps[(i, 0)]
        assert d["tenant"] == str(i)
        assert d["run_dir"].endswith(os.path.join(f"t{i}", "run"))
        assert os.path.isdir(d["run_dir"])
    # ONE shared compile cache across the fleet
    caches = {dumps[(i, 0)]["cache"] for i in range(3)}
    assert len(caches) == 1 and os.path.isdir(caches.pop())


def test_namespace_args_appended_per_tenant(fleet):
    rc, events, dumps = fleet([(2, 0.05, None)] * 2, namespace_args=True)
    assert rc == 0
    for i in range(2):
        argv = dumps[(i, 0)]["argv"]
        ck = argv[argv.index("--checkpoint_path") + 1]
        st = argv[argv.index("--state_dir") + 1]
        # isolation boundary: --resume auto must find THIS tenant's
        # checkpoints, never a neighbor's
        assert ck.endswith(os.path.join(f"t{i}", "ckpt"))
        assert st.endswith(os.path.join(f"t{i}", "state"))


# ---------------------------------------------------------------------------
# fair-share throttle
# ---------------------------------------------------------------------------


def test_max_lead_throttles_the_front_runner(fleet):
    # tenant 0 beats ~25x faster than tenant 1; with max_lead=3 the
    # orchestrator must SIGSTOP it until the straggler catches up —
    # and both still finish (the slowest tenant is never throttled,
    # so no deadlock)
    rc, events, _ = fleet([(30, 0.02, None), (6, 0.3, None)],
                          max_lead=3)
    assert rc == 0
    throttles = _evs(events, "tenant_throttle")
    unthrottles = _evs(events, "tenant_unthrottle")
    assert throttles, "front-runner was never throttled"
    assert all(e["tenant"] == 0 for e in throttles)
    assert unthrottles, "throttled tenant was never resumed"
    obs = _load_script("obs_report")
    s = obs.summarize_fleet(events)
    assert s["finished"] == 2 and s["conservation_ok"]
    assert s["tenants"]["0"]["throttles"] >= 1
    assert s["tenants"]["1"]["throttles"] == 0


# ---------------------------------------------------------------------------
# shared-cache speedup smoke (@heavy: two real jax children)
# ---------------------------------------------------------------------------


_JAX_TENANT = textwrap.dedent("""
    import json, os, sys
    out_dir = sys.argv[1]
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    pre = len(os.listdir(cache)) if os.path.isdir(cache) else -1
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x + jnp.tanh(x) @ x.T).sum())
    f(jnp.ones((128, 128), jnp.float32)).block_until_ready()
    post = len(os.listdir(cache)) if os.path.isdir(cache) else -1
    tid = os.environ.get("COMMEFFICIENT_TENANT_ID", "x")
    with open(os.path.join(out_dir, f"cache_t{tid}.json"), "w") as fh:
        json.dump({"pre": pre, "post": post}, fh)
    print("HEARTBEAT round=0", file=sys.stderr, flush=True)
    sys.exit(0)
""")


@pytest.mark.heavy
def test_second_tenant_compiles_warm(tmp_path, monkeypatch):
    """The mechanism under the packing leg's wall-clock gate: with
    warm admission, tenant 1 starts against a cache tenant 0 already
    populated — its jit comes from disk, not a second cold compile."""
    # the conftest floor (1s) would keep this tiny jit out of the
    # cache; the orchestrator only installs its own floor when the
    # ambient env has none
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    orch = _load_script("orchestrate")
    child_py = tmp_path / "jax_tenant.py"
    child_py.write_text(_JAX_TENANT)
    tenant = [sys.executable, str(child_py), str(tmp_path)]
    rc = orch.orchestrate(
        [list(tenant), list(tenant)], fleet_dir=str(tmp_path / "fleet"),
        share_cache=True, warm_admission=True, namespace_args=False,
        startup_grace=300.0, poll=0.05, out=open(os.devnull, "w"))
    assert rc == 0
    d0 = json.loads((tmp_path / "cache_t0.json").read_text())
    d1 = json.loads((tmp_path / "cache_t1.json").read_text())
    assert d0["pre"] == 0, "fleet cache must start FRESH (0.4.37 guard)"
    assert d0["post"] > 0, "warmer's compile never landed in the cache"
    assert d1["pre"] > 0, "second tenant admitted before the cache warmed"


# ---------------------------------------------------------------------------
# the real thing (@slow): packed vs sequential cv_train with bit-identity
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestPackingBench:
    def test_packed_bit_identity(self, tmp_path, monkeypatch):
        """2 tiny cv_train tenants (the crash_matrix geometry, told apart
        by seed) run solo, each on its own fresh cache, then packed under
        the orchestrator on one shared fresh cache with warm admission:
        every tenant's final fp32 weights are bit-identical to its solo
        run. Packing may change when a tenant compiles, never what it
        computes."""
        cm = _load_script("crash_matrix")
        orch = _load_script("orchestrate")
        data = str(tmp_path / "data")
        os.makedirs(data)

        def tenant_argv(i, ckpt):
            return cm.train_argv(data, ckpt, shard=False) + [
                "--num_epochs", "1", "--seed", str(i)]  # last flag wins

        for i in range(2):
            cache = tmp_path / f"solo{i}" / "cache"
            cache.mkdir(parents=True)
            cm.run_to_completion(
                tenant_argv(i, str(tmp_path / f"solo{i}" / "ckpt")),
                timeout=1800,
                env_extra={"JAX_COMPILATION_CACHE_DIR": str(cache)})
        # the orchestrator spawns from ITS process env: the solo runs'
        # sanitized child env, and no cache of the caller's
        for k, v in cm.child_env().items():
            monkeypatch.setenv(k, v)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        fleet = str(tmp_path / "fleet")
        rc = orch.orchestrate(
            [tenant_argv(i, os.path.join(fleet, f"t{i}", "ckpt"))
             for i in range(2)],
            fleet_dir=fleet, max_concurrent=min(2, os.cpu_count() or 1),
            warm_admission=True, share_cache=True,
            heartbeat_timeout=600.0, startup_grace=1800.0,
            # a restart would absorb a crash: a tenant that dies fails
            max_restarts=0, poll=0.05, out=open(os.devnull, "w"))
        assert rc == 0, f"packed fleet degraded (rc {rc}): see {fleet}"
        for i in range(2):
            cm.assert_identical(
                cm.final_weights(str(tmp_path / f"solo{i}" / "ckpt")),
                cm.final_weights(os.path.join(fleet, f"t{i}", "ckpt")),
                f"packed tenant {i} (seed {i}) vs its solo run")
