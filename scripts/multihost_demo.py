"""Real multi-process (DCN-path) validation of the distributed backend.

The reference's distributed substrate is single-host by construction
(MASTER_ADDR hard-coded to 127.0.0.1, reference fed_aggregator.py:161-162);
this framework's replacement — a ``jax.sharding.Mesh`` whose leading axis
spans hosts over DCN (``parallel/mesh.py`` multihost branch) — was until now
validated only by a monkeypatched unit test of the mesh construction
(tests/test_parallel.py). This script runs the REAL thing on one machine:

  - two OS processes, each a JAX "host" with 4 virtual CPU devices,
    joined through ``jax.distributed.initialize`` (TCP coordinator —
    the same wire path a TPU pod's hosts use over DCN);
  - ``make_mesh`` takes its multihost branch (``process_count() == 2``)
    and builds the hybrid 8-device ``clients`` mesh via
    ``create_hybrid_device_mesh`` (process-granule fallback on CPU);
  - one fused federated round (the tiny dry-run geometry — literally the
    same code, __graft_entry__.run_tiny_sketched_round) executes with the
    transmit reduce crossing the process boundary;
  - each process prints a checksum of the (replicated) new PS weights;
    the parent also computes the single-process 8-device reference and
    asserts the cross-process round matches it numerically.

The round leg is parametrized (tests/test_multihost.py runs the matrix):

  --mode {sketch,uncompressed}   compressed vs dense round
  --plan SPEC                    --collective_plan spec, including per-
                                 mesh-axis entries (docs/multihost.md);
                                 non-empty SPEC implies --server_shard
  --engine                       instead of one raw round, run the FULL
                                 engine path (__graft_entry__.
                                 run_tiny_engine: FedModel/FedOptimizer/
                                 PipelinedRoundEngine on a 2D clients x
                                 shard mesh) with a coordinated mid-run
                                 checkpoint, then ELASTICALLY resume that
                                 2-process checkpoint onto THIS process's
                                 single-process mesh and pin the weights.

Usage:  python scripts/multihost_demo.py [opts]   (parent; spawns children)
        python scripts/multihost_demo.py --child I PORT   (internal)

Exercised by tests/test_multihost.py.
"""

from __future__ import annotations

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

N_PROC = 2
DEV_PER_PROC = 4
W = N_PROC * DEV_PER_PROC  # one client slot per device
CHILD_TIMEOUT = 420        # < the outer test timeout, so children die first
BIND_ATTEMPTS = 3          # coordinator-port collision retries (see parent)

# child config rides in env vars, not argv, so the --child dispatch and the
# orphan-cleanup paths never have to parse a growing option matrix
_ENV_MODE = "COMMEFFICIENT_DEMO_MODE"
_ENV_PLAN = "COMMEFFICIENT_DEMO_PLAN"
_ENV_ENGINE = "COMMEFFICIENT_DEMO_ENGINE"
_ENV_CKPT = "COMMEFFICIENT_DEMO_CKPT"

# jax.distributed's coordinator bind failure, as seen in child output (the
# grpc server message is stable across the jaxlib versions we run)
_BIND_MARKERS = ("Failed to bind", "address already in use",
                 "Address already in use")


def _global_put(x, sharding):
    """Host-uniform numpy -> global jax.Array under ``sharding`` (every
    process holds the full value; the callback hands each addressable
    device its shard)."""
    import numpy as np

    import jax

    x = np.asarray(x)
    return jax.make_array_from_callback(x.shape, sharding,
                                        lambda idx: x[idx])


def _free_port() -> int:
    """Pick a currently-free TCP port for the coordinator. Inherently racy
    (the port is released before the coordinator binds it — TOCTOU); the
    parent bounds the race with ``BIND_ATTEMPTS`` full cohort retries on a
    detected bind failure rather than pretending the pick is atomic."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child(proc_id: int, port: int) -> None:
    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=N_PROC,
        process_id=proc_id,
    )
    assert jax.process_count() == N_PROC
    assert len(jax.devices()) == W, \
        f"expected {W} global devices, got {len(jax.devices())}"
    assert len(jax.local_devices()) == DEV_PER_PROC

    from __graft_entry__ import run_tiny_engine, run_tiny_sketched_round
    from commefficient_tpu.parallel.mesh import make_mesh

    def sync(tag: str) -> None:
        # coordination-service barrier (NOT a device collective): a loaded
        # host can skew the two children's compiles past the CPU
        # collectives' ~30 s timeout and past the client's ~30 s shutdown
        # barrier; syncing on compile-done and on exit makes both windows
        # skew-free. 300 s covers a worst-case contended compile.
        from jax._src.distributed import global_state

        global_state.client.wait_at_barrier(tag, 300_000)

    mode = os.environ.get(_ENV_MODE, "sketch")
    plan = os.environ.get(_ENV_PLAN, "")
    if os.environ.get(_ENV_ENGINE):
        # full engine path on the 2D (clients x shard) mesh, with the
        # coordinated checkpoint written mid-run (process 0 writes, both
        # processes barrier — federated/checkpoint.py)
        new_ps, ckpt = run_tiny_engine(
            W=W, rounds=4, shard_devices=2, mode=mode, collective_plan=plan,
            save_path=os.path.join(os.environ[_ENV_CKPT], "rs"), save_at=2)
        if ckpt:
            print(f"CHILD {proc_id} CKPT {ckpt}", flush=True)
    else:
        mesh = make_mesh([("clients", W)])
        new_ps, _ = run_tiny_sketched_round(
            mesh, W=W, put=_global_put, sync=sync, mode=mode,
            server_shard=bool(plan), collective_plan=plan)
    print(f"CHILD {proc_id} RESULT "
          f"sum={float(new_ps.sum()):.10e} "
          f"absmax={float(abs(new_ps).max()):.10e} d={new_ps.size}",
          flush=True)
    sync("pre_exit")


def _run_cohort(env: dict) -> list:
    """Launch the N_PROC children against one coordinator port and collect
    their output; retried by the caller on a coordinator bind failure
    (the _free_port TOCTOU — another process can claim the port between
    the probe and jax.distributed's bind)."""
    import time

    last_outs = None
    for attempt in range(BIND_ATTEMPTS):
        port = _free_port()  # fresh pick per attempt
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child", str(i),
             str(port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True) for i in range(N_PROC)]
        outs = []
        # one SHARED deadline across both children (not per-child): the
        # outer test timeout must always fire after this one, so a hang is
        # cleaned up here with the children's output still captured
        deadline = time.monotonic() + CHILD_TIMEOUT
        failed = False
        try:
            for i, p in enumerate(procs):
                remaining = max(1.0, deadline - time.monotonic())
                try:
                    out, _ = p.communicate(timeout=remaining)
                except subprocess.TimeoutExpired:
                    # kill and drain, so the hung child's partial output
                    # still reaches the log (TimeoutExpired carries none)
                    p.kill()
                    out, _ = p.communicate()
                    print(f"--- child {i} (TIMED OUT after "
                          f"{remaining:.0f}s) ---\n{out}")
                    raise
                outs.append(out)
                print(f"--- child {i} (attempt {attempt}) ---\n{out}")
                failed = failed or p.returncode != 0
        finally:
            # a child that crashed or hung must not orphan its sibling (it
            # would sit in jax.distributed.initialize burning CPU forever)
            for p in procs:
                if p.poll() is None:
                    p.kill()
        if not failed:
            return outs
        last_outs = outs
        bind_race = any(m in out for out in outs for m in _BIND_MARKERS)
        if not bind_race or attempt == BIND_ATTEMPTS - 1:
            break
        print(f"coordinator bind race on port {port} — retrying "
              f"({attempt + 1}/{BIND_ATTEMPTS})")
    raise AssertionError(
        f"child cohort failed after bind-retry ladder:\n"
        + "\n".join(last_outs or []))


def _parse_results(outs: list) -> dict:
    results = {}
    for i, out in enumerate(outs):
        for line in out.splitlines():
            if line.startswith(f"CHILD {i} RESULT"):
                parts = dict(kv.split("=") for kv in line.split()[3:])
                results[i] = (float(parts["sum"]), float(parts["absmax"]),
                              int(parts["d"]))
    assert set(results) == set(range(N_PROC)), \
        f"missing child results: {results.keys()}"
    assert results[0] == results[1], \
        f"processes disagree on the replicated result: {results}"
    return results


def parent(mode: str, plan: str, engine: bool) -> None:
    from __graft_entry__ import sanitized_cpu_env

    if os.environ.get("JAX_PLATFORMS") != "cpu" or \
            f"device_count={W}" not in os.environ.get("XLA_FLAGS", ""):
        # re-exec on the CPU backend with the device-count flag in place
        # BEFORE the interpreter starts (XLA_FLAGS is read at backend init)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + sys.argv[1:],
            env=sanitized_cpu_env(W), cwd=_REPO)
        sys.exit(proc.returncode)

    import tempfile

    import numpy as np

    env = sanitized_cpu_env(DEV_PER_PROC)
    env[_ENV_MODE] = mode
    env[_ENV_PLAN] = plan
    ckpt_dir = None
    if engine:
        ckpt_dir = tempfile.mkdtemp(prefix="multihost_demo_ckpt_")
        env[_ENV_ENGINE] = "1"
        env[_ENV_CKPT] = ckpt_dir

    outs = _run_cohort(env)
    results = _parse_results(outs)
    got_sum, got_absmax, got_d = results[0]

    # single-process 8-device reference in THIS process
    from __graft_entry__ import run_tiny_engine, run_tiny_sketched_round
    from commefficient_tpu.parallel.mesh import make_mesh

    if engine:
        ref, _ = run_tiny_engine(W=W, rounds=4, shard_devices=2,
                                 mode=mode, collective_plan=plan)
    else:
        mesh = make_mesh([("clients", W)])
        ref, _ = run_tiny_sketched_round(mesh, W=W, put=_global_put,
                                         mode=mode,
                                         server_shard=bool(plan),
                                         collective_plan=plan)
    ref_sum, ref_absmax = float(ref.sum()), float(np.abs(ref).max())
    assert got_d == ref.size
    np.testing.assert_allclose(got_sum, ref_sum, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(got_absmax, ref_absmax, rtol=1e-4, atol=1e-7)

    if engine:
        # ELASTIC RESUME: the checkpoint the 2-process cohort wrote after
        # round 2 restores onto THIS process's DIFFERENT mesh shape
        # (1 process, no shard axis) and finishes rounds 3-4; the weights
        # must land on the same point (checkpoint.py's canonical flat view
        # is mesh-shape-free; carries re-init per-slot on a plan change)
        ckpt = None
        for out in outs:
            for line in out.splitlines():
                if " CKPT " in line:
                    ckpt = line.split(" CKPT ", 1)[1].strip()
        assert ckpt and os.path.exists(ckpt), \
            f"engine cohort produced no checkpoint under {ckpt_dir}"
        elastic, _ = run_tiny_engine(W=W, rounds=4, shard_devices=1,
                                     mode=mode, collective_plan=plan,
                                     resume_path=ckpt)
        np.testing.assert_allclose(float(elastic.sum()), got_sum,
                                   rtol=1e-4, atol=1e-6)
        print("ELASTIC RESUME OK: 2-process checkpoint -> 1-process mesh")

    leg = "engine" if engine else "round"
    print(f"MULTIHOST OK: 2-process hybrid mesh {leg} == single-process "
          f"{leg} (mode={mode} plan={plan or 'fp32'}; "
          f"sum {got_sum:.6e} vs {ref_sum:.6e})")


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "--child":
        child(int(sys.argv[2]), int(sys.argv[3]))
    else:
        import argparse

        ap = argparse.ArgumentParser()
        ap.add_argument("--mode", default="sketch",
                        choices=["sketch", "uncompressed"])
        ap.add_argument("--plan", default="")
        ap.add_argument("--engine", action="store_true")
        a = ap.parse_args()
        parent(a.mode, a.plan, a.engine)
