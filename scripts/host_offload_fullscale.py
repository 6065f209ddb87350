"""Allocate the TRUE EMNIST-scale host-offloaded client state and drive it.

VERDICT r4 missing #4 / task 5: ``federated/memory.py`` plans the
3,500-client sketched state (~35 GB at the FetchSGD table geometry) and the
suite drives the streaming path at reduced row size; no run had ever
*materialized* the full-size state and streamed rounds through it.  This
script does exactly that, at the real geometry the plan documents
(reference fed_aggregator.py:105-129 is the host-shared-memory design this
replaces):

  3,500 clients (padded to a mesh multiple) x sketch 5 x 500,000 f32
  = ~35 GB of error state, one 10 MB row per client.

On the real chip the plan chooses ``host`` on its own (the v5e has ~16 GB
HBM) and the rows live in ``pinned_host``; on the CPU mesh the same
streaming wrapper runs with default memory (documented degradation).  Each
round gathers W=8 rows to a device proxy, applies a device-side delta, and
scatters the deltas back — the reference's touched-rows traffic, timed.

Run (on the TPU; the process owns the chip):
    python scripts/host_offload_fullscale.py
CPU-mesh fallback (still allocates the full 35 GB in host RAM):
    HOST_OFFLOAD_CPU=1 python scripts/host_offload_fullscale.py
Smoke mode for the suite harness: HOST_OFFLOAD_TINY=1

Prints one JSON line; it writes no file.
"""

from __future__ import annotations

import json
import os
import resource
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

if os.environ.get("HOST_OFFLOAD_CPU") == "1":
    from script_env import force_cpu_mesh

    force_cpu_mesh(8)
else:
    from commefficient_tpu.utils import configure_compile_cache

    configure_compile_cache()

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from commefficient_tpu.federated.host_state import RowStreamer  # noqa: E402
from commefficient_tpu.federated.memory import (  # noqa: E402
    client_state_sharding,
    plan_client_state_memory,
)
from commefficient_tpu.federated.rounds import (  # noqa: E402
    ClientStates,
    init_client_states,
)
from commefficient_tpu.federated.worker import WorkerConfig  # noqa: E402
from commefficient_tpu.ops.sketch import make_sketch  # noqa: E402
from commefficient_tpu.parallel.mesh import default_client_mesh  # noqa: E402

TINY = os.environ.get("HOST_OFFLOAD_TINY") == "1"
# reference fed_aggregator.py:68-72 (EMNIST client count) and the FetchSGD
# table geometry (reference utils.py:142-162 / cv_train defaults)
NUM_CLIENTS = 3500
D = 6_568_640
ROWS, COLS = 5, 500_000
W = 8
ROUNDS = int(os.environ.get("HOST_OFFLOAD_ROUNDS", "6"))
if TINY:
    NUM_CLIENTS, D, ROWS, COLS, ROUNDS = 48, 9973, 3, 1024, 3

def rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024 ** 2


def main() -> int:
    devs = jax.devices()
    platform = devs[0].platform
    mesh = default_client_mesh(len(devs))
    n = -(-NUM_CLIENTS // len(devs)) * len(devs)
    wcfg = WorkerConfig(mode="sketch", error_type="local", k=50_000,
                        num_workers=W)
    sketch = make_sketch(D, c=COLS, r=ROWS, seed=0, num_blocks=1)
    r, c_pad = sketch.table_shape
    row_mb = r * c_pad * 4 / 1024 ** 2
    total_gb = n * r * c_pad * 4 / 1024 ** 3
    print(f"[offload] platform={platform} n={n} table={r}x{c_pad} "
          f"row={row_mb:.1f} MB total={total_gb:.2f} GB", flush=True)

    # On the CPU mesh the per-device slice (35 GB / 8) fits the default
    # budget and the plan would honestly say "hbm"; force the host branch
    # there so the fallback still exercises the streaming placement the
    # script exists to drive (memory.py documents this override for
    # exactly this purpose).
    if platform == "cpu" and "COMMEFFICIENT_STATE_HBM_BUDGET" not in os.environ:
        os.environ["COMMEFFICIENT_STATE_HBM_BUDGET"] = "1"
    # this script drives the HOST (in-RAM streaming) tier specifically —
    # the disk tier is a different placement (docs/host_offload.md) — so pin the
    # host budget above the 35 GB total or a small-RAM host would resolve
    # "disk" and allocate nothing in RAM at all
    plan = plan_client_state_memory(n, D, wcfg, sketch=sketch, mesh=mesh,
                                    host_budget_bytes=1 << 46)
    print(f"[offload] plan: {plan}", flush=True)
    if not TINY and platform != "cpu" and plan.placement != "host":
        # only plausible on a giant-HBM device; record it rather than fail
        print("[offload] WARNING: plan chose hbm at 35 GB?!", flush=True)
    sharding = client_state_sharding(mesh, plan)

    t0 = time.time()
    states = init_client_states(n, D, wcfg, sketch=sketch, sharding=sharding)
    jax.block_until_ready(states.errors)
    alloc_s = time.time() - t0
    kinds = {f: getattr(getattr(states, f).sharding, "memory_kind", None)
             for f in ("errors",) if getattr(states, f) is not None}
    print(f"[offload] allocated in {alloc_s:.1f}s memory_kind={kinds} "
          f"rss={rss_gb():.1f} GB", flush=True)

    # same gate as the production aggregator: host-side compute only when
    # the plan actually placed the state in host memory on a TPU backend
    streamer = RowStreamer(mesh, sharding,
                           host_compute=(plan.placement == "host"
                                         and platform != "cpu"))
    rng = np.random.default_rng(0)
    gather_ms, scatter_ms, touched = [], [], {}
    for rd in range(ROUNDS):
        ids = rng.choice(NUM_CLIENTS, size=W, replace=False)
        t0 = time.time()
        stream = streamer.gather(states, ids)
        jax.block_until_ready(stream.proxy.errors)
        g_ms = (time.time() - t0) * 1e3
        # the "round": a device-side delta on the proxy (the real round step
        # is geometry-identical — proxy rows are its exact input/output)
        delta = jnp.full_like(stream.proxy.errors, float(rd + 1))
        new_proxy = ClientStates(None, stream.proxy.errors + delta, None)
        t0 = time.time()
        states = streamer.scatter(states, stream, stream.proxy, new_proxy)
        jax.block_until_ready(states.errors)
        s_ms = (time.time() - t0) * 1e3
        gather_ms.append(g_ms)
        scatter_ms.append(s_ms)
        for i in ids:
            touched[int(i)] = touched.get(int(i), 0.0) + float(rd + 1)
        print(f"[offload] round {rd}: gather {g_ms:.1f} ms "
              f"scatter {s_ms:.1f} ms", flush=True)

    # spot-verify touched rows carry the accumulated deltas and two
    # untouched rows stay zero — without reading the whole 35 GB back
    check_ids = list(touched)[:4]
    untouched = [i for i in range(NUM_CLIENTS) if i not in touched][:2]
    probe = streamer.gather(states,
                            np.array(check_ids + untouched +
                                     [0] * (W - len(check_ids) -
                                            len(untouched))))
    vals = np.asarray(jax.device_get(probe.proxy.errors))[:, 0, 0]
    for j, cid in enumerate(check_ids):
        np.testing.assert_allclose(vals[j], touched[cid], rtol=1e-6)
    for j in range(len(check_ids), len(check_ids) + len(untouched)):
        assert vals[j] == 0.0, f"untouched row {untouched} nonzero"
    print("[offload] spot-check ok: deltas accumulated, untouched rows zero",
          flush=True)

    # steady-state medians, skipping round 0 (jit compile of gather/scatter)
    med = lambda xs: float(np.median(xs[1:])) if len(xs) > 1 else xs[0]
    result = {
        "platform": platform,
        "tiny": TINY,
        "num_clients": NUM_CLIENTS,
        "padded_rows": n,
        "table": [r, c_pad],
        "row_mb": round(row_mb, 2),
        "total_gb": round(total_gb, 2),
        "placement": plan.placement,
        "memory_kind": kinds.get("errors"),
        "alloc_s": round(alloc_s, 2),
        "gather_ms_median": round(med(gather_ms), 2),
        "scatter_ms_median": round(med(scatter_ms), 2),
        "rounds": ROUNDS,
        "rss_gb": round(rss_gb(), 2),
        "measured_at": time.strftime("%Y-%m-%d %H:%M:%S"),
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
