"""Always-on federation service (docs/service.md, parity row A22).

Pins, churn half (--churn, federated/participation.py):

- the ``--churn`` grammar: parse/spec round trip, every rejection named
  at parse time (bad entry, unknown key, init out of range, negative
  rate, a schedule that churns nothing, a forever-empty population);
- ``RowDirectory`` lifecycle: ascending allocation, retire →
  drain-barrier hole flush → lowest-hole-first reuse, capacity and
  double-allocate asserts, the loud translate() failure for a
  departed/unregistered id, and the JSON state round trip the ``.rows``
  snapshot meta rides;
- ``PopulationManager``: the seeded Poisson trajectory is deterministic
  (events + conservation audit identical across reruns), joiners enter
  the pool exactly one churn round after registration, departures are
  permanent, and the teardown audit conserves
  registered == active + departed + quarantined;
- bit-exact mid-churn resume at the state seam: ``state_payload`` →
  ``restore_state`` into a FRESH manager continues the identical
  trajectory (the ``pop/*`` run-state keys), and resuming under a
  different spec warns;
- store integration: gathers/scatters address CLIENT ids through the
  directory, a retired row is zeroed at the drain barrier and its hole
  handed to the next joiner as fresh state, and checkpoint-coordinated
  compaction packs live rows down with content preserved;
- the loader's short-cohort pad id: a live cohort member under churn
  (client 0 may have no row), the legacy 0 on the closed path —
  byte-for-byte compatibility both ways.

Pins, serving half (federated/serving.py, scripts/serve.py):

- ``SnapshotTracker``: progress-ordered discovery over crafted
  CHECKSUMMED run states, hot swap with monotone ``model_version`` =
  ``rounds_dispatched``, a torn newest candidate skipped in favor of
  the served file, ``lag()`` counting strictly-newer checkpoints, and
  the ``.pin`` lease written before reads / released on close;
- ``prune_run_states`` never deletes a pinned checkpoint (long-lived
  serving cannot race GC) and an unreadable lease pins nothing but is
  reported;
- ``ServingReplica``: pre-snapshot requests get counted error answers,
  ``query`` is the deterministic seeded-probe projection, ``stat``/
  ``eval``/unknown-op contracts, and the flushed ``serving.jsonl``
  reproduces answers/swaps/monotone-verdict through obs_report (the
  report path IS the verifier).

The real e2e drills are @slow: the disk-tier churn run with the
conservation audit + mid-churn SIGKILL/resume bit-identity (crash_matrix
helpers), and the serving-interference bench leg
(bench.run_serving_measurement — solo vs live-replica bit-identity).
"""

from __future__ import annotations

import io
import json
import os
import sys
import zlib

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from commefficient_tpu.federated.host_state import (  # noqa: E402
    MemmapRowStore,
    RowDirectory,
)
from commefficient_tpu.federated.participation import (  # noqa: E402
    ChurnSchedule,
    PopulationManager,
    parse_churn,
)
from commefficient_tpu.federated.rounds import ClientStates  # noqa: E402
from commefficient_tpu.federated.serving import (  # noqa: E402
    ServingReplica,
    SnapshotTracker,
    read_response,
    submit_request,
)


def _load_script(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(__file__), "..", "scripts",
                           f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def write_state(ckpt_dir, rounds, seed, d=64, epoch=1):
    """Craft a checksummed run-state npz the way save_run_state lays it
    out (the serving-relevant subset: flat ps_weights + meta_json with
    the checkpoint._content_checksum contract)."""
    os.makedirs(ckpt_dir, exist_ok=True)
    w = np.random.RandomState(seed).standard_normal(d).astype(np.float32)
    crc = zlib.crc32("ps_weights".encode())
    crc = zlib.crc32(str(w.dtype).encode(), crc)
    crc = zlib.crc32(np.ascontiguousarray(w), crc)
    meta = {"checksum": crc, "rounds_dispatched": rounds}
    path = os.path.join(ckpt_dir, f"run_state_ep{epoch}_r{rounds}.npz")
    np.savez(path, ps_weights=w,
             meta_json=np.frombuffer(json.dumps(meta).encode(), np.uint8))
    return path, w


# ---------------------------------------------------------------------------
# --churn grammar
# ---------------------------------------------------------------------------


class TestChurnGrammar:
    def test_parse_and_spec_round_trip(self):
        s = parse_churn("join=1,depart=0.7,init=0.6,seed=3,compact=4")
        assert (s.join, s.depart, s.init, s.seed, s.compact) == \
            (1.0, 0.7, 0.6, 3, 4)
        assert parse_churn(s.spec()) == s

    def test_defaults(self):
        s = parse_churn("join=2")
        assert (s.depart, s.init, s.seed, s.compact) == (0.0, 1.0, 0, 0)
        assert s.active

    @pytest.mark.parametrize("bad", [
        "join",                 # no KEY=VALUE
        "frobnicate=1",         # unknown key
        "init=1.5",             # out of [0, 1]
        "depart=-1",            # negative rate
        "init=1",               # churns nothing
        "init=0,depart=1",      # forever-empty population
    ])
    def test_rejections_at_parse_time(self, bad):
        with pytest.raises((ValueError, AssertionError)):
            parse_churn(bad)

    def test_churn_off_schedule_inactive(self):
        assert not ChurnSchedule().active


# ---------------------------------------------------------------------------
# RowDirectory
# ---------------------------------------------------------------------------


class TestRowDirectory:
    def test_allocate_retire_reuse(self):
        d = RowDirectory(capacity=8)
        assert [d.allocate(c) for c in (10, 11, 12)] == [0, 1, 2]
        d.retire(11)
        assert d.holes() == 1 and d.live_count == 2
        # the mapping is gone NOW (never sampled again) ...
        with pytest.raises(KeyError, match="no allocated row"):
            d.translate(np.array([11]))
        # ... but the physical row is reusable only after the barrier
        assert d.allocate(99) == 3
        d.retire(99)
        assert sorted(d.flush_pending()) == [1, 3]
        # lowest hole first, deterministic layout
        assert d.allocate(20) == 1
        assert d.allocate(21) == 3
        np.testing.assert_array_equal(
            d.translate(np.array([10, 20, 12])), [0, 1, 2])

    def test_capacity_and_double_allocate(self):
        d = RowDirectory(capacity=2)
        d.allocate(0)
        d.allocate(1)
        with pytest.raises(AssertionError, match="row store full"):
            d.allocate(2)
        d2 = RowDirectory(capacity=2)
        d2.allocate(5)
        with pytest.raises(AssertionError, match="already has a row"):
            d2.allocate(5)

    def test_state_round_trip(self):
        d = RowDirectory(capacity=16, compact_after=3)
        for c in (3, 7, 9):
            d.allocate(c)
        d.retire(7)
        st = d.state()
        d2 = RowDirectory(capacity=16, compact_after=3)
        d2.load_state(st)
        assert d2.client_ids() == [3, 9]
        assert d2.holes() == 1 and d2.retired_total == 1
        assert d2.translate(np.array([9]))[0] == d.row_of(9)
        with pytest.raises(AssertionError, match="capacity"):
            RowDirectory(capacity=8).load_state(st)


# ---------------------------------------------------------------------------
# PopulationManager (mask-only tier)
# ---------------------------------------------------------------------------


def _run_rounds(pm, n):
    evs = []
    for _ in range(n):
        pm.step()
        evs += pm.pop_events()
    return evs


class TestPopulationManager:
    SCHED = "join=1,depart=0.5,init=0.5,seed=7"

    def test_seeded_trajectory_deterministic(self):
        s = parse_churn(self.SCHED)
        a = PopulationManager(s, num_clients=50)
        b = PopulationManager(s, num_clients=50)
        assert _run_rounds(a, 30) == _run_rounds(b, 30)
        assert a.audit() == b.audit()
        assert a.audit()["ok"]

    def test_join_enters_pool_next_round(self):
        pm = PopulationManager(parse_churn("join=3,init=0.2,seed=1"),
                               num_clients=40)
        for _ in range(20):
            pm.step()
            joins = [e for e in pm.pop_events()
                     if e["kind"] == "churn_join"]
            if joins:
                new = joins[0]["clients"]
                # registered this round, sampleable only next round
                assert pm.registered[new].all()
                assert not pm.live[new].any()
                pm.step()
                assert pm.live[new].all()
                return
        pytest.fail("seeded schedule drew no join in 20 rounds")

    def test_departures_permanent_and_conserved(self):
        pm = PopulationManager(parse_churn("depart=1,init=1,seed=2"),
                               num_clients=12)
        evs = _run_rounds(pm, 25)
        gone = [c for e in evs if e["kind"] == "churn_depart"
                for c in e["clients"]]
        assert gone, "seeded schedule drew no departure in 25 rounds"
        assert pm.departed[gone].all() and not pm.live[gone].any()
        audit = pm.audit()
        assert audit["ok"]
        assert audit["registered"] == \
            audit["active"] + audit["departed"] + audit["quarantined"]
        assert audit["registered"] == audit["initial"] + audit["joins"]

    def test_cohort_short_and_event_drain(self):
        pm = PopulationManager(parse_churn("join=1,init=0.5,seed=0"),
                               num_clients=10)
        pm.note_cohort_short(4, 2)
        evs = pm.pop_events()
        assert evs[-1] == {"kind": "cohort_short", "target": 4, "got": 2,
                           "population": pm.population}
        assert pm.pop_events() == []  # drained
        assert pm.audit()["cohort_short"] == 1

    def test_joinable_covers_pending_and_unregistered(self):
        pm = PopulationManager(parse_churn("join=0.5,init=0,seed=0"),
                               num_clients=6)
        assert pm.population == 0
        assert pm.joinable().sum() == 6  # everyone may still arrive

    def test_state_round_trip_mid_churn(self):
        s = parse_churn(self.SCHED)
        a = PopulationManager(s, num_clients=50)
        _run_rounds(a, 10)
        arrays, meta = a.state_payload()
        b = PopulationManager(s, num_clients=50)
        b.restore_state(arrays, meta)
        # the resumed twin continues the IDENTICAL churn timeline
        assert _run_rounds(a, 10) == _run_rounds(b, 10)
        assert a.audit() == b.audit()

    def test_spec_change_on_resume_warns(self):
        a = PopulationManager(parse_churn("join=1,seed=0,init=0.5"),
                              num_clients=10)
        arrays, meta = a.state_payload()
        b = PopulationManager(parse_churn("join=2,seed=0,init=0.5"),
                              num_clients=10)
        with pytest.warns(UserWarning, match="spec changed"):
            b.restore_state(arrays, meta)


# ---------------------------------------------------------------------------
# directory x MemmapRowStore: retire zeroing, hole handoff, compaction
# ---------------------------------------------------------------------------


class TestDirectoryStore:
    def _store(self, tmp_path, compact_after=0):
        store = MemmapRowStore(str(tmp_path / "rows"), 8,
                               {"errors": (2, 4)}, mesh=None)
        d = RowDirectory(capacity=8, compact_after=compact_after)
        store.attach_directory(d)
        return store, d

    def _bump(self, store, cids, delta):
        s = store.gather(np.asarray(cids))
        store.scatter(s, s.proxy, ClientStates(
            None, s.proxy.errors + delta, None))

    def test_gather_scatter_address_client_ids(self, tmp_path):
        store, d = self._store(tmp_path)
        for c in (10, 11, 12):
            d.allocate(c)
        self._bump(store, [11, 11], 3.0)  # duplicate slots still replay
        store.drain()
        full = store.read_full("errors")
        assert full[d.row_of(11)][0, 0] == 6.0
        assert full[d.row_of(10)].sum() == 0.0
        store.close()

    def test_retired_row_zeroed_and_reused_as_fresh_state(self, tmp_path):
        store, d = self._store(tmp_path)
        d.allocate(3)
        self._bump(store, [3], 5.0)
        row = d.row_of(3)
        d.retire(3)
        assert store.flush_retired() == 1
        store.drain()
        assert not store.read_full("errors")[row].any(), (
            "retired row must be zeroed before reuse")
        assert d.allocate(42) == row  # the joiner inherits the hole
        s = store.gather(np.array([42]))
        assert not np.asarray(s.proxy.errors).any(), (
            "joiner must see fresh zero state, not the departed "
            "client's residue")
        store.close()

    def test_checkpoint_coordinated_compaction(self, tmp_path):
        store, d = self._store(tmp_path, compact_after=2)
        for c in (10, 11, 12):
            d.allocate(c)
        self._bump(store, [12], 9.0)
        d.retire(10)
        assert store.maybe_compact() is None  # 1 hole < threshold 2
        d.retire(11)
        rep = store.maybe_compact()
        assert rep is not None and d.compactions == 1
        assert d.row_of(12) == 0, "live rows pack down from zero"
        assert d.holes() == 0
        store.drain()
        assert store.read_full("errors")[0][0, 0] == 9.0, (
            "compaction moved the row without its content")
        store.close()


def test_loader_pad_id_open_vs_closed_world():
    """The short-cohort pad lane id (data_utils/loader.py): client 0
    byte-for-byte on the closed path, a LIVE cohort member under churn
    (client 0 may be departed/never-registered — no row to gather)."""
    from types import SimpleNamespace

    from commefficient_tpu.data_utils.loader import FedLoader

    workers = np.array([7, 3], np.int64)
    closed = SimpleNamespace(sampler=SimpleNamespace(_population=None))
    assert FedLoader._pad_id(closed, workers) == 0
    churned = SimpleNamespace(sampler=SimpleNamespace(_population=object()))
    assert FedLoader._pad_id(churned, workers) == 7
    assert FedLoader._pad_id(churned, np.array([], np.int64)) == 0


# ---------------------------------------------------------------------------
# SnapshotTracker + the pin lease vs checkpoint GC
# ---------------------------------------------------------------------------


class TestSnapshotTracker:
    def test_discovery_swap_monotone(self, tmp_path):
        ckpt = str(tmp_path / "ckpt")
        tr = SnapshotTracker(ckpt, owner="t")
        assert not tr.poll() and tr.version == -1
        _, w3 = write_state(ckpt, 3, seed=0)
        assert tr.poll() and tr.version == 3 and tr.swaps == 1
        np.testing.assert_array_equal(tr.weights, w3)
        assert not tr.poll(), "no newer candidate — no swap"
        _, w6 = write_state(ckpt, 6, seed=1)
        assert tr.poll() and tr.version == 6 and tr.swaps == 2
        np.testing.assert_array_equal(tr.weights, w6)
        tr.release()

    def test_torn_newest_candidate_keeps_serving(self, tmp_path, capsys):
        ckpt = str(tmp_path / "ckpt")
        write_state(ckpt, 3, seed=0)
        tr = SnapshotTracker(ckpt, owner="t")
        assert tr.poll() and tr.version == 3
        # newest candidate with a LYING checksum: discovery must skip it
        path, _ = write_state(ckpt, 9, seed=2)
        with np.load(path) as z:
            flat = dict(z)
        meta = json.loads(bytes(flat["meta_json"]).decode())
        meta["checksum"] ^= 0xDEAD
        flat["meta_json"] = np.frombuffer(
            json.dumps(meta).encode(), np.uint8)
        np.savez(path, **flat)
        assert not tr.poll(), "torn candidate must not swap"
        assert tr.version == 3
        assert "skipping" in capsys.readouterr().out
        tr.release()

    def test_lag_counts_strictly_newer(self, tmp_path):
        ckpt = str(tmp_path / "ckpt")
        write_state(ckpt, 3, seed=0)
        tr = SnapshotTracker(ckpt, owner="t")
        tr.poll()
        assert tr.lag() == 0
        write_state(ckpt, 6, seed=1)
        write_state(ckpt, 9, seed=2)
        assert tr.lag() == 2
        tr.release()

    def test_pin_lease_lifecycle(self, tmp_path):
        ckpt = str(tmp_path / "ckpt")
        path, _ = write_state(ckpt, 3, seed=0)
        tr = SnapshotTracker(ckpt, owner="me")
        tr.poll()
        pin = os.path.join(ckpt, "me.pin")
        with open(pin) as f:
            lease = json.load(f)
        assert lease["owner"] == "me"
        assert os.path.basename(path) in lease["paths"]
        tr.release()
        assert not os.path.exists(pin)

    def test_prune_respects_pin(self, tmp_path, capsys):
        from commefficient_tpu.federated.checkpoint import prune_run_states

        ckpt = str(tmp_path / "ckpt")
        p3, _ = write_state(ckpt, 3, seed=0)
        p6, _ = write_state(ckpt, 6, seed=1)
        p9, _ = write_state(ckpt, 9, seed=2)
        with open(os.path.join(ckpt, "serve.pin"), "w") as f:
            json.dump({"owner": "serve", "pid": 1,
                       "paths": [os.path.basename(p3)]}, f)
        prune_run_states(ckpt, keep=1)
        assert os.path.exists(p9), "newest always kept"
        assert not os.path.exists(p6), "unpinned old state pruned"
        assert os.path.exists(p3), "pinned state survives GC"
        assert "pinned" in capsys.readouterr().out

    def test_unreadable_pin_reported_pins_nothing(self, tmp_path, capsys):
        from commefficient_tpu.federated.checkpoint import prune_run_states

        ckpt = str(tmp_path / "ckpt")
        p3, _ = write_state(ckpt, 3, seed=0)
        p6, _ = write_state(ckpt, 6, seed=1)
        with open(os.path.join(ckpt, "torn.pin"), "w") as f:
            f.write("{not json")
        prune_run_states(ckpt, keep=1)
        assert os.path.exists(p6) and not os.path.exists(p3)
        assert "unreadable pin" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# ServingReplica: the request plane + the JSONL-is-the-verifier contract
# ---------------------------------------------------------------------------


class TestServingReplica:
    def test_request_plane_end_to_end(self, tmp_path):
        ckpt = str(tmp_path / "ckpt")
        serve = str(tmp_path / "serve")
        rep = ServingReplica(ckpt, serve, owner="t")
        # before any snapshot: a counted error answer, never a drop
        rid = submit_request(serve, op="query", probe_seed=0)
        rep.step()
        resp = read_response(serve, rid, timeout=5, poll=0.01)
        assert resp["model_version"] == -1 and "error" in resp
        assert rep.errors == 1

        _, w = write_state(ckpt, 3, seed=0)
        rid = submit_request(serve, op="query", probe_seed=5)
        rep.step()  # hot swap + answer in one service iteration
        resp = read_response(serve, rid, timeout=5, poll=0.01)
        assert resp["model_version"] == 3
        v = np.random.RandomState(5).standard_normal(w.size) \
            .astype(np.float32)
        expect = float(w @ (v / np.linalg.norm(v)))
        assert resp["value"] == pytest.approx(expect, rel=1e-6)

        rid = submit_request(serve, op="stat")
        rep.step()
        resp = read_response(serve, rid, timeout=5, poll=0.01)
        assert resp["dim"] == w.size
        assert resp["norm"] == pytest.approx(float(np.linalg.norm(w)))
        assert resp["crc"] == zlib.crc32(
            np.ascontiguousarray(w).tobytes())

        rid = submit_request(serve, op="frobnicate")
        rep.step()
        assert "unknown op" in read_response(serve, rid, timeout=5,
                                             poll=0.01)["error"]
        rep.close()

    def test_eval_delegates_to_predict_fn(self, tmp_path):
        ckpt = str(tmp_path / "ckpt")
        write_state(ckpt, 3, seed=0)
        serve = str(tmp_path / "serve")
        rep = ServingReplica(
            ckpt, serve, owner="t",
            predict_fn=lambda w, inputs: [float(np.sum(w)), inputs])
        rid = submit_request(serve, op="eval", inputs=[1, 2])
        rep.step()
        resp = read_response(serve, rid, timeout=5, poll=0.01)
        assert resp["outputs"][1] == [1, 2]
        rep.close()
        # without the seam wired, eval is a counted error
        rep2 = ServingReplica(ckpt, str(tmp_path / "s2"), owner="t2")
        rid = submit_request(str(tmp_path / "s2"), op="eval")
        rep2.step()
        assert "predict_fn" in read_response(
            str(tmp_path / "s2"), rid, timeout=5, poll=0.01)["error"]
        rep2.close()

    def test_serving_jsonl_reproduces_through_obs_report(self, tmp_path):
        obs = _load_script("obs_report")
        ckpt = str(tmp_path / "ckpt")
        serve = str(tmp_path / "serve")
        write_state(ckpt, 3, seed=0)
        rep = ServingReplica(ckpt, serve, owner="t")
        for seed in range(3):
            submit_request(serve, op="query", probe_seed=seed)
            rep.step()
        write_state(ckpt, 6, seed=1)
        submit_request(serve, op="ping")
        rep.step()
        rep.close()
        sv = obs.summarize(obs.load_events(
            os.path.join(serve, "serving.jsonl")))["serving"]
        assert sv["answers"] == 4 and sv["errors"] == 0
        assert sv["swaps"] == 2 and sv["swap_versions"] == [3, 6]
        assert sv["versions_monotone"]
        assert sv["final_version"] == 6 and sv["clean_stop"]
        assert sv["reported"]["answered"] == 4
        assert sv["by_op"] == {"query": 3, "ping": 1}


def test_obs_report_churn_section_from_log_alone(tmp_path):
    """The churn story — schedule, population curve, row lifecycle,
    conservation verdict — rebuilt from a telemetry JSONL alone."""
    obs = _load_script("obs_report")
    log = tmp_path / "telemetry.jsonl"
    evs = [
        {"ev": "run_start", "t": 0.0, "argv": [],
         "churn": {"spec": "join=1,depart=0.7,init=0.6,seed=3,compact=4",
                   "join": 1.0, "depart": 0.7, "init": 0.6, "seed": 3,
                   "compact": 4}},
        {"ev": "churn_depart", "t": 1.0, "round": 0, "churn_round": 1,
         "clients": [2], "population": 2},
        {"ev": "churn_join", "t": 1.1, "round": 0, "churn_round": 1,
         "clients": [1, 3], "population": 4},
        {"ev": "cohort_short", "t": 1.2, "round": 0, "target": 2,
         "got": 1, "population": 4},
        {"ev": "rows_retired", "t": 2.0, "round": 1, "rows": 1},
        {"ev": "rows_compacted", "t": 3.0, "round": 2, "live": 3,
         "moved": 2, "holes_reclaimed": 1},
        {"ev": "churn_audit", "t": 4.0, "registered": 4, "active": 3,
         "departed": 1, "quarantined": 0, "ok": True, "initial": 2,
         "joins": 2, "departs": 1, "cohort_short": 1, "idle_rounds": 0,
         "churn_rounds": 3, "rows_live": 3, "rows_holes": 0,
         "compactions": 1},
    ]
    log.write_text("".join(json.dumps(e) + "\n" for e in evs))
    events = obs.load_events(str(log))
    s = obs.summarize(events)
    ch = s["churn"]
    assert ch["joins"] == 2 and ch["departs"] == 1
    assert ch["cohort_short"] == 1 and ch["compactions"] == 1
    assert ch["population_first"] == 2 and ch["population_last"] == 4
    assert ch["audit"]["ok"]
    buf = io.StringIO()
    obs.render(events, out=buf)
    text = buf.getvalue()
    assert "Open-world churn" in text
    assert "registered 4 == active 3 + departed 1 + quarantined 0" in text
    assert "OK" in text


# ---------------------------------------------------------------------------
# the real thing (@slow): churn e2e + kill/resume + the serving bench leg
# ---------------------------------------------------------------------------


CHURN = ["--churn", "join=1,depart=0.7,init=0.6,seed=3,compact=4"]


@pytest.mark.slow
class TestServiceE2E:
    def test_churn_disk_tier_run_conserves(self, tmp_path):
        """Seeded open-world run on the disk state tier: completes
        cleanly (including the drained-population end state), relays
        every churn event with the engine round attached, and the
        conservation audit reproduces OK from the JSONL alone."""
        cm = _load_script("crash_matrix")
        obs = _load_script("obs_report")
        data = str(tmp_path / "data")
        ckpt = str(tmp_path / "ckpt")
        run_dir = str(tmp_path / "run")
        os.makedirs(data)
        os.makedirs(run_dir)
        cm.run_to_completion(
            cm.train_argv(data, ckpt, shard=False, disk=True) + CHURN,
            env_extra=dict(cm.DISK_ENV, COMMEFFICIENT_RUN_DIR=run_dir))
        events = obs.load_events(run_dir)
        s = obs.summarize(events)
        ch = s["churn"]
        assert ch is not None and ch["audit"], "no churn_audit event"
        assert ch["audit"]["ok"], f"conservation broken: {ch['audit']}"
        assert ch["audit"]["registered"] == \
            ch["audit"]["active"] + ch["audit"]["departed"] \
            + ch["audit"]["quarantined"]
        # event totals match the audit counters (the final flush)
        assert ch["joins"] == ch["audit"]["joins"]
        assert ch["departs"] == ch["audit"]["departs"]
        buf = io.StringIO()
        obs.render(events, out=buf)
        assert "OK" in buf.getvalue()

    def test_mid_churn_kill_resume_bit_exact(self, tmp_path):
        """SIGKILL the churn run mid-timeline, resume with --resume
        auto, and the final weights are bit-identical to the
        uninterrupted twin — the pop/* run-state keys carry the
        population masks + schedule RNG exactly."""
        cm = _load_script("crash_matrix")
        data = str(tmp_path / "data")
        os.makedirs(data)
        base_ckpt = str(tmp_path / "base")
        argv = cm.train_argv(data, base_ckpt, shard=False, disk=True) \
            + CHURN
        cm.run_to_completion(argv, env_extra=cm.DISK_ENV)
        kill_ckpt = str(tmp_path / "killed")
        argv2 = cm.train_argv(data, kill_ckpt, shard=False, disk=True) \
            + CHURN
        cm.run_and_kill(argv2, kill_after_round=4, env_extra=cm.DISK_ENV)
        cm.run_to_completion(argv2 + ["--resume", "auto"],
                             env_extra=cm.DISK_ENV)
        cm.assert_identical(
            cm.final_weights(base_ckpt), cm.final_weights(kill_ckpt),
            "mid-churn kill/resume vs uninterrupted")

    def test_live_replica_leaves_training_bit_identical(self, tmp_path):
        """The docs/service.md acceptance leg: one tiny cv_train run
        solo, then the same run with a live replica (scripts/serve.py)
        tracking its checkpoint dir under a steady query load. The
        replica is read-only, so the final weights are bit-identical;
        it hot-swaps at least once, its model_version stream (rebuilt
        from serving.jsonl by obs_report) is monotone, and at least one
        query is answered from a loaded snapshot."""
        import subprocess
        import threading

        from commefficient_tpu.federated.serving import (
            read_response,
            submit_request,
        )

        cm = _load_script("crash_matrix")
        obs = _load_script("obs_report")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        data = str(tmp_path / "data")
        os.makedirs(data)

        def leg_argv(ckpt):
            return cm.train_argv(data, ckpt, shard=False) + [
                "--num_epochs", "1"]  # last flag wins

        solo_ckpt = str(tmp_path / "solo" / "ckpt")
        cm.run_to_completion(leg_argv(solo_ckpt), timeout=1800)

        live_ckpt = str(tmp_path / "live" / "ckpt")
        serve_dir = str(tmp_path / "serve")
        stop_file = str(tmp_path / "serve.stop")
        os.makedirs(live_ckpt)
        replica = subprocess.Popen(
            [sys.executable, os.path.join(repo, "scripts", "serve.py"),
             "--checkpoint_path", live_ckpt, "--serve_dir", serve_dir,
             "--owner", "test", "--poll_interval", "0.05",
             "--stop_file", stop_file, "--deadline_s", "1800"],
            env=cm.child_env(), cwd=repo, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        answered = [0]
        done = threading.Event()

        def load_loop():
            seed = 0
            while not done.is_set():
                rid = submit_request(serve_dir, op="query",
                                     probe_seed=seed)
                seed += 1
                resp = read_response(serve_dir, rid, timeout=10,
                                     poll=0.02)
                answered[0] += "error" not in resp
                done.wait(0.2)

        load = threading.Thread(target=load_loop, daemon=True)
        load.start()
        try:
            cm.run_to_completion(leg_argv(live_ckpt), timeout=1800)
        finally:
            done.set()
            load.join(timeout=30)
            with open(stop_file, "w") as f:
                f.write("done")
            try:
                replica.wait(timeout=60)
            except subprocess.TimeoutExpired:
                replica.kill()

        sv = obs.summarize(obs.load_events(
            os.path.join(serve_dir, "serving.jsonl")))["serving"]
        assert sv is not None, "replica wrote no serving.jsonl events"
        # errors before the first snapshot ("no model yet") are fair;
        # at least one query must have been served FROM a model
        assert answered[0] > 0 and sv["answers"] > sv["errors"], (
            answered[0], sv)
        assert sv["swaps"] >= 1, "replica never hot-swapped a snapshot"
        assert sv["versions_monotone"], sv["swap_versions"]
        cm.assert_identical(cm.final_weights(solo_ckpt),
                            cm.final_weights(live_ckpt),
                            "live replica vs solo baseline")
