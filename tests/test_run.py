"""The one federated run behind both entry points (federated/run.py): the
round loop, the planes' wiring, the close-out — and the seam the benchmark
and chip_smoke.py stand on: a class put at ``<entry>.PipelinedRoundEngine``
/ ``<entry>.FedModel`` before the call is the class the entry point
instantiates.

Two kinds of test. Most drive ``<entry>.run_batches`` over a FAKE engine put
at that very seam (no jax program: the loop's control flow is host code);
one tiny real run per entry point pins what only the real thing shows (the
saved sampler position, the event log, the classes ``main`` builds).
"""

import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

os.environ.setdefault("COMMEFFICIENT_TINY_MODEL", "1")
os.environ.setdefault("COMMEFFICIENT_GPT2_SEQ_LEN", "64")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import cv_train  # noqa: E402
import gpt2_train  # noqa: E402
from commefficient_tpu.federated import run as fedrun  # noqa: E402
from commefficient_tpu.federated.engine import RoundResult  # noqa: E402
from commefficient_tpu.utils import Timer  # noqa: E402

ENTRIES = {"cv_train": cv_train, "gpt2_train": gpt2_train}
W = 2  # clients a round in every fake


# ---------------------------------------------------------------------------
# fakes: an engine for the seam, a model, a loader
# ---------------------------------------------------------------------------

def fake_engine(log, entry, nan_round=None):
    """An engine class for ``<entry>.PipelinedRoundEngine``: buffers a
    round's metrics until ``drain_every`` rounds wait or ``drain()`` is
    called, as the real one does, and writes what it is asked into ``log``."""
    class FakeEngine:
        def __init__(self, model, opt, lr_scheduler=None, window=2,
                     drain_every=8):
            self.model, self.opt, self.lr_scheduler = model, opt, lr_scheduler
            self.drain_every = drain_every
            self.rounds_submitted = 0
            self._pending = []
            log.append(("engine", type(self).__name__))

        def submit(self, batch):
            i = self.rounds_submitted
            self.rounds_submitted += 1
            self.model.rounds_dispatched += 1
            loss = np.full(W, np.nan if i == nan_round else 1.0 + i)
            traffic = [np.ones(8), 2 * np.ones(8)]
            values = ([loss, np.zeros(W)] if entry == "cv_train"
                      else [loss]) + traffic
            self._pending.append(RoundResult(i, values))
            log.append(("submit", batch))
            if len(self._pending) >= self.drain_every:
                return self.drain()
            return []

        def drain(self):
            out, self._pending = self._pending, []
            log.append(("drain", len(out)))
            return out

    return FakeEngine


class FakeWatch:
    def __init__(self, at):
        self.at, self.polls = at, 0

    def pop_checkpoint(self):
        self.polls += 1
        return self.polls == self.at


def fake_model(log, watch=None, population=None):
    telemetry = SimpleNamespace(
        watch=watch, event=lambda kind, **kw: log.append((kind, kw)))
    return SimpleNamespace(train=lambda training: None, telemetry=telemetry,
                           rounds_dispatched=0, _population=population)


class FakeLoader:
    """``n`` batches (their own index), a sampler that says how many it has
    handed out."""
    def __init__(self, n):
        self.n, self.drawn = n, 0
        self.dataset = SimpleNamespace(num_clients=8)
        self.sampler = SimpleNamespace(get_state=lambda: self.drawn)

    def steps_per_epoch(self):
        return self.n

    def __iter__(self):
        for i in range(self.n):
            self.drawn += 1
            yield i


def fake_args(**kw):
    base = dict(round_window=2, metrics_drain_every=4,
                checkpoint_every_rounds=0, train_dataloader_workers=0,
                do_test=False, arch="gpt2")
    return SimpleNamespace(**{**base, **kw})


def run_fake(entry, monkeypatch, log, model, loader, args, nan_round=None,
             epoch_fraction=1):
    """``<entry>.run_batches(training=True)`` over the fakes; saves go to
    ``log`` in place of the disk."""
    mod = ENTRIES[entry]
    monkeypatch.setattr(mod, "PipelinedRoundEngine",
                        fake_engine(log, entry, nan_round))

    def save(args_, epoch, rounds_done, sampler_state, model_, opt, sched,
             totals, extras=None):
        log.append(("save", dict(epoch=epoch, rounds_done=rounds_done,
                                 sampler=sampler_state,
                                 losses=len(extras["losses"]),
                                 download=float(extras["download"].sum()))))

    monkeypatch.setattr(fedrun, "save_round_state", save)
    sched = SimpleNamespace(get_last_lr=lambda: [0.1])
    if entry == "cv_train":
        return mod.run_batches(model, "opt", sched, loader, True,
                               epoch_fraction, args)
    return mod.run_batches(model, "opt", sched, loader, args, Timer(),
                           training=True, epoch=0,
                           epoch_fraction=epoch_fraction)


def kinds(log):
    return [e[0] for e in log]


both = pytest.mark.parametrize("entry", sorted(ENTRIES))


# ---------------------------------------------------------------------------
# the round loop, over the fake engine
# ---------------------------------------------------------------------------

@both
def test_engine_at_the_entry_modules_name_is_the_one_run(entry, monkeypatch):
    """The benchmark's seam: ``run_batches`` looks ``PipelinedRoundEngine``
    up in its own module at call time, and hands that engine to the loop."""
    log = []
    out = run_fake(entry, monkeypatch, log, fake_model(log), FakeLoader(5),
                   fake_args())
    assert log[0] == ("engine", "FakeEngine")
    assert [e[1] for e in log if e[0] == "submit"] == [0, 1, 2, 3, 4]
    # 4 rounds drained by the engine's own count, the 5th by the final drain
    assert [e for e in log if e[0] == "drain"] == [("drain", 4), ("drain", 1)]
    loss, *_, download, upload = out
    assert loss == np.mean([1.0, 2.0, 3.0, 4.0, 5.0])
    assert download.sum() == 5 * 8 and upload.sum() == 5 * 16
    assert len(out) == (4 if entry == "cv_train" else 3)


@both
def test_save_every_drains_first_and_saves_what_was_folded_in(entry,
                                                              monkeypatch):
    log = []
    run_fake(entry, monkeypatch, log, fake_model(log), FakeLoader(7),
             fake_args(checkpoint_every_rounds=3, metrics_drain_every=8))
    saves = [e[1] for e in log if e[0] == "save"]
    assert [s["rounds_done"] for s in saves] == [3, 6]
    for s in saves:
        at = log.index(("save", s))
        # the window drained right before the save, and the checkpoint
        # event follows it
        assert kinds(log)[at - 1] == "drain"
        assert log[at + 1][0] == "checkpoint"
        assert log[at + 1][1] == dict(epoch=0, round=s["rounds_done"] - 1,
                                      round_in_epoch=s["rounds_done"])
        # every dispatched round's metrics are in the saved accumulators,
        # and the sampler stands where those rounds left it
        per_round = W if entry == "cv_train" else 1
        assert s["losses"] == s["rounds_done"] * per_round
        assert s["download"] == s["rounds_done"] * 8
        assert s["sampler"] == s["rounds_done"]


@both
@pytest.mark.parametrize("workers", [0, 1])
def test_watch_forced_checkpoint(entry, workers, monkeypatch, capsys):
    """The watch plane's checkpoint reaction saves at the next round
    boundary, but only where a save can be resumed from."""
    log = []
    run_fake(entry, monkeypatch, log,
             fake_model(log, watch=FakeWatch(at=2)), FakeLoader(4),
             fake_args(train_dataloader_workers=workers))
    out = capsys.readouterr().out
    saves = [e[1] for e in log if e[0] == "save"]
    if workers == 0:
        assert [s["rounds_done"] for s in saves] == [2]
        assert ("checkpoint", dict(epoch=0, round=1, round_in_epoch=2,
                                   forced_by_watch=True)) in log
        assert "checkpoint reaction skipped" not in out
    else:
        assert saves == [] and "checkpoint" not in kinds(log)
        assert ("watch: checkpoint reaction skipped (needs "
                "--train_dataloader_workers 0 for a resumable save)") in out


@both
def test_population_emptied_returns_none_as_the_loss(entry, monkeypatch):
    """--churn's end state: no cohort could be drawn, and none ever will."""
    log = []
    out = run_fake(entry, monkeypatch, log,
                   fake_model(log, population=object()), FakeLoader(0),
                   fake_args())
    assert out[0] is None
    assert out[-1].sum() == 0 and out[-2].sum() == 0
    assert "submit" not in kinds(log)


def test_cv_nan_abort_fires_at_drain_time(monkeypatch, capsys):
    """Round 1's loss is NaN; its metrics arrive with the drain after round
    3, and the epoch ends there with four NaNs."""
    log = []
    out = run_fake("cv_train", monkeypatch, log, fake_model(log),
                   FakeLoader(9), fake_args(), nan_round=1)
    assert len(out) == 4 and all(np.isnan(v) for v in out)
    assert [e[1] for e in log if e[0] == "submit"] == [0, 1, 2, 3]
    assert "IS NAN, TERMINATING TRAINING" in capsys.readouterr().out


def test_cv_nan_abort_before_a_save_writes_nothing(monkeypatch):
    log = []
    out = run_fake("cv_train", monkeypatch, log, fake_model(log),
                   FakeLoader(9),
                   fake_args(checkpoint_every_rounds=2,
                             metrics_drain_every=8), nan_round=0)
    assert all(np.isnan(v) for v in out)
    assert "save" not in kinds(log)


@both
def test_what_a_test_run_dispatches(entry, monkeypatch):
    """--test: cv_train stops after its first round; gpt2_train runs three
    batches, skips to the epoch's last ten and runs those."""
    log = []
    run_fake(entry, monkeypatch, log, fake_model(log), FakeLoader(20),
             fake_args(do_test=True))
    ran = [e[1] for e in log if e[0] == "submit"]
    assert ran == ([0] if entry == "cv_train"
                   else [0, 1, 2] + list(range(10, 20)))


@both
def test_a_fraction_of_an_epoch(entry, monkeypatch):
    log = []
    run_fake(entry, monkeypatch, log, fake_model(log), FakeLoader(10),
             fake_args(), epoch_fraction=0.5)
    assert [e[1] for e in log if e[0] == "submit"] == [0, 1, 2, 3, 4, 5]


# ---------------------------------------------------------------------------
# the close-out
# ---------------------------------------------------------------------------

def test_close_out_order():
    log = []

    def rec(name, ret=None):
        def f(*a, **kw):
            log.append(name)
            return ret
        return f

    rt = SimpleNamespace(
        event=lambda kind, **kw: log.append(f"event:{kind}"),
        close=rec("rt.close"))
    pc = SimpleNamespace(expire_pending=rec("expire_pending", 2),
                         expire_buffer=rec("expire_buffer", 1), async_k=4)
    pm = SimpleNamespace(
        audit=rec("audit", {"ok": True, "registered": 3}),
        pop_events=rec("pop_events", [{"kind": "churn_depart", "cid": 1}]))
    store = SimpleNamespace(fatal_error=OSError("disk gone"),
                            io_counters=rec("io_counters", {"reads": 1}))
    model = SimpleNamespace(
        tracer=SimpleNamespace(close=rec("tracer.close", {"round": 3})),
        _row_store=store, finalize=rec("finalize"))
    fedrun.close_run(fedrun.Planes(model, pc, pm, rt))
    assert log == [
        "expire_pending", "event:straggler_expired",
        "expire_buffer", "event:async_expired",
        "audit", "pop_events", "event:churn_depart", "event:churn_audit",
        "tracer.close", "event:trace_captured",
        "event:io_fatal", "io_counters", "event:io_counters",
        "rt.close", "finalize"]


def test_close_out_of_a_bare_run_still_finalizes():
    done = []
    model = SimpleNamespace(finalize=lambda: done.append(True))
    fedrun.close_run(fedrun.Planes(model, None, None, None))
    assert done == [True]


# ---------------------------------------------------------------------------
# one real tiny run per entry point
# ---------------------------------------------------------------------------

def tiny_argv(entry, root):
    fed = ["--num_epochs", "1", "--num_workers", "2", "--seed", "0",
           "--mode", "sketch", "--error_type", "virtual",
           "--local_momentum", "0", "--virtual_momentum", "0.9",
           "--k", "200", "--num_cols", "1024", "--num_rows", "3",
           "--num_blocks", "2", "--train_dataloader_workers", "0",
           "--checkpoint_path", str(root / "ckpt"),
           "--checkpoint_every_rounds", "2", "--metrics_drain_every", "8",
           "--dataset_dir", str(root / "data")]
    if entry == "cv_train":
        return fed + ["--dataset_name", "CIFAR10", "--local_batch_size", "4",
                      "--valid_batch_size", "8", "--iid", "--num_clients",
                      "4", "--lr_scale", "0.01", "--pivot_epoch", "0.5"]
    return fed + ["--dataset_name", "PERSONA", "--local_batch_size", "2",
                  "--valid_batch_size", "2", "--num_candidates", "2",
                  "--lr_scale", "0.001"]


def entry_main(entry):
    return cv_train.main if entry == "cv_train" else gpt2_train.train


def seam_model(mod, built):
    """A subclass for ``<entry>.FedModel`` that adds itself to ``built``
    and counts its ``finalize()`` calls."""
    class SeamModel(mod.FedModel):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.finalized = 0
            built.append(self)

        def finalize(self):
            self.finalized += 1
            return super().finalize()

    return SeamModel


@pytest.fixture(scope="module", params=sorted(ENTRIES))
def real_run(request, tmp_path_factory):
    """One sketched epoch through the entry point's ``main``, saving every
    two rounds, with subclasses at the module's ``FedModel`` and
    ``PipelinedRoundEngine`` and a recorder around the loop's save."""
    entry = request.param
    mod = ENTRIES[entry]
    root = tmp_path_factory.mktemp(entry)
    mp = pytest.MonkeyPatch()
    mp.setenv("COMMEFFICIENT_SYNTHETIC_PER_CLASS", "16")
    mp.setenv("COMMEFFICIENT_SYNTHETIC_CLIENTS", "8")
    mp.setenv("COMMEFFICIENT_RUN_DIR", str(root / "run"))
    seen = SimpleNamespace(entry=entry, models=[], engines=[], saves=[],
                           root=root)

    class SeamEngine(mod.PipelinedRoundEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            seen.engines.append(self)

    real_save = fedrun.save_round_state

    def save(args, epoch, rounds_done, sampler_state, model, *a, **kw):
        engine = seen.engines[-1]
        seen.saves.append(dict(
            rounds_done=rounds_done, pending=len(engine._pending),
            submitted=engine.rounds_submitted,
            drawn=int(np.sum(sampler_state["cursor"])),
            per_round=args.num_workers * args.local_batch_size))
        return real_save(args, epoch, rounds_done, sampler_state, model,
                         *a, **kw)

    mp.setattr(mod, "FedModel", seam_model(mod, seen.models))
    mp.setattr(mod, "PipelinedRoundEngine", SeamEngine)
    mp.setattr(fedrun, "save_round_state", save)
    try:
        seen.result = entry_main(entry)(tiny_argv(entry, root))
    finally:
        mp.undo()
    with open(root / "run" / "telemetry.jsonl") as f:
        seen.events = [json.loads(line) for line in f]
    return seen


class TestRealRun:
    def test_main_builds_the_classes_at_its_modules_names(self, real_run):
        assert real_run.result is not None
        assert len(real_run.models) == 1 and real_run.engines
        assert type(real_run.models[0]).__name__ == "SeamModel"
        assert all(type(e).__name__ == "SeamEngine"
                   for e in real_run.engines)
        assert real_run.models[0].finalized == 1

    def test_saved_position_is_the_rounds_folded_in(self, real_run):
        saves = real_run.saves
        assert saves and [s["rounds_done"] for s in saves] == list(
            range(2, 2 * len(saves) + 1, 2))
        for s in saves:
            assert s["pending"] == 0, "saved with rounds still in flight"
            assert s["submitted"] == s["rounds_done"]
            # the sampler has handed out those rounds' examples and no
            # more: the lookahead draws batch t+1 only after round t's
            # body (an epoch's last cohorts may be short)
            assert s["drawn"] <= s["rounds_done"] * s["per_round"]
        assert saves[0]["drawn"] == 2 * saves[0]["per_round"]
        drawn = [s["drawn"] for s in saves]
        assert drawn == sorted(set(drawn))
        files = sorted(os.listdir(real_run.root / "ckpt"))
        assert [f"run_state_ep1_r{s['rounds_done']}.npz" for s in saves] \
            == sorted((f for f in files if f.startswith("run_state_ep1_r")),
                      key=lambda f: int(f[len("run_state_ep1_r"):-4]))

    def test_event_log_of_the_run(self, real_run):
        evs = [e["ev"] for e in real_run.events]
        assert evs[0] == "run_start"
        assert real_run.events[0]["entrypoint"] == real_run.entry
        cps = [e for e in real_run.events if e["ev"] == "checkpoint"]
        assert [c["round_in_epoch"] for c in cps] == \
            [s["rounds_done"] for s in real_run.saves]
        assert all(c["round"] == c["round_in_epoch"] - 1 for c in cps)
        assert "resume" not in evs


    def test_the_run_records_its_start_up(self, real_run):
        """profiling.py's record in a run of each entry point: the
        ``setup`` event right after ``run_start``, its phases in order and
        apart; the programs built, with the phase or round they were built
        in; a ``val`` event a validation pass; a memory sample (None on
        the CPU) on every drain and on ``run_end``."""
        evs = [e["ev"] for e in real_run.events]
        assert evs[:2] == ["run_start", "setup"] and evs.count("setup") == 1
        phases = real_run.events[1]["phases"]
        names = [p["phase"] for p in phases]
        assert [n for n in names if n != "import"] == \
            ["data", "model", "fed", "planes"]
        for a, b in zip(phases, phases[1:]):
            assert a["start_s"] + a["seconds"] <= b["start_s"] + 2e-3
        assert sum(p["programs"] for p in phases) > 0
        # (the test process built programs before this run: its first run
        # keeps what came before it, as the `import` phase's)
        began = real_run.events[1]["t"] - 1.0 - sum(
            p["seconds"] for p in phases if p["phase"] != "import")
        progs = [e for e in real_run.events if e["ev"] == "program"
                 and e["t"] >= began]
        named = {e["name"] for e in progs}
        assert {"jit(client_step)", "jit(server_step)"} <= named, named
        step = next(e for e in progs if e["name"] == "jit(client_step)")
        assert step["round"] == 0 and step["phase"] is None
        assert step["trace_s"] > 0 and step["backend_s"] > 0
        assert any(e["phase"] in ("model", "fed") and e["round"] is None
                   for e in progs if e["t"] < real_run.events[1]["t"])
        vals = [e for e in real_run.events if e["ev"] == "val"]
        assert vals and all(v["seconds"] > 0 and v["memory_end"] is None
                            for v in vals)
        drains = [e for e in real_run.events if e["ev"] == "drain"]
        assert drains and all(d["inflight"] == 0 and "memory" in d
                              for d in drains)
        end = real_run.events[-1]
        assert end["ev"] == "run_end" and "memory" in end
        assert "jit(client_step)" in end["programs"]
        for name in ("fed_setup_data", "fed_setup_fed", "fed_val_pass",
                     "fed_memory_sample", "fed_program_listener"):
            assert end["spans"][name]["count"] >= 1, name


@both
def test_finalize_when_training_raises(entry, tmp_path, monkeypatch):
    """The close-out runs on the error path too: the recorder is closed and
    the model finalized when ``train`` raises."""
    mod = ENTRIES[entry]
    monkeypatch.setenv("COMMEFFICIENT_SYNTHETIC_PER_CLASS", "16")
    monkeypatch.setenv("COMMEFFICIENT_SYNTHETIC_CLIENTS", "8")
    monkeypatch.setenv("COMMEFFICIENT_RUN_DIR", str(tmp_path / "run"))
    built = []

    def boom(*a, **kw):
        raise RuntimeError("training fell over")

    monkeypatch.setattr(mod, "FedModel", seam_model(mod, built))
    monkeypatch.setattr(
        mod, "train" if entry == "cv_train" else "train_gpt2", boom)
    with pytest.raises(RuntimeError, match="training fell over"):
        entry_main(entry)(tiny_argv(entry, tmp_path))
    assert [m.finalized for m in built] == [1]
    with open(tmp_path / "run" / "telemetry.jsonl") as f:
        evs = [json.loads(line)["ev"] for line in f]
    assert evs[0] == "run_start" and evs[-1] == "run_end"
