"""The step's report beside the next step (PR 67, which re-lands
PR 66's change): ``report_step``
hands its record over and the events, the counters' fetch, the chip's
memory line and the metrics file are written inside the NEXT step's
``compute`` phase, after the dispatch and before the block; a loop
without a blocked ``compute`` phase and a process with a fault
injector armed write at once, as they always did."""

import atexit
import gc
import inspect
import json
import os
import subprocess
import sys
import weakref

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu import chaos
from dlrover_tpu.chaos import primitives
from dlrover_tpu.telemetry.events import read_events
from dlrover_tpu.telemetry.schema import validate_event
from dlrover_tpu.trainer import elastic_trainer
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STEPS = 4


@pytest.fixture()
def paths(tmp_path, monkeypatch):
    log, metrics = str(tmp_path / "events.jsonl"), str(tmp_path / "m.json")
    monkeypatch.setenv("DLROVER_EVENT_LOG", log)
    monkeypatch.setenv("DLROVER_METRICS_FILE", metrics)
    return log, metrics


@pytest.fixture()
def no_chaos():
    chaos.uninstall()
    yield
    chaos.uninstall()


def metrics_of(step):
    """A step's metrics as the device arrays a jitted step returns:
    the loss, a norm, two of a model's counters and one array that
    is no scalar (never written)."""
    return {
        "loss": jnp.float32(2.0 / step),
        "grad_norm": jnp.float32(0.5 * step),
        "moe.load_max_over_mean": jnp.float32(1.0 + step),
        "ssm.state_rms_max": jnp.float32(3.0),
        "per_layer": jnp.arange(3.0),
    }


def run_loop(trainer, steps=STEPS, block=True, between=None):
    """The loop a user writes; ``block`` brackets the compute phase
    as ``PhaseHandle.block`` does, ``between`` runs after each
    ``report_step`` (a save)."""
    for _ in range(steps):
        with trainer.profile("compute") as p:
            metrics = metrics_of(trainer.global_step + 1)
            if block:
                p.block(metrics)
        trainer.report_step(metrics)
        if between is not None:
            between(trainer)


def events_of(log, type_):
    if not os.path.exists(log):
        return []
    return [e for e in read_events(log) if e["type"] == type_]


def read_file(path):
    with open(path) as f:
        return json.load(f)


def log_of(tmp_path, monkeypatch, name, block):
    """The events and every step's metrics file of one loop."""
    log = str(tmp_path / f"{name}.jsonl")
    metrics = str(tmp_path / f"{name}.json")
    monkeypatch.setenv("DLROVER_EVENT_LOG", log)
    monkeypatch.setenv("DLROVER_METRICS_FILE", metrics)
    trainer = ElasticTrainer(4, 4, dp_size=1)
    files = []

    def keep_file(_):
        if os.path.exists(metrics):
            files.append(read_file(metrics))

    run_loop(trainer, block=block, between=keep_file)
    trainer.flush_reports()
    keep_file(trainer)
    by_step = {f["global_step"]: f for f in files}
    return {
        "train_step": events_of(log, "train_step"),
        "step_phases": events_of(log, "step_phases"),
        "metrics_file": [by_step[s] for s in sorted(by_step)],
    }


# where a field holds a time or a duration the two paths differ by
# construction; the phases' NAMES differ by the sub-phases alone
CLOCKS = {"ts", "timestamp"}


def same_but_clocks(a, b):
    assert list(a) == list(b)
    for key in a:
        if key not in CLOCKS:
            assert a[key] == b[key], key


def phase_names(phases):
    """The names of a breakdown with the report's sub-phases folded
    onto the immediate path's."""
    return {k.replace("compute.report", "report") for k in phases}


@pytest.mark.parametrize(
    "kind", ["train_step", "step_phases", "metrics_file"]
)
def test_deferred_log_is_the_immediate_log(
    tmp_path, monkeypatch, no_chaos, kind
):
    now = log_of(tmp_path, monkeypatch, "now", block=False)[kind]
    later = log_of(tmp_path, monkeypatch, "later", block=True)[kind]
    assert len(now) == len(later) == STEPS
    for a, b in zip(now, later):
        if kind == "train_step":
            same_but_clocks(a, b)
            assert b["step"] in range(1, STEPS + 1)
            assert b["moe.load_max_over_mean"] == 1.0 + b["step"]
            assert "per_layer" not in b and "grad_norm" not in b
        elif kind == "step_phases":
            assert a["step"] == b["step"]
            assert a["node_rank"] == b["node_rank"]
            # (the first step has no report to write beside it)
            if b["step"] > 1:
                assert phase_names(a) == phase_names(b)
            else:
                assert phase_names(b) < phase_names(a)
            assert not validate_event(b)
        else:
            same_but_clocks(
                {k: v for k, v in a.items() if k != "phases"},
                {k: v for k, v in b.items() if k != "phases"},
            )
            assert b["loss"] == pytest.approx(2.0 / b["global_step"])
            assert "per_layer" not in b
            # the collectors' phases: the deferred file holds the
            # CLOSED step's, beside it the report it wrote in its
            # compute (none in the first step)
            main = {"compute", "report", "gc", "total_s", "other_s"}
            assert main <= set(a["phases"]) and main <= set(b["phases"])
            assert ("compute.report" in b["phases"]) == (
                b["global_step"] > 1
            )


def test_report_step_hands_over_and_touches_no_file(paths, no_chaos):
    log, metrics = paths
    trainer = ElasticTrainer(4, 4, dp_size=1)
    before = len(list(read_events(log)))
    run_loop(trainer, steps=1)
    assert trainer.global_step == 1
    assert trainer.profiler.step == 2
    assert len(list(read_events(log))) == before
    assert not os.path.exists(metrics)
    # the closed phases are the caller's at once
    assert "compute" in trainer.last_step_phases
    trainer.flush_reports()
    assert [e["step"] for e in events_of(log, "train_step")] == [1]
    assert read_file(metrics)["global_step"] == 1


class Dispatched:
    """Stands for a dispatched step's result: ``block_until_ready``
    on it notes what the log held when the loop came to block."""

    def __init__(self, log, seen):
        self.log, self.seen = log, seen

    def block_until_ready(self):
        self.seen.append(
            [e["step"] for e in events_of(self.log, "train_step")]
        )
        return self


def test_deferred_work_runs_between_dispatch_and_block(paths, no_chaos):
    log, _ = paths
    trainer = ElasticTrainer(4, 4, dp_size=1)
    at_dispatch, at_block = [], []
    for _ in range(3):
        with trainer.profile("compute") as p:
            at_dispatch.append(
                [e["step"] for e in events_of(log, "train_step")]
            )
            p.block(Dispatched(log, at_block))
        trainer.report_step(metrics_of(trainer.global_step + 1))
    # step N's event is not there while step N + 1 is dispatched and
    # is there when the loop blocks on it
    assert at_dispatch == [[], [], [1]]
    assert at_block == [[], [1], [1, 2]]


@pytest.mark.parametrize("loop", ["no_phase", "unblocked_phase"])
def test_a_loop_without_a_blocked_compute_writes_at_once(
    paths, no_chaos, loop
):
    log, metrics = paths
    trainer = ElasticTrainer(4, 4, dp_size=1)
    for step in range(1, 4):
        if loop == "no_phase":
            trainer.report_step(metrics_of(step))
        else:
            run_loop(trainer, steps=1, block=False)
        assert [
            e["step"] for e in events_of(log, "train_step")
        ] == list(range(1, step + 1))
        assert read_file(metrics)["global_step"] == step
    phases = events_of(log, "step_phases")
    assert [e["step"] for e in phases] == [1, 2, 3]
    assert all("compute.report" not in e for e in phases)
    assert all("report.metrics_file" in e for e in phases)


EXIT_LOOP = '''
import jax.numpy as jnp
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

trainer = ElasticTrainer(4, 4, dp_size=1)
for step in range(1, 4):
    with trainer.profile("compute") as p:
        metrics = p.block({"loss": jnp.float32(step)})
    trainer.report_step(metrics)
'''


@pytest.mark.parametrize(
    "how", ["next_report_step", "flush_reports", "interpreter_exit"]
)
def test_what_is_pending_is_written_in_order_and_once(
    paths, no_chaos, how
):
    log, metrics = paths
    if how == "interpreter_exit":
        subprocess.run(
            [sys.executable, "-c", EXIT_LOOP], check=True, timeout=120,
            env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"),
        )
    else:
        trainer = ElasticTrainer(4, 4, dp_size=1)
        run_loop(trainer, steps=2)
        if how == "next_report_step":
            # a step that opened no compute phase: it finds step 2's
            # report still there, writes it, then its own at once
            trainer.report_step(metrics_of(3))
        else:
            run_loop(trainer, steps=1)
            trainer.flush_reports()
        trainer.flush_reports()  # nothing left: writes nothing
    for type_ in ("train_step", "step_phases"):
        assert [e["step"] for e in events_of(log, type_)] == [1, 2, 3]
    assert read_file(metrics)["global_step"] == 3
    stamps = [e["ts"] for e in events_of(log, "train_step")]
    assert stamps == sorted(stamps)


def test_entering_the_checkpoint_phase_writes_nothing(paths, no_chaos):
    log, metrics = paths
    trainer = ElasticTrainer(4, 4, dp_size=1)
    seen = []

    def save(t):
        with t.profile("checkpoint"):
            seen.append(len(events_of(log, "train_step")))

    run_loop(trainer, steps=3, between=save)
    # at the save after step N the log holds the steps before N
    assert seen == [0, 1, 2]
    trainer.flush_reports()
    checkpointed = [
        e for e in events_of(log, "step_phases") if "checkpoint" in e
    ]
    # (a save after step N is booked to step N + 1: as it always was)
    assert [e["step"] for e in checkpointed] == [2, 3]
    assert all("checkpoint.report" not in e for e in checkpointed)


@pytest.mark.parametrize("error", [ValueError, StopIteration])
def test_a_compute_body_that_raises_leaves_the_report_pending(
    paths, no_chaos, error
):
    log, _ = paths
    trainer = ElasticTrainer(4, 4, dp_size=1)
    run_loop(trainer, steps=1)
    # the exception is the caller's own (through two generator
    # context managers, ``StopIteration`` included) and step 1's
    # report is neither written in the failed phase nor lost
    with pytest.raises(error):
        with trainer.profile("compute") as p:
            p.block(metrics_of(2))
            raise error("the step failed")
    assert events_of(log, "train_step") == []
    trainer.flush_reports()
    trainer.flush_reports()
    for type_ in ("train_step", "step_phases"):
        assert [e["step"] for e in events_of(log, type_)] == [1]


@pytest.mark.parametrize("fault", ["metrics_no_dict", "close_raises"])
def test_a_report_step_that_raises_leaves_no_half_built_record(
    paths, no_chaos, monkeypatch, fault
):
    log, _ = paths
    trainer = ElasticTrainer(4, 4, dp_size=1)
    run_loop(trainer, steps=1)
    with trainer.profile("compute") as p:
        p.block(metrics_of(2))
    if fault == "metrics_no_dict":
        with pytest.raises(TypeError):
            trainer.report_step(3.0)
        # (raised before the counter moved)
        assert trainer.global_step == 1
    else:
        def broken():
            raise RuntimeError("no clock")

        with monkeypatch.context() as m:
            m.setattr(trainer.profiler, "finish_step", broken)
            with pytest.raises(RuntimeError):
                trainer.report_step(metrics_of(2))
        assert trainer.global_step == 2
    # nothing waits that lacks its phases or its time: a flush
    # writes nothing and the loop goes on
    trainer.flush_reports()
    assert [e["step"] for e in events_of(log, "train_step")] == [1]
    run_loop(trainer, steps=1)
    trainer.flush_reports()
    last = trainer.global_step
    assert [e["step"] for e in events_of(log, "train_step")] == [1, last]
    assert all("total_s" in e for e in events_of(log, "step_phases"))


def test_no_trainer_is_kept_alive_for_the_exit(
    paths, no_chaos, monkeypatch
):
    log, _ = paths
    at_exit = []
    monkeypatch.setattr(
        atexit, "register", lambda f, *a, **k: at_exit.append((f, a, k))
    )
    trainers = [ElasticTrainer(4, 4, dp_size=1) for _ in range(3)]
    assert len(at_exit) == 3
    run_loop(trainers[0], steps=1)
    refs = [weakref.ref(t) for t in trainers]
    kept = trainers[0]
    del trainers
    gc.collect()
    assert [r() is None for r in refs] == [False, True, True]
    # what the interpreter calls on its way out: the trainer that is
    # still there writes what waits, the dead ones are passed over
    for f, a, k in at_exit:
        f(*a, **k)
    assert [e["step"] for e in events_of(log, "train_step")] == [1]
    assert kept.global_step == 1


class Killed(Exception):
    """Stands for the process's end under a kill rule."""


@pytest.mark.parametrize("rule", ["none_fires", "kill_at_step_2"])
def test_an_armed_injector_means_nothing_is_deferred(
    paths, monkeypatch, no_chaos, rule
):
    log, metrics = paths
    saves = []

    def kill(args, ctx):
        # what the log holds when the rule fires: the checkers'
        # "step N completed" must be there already
        saves.append(("kill", [
            e["step"] for e in events_of(log, "train_step")
        ]))
        raise Killed()

    monkeypatch.setitem(primitives.ACTIONS, "kill", kill)
    chaos.install({
        "name": "t", "seed": 0,
        "rules": [{
            "point": "trainer.step", "action": "kill",
            "at_step": 2 if rule == "kill_at_step_2" else 99,
        }],
    })
    trainer = ElasticTrainer(4, 4, dp_size=1)

    def save(t):
        saves.append(("save", t.global_step))
        # the scenarios' loops save right after report_step: the log
        # is whole up to the step saved
        assert [
            e["step"] for e in events_of(log, "train_step")
        ] == list(range(1, t.global_step + 1))
        assert read_file(metrics)["global_step"] == t.global_step

    if rule == "none_fires":
        run_loop(trainer, steps=3, between=save)
        assert saves == [("save", 1), ("save", 2), ("save", 3)]
        assert all(
            "compute.report" not in e
            for e in events_of(log, "step_phases")
        )
        return
    with pytest.raises(Killed):
        run_loop(trainer, steps=3, between=save)
    # step 2's event is in the log, the rule fired after it and
    # before the save that follows step 2
    assert saves == [("save", 1), ("kill", [1, 2])]
    types = [
        e["type"] for e in read_events(log)
        if e["type"] in ("train_step", "chaos_inject")
    ]
    assert types == ["train_step", "train_step", "chaos_inject"]


def test_step_phases_of_a_deferred_step(paths, no_chaos):
    log, _ = paths
    trainer = ElasticTrainer(4, 4, dp_size=1)
    f = jax.jit(lambda x: {"loss": jnp.sum(x), "moe.a": jnp.mean(x)})
    x = jnp.ones((64, 64))
    for _ in range(6):
        with trainer.profile("compute") as p:
            metrics = p.block(f(x))
        trainer.report_step(metrics)
        with trainer.profile("checkpoint"):
            pass
    trainer.flush_reports()
    phases = events_of(log, "step_phases")
    assert [e["step"] for e in phases] == [1, 2, 3, 4, 5, 6]
    # the first step has no report to write; every later step writes
    # the step before it, as a part of its compute
    assert "compute.report" not in phases[0]
    for e in phases[1:]:
        parts = [
            e["compute.report.events"], e["compute.report.chip_metrics"],
            e["compute.report.metrics_file"],
        ]
        assert 0 < sum(parts) <= e["compute.report"] + 1e-6
        assert e["compute.report"] <= e["compute"] + 1e-6
        assert "report.events" not in e
        # the hand-over alone is what the host still costs the chip:
        # what ``trainer.host_ms_per_step`` reads
        assert e["report"] < 1e-3
        host = e["total_s"] - e["compute"] - e.get("checkpoint", 0.0)
        assert host < 1e-3
    # each event is stamped with its step's completion, not its write
    stamps = [e["ts"] for e in phases]
    steps = [e["ts"] for e in events_of(log, "train_step")]
    assert stamps == steps == sorted(stamps)
    for e, after in zip(phases, phases[1:]):
        assert after["ts"] - e["ts"] == pytest.approx(
            after["total_s"], abs=2e-3
        )


def test_make_train_step_stands_where_the_parent_has_it():
    # The kernels' payloads in the lowered step carry the source
    # lines of ``make_train_step`` (``tests/test_step_texts.py``
    # hashes twelve such texts, and the AOT cache keys on them): an
    # edit that moves them is a cold set-up in every cell on the
    # chip.  New code of this file goes BELOW ``abstract_like``, a
    # new import inside the function that needs it.
    lines, first = inspect.getsourcelines(elastic_trainer.make_train_step)
    assert (first, first + len(lines) - 1) == (298, 421)
