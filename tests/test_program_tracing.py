"""The measurement inside the program (PR 25): spans and step phases
on one clock with the profiler, the save path's span tree, device
scopes in the step program, slow-step attribution's raw material."""

import gc
import glob
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType
from dlrover_tpu.common import aot_cache
from dlrover_tpu.common.multi_process import SharedLock
from dlrover_tpu.models.gpt import GPT, GPTConfig, cross_entropy_loss
from dlrover_tpu.telemetry import tracing
from dlrover_tpu.telemetry.events import read_events
from dlrover_tpu.telemetry.schema import validate_event
from dlrover_tpu.trainer.elastic_trainer import (
    ElasticTrainer,
    StepPhaseProfiler,
    TrainState,
    make_train_step,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def event_log(tmp_path, monkeypatch):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("DLROVER_EVENT_LOG", path)
    return path


def spans_of(path, name=None):
    if not os.path.exists(path):
        return []
    return [
        e for e in read_events(path)
        if e["type"] == "span" and (name is None or e["name"] == name)
    ]


def covered(root, children):
    """Seconds of ``root`` covered by the union of ``children``."""
    r0, r1 = root["start_ts"], root["start_ts"] + root["duration_s"]
    total, cursor = 0.0, r0
    for e in sorted(children, key=lambda e: e["start_ts"]):
        start = max(cursor, e["start_ts"])
        end = min(r1, e["start_ts"] + e["duration_s"])
        if end > start:
            total += end - start
            cursor = end
    return total


def uncovered(root, children):
    return root["duration_s"] - covered(root, children)


def slack(root):
    """What a save's children may leave unnamed: a tenth of the span,
    and 50 ms for the lock probe, the config, the event and the log
    line between them, which other threads stretch on a busy host."""
    return 0.1 * root["duration_s"] + 0.05


# -- A. one clock ------------------------------------------------------------


def test_span_event_carries_start_ts(event_log):
    before = time.time()
    with tracing.Tracer().span("ckpt.save", step=3) as sp:
        time.sleep(0.01)
    (event,) = spans_of(event_log, "ckpt.save")
    assert validate_event(event) == []
    assert before <= event["start_ts"] <= event["ts"]
    assert event["start_ts"] == sp.start_time
    assert event["duration_s"] >= 0.01


def test_record_span_takes_the_callers_clock(event_log):
    tracer = tracing.Tracer()
    with tracer.span("ckpt.persist", step=9) as parent:
        tracer.record_span(
            "ckpt.persist.lock_hold", 100.0, 102.5, step=9, shard=0
        )
    (event,) = spans_of(event_log, "ckpt.persist.lock_hold")
    assert event["start_ts"] == 100.0
    assert event["duration_s"] == 2.5
    assert event["trace_id"] == parent.trace_id
    assert event["parent_id"] == parent.span_id


def test_nested_spans_are_written_once_with_the_outermost(event_log):
    """The ~35 spans of a flash save cost one append, not 35: a span
    that ends inside another waits for it.  A thread that continues
    the trace through ``attach_context`` writes on its own."""
    import contextvars
    import threading

    tracer = tracing.Tracer()
    with tracer.span("ckpt.save", step=1) as root:
        with tracer.span("ckpt.save.fetch", step=1):
            pass
        assert spans_of(event_log) == []        # nothing written yet
        # a thread handed a COPY of the context shares the buffer
        thread = threading.Thread(
            target=contextvars.copy_context().run,
            args=(lambda: tracer.record_span(
                "ckpt.save.memcpy", 5.0, 6.0
            ),),
        )
        thread.start()
        thread.join()
        # a thread that attaches the context is its own outermost
        wire = tracing.inject_context()

        def writer():
            with tracing.attach_context(wire):
                with tracer.span("ckpt.save.write", step=1):
                    with tracer.span("ckpt.save.layout", step=1):
                        pass

        thread = threading.Thread(target=writer)
        thread.start()
        thread.join()
        assert [e["name"] for e in spans_of(event_log)] == [
            "ckpt.save.layout", "ckpt.save.write",
        ]
    names = [e["name"] for e in spans_of(event_log)]
    assert names == [
        "ckpt.save.layout", "ckpt.save.write",
        "ckpt.save.fetch", "ckpt.save.memcpy", "ckpt.save",
    ]
    events = spans_of(event_log)
    assert {e["trace_id"] for e in events} == {root.trace_id}
    # each keeps the time it ended as its ts
    assert events[3]["ts"] == 6.0
    assert all(validate_event(e) == [] for e in events)
    with open(event_log) as f:
        assert len(f.read().splitlines()) == 5


def test_annotation_helper_never_imports_jax():
    """The agent and the master never touch jax (a process that did
    would hold the chip): their spans reach the event log only."""
    code = (
        "import sys\n"
        "from dlrover_tpu.telemetry import tracing\n"
        "import dlrover_tpu.checkpoint.saver\n"
        "import dlrover_tpu.agent.monitor\n"
        "import dlrover_tpu.master.goodput_ledger\n"
        "assert tracing.annotation('x', 1) is None\n"
        "with tracing.span('ckpt.persist', step=1):\n"
        "    tracing.record_span('ckpt.persist.lock_hold', 1.0, 2.0)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
    )
    out = subprocess.run(  # noqa: S603
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=REPO),
    )
    assert out.returncode == 0, out.stdout + out.stderr


def test_annotations_and_span_events_share_one_clock(
    tmp_path, event_log
):
    """A program span is in the profiler's trace as
    ``dlrover.<name>`` with ``wall_ns`` equal to its event's
    ``start_ts``, and both agree on the duration: any one of them
    maps the event log onto the profiler's clock."""
    from jax.profiler import ProfileData

    tracer = tracing.Tracer()
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        with tracer.span("ckpt.save", step=11):
            with tracer.span("ckpt.save.fetch", step=11, bytes=4):
                time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        str(tmp_path / "trace" / "**" / "*.xplane.pb"), recursive=True
    )
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dlrover.ckpt.save"):
                    found[e.name] = (e, dict(e.stats))
    offsets = []
    for name in ("ckpt.save", "ckpt.save.fetch"):
        (event,) = spans_of(event_log, name)
        ann, stats = found["dlrover." + name]
        assert abs(stats["wall_ns"] / 1e9 - event["start_ts"]) < 1e-3
        assert abs(ann.duration_ns / 1e9 - event["duration_s"]) < 1e-3
        assert stats["span_id"] == "s" + event["span_id"]
        assert int(stats["step"]) == 11
        offsets.append(stats["wall_ns"] - ann.start_ns)
    # one offset serves every span
    assert abs(offsets[0] - offsets[1]) < 1e6


# -- B. the save path ----------------------------------------------------------


def test_ckpt_save_children_cover_the_save(tmp_path, event_log):
    """MEMORY or DISK, a save's call is the snapshot and the
    hand-over, and its children name all of it but a bounded
    remainder (``slack``); the transfers' kick-off and the shm write
    (``fetch``, ``memcpy`` ...) run on the writer thread under the
    call's trace id, and end after the call returned."""
    state = {
        "w": jnp.ones((64, 1024, 1024), jnp.float32),  # 256 MB
        "b": jnp.arange(8, dtype=jnp.int32),
        "step": 3,
    }
    ckpt = Checkpointer(str(tmp_path / "ckpt"))
    try:
        assert ckpt.save_checkpoint(
            1, state, storage_type=StorageType.MEMORY
        )
        assert ckpt.save_checkpoint(
            1, state, storage_type=StorageType.MEMORY
        )
        assert ckpt.save_checkpoint(
            2, state, storage_type=StorageType.DISK
        )
        assert ckpt.wait()
        # the in-process saver persists the DISK save on its own
        # thread
        deadline = time.time() + 30
        while time.time() < deadline and not any(
            e["type"] == "checkpoint_persist"
            for e in read_events(event_log)
        ):
            time.sleep(0.05)
    finally:
        ckpt._engine._shm_handler.unlink()  # 256 MB of /dev/shm
        ckpt.close()
    spans = spans_of(event_log)
    assert all(validate_event(e) == [] for e in spans)
    roots = [e for e in spans if e["name"] == "ckpt.save"]
    assert [r["attributes"]["storage"] for r in roots] == [
        "memory", "memory", "disk"
    ]
    assert {r["attributes"]["route"] for r in roots} == {"snapshot"}
    # the second MEMORY save (the first creates the segment) and the
    # DISK save: the same tree
    for root in roots[1:]:
        assert root["attributes"]["bytes"] >= 256 * 2**20
        same_trace = [
            e for e in spans
            if e["trace_id"] == root["trace_id"] and e is not root
        ]
        by_name = {e["name"]: e for e in same_trace}
        assert set(by_name) >= {
            "ckpt.save.writer_wait", "ckpt.save.snapshot",
            "ckpt.save.d2h_kickoff", "ckpt.save.enqueue",
            "ckpt.save.write", "ckpt.save.layout",
            "ckpt.save.publish_meta", "ckpt.save.fetch",
            "ckpt.save.memcpy", "ckpt.save.scalars",
        }
        # on the caller's thread: the call's own children
        called = [
            e for e in same_trace if e["parent_id"] == root["span_id"]
            and e["name"] != "ckpt.save.write"
        ]
        assert {e["name"] for e in called} == {
            "ckpt.save.writer_wait", "ckpt.save.route",
            "ckpt.save.snapshot", "ckpt.save.enqueue",
        }
        assert uncovered(root, called) <= slack(root)
        # on the writer thread: the write and everything beneath it
        write = by_name["ckpt.save.write"]
        assert write["parent_id"] == root["span_id"]
        written = [
            e for e in same_trace
            if e not in called and e is not write
            and not e["name"].startswith("ckpt.persist")
        ]
        assert all(e["parent_id"] == write["span_id"] for e in written)
        assert by_name["ckpt.save.d2h_kickoff"] in written
        assert uncovered(write, written) <= slack(write)
        memcpy = by_name["ckpt.save.memcpy"]
        assert memcpy["attributes"]["bytes"] >= 256 * 2**20
        assert memcpy["attributes"]["copy_s"] <= memcpy["duration_s"]
        # the call itself returned before the write ended
        assert (
            write["start_ts"] + write["duration_s"]
            > root["start_ts"] + root["duration_s"]
        )
    disk = roots[2]
    same_trace = {
        e["name"]: e for e in spans
        if e["trace_id"] == disk["trace_id"]
    }
    # the in-process saver persisted it, under the same trace too
    persist = same_trace.get("ckpt.persist")
    assert persist and persist["attributes"]["step"] == 2
    commits = [
        e for e in read_events(event_log)
        if e["type"] == "checkpoint_commit"
    ]
    assert commits and commits[-1]["seconds"] >= 0
    assert commits[-1]["start_ts"] <= commits[-1]["ts"]


def test_shared_lock_tells_a_waiter_who_holds_it():
    name = f"test_lock_note_{os.getpid()}"
    server = SharedLock(name, create=True)
    client = SharedLock(name, create=False)
    try:
        assert server.acquire(note="persist:120")
        assert client.holder() == "persist:120"
        assert not client.acquire(blocking=False)
        assert client.contended_with == "persist:120"
        assert server.release()
        assert client.holder() is None
        assert client.acquire(blocking=False)
        assert client.contended_with is None
        assert client.release()
    finally:
        client.close()
        server.close()


# -- C. device scopes -------------------------------------------------------------


def tiny_step():
    cfg = GPTConfig.tiny(remat=True)
    model = GPT(cfg)
    optimizer = optax.adamw(1e-3)

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["x"])
        return cross_entropy_loss(logits, batch["y"])

    params = model.init_params(jax.random.PRNGKey(0), seq_len=32)
    state = TrainState.create(params, optimizer)
    tokens = np.zeros((2, 33), np.int32)
    batch = {"x": tokens[:, :-1], "y": tokens[:, 1:]}
    return make_train_step(loss_fn, optimizer), state, batch


def test_lowered_step_carries_the_scopes():
    step, state, batch = tiny_step()
    text = step.lower(state, batch).as_text(debug_info=True)
    for scope in ("optimizer", "loss_head", "forward_backward",
                  "rematted_computation"):
        assert f"/{scope}/" in text, scope
    # both halves of the head: the projection inside the model, and
    # the cross entropy, whose scope opens the differentiated
    # function and so reads jvp(loss_head) / transpose(jvp(loss_head))
    assert "/loss_head/wte.attend/dot_general" in text
    assert "/jvp(loss_head)/jit(log_softmax)" in text
    assert "/transpose(jvp(loss_head))/" in text


def test_op_names_map_is_written_beside_the_aot_entry(
    tmp_path, monkeypatch
):
    """The executable's own instruction -> name-stack map: what a
    trace without name stacks is joined with."""
    step, state, batch = tiny_step()
    compiled = step.lower(state, batch).compile()
    op_map = aot_cache.op_names(compiled.as_text())
    assert op_map["module"] == "jit_step_fn"
    stacks = op_map["op_names"].values()
    for scope in ("optimizer", "loss_head", "forward_backward"):
        assert any(f"/{scope}/" in s for s in stacks), scope
    resolution = aot_cache.resolve_step(
        step, (state, batch), label="t", cache_dir=str(tmp_path)
    )
    if resolution.wrote:
        path = aot_cache.op_names_path(resolution.key, str(tmp_path))
        with open(path) as f:
            assert json.load(f)["module"] == "jit_step_fn"


# -- D. the step's phases ------------------------------------------------------------


def test_gc_seconds_show_in_step_phases(tmp_path, event_log, monkeypatch):
    monkeypatch.setenv(
        "DLROVER_METRICS_FILE", str(tmp_path / "metrics.json")
    )
    trainer = ElasticTrainer(4, 4, dp_size=1)
    with trainer.profile("compute"):
        junk = [[i] for i in range(50000)]
        del junk
        gc.collect()
    trainer.report_step({"loss": 1.0})
    trainer.report_step({"loss": 0.9})
    first, second = [
        e for e in read_events(event_log) if e["type"] == "step_phases"
    ]
    assert first["gc"] > 0
    assert first["gc"] <= first["compute"]
    # gc stands beside the phases: it is not taken out of other_s
    assert first["other_s"] <= first["total_s"] - first["compute"]
    assert second["gc"] == 0.0


def test_report_is_booked_in_three_sub_phases(
    tmp_path, event_log, monkeypatch
):
    path = str(tmp_path / "metrics.json")
    monkeypatch.setenv("DLROVER_METRICS_FILE", path)
    trainer = ElasticTrainer(4, 4, dp_size=1)
    for _ in range(3):
        with trainer.profile("compute"):
            time.sleep(0.002)
        trainer.report_step({"loss": 1.0})
    phases = [
        e for e in read_events(event_log) if e["type"] == "step_phases"
    ][-1]
    parts = [
        phases["report.events"], phases["report.chip_metrics"],
        phases["report.metrics_file"],
    ]
    assert all(p >= 0 for p in parts)
    assert 0 < sum(parts) <= phases["report"] + 1e-6
    # nothing of the report falls into the next step's "other"
    assert phases["other_s"] < 0.001
    # the agent's collectors read the same names from the file
    with open(path) as f:
        record = json.load(f)
    assert record["global_step"] == 3
    assert {"compute", "report", "report.events"} <= set(
        record["phases"]
    )


def test_phase_annotations_carry_the_step():
    prof = StepPhaseProfiler()
    prof.step = 41
    with prof.phase("compute"), prof.phase("report.events"):
        pass
    phases = prof.finish_step()
    assert "compute" in phases and "report.events" in phases
    # a sub-phase is not summed into the profiled total
    assert phases["other_s"] <= phases["total_s"]
    assert prof.peek()["total_s"] >= 0


# -- the periodic services beside the worker ------------------------------------------


def test_agent_monitor_ticks_are_spans(tmp_path, event_log):
    from dlrover_tpu.agent.monitor import TrainingMonitor

    class Client:
        def report_global_step(self, step, ts):
            self.step = step

    path = tmp_path / "metrics.json"
    path.write_text(json.dumps({"global_step": 5, "timestamp": 1.0}))
    client = Client()
    TrainingMonitor(str(path), client=client).report_once()
    assert client.step == 5
    assert spans_of(event_log, "agent.training_monitor")


def test_goodput_ledger_tick_is_a_span(tmp_path, event_log):
    from dlrover_tpu.master.goodput_ledger import GoodputLedgerService
    from dlrover_tpu.telemetry.events import emit_event

    emit_event("train_step", step=1, restart_count=0, node_rank=0)
    service = GoodputLedgerService(sources=[event_log])
    service.tick()
    (tick,) = spans_of(event_log, "master.goodput_ledger_tick")
    assert tick["attributes"]["events"] >= 1


# -- E. a launch on one clock (PR 37) -------------------------------------------------

LAUNCH_SPANS = (
    "tpurun.boot", "tpurun.master_boot", "master.boot", "agent.init",
    "rdzv.join", "agent.spawn_workers", "trainer.distributed_init",
    "trainer.backend_open", "trainer.init",
)

# a worker as small as a worker gets: the entrypoint's three first
# calls, a set-up that is no part of any step, then steps.  Its first
# incarnation kills itself after three steps.
TOY_WORKER = '''
import os, signal, time
from dlrover_tpu.trainer.elastic_trainer import (
    ElasticTrainer, init_jax_distributed,
)
from dlrover_tpu.trainer.recovery import RecoveryProfiler

init_jax_distributed()
prof = RecoveryProfiler()
trainer = ElasticTrainer(4, 4, dp_size=1)
time.sleep(1.0)
for step in range(1, 26):
    with trainer.profile("compute"):
        time.sleep(0.005)
    trainer.report_step({"loss": 1.0})
    if step == 1:
        prof.record_first_step()
    if step == 3 and prof.restart_count == 0:
        os.kill(os.getpid(), signal.SIGKILL)
'''


@pytest.fixture(scope="module")
def launch_log(tmp_path_factory):
    """The event log of one ``tpurun`` job on the CPU backend: a
    launch, a SIGKILL of the worker, a respawn that runs to its end.
    tpurun is a process of its own, as a user starts it."""
    tmp = tmp_path_factory.mktemp("launch")
    script = tmp / "worker.py"
    script.write_text(TOY_WORKER)
    log = str(tmp / "events.jsonl")
    env = dict(
        os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
        DLROVER_EVENT_LOG=log,
        DLROVER_SHARED_DIR=str(tmp / "sock"),
        DLROVER_JOB_NAME=f"launch{os.getpid()}",
        DLROVER_METRICS_FILE=str(tmp / "metrics.json"),
        # (a cache of its own: ROADMAP B7)
        JAX_COMPILATION_CACHE_DIR=str(tmp / "jax_cache"),
    )
    env.pop("DLROVER_TRACE_PARENT", None)
    done = subprocess.run(  # noqa: S603
        [sys.executable, "-m", "dlrover_tpu.run", "--nproc_per_node=1",
         "--max_restarts=1", "--monitor_interval=0.3", str(script)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    return list(read_events(log))


def launch_spans(events, restart_count):
    """``{name: span}`` of the launch chain's spans of one
    incarnation (the agent's side of ``rdzv.join``)."""
    return {
        e["name"]: e for e in events
        if e["type"] == "span" and e["name"] in LAUNCH_SPANS
        and e["attributes"].get("restart_count") == restart_count
    }


def test_a_launch_is_nine_spans_on_one_clock_under_one_trace(launch_log):
    spans = launch_spans(launch_log, 0)
    assert set(spans) == set(LAUNCH_SPANS)
    assert all(validate_event(e) == [] for e in spans.values())
    assert {e["trace_id"] for e in spans.values()} == {
        spans["tpurun.boot"]["trace_id"]
    }
    assert all(e["attributes"]["node_rank"] == 0 for e in spans.values())
    # the chain's links: tpurun.boot is the root, the master's boot
    # hangs under the launcher's wait for it, the worker's spans
    # under the agent's spawn (both through the environment)
    root = spans["tpurun.boot"]
    assert root["parent_id"] is None
    for name in ("tpurun.master_boot", "agent.init", "rdzv.join",
                 "agent.spawn_workers"):
        assert spans[name]["parent_id"] == root["span_id"], name
    assert (spans["master.boot"]["parent_id"]
            == spans["tpurun.master_boot"]["span_id"])
    for name in ("trainer.distributed_init", "trainer.backend_open",
                 "trainer.init"):
        assert (spans[name]["parent_id"]
                == spans["agent.spawn_workers"]["span_id"]), name
    # in order on one clock (/proc's process start counts in 10 ms)
    order = [n for n in LAUNCH_SPANS if n != "master.boot"]
    starts = [spans[n]["start_ts"] for n in order]
    assert starts == sorted(starts)
    ends = [spans[n]["start_ts"] + spans[n]["duration_s"] for n in order]
    assert all(e <= s + 0.02 for e, s in zip(ends, starts[1:]))
    inside, around = spans["master.boot"], spans["tpurun.master_boot"]
    assert around["start_ts"] - 0.02 <= inside["start_ts"]
    assert (inside["start_ts"] + inside["duration_s"]
            <= around["start_ts"] + around["duration_s"] + 0.02)
    # what the polls cost is on the spans that poll
    assert around["attributes"]["polls"] >= 1
    assert around["attributes"]["slept_s"] == pytest.approx(
        0.3 * (around["attributes"]["polls"] - 1)
    )
    join = spans["rdzv.join"]["attributes"]
    assert join["polls"] >= 1 and join["slept_s"] >= 0.0
    spawn = spans["agent.spawn_workers"]["attributes"]
    assert spawn["workers"] == 1 and spawn["warm_fork"] is False
    assert spans["trainer.backend_open"]["attributes"]["platform"] == "cpu"
    # the worker's process exists after the agent began to spawn it,
    # and its imports end where the program's first call begins
    (imported,) = [
        e for e in launch_log if e["type"] == "recovery_phase"
        and e["phase"] == "import" and e["restart_count"] == 0
    ]
    assert (imported["ts"] - imported["seconds"]
            >= spans["agent.spawn_workers"]["start_ts"] - 0.02)
    assert imported["ts"] == pytest.approx(
        spans["trainer.distributed_init"]["start_ts"], abs=1e-3
    )


def test_a_respawn_writes_the_launchs_spans_again(launch_log):
    """After the SIGKILL the second incarnation runs the same chain
    under the same names and the same trace, ``restart_count`` 1,
    with a ``spawn`` phase from the death's witness."""
    first, again = launch_spans(launch_log, 0), launch_spans(launch_log, 1)
    assert set(again) == {
        "rdzv.join", "agent.spawn_workers", "trainer.distributed_init",
        "trainer.backend_open", "trainer.init",
    }
    assert {e["trace_id"] for e in again.values()} == {
        first["tpurun.boot"]["trace_id"]
    }
    phases = {
        e["phase"]: e for e in launch_log
        if e["type"] == "recovery_phase" and e["restart_count"] == 1
    }
    assert {"spawn", "import", "backend", "first_step"} <= set(phases)
    (restart,) = [e for e in launch_log if e["type"] == "worker_restart"]
    spawn = phases["spawn"]
    assert spawn["ts"] - spawn["seconds"] == pytest.approx(
        restart["ts"], abs=0.05
    )
    # the phases tile: each begins where the one before it ended
    assert phases["import"]["ts"] - phases["import"]["seconds"] == (
        pytest.approx(spawn["ts"], abs=0.02)
    )
    assert phases["backend"]["ts"] - phases["backend"]["seconds"] == (
        pytest.approx(phases["import"]["ts"], abs=1e-3)
    )


def test_step_one_is_the_first_step_and_not_the_setup(launch_log):
    """The worker sleeps a second between the trainer's construction
    and its first step: no part of step 1."""
    for restart_count in (0, 1):
        first = next(
            e for e in launch_log if e["type"] == "step_phases"
            and e["step"] == 1 and e["ts"] > launch_spans(
                launch_log, restart_count
            )["trainer.init"]["ts"]
        )
        own = first["compute"] + first["report"]
        assert own <= first["total_s"] <= own + 0.05
        assert first["other_s"] <= 0.05


def test_a_steady_step_writes_the_parents_two_events(launch_log):
    """Nothing of the launch's tracing landed in the step loop: over
    20 steady steps the worker writes ``train_step`` and
    ``step_phases``, one each a step, as the parent of PR 37 does."""
    respawned = next(
        e["pid"] for e in launch_log
        if e["type"] == "train_step" and e["restart_count"] == 1
    )
    steady = [e for e in launch_log if e["pid"] == respawned]
    at = {e["step"]: i for i, e in enumerate(steady)
          if e["type"] == "train_step"}
    between = steady[at[4]:at[24]]
    assert len(between) == 2 * 20
    assert {e["type"] for e in between} == {"train_step", "step_phases"}


def test_the_harness_rehearses_the_launch_readers(tmp_path, checkout):
    """``benchmarks/run.py`` on the toy configuration with the five
    ``launch.*`` readers beside ``agent.start_s`` and
    ``cache.load_s``: each finds a value, and the set-up splits into
    program + harness + uncovered with little left uncovered."""
    import re

    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(  # noqa: S603
        # (from a checkout of its own: conftest.py, ROADMAP B7)
        [sys.executable, os.path.join(checkout, "benchmarks", "run.py"),
         "--cells", os.path.join(REPO, "benchmarks",
                                 "rehearsal_launch.json"),
         "--workload", "toy_steady", "--seed", "3700000011",
         "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    out = done.stdout
    assert done.returncode == 3, out[-3000:] + done.stderr[-3000:]
    assert '"correct": true' in out
    (found,) = re.findall(r"readers that found a value: (\[.*\])", out)
    assert set(json.loads(found.replace("'", '"'))) == {
        "agent.start_s", "cache.load_s", "launch.tpurun_boot_s",
        "launch.worker_import_s", "launch.backend_open_s",
        "launch.first_step_s", "launch.unattributed_pct",
    }
    ((setup, program, harness, uncovered, share),) = re.findall(
        r"launch: setup_s ([\d.]+) = program ([\d.]+) \+ harness "
        r"([\d.]+) \+ uncovered ([\d.]+) \(([\d.]+)%\)", out,
    )
    setup, share = float(setup), float(share)
    assert float(program) + float(harness) + float(uncovered) == (
        pytest.approx(setup, rel=0.01)
    )
    assert share == pytest.approx(
        100 * float(uncovered) / setup, abs=0.1
    )
    assert share < 25
