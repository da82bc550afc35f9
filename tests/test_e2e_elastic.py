"""End-to-end slice (SURVEY.md §7 step 5): tpurun launches a local
master + elastic agent; the training process runs a tiny GPT train
loop with flash checkpointing, crashes mid-run, is restarted by the
agent, restores from the agent-held shared-memory snapshot, and
finishes.  This exercises rendezvous, process supervision, the saver
factory handshake, shm surviving a dead trainer, and the storage
commit protocol in one test."""

import time

import pytest

from dlrover_tpu import run as tpurun
from dlrover_tpu.checkpoint.saver import (
    AsyncCheckpointSaver,
    read_last_checkpoint,
)

# The job's training script.  Every incarnation runs the
# RecoveryProfiler: restore overlaps the model/step build via
# load_checkpoint_async, the first step's trace+compile is bracketed
# as the retrace phase (compile-cache hit/miss witnessed from the
# cache dir), and the whole death->first-step budget lands as
# recovery_phase events.  It crashes itself once.  argv: ckpt_dir
# crash_flag restored_flag crash_mode(exit|kill)
TRAIN_SCRIPT = r'''
import os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType
from dlrover_tpu.models.gpt import GPT, GPTConfig, cross_entropy_loss
from dlrover_tpu.trainer.elastic_trainer import (
    ElasticTrainer, TrainState, abstract_like, make_train_step,
    restore_train_state,
)
from dlrover_tpu.trainer.recovery import RecoveryProfiler

ckpt_dir, crash_flag, restored_flag, crash_mode = sys.argv[1:5]

prof = RecoveryProfiler()
# restore overlap: read/assemble run on a background thread while the
# model/optimizer/jitted step are built below
ckpt = Checkpointer(ckpt_dir)
load_handle = ckpt.load_checkpoint_async()

cfg = GPTConfig.tiny()
model = GPT(cfg)
optimizer = optax.adam(1e-3)

def loss_fn(p, batch):
    logits = model.apply({"params": p}, batch["x"])
    return cross_entropy_loss(logits, batch["y"])

step_fn = make_train_step(loss_fn, optimizer)
rng = np.random.default_rng(0)
data = rng.integers(0, cfg.vocab_size, (8, 17), dtype=np.int32)

# AOT executable cache, resolved while the restore read runs on its
# own thread: a warm incarnation resolves through the label index
# and deserializes the compiled step (no eval_shape, no trace); a
# cold one traces and writes the entry + index the replacement hits
batch = {"x": jnp.asarray(data[:, :-1]), "y": jnp.asarray(data[:, 1:])}

def _abstract_examples():
    abs_params = jax.eval_shape(
        model.init_params, jax.random.PRNGKey(0)
    )
    abs_state = jax.eval_shape(
        lambda p: TrainState.create(p, optimizer), abs_params
    )
    return abs_state, abstract_like(batch)

step = prof.resolve_step(
    step_fn, _abstract_examples,
    restore_busy=lambda: not load_handle.done(),
)

start_step, restored = load_handle.result()
prof.record_restore(ckpt.last_restore_phases)
if start_step is None:
    params = model.init_params(jax.random.PRNGKey(0))
    start_step = 0
    state = TrainState.create(params, optimizer)
else:
    # shaved state_build: batched device_put + deferred optimizer
    # init (the checkpoint supplies the optax slots)
    state = restore_train_state(optimizer, restored["state"])

trainer = ElasticTrainer(global_batch_size=8, micro_batch_size=8,
                         dp_size=1)
trainer.global_step = start_step

_first_step = True
for i in range(start_step, 5):
    with trainer.profile("h2d"):
        batch = {"x": jnp.asarray(data[:, :-1]),
                 "y": jnp.asarray(data[:, 1:])}
    with trainer.profile("compute") as _p:
        state, metrics = step(state, batch)
        if _first_step:
            _first_step = False
            jax.block_until_ready(metrics)
            prof.record_first_step()
        _p.block(metrics)
    trainer.report_step(metrics)
    ckpt.save_checkpoint(
        trainer.global_step,
        {"state": state, "trainer": trainer.state_dict()},
        storage_type=StorageType.MEMORY,
    )
    ckpt.wait()  # the crash below comes AFTER the commit to shm
    if start_step > 0 and not os.path.exists(restored_flag):
        open(restored_flag, "w").close()  # first step after restore
    if trainer.global_step == 3 and not os.path.exists(crash_flag):
        open(crash_flag, "w").close()
        if crash_mode == "kill":
            os.kill(os.getpid(), 9)  # hard kill AFTER the shm save
        sys.exit(17)  # simulated crash AFTER the shm save

ckpt.save_checkpoint(
    5, {"state": state, "trainer": trainer.state_dict()},
    storage_type=StorageType.DISK,
)
# wait for the agent-side async persist to commit before exiting
ckpt.wait()
tracker = os.path.join(ckpt_dir, "latest_checkpointed_iteration.txt")
deadline = time.time() + 60
while time.time() < deadline and not os.path.exists(tracker):
    time.sleep(0.2)
assert os.path.exists(tracker), "checkpoint commit did not land"
ckpt.close()
'''


def test_tpurun_crash_restart_restore(tmp_path, monkeypatch):
    # the agent runs in THIS process: a saver factory that an earlier
    # test file of the same xdist worker left behind listens under
    # that file's socket directory, ``start_async_saving_ckpt`` would
    # keep it, and every save of this job would wait 300 s for an IPC
    # server that is not there (seen under ``--dist loadfile``, PR 28)
    AsyncCheckpointSaver.reset()
    monkeypatch.setenv("DLROVER_SHARED_DIR", str(tmp_path / "sock"))
    # one JSONL event log collects the whole job: the master
    # subprocess, this (agent) process and the trainer workers all
    # inherit the env var and append to it
    event_log = tmp_path / "events.jsonl"
    monkeypatch.setenv("DLROVER_EVENT_LOG", str(event_log))
    script = tmp_path / "train.py"
    script.write_text(TRAIN_SCRIPT)
    ckpt_dir = tmp_path / "ckpt"
    crash_flag = tmp_path / "crashed"

    rc = tpurun.main(
        [
            "--nproc_per_node=1",
            "--max_restarts=2",
            "--monitor_interval=0.3",
            str(script),
            str(ckpt_dir),
            str(crash_flag),
            str(tmp_path / "restored"),
            "exit",
        ]
    )
    assert rc == 0
    assert crash_flag.exists()  # the crash really happened
    step, shards = read_last_checkpoint(str(ckpt_dir))
    assert step == 5 and 0 in shards
    _assert_telemetry(event_log)


def _assert_telemetry(event_log):
    """One elastic run must leave the full observability trail
    (ISSUE 1 acceptance): linked rendezvous spans across the
    agent->master RPC, checkpoint events, queryable histograms, and
    a Prometheus dump with dlrover_ metrics."""
    from dlrover_tpu.telemetry.events import read_events
    from dlrover_tpu.telemetry.metrics import get_registry

    events = list(read_events(str(event_log)))
    by_type = {}
    for e in events:
        by_type.setdefault(e["type"], []).append(e)

    # rendezvous: the master emitted round completion, and its
    # handler-side rdzv.join span is the child of the agent-side
    # span whose context rode the RPC frame
    assert by_type.get("rendezvous_complete"), events
    spans = by_type.get("span", [])
    agent_joins = [
        s for s in spans
        if s["name"] == "rdzv.join" and s["source"] == "agent"
    ]
    master_joins = [
        s for s in spans
        if s["name"] == "rdzv.join" and s["source"] == "master"
    ]
    assert agent_joins and master_joins
    agent_ids = {s["span_id"] for s in agent_joins}
    agent_traces = {s["trace_id"] for s in agent_joins}
    linked = [
        m for m in master_joins
        if m["parent_id"] in agent_ids
        and m["trace_id"] in agent_traces
    ]
    assert linked, (agent_joins, master_joins)

    # checkpoint path: trainer-side shm saves, agent-side persist
    assert by_type.get("checkpoint_shm_save")
    assert by_type.get("checkpoint_persist")
    # the crash triggered a worker restart event
    assert by_type.get("worker_restart")
    for e in events:
        assert e["schema"] == 1
        assert e["source"] in ("master", "agent", "trainer")

    # histograms queryable from THIS process's registry (the agent
    # and the async saver run here): checkpoint persist latency and
    # the agent's rendezvous latency both recorded
    reg = get_registry()
    persist = reg.get("dlrover_checkpoint_persist_seconds")
    assert persist is not None and persist.snapshot()["count"] >= 1
    rdzv = reg.get("dlrover_agent_rdzv_seconds")
    assert rdzv.snapshot(rdzv="elastic-training")["count"] >= 1

    # Prometheus text dump carries the dlrover_ metric families
    dump = reg.render_prometheus()
    assert "dlrover_checkpoint_persist_seconds_bucket" in dump
    assert dump.count("dlrover_") > 10


def test_goodput_accounting_through_crash(tmp_path, monkeypatch):
    """North-star metric plumbing end to end: a test-hosted master
    observes step reports from a tpurun-supervised trainer that
    crashes once; after recovery the master's SpeedMonitor carries
    steps, positive goodput, and the restart shows up as a worker
    adjustment (BASELINE.md: goodput under churn is THE metric)."""
    from dlrover_tpu.master.master import JobMaster

    monkeypatch.setenv("DLROVER_SHARED_DIR", str(tmp_path / "sock"))
    # own metrics file: the shared default could carry a stale step
    # from an earlier test and satisfy the assertions vacuously
    monkeypatch.setenv(
        "DLROVER_METRICS_FILE", str(tmp_path / "metrics.json")
    )
    master = JobMaster(port=0, node_num=1, job_name="goodput-e2e")
    master.prepare()
    monkeypatch.setenv(
        "DLROVER_MASTER_ADDR", f"127.0.0.1:{master.port}"
    )
    try:
        script = tmp_path / "train.py"
        script.write_text(TRAIN_SCRIPT)
        rc = tpurun.main(
            [
                "--nproc_per_node=1",
                "--max_restarts=2",
                "--monitor_interval=0.3",
                str(script),
                str(tmp_path / "ckpt"),
                str(tmp_path / "crashed"),
                str(tmp_path / "restored"),
                "exit",
            ]
        )
        assert rc == 0
        assert (tmp_path / "crashed").exists()
        sm = master.speed_monitor
        # the monitor reports on an interval and the master's
        # servicer processes them on its own threads; under load the
        # last report can land seconds after tpurun returns — poll
        # instead of asserting a race
        deadline = time.time() + 15
        while (
            time.time() < deadline and sm.completed_global_step < 3
        ):
            time.sleep(0.2)
        assert sm.completed_global_step >= 3
        # goodput accumulates BETWEEN step reports; a seconds-long toy
        # run may only get one report in, but the accounting must have
        # engaged and never exceed 1
        assert sm._last_productive_mark > 0
        assert 0.0 <= sm.goodput() <= 1.0
        # the crash+restart left a membership adjustment mark
        assert sm._worker_adjustment_time > 0
    finally:
        master.stop()
