"""Elastic world-resize units (ISSUE 8): the master's resize
coordinator (shrink/grow decisions, debounce, action delivery,
journal persistence + replay), the rejoin path that re-admits a
written-off node, the engine's cross-world shm-tier skip, the
timeline's resize phase assembly + ``resize`` goodput bucket, and the
agent-side shm restore prefetch.  Stdlib/numpy-heavy and fast — the
e2e churn lives in test_chaos_e2e.py."""

import os
import time

import numpy as np
import pytest

from dlrover_tpu.common.constants import (
    MasterAction,
    NodeStatus,
    NodeType,
)
from dlrover_tpu.master.auto_scaler import ResizeCoordinator
from dlrover_tpu.master.job_manager import JobManager
from dlrover_tpu.master.journal import StateJournal
from dlrover_tpu.master.rdzv_manager import (
    ElasticTrainingRendezvousManager,
)
from dlrover_tpu.master.speed_monitor import SpeedMonitor


class _FakeServicer:
    def __init__(self):
        self.actions = []

    def request_node_action(self, node_id, action):
        self.actions.append((node_id, action))


def _two_node_world():
    """A completed 2-node elastic round + matching job-manager view."""
    rdzv = ElasticTrainingRendezvousManager()
    rdzv.update_rdzv_params(min_nodes=1, max_nodes=2)
    rdzv.join_rendezvous(0, 0, 1, "10.0.0.1")
    rdzv.join_rendezvous(1, 1, 1, "10.0.0.2")
    _, _, world, _ = rdzv.get_comm_world(0)
    assert len(world) == 2
    jm = JobManager()
    for node_id in (0, 1):
        jm.add_node(NodeType.WORKER, node_id)
        jm.collect_heartbeat(node_id)
    return rdzv, jm


def _coordinator(rdzv, jm, monkeypatch, grace="0"):
    monkeypatch.setenv("DLROVER_RESIZE_GRACE_S", grace)
    speed = SpeedMonitor()
    servicer = _FakeServicer()
    coord = ResizeCoordinator(
        rdzv, jm, speed, servicer, min_nodes=1, max_nodes=2,
    )
    return coord, speed, servicer


def test_elastic_round0_waits_for_full_world():
    """min_nodes < max_nodes must not let joiner order decide the
    initial world: the first round completes only at max_nodes (or
    through the waiting timeout)."""
    rdzv = ElasticTrainingRendezvousManager()
    rdzv.update_rdzv_params(min_nodes=1, max_nodes=2)
    rdzv.join_rendezvous(0, 0, 1)
    _, _, world, _ = rdzv.get_comm_world(0)
    assert world == {}, "round 0 completed below capacity"
    rdzv.join_rendezvous(1, 1, 1)
    _, _, world, _ = rdzv.get_comm_world(0)
    assert len(world) == 2


def test_inplace_rejoin_of_culprit_keeps_round():
    """A hang-diagnosed node's restart re-joins its OWN slot of an
    otherwise-unchanged multi-node world: same round, world handed
    back immediately, nothing shows as waiting (a waiting entry
    would trip the healthy peers' membership polls)."""
    rdzv, _jm = _two_node_world()
    round_before = rdzv.current_round()
    got = rdzv.join_rendezvous(1, 1, 1, "10.0.0.2")
    assert got == round_before
    assert rdzv.num_nodes_waiting() == 0
    r, _g, world, _c = rdzv.get_comm_world(1)
    assert r == round_before and len(world) == 2


def test_rejoin_with_dead_member_forms_new_round():
    """With a member gone from the liveness set, a re-join must NOT
    resolve in place — the world has to shrink through a new round
    (the elastic-resize path)."""
    rdzv, _jm = _two_node_world()
    rdzv.remove_alive_node(1)
    rdzv.join_rendezvous(0, 0, 1, "10.0.0.1")
    _r, _g, world, _c = rdzv.get_comm_world(0)
    assert len(world) == 1
    assert rdzv.current_round() == 2


def test_rejoin_under_new_node_id_forms_new_round():
    """A REPLACEMENT host under the same rank (different node_id)
    re-forms the world instead of silently taking the old slot."""
    rdzv, _jm = _two_node_world()
    rdzv.join_rendezvous(7, 1, 1, "10.0.0.9")  # rank 1, new id
    assert rdzv.num_nodes_waiting() == 1


def test_coordinator_shrinks_then_grows(monkeypatch):
    rdzv, jm = _two_node_world()
    coord, speed, servicer = _coordinator(rdzv, jm, monkeypatch)
    speed.collect_global_step(4)
    coord.poll()
    assert coord.pending is None  # capacity matches world

    # node 1 vanishes (heartbeat silence path removes it)
    rdzv.remove_alive_node(1)
    coord.poll()  # observes the mismatch (debounce baseline)
    coord.poll()  # grace=0: decides
    assert coord.pending is not None
    assert coord.pending["target"] == 1
    assert coord.pending["reason"] == "node-loss"
    # only the surviving world member is drained
    assert servicer.actions == [(0, MasterAction.RESIZE)]

    # survivor re-joins; the round completes at world=1
    rdzv.join_rendezvous(0, 0, 1, "10.0.0.1")
    _, _, world, _ = rdzv.get_comm_world(0)
    assert len(world) == 1
    coord.poll()
    assert coord._state == "await_first_step"
    speed.collect_global_step(7)
    coord.poll()
    assert coord.pending is None and coord._state == "idle"

    # replacement arrives: grow back
    rdzv.join_rendezvous(1, 1, 1, "10.0.0.2")
    coord.poll()
    coord.poll()
    assert coord.pending is not None
    assert coord.pending["target"] == 2
    assert (0, MasterAction.RESIZE) in servicer.actions[1:]
    rdzv.join_rendezvous(0, 0, 1, "10.0.0.1")
    _, _, world, _ = rdzv.get_comm_world(0)
    assert len(world) == 2
    coord.poll()
    speed.collect_global_step(9)
    coord.poll()
    assert coord.pending is None
    assert coord.resizes == 2


def test_coordinator_debounce_respects_grace(monkeypatch):
    rdzv, jm = _two_node_world()
    coord, _speed, servicer = _coordinator(
        rdzv, jm, monkeypatch, grace="300"
    )
    rdzv.remove_alive_node(1)
    coord.poll()
    coord.poll()
    assert coord.pending is None, "decided inside the grace window"
    assert servicer.actions == []


def test_coordinator_operator_request(monkeypatch):
    rdzv, jm = _two_node_world()
    coord, _speed, servicer = _coordinator(rdzv, jm, monkeypatch)
    coord.request(1, reason="operator")
    coord.poll()
    assert coord.pending is not None
    assert coord.pending["reason"] == "operator"
    assert coord.pending["target"] == 1
    assert servicer.actions[0] == (0, MasterAction.RESIZE)
    assert (1, MasterAction.RESIZE) in servicer.actions


def test_coordinator_journal_replay_mid_resize(monkeypatch, tmp_path):
    """A master crash between the decision and the reconverged round
    replays the decision and re-delivers the drain actions."""
    rdzv, jm = _two_node_world()
    coord, _speed, _servicer = _coordinator(rdzv, jm, monkeypatch)
    journal = StateJournal(str(tmp_path / "journal"))
    coord.journal = journal
    rdzv.remove_alive_node(1)
    coord.poll()
    coord.poll()
    assert coord.pending is not None
    journal.close()

    # "respawned" master: fresh managers restored to the pre-crash
    # rendezvous state, journal replayed into a fresh coordinator
    rdzv2 = ElasticTrainingRendezvousManager()
    rdzv2.update_rdzv_params(min_nodes=1, max_nodes=2)
    state = rdzv.journal_state()
    rdzv2.restore_round(state["round"], state["participants"])
    coord2, _speed2, servicer2 = _coordinator(
        rdzv2, jm, monkeypatch
    )
    replayed = StateJournal(str(tmp_path / "journal"))
    applied = [
        coord2.apply_journal_entry(kind, data)
        for _seq, kind, data in replayed.recovered.entries
    ]
    assert any(applied), "resize record not replayed"
    assert coord2.pending is not None
    assert coord2.pending["target"] == 1
    assert coord2._state == "resizing"
    # the respawned master re-drives the drain
    rdzv2.remove_alive_node(1)
    coord2.poll()
    assert (0, MasterAction.RESIZE) in servicer2.actions
    replayed.close()


def test_coordinator_replay_of_completed_resize_is_noop(
    monkeypatch,
):
    """A resize whose target round already completed replays as a
    no-op (idempotence across double restarts)."""
    rdzv, jm = _two_node_world()
    rdzv.remove_alive_node(1)
    rdzv.join_rendezvous(0, 0, 1)
    _, _, world, _ = rdzv.get_comm_world(0)
    assert len(world) == 1  # round 2 at world 1 already exists
    coord, _speed, servicer = _coordinator(rdzv, jm, monkeypatch)
    coord.apply_journal_entry(
        "resize",
        {"id": 1, "target": 1, "from_world": 2,
         "reason": "node-loss", "round": 1,
         "detected_ts": time.time(), "decided_ts": time.time(),
         "step_at_decision": 0},
    )
    assert coord.pending is None and coord._state == "idle"
    assert servicer.actions == []


def test_reconcile_after_replay_drops_completed_resize(monkeypatch):
    """Journal seq order replays the resize record BEFORE the rdzv
    record that completed it; the replay epilogue must re-judge the
    pending decision against the final restored round state instead
    of re-driving (and re-timing) a finished resize."""
    rdzv = ElasticTrainingRendezvousManager()
    rdzv.update_rdzv_params(min_nodes=1, max_nodes=2)
    jm = JobManager()
    coord, _speed, servicer = _coordinator(rdzv, jm, monkeypatch)
    # entry replay order: resize first (round 1 still current)...
    rdzv.restore_round(1, {"0": {"node_id": 0}, "1": {"node_id": 1}})
    coord.apply_journal_entry(
        "resize",
        {"id": 1, "target": 1, "from_world": 2,
         "reason": "node-loss", "round": 1,
         "detected_ts": time.time(), "decided_ts": time.time(),
         "step_at_decision": 0},
    )
    assert coord.pending is not None  # looks unfinished mid-replay
    # ...then the completing round record lands
    rdzv.restore_round(2, {"0": {"node_id": 0}})
    coord.reconcile_after_replay()
    assert coord.pending is None and coord._state == "idle"
    coord.poll()
    assert servicer.actions == []


def test_planned_restarts_do_not_burn_failure_budget(monkeypatch):
    """A resize/membership drain must not eat max_restarts: only
    failure- and hang-driven restarts count against the budget."""
    from dlrover_tpu.agent.training import (
        ElasticTrainingAgent,
        WorkerSpec,
    )

    agent = ElasticTrainingAgent.__new__(ElasticTrainingAgent)
    agent._spec = WorkerSpec(max_restarts=3)
    agent._node_rank = 0
    agent._restart_count = 0
    agent._budget_restarts = 0
    agent._save_ckpt_hook = None
    agent._save_thread = None
    agent._recovery_t0 = 0.0
    agent._procs = []
    agent._forkserver = None
    agent._hang_watchdog = None
    monkeypatch.setattr(agent, "_initialize_workers", lambda: None)
    monkeypatch.setattr(
        agent, "_prefetch_shm_for_restore", lambda: None
    )
    for reason in ("resize", "membership", "resize"):
        agent._restart_workers(reason=reason)
    assert agent._restart_count == 3
    assert agent._budget_restarts == 0
    agent._restart_workers(reason="failure")
    agent._restart_workers(reason="hang")
    assert agent._budget_restarts == 2
    assert agent._restart_count == 5


def test_servicer_routes_resize_request():
    from dlrover_tpu.common import messages as msg
    from dlrover_tpu.master.kv_store import KVStoreService
    from dlrover_tpu.master.rdzv_manager import (
        NetworkCheckRendezvousManager,
    )
    from dlrover_tpu.master.servicer import MasterServicer
    from dlrover_tpu.master.task_manager import TaskManager

    rdzv, jm = _two_node_world()
    servicer = MasterServicer(
        task_manager=TaskManager(),
        job_manager=jm,
        rdzv_managers={
            "elastic-training": rdzv,
            "network-check": NetworkCheckRendezvousManager(),
        },
        kv_store=KVStoreService(),
        speed_monitor=SpeedMonitor(),
    )

    class _Coord:
        def __init__(self):
            self.requests = []

        def request(self, target, reason):
            self.requests.append((target, reason))

    coord = _Coord()
    servicer.resize_coordinator = coord
    ok = servicer.report(0, "worker", msg.ResizeRequest(target=1))
    assert ok and coord.requests == [(1, "operator")]
    servicer.resize_coordinator = None
    assert not servicer.report(
        0, "worker", msg.ResizeRequest(target=1)
    )


def test_job_manager_rejoin_readmits_failed_node():
    jm = JobManager()
    jm.add_node(NodeType.WORKER, 1)
    jm.collect_heartbeat(1)
    jm.update_node_status(1, NodeType.WORKER, NodeStatus.FAILED,
                          "no-heartbeat")
    assert jm.handle_node_rejoin(1, NodeType.WORKER)
    assert jm.get_node(1).status == NodeStatus.RUNNING
    # a RUNNING node rejoining is a no-op
    assert not jm.handle_node_rejoin(1, NodeType.WORKER)


def test_job_manager_rejoin_respects_terminal_decision():
    jm = JobManager()
    jm.add_node(NodeType.WORKER, 2)
    jm.update_node_status(2, NodeType.WORKER, NodeStatus.FAILED,
                          "fatal")
    jm.record_exit_decision(2, "no-relaunch", "budget exhausted")
    assert not jm.handle_node_rejoin(2, NodeType.WORKER)
    assert jm.get_node(2).status == NodeStatus.FAILED


# ---------------------------------------------------------------------------
# engine: cross-world shm skip (the reshard comes from committed
# storage, never from a per-node snapshot of another world size)
# ---------------------------------------------------------------------------


@pytest.fixture()
def saver(tmp_path):
    from dlrover_tpu.checkpoint.saver import (
        AsyncCheckpointSaver,
        SaverConfig,
    )

    AsyncCheckpointSaver.reset()
    s = AsyncCheckpointSaver(
        SaverConfig(
            checkpoint_dir=str(tmp_path), local_shard_num=1,
            global_shard_num=1, node_rank=0,
        )
    )
    AsyncCheckpointSaver._instance = s
    yield s
    AsyncCheckpointSaver.reset()


def _sharded_state(ndev: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:ndev]), ("fsdp",))
    w = jnp.asarray(
        np.random.default_rng(5).normal(size=(32, 4)).astype(
            np.float32
        )
    )
    return {
        "w": jax.device_put(w, NamedSharding(mesh, P("fsdp"))),
    }, w


def test_engine_skips_shm_tier_across_world_change(saver, tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    state, w = _sharded_state(4)
    engine2 = CheckpointEngine(
        str(tmp_path), replicated=False, local_rank=0, global_rank=0,
        world_size=2,
    )
    assert engine2.save(6, state)
    assert engine2.save(6, state, persist=True)
    assert engine2.wait_async(timeout=30.0)
    tracker = tmp_path / "latest_checkpointed_iteration.txt"
    deadline = time.time() + 30
    while time.time() < deadline and not tracker.exists():
        time.sleep(0.1)
    assert tracker.exists()

    target_mesh = Mesh(np.array(jax.devices()[:2]), ("fsdp",))
    target = {
        "w": jax.device_put(
            jnp.zeros((32, 4)),
            NamedSharding(target_mesh, P("fsdp")),
        ),
    }
    # same world size: the shm fast path is taken
    step, restored = engine2.load_sharded(target)
    assert step == 6
    assert engine2.last_restore_phases["tier"] == "shm"
    # a NEW world size must refuse shm and reshard from storage
    engine1 = CheckpointEngine(
        str(tmp_path), replicated=False, local_rank=0, global_rank=0,
        world_size=1,
    )
    step, restored = engine1.load_sharded(target)
    assert step == 6
    assert engine1.last_restore_phases["tier"] == "storage"
    assert np.asarray(restored["w"]).tobytes() == np.asarray(
        w
    ).tobytes()
    engine1.close()
    engine2.close()


def test_saver_prefetch_touches_snapshot(saver, tmp_path,
                                         monkeypatch):
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
    from dlrover_tpu.telemetry.events import (
        EVENT_LOG_ENV,
        read_events,
    )

    evlog = str(tmp_path / "events.jsonl")
    monkeypatch.setenv(EVENT_LOG_ENV, evlog)
    engine = CheckpointEngine(
        str(tmp_path), replicated=True, local_rank=0, global_rank=0,
        world_size=1,
    )
    state = {"w": np.arange(4096, dtype=np.float32)}
    assert engine.save(3, state)
    touched = AsyncCheckpointSaver.prefetch_shm_snapshots(
        restart_count=1
    )
    assert touched >= state["w"].nbytes
    events = [
        e for e in read_events(evlog)
        if e.get("type") == "shm_prefetch"
    ]
    assert events and events[-1]["bytes"] == touched
    assert events[-1]["restart_count"] == 1
    engine.close()


# ---------------------------------------------------------------------------
# timeline: resize phase assembly + resize goodput bucket
# ---------------------------------------------------------------------------


def _resize_event_trail():
    """Synthetic log of one shrink: steady steps, node loss at t=8,
    decision at t=10, drain/round/restore/first-step trail, steps
    resume at t=13.2."""
    t0 = 1000.0
    ev = []
    for i in range(1, 16):  # steady 0.5 s steps until the loss
        ev.append({
            "type": "train_step", "ts": t0 + i * 0.5, "step": i,
            "restart_count": 0, "node_rank": 0, "source": "trainer",
        })
    ev += [
        {"type": "resize_decision", "ts": t0 + 10.0,
         "detected_ts": t0 + 8.0, "target": 1, "from_world": 2,
         "reason": "node-loss", "round": 1, "source": "master"},
        {"type": "worker_restart", "ts": t0 + 10.5, "node_rank": 0,
         "restart_count": 1, "reason": "resize", "source": "agent"},
        {"type": "rendezvous_complete", "ts": t0 + 12.0,
         "rdzv": "elastic-training", "round": 2, "nodes": [0],
         "wait_s": 0.4, "source": "master"},
        {"type": "checkpoint_restore", "ts": t0 + 12.8, "step": 14,
         "tier": "storage", "rank": 0, "total_s": 0.5,
         "source": "trainer", "node_rank": 0},
        {"type": "train_step", "ts": t0 + 13.2, "step": 15,
         "restart_count": 1, "node_rank": 0, "source": "trainer"},
        {"type": "train_step", "ts": t0 + 13.7, "step": 16,
         "restart_count": 1, "node_rank": 0, "source": "trainer"},
    ]
    return ev


def test_timeline_assembles_resize_phases_and_bucket():
    from dlrover_tpu.telemetry import timeline as flight

    tl = flight.assemble(_resize_event_trail())
    slices = tl.slices_by_cat(flight.CAUSE_RESIZE)
    phases = {s.meta["phase"]: s for s in slices}
    assert set(phases) == {
        "decide", "drain", "rendezvous", "reshard_restore",
        "first_step",
    }
    # contiguous chain from the detected outage to the first step
    assert phases["decide"].start == pytest.approx(1008.0)
    assert phases["decide"].end == pytest.approx(1010.0)
    assert phases["drain"].end == pytest.approx(1010.5)
    assert phases["rendezvous"].end == pytest.approx(1012.0)
    assert phases["reshard_restore"].end == pytest.approx(1012.8)
    assert phases["first_step"].end == pytest.approx(1013.2)

    attr = flight.attribute_goodput_loss(tl)
    assert attr["loss_s"] > 0
    # the outage books under the resize cause, not generic
    # rendezvous/restore
    assert attr["buckets"][flight.CAUSE_RESIZE] > 0
    assert attr["buckets"][flight.CAUSE_RESIZE] >= (
        0.5 * attr["loss_s"]
    )


def test_resize_invariants_on_synthetic_trail():
    """The harness invariant classes decide from events alone."""
    from dlrover_tpu.chaos import harness
    from dlrover_tpu.telemetry import timeline as flight

    ev = _resize_event_trail()
    tl = flight.assemble(ev)

    class _Run:
        job_timeline = tl
        attribution = flight.attribute_goodput_loss(tl)

    res = harness.ResizePhasesOnTimeline(min_resizes=1).check(
        ev, _Run()
    )
    assert res.ok, res.detail
    res = harness.BoundedStepLossPerRestart(interval=2).check(
        ev, _Run()
    )
    assert res.ok, res.detail
    # world trajectory: needs the 2-node round too
    ev2 = [{
        "type": "rendezvous_complete", "ts": 999.0,
        "rdzv": "elastic-training", "round": 1, "nodes": [0, 1],
        "wait_s": 0.1, "source": "master",
    }] + ev
    res = harness.WorldSizeTrajectory([2, 1]).check(ev2, _Run())
    assert res.ok, res.detail
    res = harness.WorldSizeTrajectory([2, 1, 2]).check(ev2, _Run())
    assert not res.ok


def test_bounded_step_loss_commit_aware():
    """A restart may lose more than one disk interval when the loop
    outran the commit cadence — excused iff it resumed exactly from
    the newest durable commit that existed when it booted."""
    from dlrover_tpu.chaos import harness

    def step(s, rank, count, ts):
        return {"type": "train_step", "step": s, "node_rank": rank,
                "restart_count": count, "ts": ts}

    def restart(rank, count, ts):
        return {"type": "worker_restart", "node_rank": rank,
                "restart_count": count, "ts": ts}

    def commit(s, ts):
        return {"type": "checkpoint_commit", "step": s, "ts": ts,
                "source": "agent"}

    # committed step 3, then stepped ahead to 9 before the kill:
    # resuming from 4 loses 6 > interval 3, but step 3 WAS the
    # newest durable commit at boot time — excused
    ev = ([step(s, 0, 0, float(s)) for s in range(1, 10)]
          + [commit(3, 3.5), restart(0, 1, 10.0)]
          + [step(s, 0, 1, 10.0 + s) for s in range(4, 12)])
    res = harness.BoundedStepLossPerRestart(interval=3).check(ev, None)
    assert res.ok, res.detail
    # a commit at step 6 existed before the reboot: resuming from 4
    # is a stale restore, not cadence outrun — still fails
    res = harness.BoundedStepLossPerRestart(interval=3).check(
        ev + [commit(6, 6.5)], None
    )
    assert not res.ok
    # resuming AHEAD of recorded progress always fails
    ahead = ([step(s, 0, 0, float(s)) for s in range(1, 5)]
             + [commit(3, 3.5), restart(0, 1, 5.0)]
             + [step(s, 0, 1, 5.0 + s) for s in range(6, 9)])
    res = harness.BoundedStepLossPerRestart(interval=3).check(
        ahead, None
    )
    assert not res.ok


def test_loss_trajectory_invariant():
    from dlrover_tpu.chaos import harness

    expected = [1.0, 0.9, 0.8, 0.7]

    def step(s, rank, count, loss):
        return {"type": "train_step", "step": s, "node_rank": rank,
                "restart_count": count, "loss": loss, "ts": s}

    ok_events = [
        step(1, 0, 0, 1.0), step(1, 1, 0, 1.0000001),
        step(2, 0, 0, 0.9), step(3, 0, 1, 0.8),
        step(3, 0, 0, 0.80000005),  # replay overlap agrees
    ]
    res = harness.LossTrajectoryMatches(expected).check(
        ok_events, None
    )
    assert res.ok, res.detail
    bad = ok_events + [step(4, 0, 1, 0.9)]  # diverged from control
    res = harness.LossTrajectoryMatches(expected).check(bad, None)
    assert not res.ok
    # no multi-incarnation agreement at all -> inconclusive = FAIL
    res = harness.LossTrajectoryMatches(expected).check(
        [step(1, 0, 0, 1.0)], None
    )
    assert not res.ok


def test_kill_node_action_registered():
    from dlrover_tpu.chaos.primitives import ACTIONS
    from dlrover_tpu.chaos.schedule import KNOWN_ACTIONS

    assert "kill_node" in KNOWN_ACTIONS
    assert "kill_node" in ACTIONS


def test_master_wires_resize_coordinator(tmp_path, monkeypatch):
    """JobMaster(min_node_num < node_num) arms the coordinator, the
    journal hook is attached, and ResizeRequest routes to it."""
    from dlrover_tpu.master.master import JobMaster

    monkeypatch.setenv("DLROVER_RESIZE_GRACE_S", "0")
    master = JobMaster(
        port=0, node_num=2, job_name="resize-unit",
        journal_dir=str(tmp_path / "journal"), min_node_num=1,
    )
    try:
        coord = master.resize_coordinator
        assert coord.enabled
        assert coord.journal is master.journal
        assert master.servicer.resize_coordinator is coord
        # the rdzv params carry the elastic floor
        assert master.elastic_rdzv._params.min_nodes == 1
        assert master.elastic_rdzv._params.max_nodes == 2
    finally:
        master.stop()
