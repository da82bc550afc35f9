"""Chaos e2e (ISSUE 2 acceptance): a seeded kill of the training
worker mid-step drives the REAL recovery machinery — agent monitor
loop, breakpoint shm persist, master re-rendezvous, worker respawn,
flash restore — and the invariant checkers verify recovery from the
telemetry event log alone.  The long/bulk scenarios are ``slow``; the
deterministic-seed kill scenario is the tier-1 regression net."""

import json
import subprocess
import sys

import pytest

from dlrover_tpu.chaos import harness, scenarios
from dlrover_tpu.checkpoint.saver import (
    AsyncCheckpointSaver,
    read_last_checkpoint,
)

pytestmark = pytest.mark.chaos

TOTAL_STEPS = 8
CKPT_EVERY = 2


def _run(tmp_path, scenario, **kwargs):
    # the agent runs in THIS process: a saver factory that an earlier
    # test file of the same xdist worker left behind would be kept, and
    # every save of this job would wait 300 s for an IPC server under
    # that file's socket directory (``tests/test_e2e_elastic.py`` has
    # the same cure; seen here under ``--dist loadfile`` in PR 29, when
    # new tests moved this file behind another on its worker)
    AsyncCheckpointSaver.reset()
    return harness.run_scenario(
        scenario,
        workdir=str(tmp_path / "run"),
        total_steps=TOTAL_STEPS,
        ckpt_every=CKPT_EVERY,
        monitor_interval=0.3,
        **kwargs,
    )


def test_kill_worker_midstep_recovers(tmp_path):
    """Acceptance: kill one worker mid-step with a fixed seed →
    rendezvous reconverges, training resumes from the shm checkpoint
    losing ≤ 1 checkpoint interval, final step commits, nothing is
    orphaned — all verified from telemetry events."""
    scenario = scenarios.kill_worker_midstep(seed=42)
    # narrow the window to the shortened step budget
    scenario.rules[0].step_window = [3, 6]
    report = _run(tmp_path, scenario)
    assert report.ok, report.summary()

    # exactly one seeded kill, mid-step, in the window
    assert len(report.timeline) == 1, report.timeline
    seq, point, rule, action, step = report.timeline[0]
    assert point == "trainer.step" and action == "kill"
    assert 3 <= step <= 6

    # the run really finished: last committed checkpoint on storage
    # is the final step
    final_step, shards = read_last_checkpoint(
        str(tmp_path / "run" / "ckpt")
    )
    assert final_step == TOTAL_STEPS and 0 in shards


@pytest.mark.slow
def test_kill_scenario_timeline_deterministic_across_runs(tmp_path):
    """Same scenario + same seed twice → byte-identical fault
    timelines (CI satellite).  Two full mini-cluster runs, so slow."""
    scenario = scenarios.kill_worker_midstep(seed=1234)
    scenario.rules[0].step_window = [3, 6]
    first = _run(tmp_path / "a", scenario)
    assert first.ok, first.summary()
    second = _run(
        tmp_path / "b", scenario,
        invariants=harness.default_invariants(
            TOTAL_STEPS, CKPT_EVERY, str(tmp_path / "b" / "run")
        ) + [harness.DeterministicTimeline(first.timeline)],
    )
    assert second.ok, second.summary()
    assert second.timeline == first.timeline


@pytest.mark.slow
def test_rpc_partition_survived_by_backoff(tmp_path):
    """A 2 s full RPC partition early in the run: the hardened
    reconnect path rides it out; the job completes with no restart
    and no steps lost."""
    report = _run(
        tmp_path,
        scenarios.rpc_partition(seed=7),
        invariants=[
            harness.TrainingCompleted(total_steps=TOTAL_STEPS),
            harness.NoOrphanProcesses(
                marker=str(tmp_path / "run")
            ),
        ],
    )
    assert report.rc == 0, report.summary()
    assert all(r.ok for r in report.invariants), report.summary()
    # the partition really dropped frames
    assert any(t[3] == "drop" for t in report.timeline), (
        report.timeline
    )


@pytest.mark.slow
def test_storage_brownout_degrades_and_recovers(tmp_path):
    """First persist attempts fail with injected IO errors: the saver
    reports the failure through telemetry (no silent loss) and a later
    interval still commits; the job completes."""
    report = _run(
        tmp_path,
        scenarios.storage_brownout(seed=11),
        invariants=[
            harness.TrainingCompleted(total_steps=TOTAL_STEPS),
            harness.NoOrphanProcesses(
                marker=str(tmp_path / "run")
            ),
        ],
    )
    assert report.rc == 0, report.summary()
    assert all(r.ok for r in report.invariants), report.summary()
    injected = [t for t in report.timeline if t[3] == "io_error"]
    assert injected, report.timeline


def test_shm_corruption_falls_back_to_storage_tier(tmp_path):
    """Satellite acceptance (ISSUE 3): tear the shm snapshot, kill
    the worker → the respawned trainer refuses the torn shm tier and
    restores from the last committed DISK step; the RestoredFromTier
    invariant decides from the checkpoint_restore event's tier field
    alone.  disk_every/step-loss bound come from the scenario's
    RUN_OPTIONS (harness default selection)."""
    report = _run(
        tmp_path, scenarios.shm_corrupt_storage_fallback(seed=23)
    )
    assert report.ok, report.summary()
    # both seeded faults executed, in order: tear then kill
    actions = [t[3] for t in report.timeline]
    assert actions == ["corrupt_shm", "kill"], report.timeline
    # the tier fact, straight from telemetry: first post-fault
    # restore is storage (shm was refused), never shm
    restores = [
        e for e in report.events
        if e.get("type") == "checkpoint_restore"
    ]
    assert restores and restores[0]["tier"] == "storage", restores
    final_step, shards = read_last_checkpoint(
        str(tmp_path / "run" / "ckpt")
    )
    assert final_step == TOTAL_STEPS and 0 in shards


def test_kill_between_accept_and_commit_falls_to_storage(tmp_path):
    """A MEMORY save returned True, the loop went on with no
    ``wait()``, and the worker is SIGKILLed on its writer thread
    inside that save's copy: the segment says ``writing`` (step 4's
    snapshot is gone under half of step 6), the respawned trainer
    refuses it and restores the committed DISK step 4 from storage;
    an accepted save costs at most ``disk_every`` steps."""
    report = _run(
        tmp_path, scenarios.kill_between_accept_and_commit(seed=29)
    )
    assert report.ok, report.summary()
    (fault,) = report.timeline
    _seq, point, _rule, action, step = fault
    assert (point, action, step) == ("ckpt.shm_write", "kill", 6)
    # the train script really has no commit-wait behind a MEMORY save
    assert scenarios.NO_COMMIT_WAIT_TRAIN_SCRIPT.count(
        "ckpt.wait()"
    ) == scenarios.CHAOS_TRAIN_SCRIPT.count("ckpt.wait()") - 1
    restores = [
        e for e in report.events
        if e.get("type") == "checkpoint_restore"
    ]
    assert restores and restores[0]["tier"] == "storage", restores
    assert restores[0]["step"] == 4, restores
    # step 6 was accepted and never committed in the first incarnation
    commits = [
        e["step"] for e in report.events
        if e.get("type") == "checkpoint_shm_save"
        and e["ts"] < restores[0]["ts"]
    ]
    assert commits == [2, 4], commits
    final_step, shards = read_last_checkpoint(
        str(tmp_path / "run" / "ckpt")
    )
    assert final_step == TOTAL_STEPS and 0 in shards


def test_master_kill_restart_midround(tmp_path):
    """ISSUE 4 acceptance (tier-1): SIGKILL the MASTER on its 3rd
    shard dispatch mid-rendezvous-round.  tpurun's watchdog respawns
    it on the same port; the new incarnation replays the state
    journal, re-enters round 1, re-queues only the un-acked shard,
    parked clients session-resync — and training completes with NO
    healthy-worker restart, no duplicate shard completions, none
    lost.  All decided from telemetry events."""
    report = _run(
        tmp_path, scenarios.master_kill_restart_midround(seed=31)
    )
    assert report.ok, report.summary()
    # exactly one seeded master kill, at a shard dispatch
    assert len(report.timeline) == 1, report.timeline
    _seq, point, _rule, action, _step = report.timeline[0]
    assert point == "master.task_dispatch" and action == "kill"
    # the recovery trail, straight from the events: respawn observed,
    # journal replayed exactly once, the in-flight lease re-queued
    respawns = [
        e for e in report.events if e.get("type") == "master_respawn"
    ]
    recoveries = [
        e for e in report.events
        if e.get("type") == "master_recovered"
    ]
    assert len(respawns) == 1 and len(recoveries) == 1
    assert recoveries[0]["requeued"] >= 1
    assert recoveries[0]["rdzv_round"] == 1
    # the final state on disk is the full run
    final_step, shards = read_last_checkpoint(
        str(tmp_path / "run" / "ckpt")
    )
    assert final_step == TOTAL_STEPS and 0 in shards

    # -- flight recorder acceptance (ISSUE 5): the harness hands the
    # assembled timeline + goodput-loss attribution to every run
    from dlrover_tpu.telemetry import timeline as flight

    jt = report.job_timeline
    assert jt is not None and jt.master_incarnations == 2
    chrome = json.loads(
        json.dumps(flight.to_chrome_trace(jt, report.attribution))
    )
    cats = {
        e.get("cat") for e in chrome["traceEvents"] if "cat" in e
    }
    # rendezvous + recovery slices present for this run's
    # incarnations (no worker restart here, so no restore tier)
    assert flight.CAUSE_RENDEZVOUS in cats
    assert flight.CAUSE_MASTER_RECOVERY in cats
    attr = report.attribution
    assert attr["loss_s"] > 0
    # buckets (unattributed included) account for the full measured
    # loss (>= 90% required by acceptance; exact by construction)
    assert sum(attr["buckets"].values()) >= 0.9 * attr["loss_s"]
    # the NON-tautological half: NAMED causes explain the outage,
    # and the dominant cause of a master kill IS master recovery
    named = sum(
        v for k, v in attr["buckets"].items() if k != "unattributed"
    )
    assert named >= 0.5 * attr["loss_s"], attr["buckets"]
    assert attr["buckets"]["master_recovery"] >= 0.5 * attr["loss_s"]
    # the CLI emits the same valid Chrome trace from the raw log
    out = subprocess.run(  # noqa: S603
        [sys.executable, "-m", "dlrover_tpu.telemetry.timeline",
         report.event_log, "--chrome", "-"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["traceEvents"]
    assert doc["otherData"]["master_incarnations"] == 2


def test_trainer_hang_detected_and_culprit_restarted(tmp_path):
    """ISSUE 7 acceptance (tier-1): freeze the trainer mid-step with
    the stall primitive.  The agent watchdog must capture hang flight
    data (faulthandler stacks + /proc worker tree) and ship it; the
    master's inference chain must reach a *hung* verdict carrying the
    evidence and a measured stall; ONLY the culprit node is restarted
    (via the heartbeat-action relaunch path), the restored
    incarnation finishes the budget, and the goodput attribution
    books the stall under the ``hang`` bucket with real durations."""
    report = _run(tmp_path, scenarios.trainer_hang_detected(seed=47))
    assert report.ok, report.summary()

    # exactly one seeded stall, at the chosen step
    assert len(report.timeline) == 1, report.timeline
    _seq, point, _rule, action, step = report.timeline[0]
    assert point == "trainer.step" and action == "stall"
    assert step == 5

    # flight data: the watchdog captured stacks + worker /proc state
    evidence = [
        e for e in report.events if e.get("type") == "hang_evidence"
    ]
    assert evidence, "no hang_evidence events"
    assert any("pid" in (e.get("workers") or "") for e in evidence)

    # the verdict carries the measured stall and the excerpt
    verdicts = [
        e for e in report.events
        if e.get("type") == "diagnosis_verdict" and e.get("hung")
    ]
    assert verdicts, "no hung verdict"
    assert verdicts[0]["stall_s"] > 0
    assert verdicts[0]["evidence"]
    assert verdicts[0]["culprit_node"] >= 0

    # attribution: full coverage, hang booked with real durations
    attr = report.attribution
    assert attr["loss_s"] > 0
    assert sum(attr["buckets"].values()) >= 0.9 * attr["loss_s"]
    assert attr["buckets"]["hang"] > 0, attr["buckets"]

    # the run really finished
    final_step, shards = read_last_checkpoint(
        str(tmp_path / "run" / "ckpt")
    )
    assert final_step == TOTAL_STEPS and 0 in shards


def test_elastic_resize_churn(tmp_path):
    """ISSUE 8 acceptance (tier-1): kill one of two agents mid-run
    (whole supervision tree — a vanished node, no failure report).
    The master's resize coordinator must detect the silence, decide
    world 2 -> 1, drain the survivor over the heartbeat-action
    channel, and the re-formed world must restore the checkpoint
    RESHARDED from the committed storage tier (node 1's shards
    redistributed onto node 0's devices) and keep stepping.  When the
    harness respawns the lost agent (a replacement host), the world
    grows back to 2 the same way.  Verified from telemetry alone:
    completed-world sizes 2 -> 1 -> 2, every reported loss equal to
    the uninterrupted-control trajectory, per-restart step loss
    bounded, dataset shards exactly-once, final step committed,
    resize phase breakdown on the assembled timeline, goodput loss
    booked under the resize cause."""
    report = harness.run_elastic_resize_scenario(
        scenarios.elastic_resize_churn(seed=53),
        workdir=str(tmp_path / "run"),
        nnodes=2,
    )
    assert report.ok, report.summary()
    # the node loss really happened, on rank 1, exactly once
    kills = [t for t in report.timeline if t[3] == "kill_node"]
    assert len(kills) == 1, report.timeline
    # both resize directions were decided by the coordinator
    decisions = [
        e for e in report.events
        if e.get("type") == "resize_decision"
    ]
    targets = [e["target"] for e in decisions]
    assert 1 in targets and 2 in targets, decisions
    # the drain rode the heartbeat-action channel: resize-reason
    # restarts on the surviving node
    resize_restarts = [
        e for e in report.events
        if e.get("type") == "worker_restart"
        and e.get("reason") == "resize"
    ]
    assert resize_restarts, "no resize-driven worker restart"
    # cross-world restores resharded from storage, never from a
    # stale per-node shm snapshot
    restores = [
        e for e in report.events
        if e.get("type") == "checkpoint_restore"
    ]
    assert restores and all(
        e.get("tier") == "storage" for e in restores
    ), restores


def test_sparse_kill_restore(tmp_path):
    """ISSUE 9 acceptance (tier-1): SIGKILL a DeepFM job whose
    embedding + GroupAdam slot tables live in host KvVariable tables
    with an ACTIVE spill tier.  The sparse state must ride the flash
    checkpoint: the restored incarnation's loss trajectory equals the
    uninterrupted control (a lost row/freq/moment forks it at the
    first replayed step) and the kv_checkpoint digests prove every
    row, frequency count and optimizer slot bit-identical through
    the cycle — all decided from telemetry events alone."""
    report = harness.run_scenario(
        scenarios.sparse_kill_restore(seed=61),
        workdir=str(tmp_path / "run"),
        monitor_interval=0.3,
    )
    assert report.ok, report.summary()
    # exactly one seeded kill, mid-step, in the window
    assert len(report.timeline) == 1, report.timeline
    _seq, point, _rule, action, step = report.timeline[0]
    assert point == "trainer.step" and action == "kill"
    assert 5 <= step <= 7
    # the spill tier was genuinely active at export time
    exports = [
        e for e in report.events
        if e.get("type") == "kv_checkpoint"
        and e.get("stage") == "export"
    ]
    assert exports and any(e["spilled_rows"] > 0 for e in exports)
    # same-world restore: own shard verbatim, never a reshard
    restores = [
        e for e in report.events
        if e.get("type") == "kv_checkpoint"
        and e.get("stage") == "restore"
    ]
    assert restores and all(
        not e.get("resharded") for e in restores
    ), restores
    # the run really finished
    steps = scenarios.RUN_OPTIONS["sparse-kill-restore"][
        "total_steps"
    ]
    final_step, shards = read_last_checkpoint(
        str(tmp_path / "run" / "ckpt")
    )
    assert final_step == steps and 0 in shards


def test_sparse_spill_io_error_graceful(tmp_path):
    """ISSUE 9 acceptance (tier-1): the spill tier's disk dies DURING
    a checkpoint export.  Graceful degradation, not corruption: the
    stranded cold rows drop out of that export (lost_rows stamped),
    the production write-failure breaker trips on the next spill pass
    (spill_disabled on a later export), the DRAM-resident rows still
    commit, and the post-kill restore round-trips the post-fault
    export bit-exact (KvStateRoundTrip invariant)."""
    report = harness.run_scenario(
        scenarios.sparse_spill_io_error(seed=67),
        workdir=str(tmp_path / "run"),
        monitor_interval=0.3,
    )
    assert report.ok, report.summary()
    actions = sorted(t[3] for t in report.timeline)
    assert actions == ["io_error", "kill"], report.timeline
    exports = [
        e for e in report.events
        if e.get("type") == "kv_checkpoint"
        and e.get("stage") == "export"
    ]
    assert any(e.get("lost_rows", 0) > 0 for e in exports), exports
    assert any(e.get("spill_disabled") for e in exports), exports


def test_sparse_streaming_reshard_kill(tmp_path):
    """ISSUE 14 acceptance (tier-1): SIGKILL a worker MID-STREAMING-
    RESHARD.  The harness pre-seeds a committed world-2 sparse
    checkpoint; the world-1 job's first restore streams the
    cross-world reshard in bounded windows and dies on the 3rd
    ``kv.reshard_chunk``.  Committed storage is untouched by the
    partial reshard, so the replacement replays it from the same
    shards: the digest sums on its resharded restore equal the
    seeder's per-shard export sums with imported rows == the distinct
    union — exactly-once, no chunk double-imported — and the job
    still trains to completion."""
    report = harness.run_scenario(
        scenarios.sparse_streaming_reshard_kill(seed=79),
        workdir=str(tmp_path / "run"),
        monitor_interval=0.3,
    )
    assert report.ok, report.summary()
    # exactly one seeded kill, ON the reshard-chunk hook
    assert len(report.timeline) == 1, report.timeline
    _seq, point, _rule, action, _step = report.timeline[0]
    assert point == "kv.reshard_chunk" and action == "kill"
    # both incarnations streamed: the first emitted partial chunk
    # events before dying, the second a full set + the restore event
    chunk_events = [
        e for e in report.events
        if e.get("type") == "kv_reshard_chunk"
    ]
    assert chunk_events, "no kv_reshard_chunk events recorded"
    restores = [
        e for e in report.events
        if e.get("type") == "kv_checkpoint"
        and e.get("stage") == "restore" and e.get("resharded")
    ]
    assert restores and restores[-1].get("streamed"), restores
    assert restores[-1].get("chunks", 0) > 1
    # the incomplete first attempt emitted FEWER chunk events than
    # the completed replay's chunk count (it died at chunk 3)
    assert len(chunk_events) > restores[-1]["chunks"]


@pytest.mark.slow
def test_sparse_resize_churn(tmp_path):
    """ISSUE 9 acceptance (slow): the genuinely novel combination —
    a 2-node sparse job whose hash-partitioned KvVariable embedding
    survives a world 2 -> 1 -> 2 churn.  Each world change must
    RESHARD the hash table from committed storage (all old ranks' kv
    shards read, rows repartitioned by key hash, owned subsets
    imported) with exactly-once row accounting, the shm tier refused
    across world sizes, and the dense loss trajectory still equal to
    the uninterrupted control."""
    report = harness.run_elastic_resize_scenario(
        scenarios.sparse_resize_churn(seed=71),
        workdir=str(tmp_path / "run"),
        nnodes=2,
    )
    assert report.ok, report.summary()
    kills = [t for t in report.timeline if t[3] == "kill_node"]
    assert len(kills) == 1, report.timeline
    # both directions resharded the kv state (2->1 and 1->2), and
    # every cross-world restore came from committed storage
    reshards = [
        e for e in report.events
        if e.get("type") == "kv_checkpoint"
        and e.get("stage") == "restore" and e.get("resharded")
    ]
    worlds = {e["world_size"] for e in reshards}
    assert worlds == {1, 2}, reshards
    assert all(e.get("tier") == "storage" for e in reshards)


@pytest.mark.slow
def test_multinode_hang_culprit_restart(tmp_path):
    """ROADMAP carried-forward satellite: the culprit-selection
    evidence scoring exercised MULTINODE — node 1's trainer freezes
    while node 0 keeps stepping, so the global-silence rule cannot
    convict; the verdict must come from per-node flight data and
    restart ONLY node 1."""
    steps = scenarios.RUN_OPTIONS["multinode-hang-culprit"][
        "total_steps"
    ]
    report = harness.run_scenario_multinode(
        scenarios.multinode_hang_culprit(seed=59),
        workdir=str(tmp_path / "run"),
        nnodes=2,
        invariants=[
            harness.HangDiagnosed(within_s=45.0),
            harness.OnlyCulpritRestarted(culprit_rank=1),
            harness.NodeCompletedSteps(0, steps),
            harness.NodeCompletedSteps(1, steps),
            harness.NoOrphanProcesses(
                marker=str(tmp_path / "run")
            ),
        ],
    )
    assert report.rc == 0, report.summary()
    assert all(r.ok for r in report.invariants), report.summary()
    stalls = [t for t in report.timeline if t[3] == "stall"]
    assert stalls, report.timeline
    # the verdict named node 1, from evidence, not silence
    verdicts = [
        e for e in report.events
        if e.get("type") == "diagnosis_verdict" and e.get("hung")
    ]
    assert verdicts and verdicts[0]["culprit_node"] == 1, verdicts


@pytest.mark.slow
def test_multinode_partition_subset_rejoins(tmp_path):
    """ISSUE 4 satellite: drop RPC for ONE node of a two-agent job
    (env_equals-targeted partition).  The un-partitioned agent keeps
    training (never restarted), the partitioned one rides out the
    window on the reconnect path and rejoins without a full-job
    restart; both complete their step budget."""
    report = harness.run_scenario_multinode(
        scenarios.multinode_rpc_partition(seed=29),
        workdir=str(tmp_path / "run"),
        nnodes=2,
        total_steps=TOTAL_STEPS,
        faulted_rank=1,
    )
    assert report.rc == 0, report.summary()
    assert all(r.ok for r in report.invariants), report.summary()
    # the partition really dropped frames, on rank 1 only
    drops = [t for t in report.timeline if t[3] == "drop"]
    assert drops, report.timeline


@pytest.mark.slow
@pytest.mark.parametrize(
    "factory", ["warm_template_import_kill",
                "warm_template_midspawn_kill"],
)
def test_warm_restart_template_chaos(tmp_path, factory):
    """ISSUE 4 satellite: kill the forkserver template during its
    preload imports / mid-spawn — the agent must detect the dead
    template immediately, fall back to cold spawns
    (warm_fork_fallback event), finish the job, and leave no orphan
    processes (template children included)."""
    report = harness.run_scenario(
        scenarios.SCENARIOS[factory](),
        workdir=str(tmp_path / "run"),
        total_steps=6,
        ckpt_every=CKPT_EVERY,
        monitor_interval=0.3,
    )
    assert report.ok, report.summary()
    assert any(
        t[1].startswith("forkserver.") and t[3] == "kill"
        for t in report.timeline
    ), report.timeline


@pytest.mark.slow
def test_goodput_under_scheduled_churn(tmp_path):
    """ISSUE 4 satellite: goodput under churn as a seeded
    scenario — one SIGKILL per incarnation at fixed absolute steps,
    warm restarts + per-step flash snapshots keeping recovery short.
    The master's own accounting (dlrover_goodput_ratio, stamped on
    master_exit) must stay >= 0.90."""
    report = harness.run_scenario(
        scenarios.goodput_under_scheduled_churn(seed=43),
        workdir=str(tmp_path / "run"),
        max_restarts=3,
        monitor_interval=0.3,
    )
    assert report.ok, report.summary()
    kills = [t for t in report.timeline if t[3] == "kill"]
    assert len(kills) == 2, report.timeline
    exits = [
        e for e in report.events if e.get("type") == "master_exit"
    ]
    assert exits and float(exits[-1]["goodput"]) >= 0.90, exits


@pytest.mark.slow
def test_ckpt_brownout_during_preemption(tmp_path):
    """ROADMAP scenario: storage browns out exactly while the
    preemption notice's breakpoint save is persisting — the two grace
    paths compete for the persist executor.  The job rides it out:
    the failed persist is reported through telemetry, later saves
    commit, training completes, nothing orphans.  Wall-clock
    triggered, so assertions are bounded (notice fired, ≥1 injected
    write failure, persist failure REPORTED) rather than byte-stable.
    """
    report = _run(
        tmp_path, scenarios.ckpt_brownout_during_preemption(seed=19)
    )
    assert report.rc == 0, report.summary()
    assert all(r.ok for r in report.invariants), report.summary()
    actions = [t[3] for t in report.timeline]
    assert "preempt" in actions, report.timeline
    assert "io_error" in actions, report.timeline
    # no silent loss: the browned-out persist surfaced as a failed
    # checkpoint_persist event
    failed = [
        e for e in report.events
        if e.get("type") == "checkpoint_persist" and not e.get("ok")
    ]
    assert failed, "injected persist failure left no telemetry trail"
    # and a later persist still committed the final step
    commits = [
        e.get("step") for e in report.events
        if e.get("type") == "checkpoint_commit"
    ]
    assert TOTAL_STEPS in commits, commits


def test_warm_recovery_cache_hit(tmp_path):
    """ISSUE 10 acceptance (tier-1): a SIGKILLed worker under warm
    restarts + the job-keyed persistent compile cache recovers with a
    PROVEN cache hit — the replacement's first post-restore step adds
    no new cache entries over the warm dir (``compile_cache`` event),
    its measured ``retrace_s`` stays under the ceiling, and the whole
    death->first-step budget lands as ``recovery_phase`` slices on the
    assembled timeline.  Every assertion reads telemetry alone."""
    report = harness.run_scenario(
        scenarios.warm_recovery_cache_hit(seed=73),
        workdir=str(tmp_path / "run"),
        max_restarts=2,
    )
    assert report.ok, report.summary()
    # the per-cycle budget is also derivable through the shared
    # ingestion helper (what the incident report uses)
    from dlrover_tpu.telemetry.timeline import recovery_budgets

    budgets = {
        count: phases
        for (_rank, count), phases in recovery_budgets(
            report.events
        ).items()
        if count > 0
    }
    assert budgets, "no recovery budget for the respawned incarnation"
    phases = budgets[min(budgets)]
    assert phases.get("compile_cache_hit") is True
    for phase in ("restore", "retrace", "first_step"):
        assert phase in phases, phases
    # and the incident report prints the budget line
    from dlrover_tpu.telemetry import timeline as flight

    text = flight.to_report(report.job_timeline)
    assert "recovery budgets" in text
    assert "cache=HIT" in text


@pytest.mark.slow
def test_master_respawn_other_host(tmp_path):
    """ISSUE 10 (slow): the master is SIGKILLed mid-dispatch and its
    respawn gets a FRESH, EMPTY journal dir — a replacement host's
    view — so recovery must be seeded from the storage-tier journal
    mirror (async group commit).  Exactly-once sharding still holds:
    the session-resync ack-reconciliation closes any lease whose ack
    the mirror's group-commit lag dropped."""
    report = harness.run_scenario(
        scenarios.master_respawn_other_host(seed=79),
        workdir=str(tmp_path / "run"),
        max_restarts=2,
    )
    assert report.ok, report.summary()
    recovered = [
        e for e in report.events
        if e.get("type") == "master_recovered"
    ]
    assert recovered and recovered[0].get("from_mirror") is True
    # the mirror's group commits left their witness trail
    flushes = [
        e for e in report.events
        if e.get("type") == "journal_mirror_flush"
    ]
    assert flushes
    # every flush's lag stayed within a few group-commit windows
    # (scheduling jitter rides on top of the 0.05s interval)
    assert all(e.get("lag_s", 0) < 5.0 for e in flushes), flushes


def test_serving_replica_kill_midingest(tmp_path):
    """ISSUE 13 acceptance (tier-1): the serving replica is SIGKILLed
    INSIDE a generation apply (swap lock held, tables half-applied).
    The respawned replica re-bases from the newest committed
    generation and converges on the trainer's final publish; the
    digest chain on serving_ingest vs serving_publish events proves
    the replica never served a torn or uncommitted generation — all
    decided from the event log alone."""
    report = harness.run_serving_scenario(
        scenarios.serving_replica_kill_midingest(seed=83),
        workdir=str(tmp_path / "run"),
        monitor_interval=0.3,
    )
    assert report.ok, report.summary()
    # exactly one seeded kill, inside the replica's ingest hook
    assert len(report.timeline) == 1, report.timeline
    _seq, point, _rule, action, _step = report.timeline[0]
    assert point == "serving.ingest" and action == "kill"
    # the generation being applied at the kill emitted NO ingest
    # event from the first replica life (the event is post-apply):
    # every recorded ingest digest-matches its publish, and the
    # respawned replica's trail starts with a base
    ingests = [
        e for e in report.events
        if e.get("type") == "serving_ingest"
    ]
    respawned = [e for e in ingests if e.get("respawned")]
    assert respawned and respawned[0]["kind"] == "base"
    # lookup traffic ran, and freshness was measured
    lookups = [
        e for e in report.events
        if e.get("type") == "serving_lookup_stats"
    ]
    assert lookups and all(e["p99_ms"] > 0 for e in lookups)
    fresh = [
        e for e in report.events
        if e.get("type") == "serving_freshness"
    ]
    assert fresh, "no serving_freshness events"


def test_serving_trainer_kill_midpublish(tmp_path):
    """ISSUE 13 acceptance (tier-1): the trainer is SIGKILLed between
    a generation's blobs/manifest and its DONE marker.  The
    half-published generation never commits (the replica keeps
    serving the previous one), the respawned trainer restores from
    the flash checkpoint and re-bases at a fresh number, and every
    committed generation carries exactly one serving_publish event —
    publish exactly-once across the replacement, with the restored
    loss trajectory still equal to the uninterrupted control."""
    report = harness.run_serving_scenario(
        scenarios.serving_trainer_kill_midpublish(seed=89),
        workdir=str(tmp_path / "run"),
        monitor_interval=0.3,
    )
    assert report.ok, report.summary()
    assert len(report.timeline) == 1, report.timeline
    _seq, point, _rule, action, _step = report.timeline[0]
    assert point == "serving.publish" and action == "kill"
    # the replacement's first publish after the fault is a BASE (a
    # fresh publisher cannot know what its predecessor half-wrote)
    fault_ts = min(
        e["ts"] for e in report.events
        if e.get("type") == "chaos_inject"
    )
    post = [
        e for e in report.events
        if e.get("type") == "serving_publish" and e["ts"] >= fault_ts
    ]
    assert post and post[0]["kind"] == "base", post[:2]
    # serving slices landed on the assembled timeline (the flight
    # recorder's "serving" track)
    from dlrover_tpu.telemetry.timeline import CAT_SERVING

    assert report.job_timeline is not None
    serving_slices = report.job_timeline.slices_by_cat(CAT_SERVING)
    assert serving_slices, "no serving slices on the timeline"


def test_serving_fleet_replica_kill(tmp_path):
    """ISSUE 17 acceptance (tier-1): under live routed traffic
    against a 3-replica pool, SIGKILL replica 0 mid-ingest AND the
    lookup router mid-stream.  The router sheds the dead member
    within the heartbeat window and keeps answering from survivors —
    zero failed and zero stale lookups on the serving_route windows,
    zero client-visible failures in the load aggregate — the
    respawned router replays its journaled membership to the
    identical live routing table without restarting healthy
    replicas, and the freshness floor never regresses."""
    report = harness.run_serving_fleet_scenario(
        scenarios.serving_fleet_replica_kill(seed=97),
        workdir=str(tmp_path / "run"),
    )
    assert report.ok, report.summary()
    # both seeded kills fired: the replica's ingest hook and the
    # router's route hook
    points = {t[1] for t in report.timeline}
    assert points == {"serving.ingest", "serving.route"}, (
        report.timeline
    )
    # routed windows exist on both sides of the router kill (the
    # respawn resumed emitting), and the fleet's stats windows landed
    # on the assembled timeline's "serving fleet" track
    router_kill_ts = min(
        e["ts"] for e in report.events
        if e.get("type") == "chaos_inject"
        and e.get("point") == "serving.route"
    )
    windows = [
        e for e in report.events if e.get("type") == "serving_route"
    ]
    assert any(e["ts"] < router_kill_ts for e in windows)
    assert any(e["ts"] > router_kill_ts for e in windows)
    assert report.job_timeline is not None
    fleet_slices = [
        s for s in report.job_timeline.slices
        if s.track == "serving fleet"
    ]
    assert fleet_slices, "no serving-fleet slices on the timeline"
    # the load harness's client-side aggregate is in the event log
    # (the zero-client-visible-failure half of the verdict)
    loads = [
        e for e in report.events
        if e.get("type") == "serving_lookup_stats"
        and e.get("replica") == "load"
    ]
    assert loads and loads[0]["failed"] == 0, loads


def test_rl_rollout_worker_kill(tmp_path):
    """ISSUE 16 acceptance (tier-1): SIGKILL the PPO rollout worker
    mid-iteration — on lease 2's ``rl.rollout`` hook, after the
    experience batch is generated but before it is buffered, flash-
    checkpointed or acked.  The master requeues the lease off the
    dead worker; the replacement restores the four-role state +
    partial buffer + cursor from the post-lease-1 flash snapshot,
    replays the interrupted iteration's PPO steps, regenerates the
    lost lease bit-identically, and finishes the budget with the
    loss trajectory EQUAL to the uninterrupted control.  Exactly-once
    lease accounting and recovery-loss attribution are decided from
    the event log alone (invariants in the harness)."""
    report = harness.run_scenario(
        scenarios.rl_rollout_worker_kill(seed=97),
        workdir=str(tmp_path / "run"),
        monitor_interval=0.3,
    )
    assert report.ok, report.summary()
    # exactly one seeded kill, on the rollout hook of lease 2
    assert len(report.timeline) == 1, report.timeline
    _seq, point, _rule, action, step = report.timeline[0]
    assert point == "rl.rollout" and action == "kill"
    assert step == 2
    # the RL plane reported its iteration anatomy, across BOTH
    # incarnations (the replay re-trains the restored buffer)
    iters = [
        e for e in report.events if e.get("type") == "rl_iteration"
    ]
    assert iters, "no rl_iteration events"
    assert {e["restart_count"] for e in iters} == {0, 1}, iters
    assert all(
        e["rollout_s"] >= 0 and e["train_s"] > 0 for e in iters
    ), iters
    # RL phase slices landed on the assembled timeline
    from dlrover_tpu.telemetry.timeline import CAT_RL

    assert report.job_timeline is not None
    rl_slices = report.job_timeline.slices_by_cat(CAT_RL)
    assert rl_slices, "no rl phase slices on the timeline"
    # the run really finished: the final PPO update committed durably
    steps = scenarios.RUN_OPTIONS["rl-rollout-worker-kill"][
        "total_steps"
    ]
    final_step, shards = read_last_checkpoint(
        str(tmp_path / "run" / "ckpt")
    )
    assert final_step == steps and 0 in shards
