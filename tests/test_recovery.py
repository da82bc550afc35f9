"""Invisible recovery (ISSUE 10) units: the job-keyed persistent
compile cache, the trainer-side RecoveryProfiler (measured
death->first-step budget + cache-hit witness), the timeline's
recovery-breakdown slices, and the agent-side overlap knobs."""

import os
import time

import pytest

from dlrover_tpu.common import compile_cache as cc
from dlrover_tpu.telemetry import timeline as flight
from dlrover_tpu.telemetry.events import EVENT_LOG_ENV, read_events
from dlrover_tpu.trainer import recovery as rec


@pytest.fixture()
def event_log(tmp_path, monkeypatch):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv(EVENT_LOG_ENV, path)
    return path


# -- compile cache ------------------------------------------------------


@pytest.mark.parametrize("other", [
    {},
    {"DLROVER_COMPILE_CACHE_DIR": "/operator"},
    {"DLROVER_JOB_NAME": "some-job", "TMPDIR": "/elsewhere"},
])
def test_job_cache_dir_is_the_jax_env_when_set(monkeypatch, other):
    """``JAX_COMPILATION_CACHE_DIR`` wins whatever else is set — no
    code of the job may set another directory."""
    for key, val in other.items():
        monkeypatch.setenv(key, val)
    monkeypatch.setenv(cc.CACHE_DIR_ENV, "/ambient")
    assert cc.job_cache_dir() == "/ambient"
    assert cc.cache_env()[cc.CACHE_DIR_ENV] == "/ambient"
    from dlrover_tpu.common.aot_cache import aot_cache_dir

    assert aot_cache_dir() == "/ambient/aot"


def test_job_cache_dir_default_is_fixed_in_checkout(
    tmp_path, monkeypatch
):
    """Without the env var: ONE directory inside the checkout, the
    same under two socket dirs, job names and temp dirs (a cache
    directory that moves never hits)."""
    import dlrover_tpu

    monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
    monkeypatch.setenv("DLROVER_SHARED_DIR", str(tmp_path / "a"))
    a = cc.job_cache_dir()
    monkeypatch.setenv("DLROVER_SHARED_DIR", str(tmp_path / "b"))
    monkeypatch.setenv("DLROVER_JOB_NAME", "another-job")
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    b = cc.job_cache_dir()
    checkout = os.path.dirname(os.path.dirname(dlrover_tpu.__file__))
    assert a == b == os.path.join(checkout, ".jax_cache")


def test_cache_env_and_entry_count(tmp_path, monkeypatch):
    cache = tmp_path / "cache"
    monkeypatch.setenv(cc.CACHE_DIR_ENV, str(cache))
    env = cc.cache_env()
    assert env[cc.CACHE_DIR_ENV] == str(cache)
    assert env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] == "0"
    # entry counting: only *-cache files are executables; the -atime
    # siblings are hit markers
    assert cc.cache_entries(str(cache)) == 0
    cache.mkdir()
    (cache / "jit_f-abc-cache").write_bytes(b"x")
    (cache / "jit_f-abc-atime").write_bytes(b"")
    (cache / "jit_g-def-cache").write_bytes(b"y")
    assert cc.cache_entries(str(cache)) == 2


# -- recovery profiler --------------------------------------------------


def test_profiler_phases_and_events(tmp_path, monkeypatch, event_log):
    monkeypatch.setenv(
        cc.CACHE_DIR_ENV, str(tmp_path / "cache")
    )
    monkeypatch.setenv("DLROVER_RESTART_COUNT", "2")
    monkeypatch.setenv("DLROVER_NODE_RANK", "0")
    # T0 slightly in the past: the spawn phase is proc_start - t0
    monkeypatch.setenv(
        rec.RECOVERY_T0_ENV, f"{time.time() - 5.0:.6f}"
    )
    prof = rec.RecoveryProfiler()
    assert prof.restart_count == 2
    assert "import" in prof.phases
    # spawn only books when the kernel start time resolves; on /proc
    # platforms it must be ~the 5s offset
    if "spawn" in prof.phases:
        assert 0.0 <= prof.phases["spawn"] <= 60.0
    prof.record_restore({"total_s": 0.25, "tier": "shm"})
    assert prof.phases["restore"] == 0.25
    with prof.phase("custom"):
        time.sleep(0.01)
    assert prof.phases["custom"] >= 0.01
    prof.record_first_step()
    assert prof.phases["first_step"] >= 0.0
    types = [e["type"] for e in read_events(event_log)]
    assert types.count("recovery_phase") >= 4


def test_retrace_hit_vs_miss_witness(tmp_path, monkeypatch,
                                     event_log):
    """The cache-hit rule: no NEW *-cache entries across the bracket
    over a WARM dir = HIT; new entries (or an empty dir) = MISS."""
    cache = tmp_path / "cache"
    monkeypatch.setenv(cc.CACHE_DIR_ENV, str(cache))
    monkeypatch.setenv("DLROVER_RESTART_COUNT", "1")
    prof = rec.RecoveryProfiler()

    # cold dir: whatever happens, not a hit
    with prof.measured_retrace():
        (cache / "jit_f-1-cache").write_bytes(b"x")  # a compile
    assert prof.cache_hit is False

    # warm dir, no new entries: hit
    prof2 = rec.RecoveryProfiler()
    with prof2.measured_retrace():
        pass
    assert prof2.cache_hit is True

    events = [
        e for e in read_events(event_log)
        if e["type"] == "compile_cache"
    ]
    assert [e["hit"] for e in events] == [False, True]
    assert all("retrace_s" in e for e in events)
    # and retrace landed in the phase dict both times
    assert "retrace" in prof2.phases


# -- timeline integration ----------------------------------------------


def _mk_events():
    t = 1000.0
    return [
        {"type": "recovery_phase", "ts": t + 1.0, "phase": "spawn",
         "seconds": 0.2, "restart_count": 1, "node_rank": 0,
         "source": "trainer"},
        {"type": "recovery_phase", "ts": t + 1.5, "phase": "restore",
         "seconds": 0.3, "restart_count": 1, "node_rank": 0,
         "source": "trainer"},
        {"type": "recovery_phase", "ts": t + 2.5, "phase": "retrace",
         "seconds": 0.9, "restart_count": 1, "node_rank": 0,
         "source": "trainer"},
        {"type": "recovery_phase", "ts": t + 2.6,
         "phase": "first_step", "seconds": 0.1, "restart_count": 1,
         "node_rank": 0, "source": "trainer"},
        {"type": "compile_cache", "ts": t + 2.5, "hit": True,
         "retrace_s": 0.9, "entries_before": 40,
         "entries_after": 40, "restart_count": 1, "node_rank": 0,
         "source": "trainer"},
    ]


def test_timeline_recovery_slices_and_budgets():
    tl = flight.assemble(_mk_events())
    slices = tl.slices_by_cat(flight.CAT_RECOVERY_PHASE)
    assert {s.meta["phase"] for s in slices} == {
        "spawn", "restore", "retrace", "first_step",
    }
    retrace = next(s for s in slices if s.meta["phase"] == "retrace")
    assert retrace.duration == pytest.approx(0.9)
    # compile_cache joins the instants with a readable description
    cache = [
        e for e in tl.instants if e["type"] == "compile_cache"
    ]
    assert cache
    # the shared ingestion helper agrees
    budgets = flight.recovery_budgets(tl.events)
    assert budgets[(0, 1)]["retrace"] == pytest.approx(0.9)
    assert budgets[(0, 1)]["compile_cache_hit"] is True
    # and the incident report prints the budget with the cache mark
    text = flight.to_report(tl)
    assert "recovery budgets" in text
    assert "cache=HIT" in text
    assert "retrace=0.900s" in text


# -- agent-side knobs ---------------------------------------------------


def test_agent_overlap_save_knob(monkeypatch):
    from dlrover_tpu.agent.training import ElasticTrainingAgent

    monkeypatch.delenv(
        "DLROVER_OVERLAP_BREAKPOINT_SAVE", raising=False
    )
    assert ElasticTrainingAgent._overlap_save_enabled()
    monkeypatch.setenv("DLROVER_OVERLAP_BREAKPOINT_SAVE", "0")
    assert not ElasticTrainingAgent._overlap_save_enabled()


def test_worker_env_exports_recovery_t0(monkeypatch):
    """The agent stamps DLROVER_RECOVERY_T0 into respawned workers'
    env (and never into a first start's)."""
    from dlrover_tpu.agent.training import (
        ElasticTrainingAgent, RendezvousOutcome, WorkerSpec,
    )

    agent = ElasticTrainingAgent.__new__(ElasticTrainingAgent)
    agent._spec = WorkerSpec(entrypoint=["x.py"])
    agent._node_rank = 0
    agent._restart_count = 0
    agent._recovery_t0 = 0.0

    class _C:
        master_addr = "127.0.0.1:1"

    agent._client = _C()
    outcome = RendezvousOutcome(
        round=1, world={0: 1}, coordinator="127.0.0.1:2"
    )
    env = agent._worker_env(outcome, 0)
    assert "DLROVER_RECOVERY_T0" not in env
    # compile-cache env always rides along
    assert env.get("JAX_COMPILATION_CACHE_DIR")
    agent._recovery_t0 = time.time()
    agent._restart_count = 1
    env = agent._worker_env(outcome, 0)
    assert float(env["DLROVER_RECOVERY_T0"]) == pytest.approx(
        agent._recovery_t0, abs=1e-3
    )


# -- the launch's phases (PR 37) ---------------------------------------


def test_import_ends_where_the_backend_phase_begins(
    tmp_path, monkeypatch, event_log
):
    """``init_jax_distributed()`` before the profiler: ``import`` is
    the process's start to that call, ``backend`` the call to the
    profiler's construction; together what ``import`` used to be
    (process start -> construction), and each event lies where its
    phase ended."""
    from dlrover_tpu.common import env_utils
    from dlrover_tpu.trainer.elastic_trainer import init_jax_distributed

    monkeypatch.setenv(cc.CACHE_DIR_ENV, str(tmp_path / "cache"))
    monkeypatch.delenv(rec.RECOVERY_T0_ENV, raising=False)
    called = time.time()
    assert init_jax_distributed() is False   # one process
    time.sleep(0.2)
    prof = rec.RecoveryProfiler()
    built = time.time()
    assert prof.phases["backend"] == pytest.approx(
        built - called, abs=0.02
    )
    assert prof.phases["backend"] >= 0.2
    assert prof.phases["import"] + prof.phases["backend"] == (
        pytest.approx(built - env_utils.proc_start_before(built),
                      abs=0.02)
    )
    by_phase = {
        e["phase"]: e for e in read_events(event_log)
        if e["type"] == "recovery_phase"
    }
    assert by_phase["import"]["ts"] == pytest.approx(called, abs=0.02)
    assert by_phase["backend"]["ts"] == pytest.approx(built, abs=0.02)
    spans = [
        e["name"] for e in read_events(event_log) if e["type"] == "span"
    ]
    assert spans == ["trainer.distributed_init", "trainer.backend_open"]


def test_first_step_is_the_remainder_since_the_last_phase(
    tmp_path, monkeypatch
):
    """Not everything since the profiler's construction: what an
    entrypoint does before the resolve (the trainer's constructor,
    the backend's opening where the profiler came first) has its own
    names and is no part of ``first_step``."""
    monkeypatch.setenv(cc.CACHE_DIR_ENV, str(tmp_path / "cache"))
    prof = rec.RecoveryProfiler()
    time.sleep(0.3)          # stands for the trainer's init
    with prof.phase("aot"):
        time.sleep(0.01)
    time.sleep(0.05)         # the first step itself
    prof.record_first_step()
    assert 0.05 <= prof.phases["first_step"] < 0.25


def test_ledger_books_the_backend_phase_to_the_respawn_gap():
    """The chip's opening is neither XLA work nor idle: beside
    ``import`` under ``respawn_gap``."""
    from dlrover_tpu.telemetry import goodput

    t = 5000.0

    def phase(ts, name, seconds):
        return {"type": "recovery_phase", "ts": ts, "phase": name,
                "seconds": seconds, "restart_count": 0, "node_rank": 0,
                "source": "trainer"}

    events = [
        phase(t, "import", 5.0),
        phase(t + 8.0, "backend", 8.0),
        phase(t + 9.0, "aot", 1.0),
    ] + [
        {"type": "train_step", "ts": t + 10.0 + 0.1 * i, "step": i + 1,
         "restart_count": 0, "node_rank": 0, "source": "trainer"}
        for i in range(20)
    ]
    ledger = goodput.build_ledger(events)
    assert ledger.conservation_errors() == []
    assert ledger.totals[goodput.RESPAWN] == pytest.approx(8.0)
    assert ledger.totals[goodput.COMPILE] == pytest.approx(1.0)
    assert "backend" in flight.RECOVERY_PHASES
    slices = flight.assemble(events).slices_by_cat(
        flight.CAT_RECOVERY_PHASE
    )
    assert "backend" in {s.meta["phase"] for s in slices}
