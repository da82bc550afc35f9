"""Deep diagnosis (ISSUE 7): step-phase profiler, hang flight data,
actionable verdicts, per-verb RPC SLOs, streaming timeline assembly.

Everything here is deterministic and network-free: the watchdog runs
on an injected clock, the master components are driven in-process,
and the timeline tests build synthetic event streams."""

import json
import os
import time

import jax.numpy as jnp
import pytest

from dlrover_tpu.agent.diagnosis import (
    HangWatchdog,
    StepPhaseCollector,
    capture_hang_evidence,
)
from dlrover_tpu.common import messages as msg
from dlrover_tpu.common.messages import DiagnosisData
from dlrover_tpu.master.diagnosis import Diagnosis, DiagnosisManager
from dlrover_tpu.master.speed_monitor import SpeedMonitor
from dlrover_tpu.telemetry import timeline as tl
from dlrover_tpu.telemetry.events import (
    EVENT_LOG_ENV,
    collect_events,
    iter_collect_events,
    read_events,
)
from dlrover_tpu.telemetry.metrics import MetricsRegistry, get_registry
from dlrover_tpu.telemetry.schema import validate_event
from dlrover_tpu.telemetry.slo import (
    SloChecker,
    SloRule,
    estimate_quantile,
    parse_slo_spec,
)
from dlrover_tpu.trainer.elastic_trainer import (
    ElasticTrainer,
    StepPhaseProfiler,
)


@pytest.fixture
def event_log(tmp_path, monkeypatch):
    path = tmp_path / "events.jsonl"
    monkeypatch.setenv(EVENT_LOG_ENV, str(path))
    return path


def _events_of(path, etype):
    return [e for e in read_events(str(path)) if e["type"] == etype]


# -- step-phase profiler ---------------------------------------------------


def test_profiler_phase_breakdown_and_event(event_log, tmp_path):
    trainer = ElasticTrainer(
        global_batch_size=8, micro_batch_size=8, dp_size=1,
        metrics_path=str(tmp_path / "metrics.json"),
    )
    with trainer.profile("data_wait"):
        time.sleep(0.02)
    with trainer.profile("compute") as p:
        x = jnp.ones(8) * 2
        p.block(x)
    trainer.report_step({"loss": 0.5})
    # (a blocked compute phase: the report waits for the next step)
    trainer.flush_reports()

    phases = trainer.last_step_phases
    assert phases["data_wait"] >= 0.015
    assert phases["compute"] >= 0.0
    assert "report" in phases
    assert phases["total_s"] >= phases["data_wait"]
    assert phases["other_s"] >= 0.0

    # the metrics file carries the breakdown for the agent collectors
    with open(tmp_path / "metrics.json") as f:
        record = json.load(f)
    assert record["phases"]["data_wait"] == phases["data_wait"]

    # a step_phases event per step, schema-valid
    events = _events_of(event_log, "step_phases")
    assert len(events) == 1
    assert events[0]["step"] == 1
    assert validate_event(events[0]) == []

    # the histogram saw every phase
    hist = get_registry().get("dlrover_step_phase_seconds")
    assert hist.snapshot(phase="data_wait")["count"] >= 1
    assert hist.snapshot(phase="other")["count"] >= 1


def test_profiler_accumulates_and_resets_per_step(tmp_path):
    trainer = ElasticTrainer(
        global_batch_size=8, micro_batch_size=8, dp_size=1,
        metrics_path=str(tmp_path / "metrics.json"),
    )
    with trainer.profile("data_wait"):
        pass
    with trainer.profile("data_wait"):
        pass
    trainer.report_step()
    assert "data_wait" in trainer.last_step_phases
    trainer.report_step()  # no profiled phases this step
    assert "data_wait" not in trainer.last_step_phases
    assert trainer.last_step_phases["total_s"] >= 0.0


def test_profiler_overhead_is_negligible():
    """Always-on contract: a full profile+finish cycle must cost
    microseconds, not milliseconds (<2% of any real step)."""
    prof = StepPhaseProfiler()
    n = 2000
    start = time.perf_counter()
    for _ in range(n):
        with prof.phase("data_wait"):
            pass
        with prof.phase("compute"):
            pass
        prof.finish_step()
    per_step = (time.perf_counter() - start) / n
    assert per_step < 2e-4, f"profiler costs {per_step * 1e6:.0f}µs"


# -- hang watchdog ---------------------------------------------------------


class _FakeClient:
    def __init__(self):
        self.reports = []

    def report_diagnosis_data(self, data_type, content):
        self.reports.append((data_type, content))
        return True


def test_capture_hang_evidence_has_stacks_and_worker_tree():
    ev = capture_hang_evidence([os.getpid()])
    assert "File" in ev["stacks"] or "Thread" in ev["stacks"]
    assert f"pid {os.getpid()}" in ev["workers"]
    assert "state=" in ev["workers"]


def test_hang_watchdog_lifecycle(event_log, tmp_path):
    path = tmp_path / "metrics.json"
    now = [1000.0]
    client = _FakeClient()
    wd = HangWatchdog(
        metrics_path=str(path),
        worker_pids_fn=lambda: [os.getpid()],
        threshold=5.0,
        interval=3600,
        client=client,
        clock=lambda: now[0],
    )
    # startup: no metrics file, arbitrarily long wait — NOT a hang
    now[0] += 500
    assert wd.poll_once() is None

    # first progress arms the watchdog
    path.write_text(json.dumps({"global_step": 3, "timestamp": 1.0}))
    assert wd.poll_once() is None

    # stall past the threshold: capture fires with flight data
    now[0] += 6
    payload = wd.poll_once()
    assert payload is not None
    assert payload["stall_s"] >= 5.0
    assert payload["last_step"] == 3
    assert payload["stacks"]
    assert f"pid {os.getpid()}" in payload["workers"]
    assert client.reports and client.reports[0][0] == "hang_evidence"

    # rate limit: same window, no re-capture
    now[0] += 1
    assert wd.poll_once() is None
    # next window: re-capture with the larger stall
    now[0] += 6
    second = wd.poll_once()
    assert second is not None and second["stall_s"] > payload["stall_s"]

    # progress resets everything
    path.write_text(json.dumps({"global_step": 4, "timestamp": 2.0}))
    assert wd.poll_once() is None
    now[0] += 3
    assert wd.poll_once() is None  # below threshold again

    # reset() disarms until fresh progress (post-restart recovery)
    wd.reset()
    now[0] += 500
    assert wd.poll_once() is None

    events = _events_of(event_log, "hang_evidence")
    assert len(events) == 2
    assert validate_event(events[0]) == []


def test_step_phase_collector_reports_rolling_mean(tmp_path):
    path = tmp_path / "metrics.json"
    col = StepPhaseCollector(str(path), window=4)
    assert col.collect() == ""  # no file
    path.write_text(json.dumps({
        "global_step": 5,
        "phases": {"data_wait": 0.4, "compute": 0.1, "total_s": 0.6},
    }))
    out = json.loads(col.collect())
    assert out["data_wait"] == pytest.approx(0.4)
    assert out["n"] == 1
    assert col.collect() == ""  # same step: nothing new
    path.write_text(json.dumps({
        "global_step": 6,
        "phases": {"data_wait": 0.2, "compute": 0.1, "total_s": 0.4},
    }))
    out = json.loads(col.collect())
    assert out["data_wait"] == pytest.approx(0.3)
    assert out["n"] == 2


# -- master: actionable verdicts -------------------------------------------


def _stepping_monitor():
    sm = SpeedMonitor()
    sm.collect_global_step(5, time.time())
    return sm


def test_hang_verdict_from_agent_evidence(event_log):
    """The agent's measured stall convicts even while the master's
    own silence clock is still inside its window — with stacks in
    the verdict."""
    mgr = DiagnosisManager()
    payload = {
        "node_rank": 2, "stall_s": 120.0, "last_step": 7,
        "stacks": "Thread 123: waiting in allreduce barrier",
        "workers": "pid 9 (python): state=D wchan=futex_wait",
    }
    mgr.collect(DiagnosisData(
        node_id=2, data_type="hang_evidence",
        content=json.dumps(payload), timestamp=time.time(),
    ))
    verdict = mgr.diagnose(_stepping_monitor(), hang_timeout=60.0)
    assert verdict.hung
    assert verdict.verdict == "hung"
    assert verdict.culprit_node == 2
    assert verdict.action == "relaunch"
    assert verdict.stall_s >= 120.0
    assert verdict.duration_s >= 120.0
    assert "state=D" in verdict.evidence

    events = _events_of(event_log, "diagnosis_verdict")
    assert events and events[-1]["verdict"] == "hung"
    assert events[-1]["stall_s"] >= 120.0
    assert events[-1]["evidence"]
    assert validate_event(events[-1]) == []


def test_stale_hang_evidence_does_not_convict():
    mgr = DiagnosisManager()
    mgr.collect(DiagnosisData(
        node_id=1, data_type="hang_evidence",
        content=json.dumps({"stall_s": 9999.0, "last_step": 2}),
        timestamp=time.time() - 100000,
    ))
    verdict = mgr.diagnose(_stepping_monitor(), hang_timeout=60.0)
    assert not verdict.hung


def test_data_starved_verdict_records_without_restart(event_log):
    mgr = DiagnosisManager()
    mgr.collect(DiagnosisData(
        node_id=1, data_type="step_phases",
        content=json.dumps({
            "data_wait": 0.8, "compute": 0.15, "total_s": 1.0,
        }),
        timestamp=time.time(),
    ))
    verdict = mgr.diagnose(_stepping_monitor())
    assert not verdict.hung
    assert verdict.verdict == "data_starved"
    assert verdict.culprit_node == 1
    assert verdict.action == "none"  # record, never a restart
    assert "data_wait" in verdict.reason

    events = _events_of(event_log, "diagnosis_verdict")
    assert events and events[-1]["verdict"] == "data_starved"


def test_stale_step_phases_do_not_convict():
    """A breakdown from a trainer that died long ago must not keep
    producing data_starved verdicts forever."""
    mgr = DiagnosisManager()
    mgr.collect(DiagnosisData(
        node_id=1, data_type="step_phases",
        content=json.dumps({
            "data_wait": 0.9, "compute": 0.05, "total_s": 1.0,
        }),
        timestamp=time.time() - 100000,
    ))
    verdict = mgr.diagnose(_stepping_monitor())
    assert verdict.verdict == ""


def test_compute_bound_step_is_not_data_starved():
    mgr = DiagnosisManager()
    mgr.collect(DiagnosisData(
        node_id=1, data_type="step_phases",
        content=json.dumps({
            "data_wait": 0.05, "compute": 0.9, "total_s": 1.0,
        }),
        timestamp=time.time(),
    ))
    verdict = mgr.diagnose(_stepping_monitor())
    assert verdict.verdict == ""
    assert verdict.action == "none"


def test_straggler_verdict_measures_excess_duration(event_log):
    mgr = DiagnosisManager()
    for node, step_s in ((0, 1.0), (1, 1.0), (2, 5.0)):
        for _ in range(4):
            mgr.collect(DiagnosisData(
                node_id=node, data_type="step_time",
                content=str(step_s),
            ))
    verdict = mgr.diagnose(_stepping_monitor())
    assert verdict.verdict == "straggler"
    assert verdict.culprit_node == 2
    # measured excess: (5.0 - 1.0) x 4 windowed samples
    assert verdict.duration_s == pytest.approx(16.0)
    events = _events_of(event_log, "diagnosis_verdict")
    assert events[-1]["duration_s"] == pytest.approx(16.0)


def test_clear_node_drops_evidence_and_data():
    mgr = DiagnosisManager()
    mgr.collect(DiagnosisData(
        node_id=3, data_type="hang_evidence",
        content=json.dumps({"stall_s": 100.0, "last_step": 1}),
        timestamp=time.time(),
    ))
    assert 3 in mgr.latest_hang_evidence()
    mgr.clear_node(3)
    assert mgr.latest_hang_evidence() == {}
    assert mgr.node_data(3) == []


def test_hang_culprit_prefers_evidence_node():
    """A node that shipped hang evidence outranks one that merely
    reported a quiet stack."""
    mgr = DiagnosisManager()
    mgr.collect(DiagnosisData(
        node_id=0, data_type="stack", content="state=R all good",
    ))
    mgr.collect(DiagnosisData(
        node_id=1, data_type="hang_evidence",
        content=json.dumps({
            "stall_s": 80.0, "last_step": 4,
            "stacks": "blocked in psum collective",
            "workers": "pid 7: state=D",
        }),
        timestamp=time.time(),
    ))
    sm = SpeedMonitor()
    sm.add_running_worker(0)
    sm.collect_global_step(5, time.time() - 4000)
    verdict = mgr.diagnose(sm, hang_timeout=1800)
    assert verdict.hung and verdict.culprit_node == 1


# -- master: culprit-only restart wiring -----------------------------------


def _fresh_master():
    from dlrover_tpu.master.master import JobMaster

    return JobMaster(port=0, node_num=1)


def test_handle_hang_requests_culprit_restart_once():
    m = _fresh_master()
    try:
        verdict = Diagnosis(
            hung=True, culprit_node=3, stall_s=9.0, reason="test",
        )
        assert m._handle_hang(verdict) is True
        # the action rides node 3's next heartbeat ack, exactly once
        resp = m.servicer.get(
            3, "worker", msg.HeartbeatRequest(node_id=3)
        )
        assert resp.action == "restart_workers"
        resp = m.servicer.get(
            3, "worker", msg.HeartbeatRequest(node_id=3)
        )
        assert resp.action == ""
        # other nodes never see it
        resp = m.servicer.get(
            0, "worker", msg.HeartbeatRequest(node_id=0)
        )
        assert resp.action == ""
    finally:
        m._server.stop()


def test_handle_hang_budget_exhaustion_aborts():
    m = _fresh_master()
    try:
        from dlrover_tpu.common.global_context import Context

        budget = Context.instance().relaunch_on_worker_failure
        verdict = Diagnosis(hung=True, culprit_node=1, reason="x")
        for _ in range(budget):
            assert m._handle_hang(verdict) is True
        assert m._handle_hang(verdict) is False
        assert m.job_manager.job_exit_reason == "hang_error"
    finally:
        m._server.stop()


def test_handle_hang_culpritless_grace_then_abort():
    m = _fresh_master()
    try:
        verdict = Diagnosis(hung=True, culprit_node=-1, reason="x")
        for _ in range(3):
            assert m._handle_hang(verdict) is True  # evidence grace
        assert m._handle_hang(verdict) is False
        assert m.job_manager.job_exit_reason == "hang_error"
    finally:
        m._server.stop()


# -- per-verb RPC histograms + SLOs ----------------------------------------


def test_rpc_seconds_histogram_per_verb():
    m = _fresh_master()
    try:
        m.servicer.get(0, "worker", msg.HeartbeatRequest(node_id=0))
        m.servicer.report(
            0, "worker",
            msg.GlobalStepRecord(node_id=0, global_step=1),
        )
        hist = get_registry().get("dlrover_rpc_seconds")
        assert hist.snapshot(
            verb="get.HeartbeatRequest"
        )["count"] >= 1
        assert hist.snapshot(
            verb="report.GlobalStepRecord"
        )["count"] >= 1
    finally:
        m._server.stop()


def test_estimate_quantile_interpolates():
    bounds = [0.1, 1.0, 10.0]
    counts = [90, 9, 1, 0]  # +Inf bucket empty
    p50 = estimate_quantile(bounds, counts, 0.5)
    assert p50 == pytest.approx(0.1 * (50 / 90), rel=1e-6)
    p99 = estimate_quantile(bounds, counts, 0.99)
    assert p99 == pytest.approx(1.0, rel=1e-6)
    # all mass in +Inf clamps to the last finite bound
    assert estimate_quantile(bounds, [0, 0, 0, 5], 0.5) == 10.0
    assert estimate_quantile(bounds, [0, 0, 0, 0], 0.5) == 0.0


def test_parse_slo_spec_tolerates_garbage():
    rules = parse_slo_spec(
        "get.*:p99:1.0, report.*:p95:0.25, nonsense, a:b:c"
    )
    assert len(rules) == 2
    assert rules[0].verb_pattern == "get.*"
    assert rules[0].quantile == pytest.approx(0.99)
    assert rules[1].threshold_s == pytest.approx(0.25)


def test_slo_checker_breach_gauges_and_single_event(event_log):
    reg = MetricsRegistry()
    h = reg.histogram("dlrover_rpc_seconds")
    for _ in range(20):
        h.observe(2.0, verb="get.SlowThing")
        h.observe(0.01, verb="get.FastThing")
    checker = SloChecker(
        rules=[SloRule("get.*", 0.99, 1.0)], registry=reg,
    )
    breaches = checker.check()
    assert [b.verb for b in breaches] == ["get.SlowThing"]
    assert breaches[0].observed_s > 1.0
    breach_gauge = reg.get("dlrover_rpc_slo_breach")
    assert breach_gauge.value(
        verb="get.SlowThing", quantile="p99"
    ) == 1.0
    assert breach_gauge.value(
        verb="get.FastThing", quantile="p99"
    ) == 0.0
    q = reg.get("dlrover_rpc_quantile_seconds")
    assert q.value(verb="get.SlowThing", quantile="p99") > 1.0

    # breach onset emitted once, not per poll
    checker.check()
    events = _events_of(event_log, "rpc_slo_breach")
    assert len(events) == 1
    assert validate_event(events[0]) == []
    assert events[0]["verb"] == "get.SlowThing"

    # too few samples: never a breach
    h2 = reg.histogram("dlrover_rpc_seconds")
    h2.observe(9.0, verb="get.Rare")
    assert all(
        b.verb != "get.Rare" for b in checker.check(emit=False)
    )


def test_slo_breach_in_incident_report():
    events = [
        {"type": "train_step", "ts": 1.0, "step": 1,
         "restart_count": 0, "node_rank": 0, "source": "trainer"},
        {"type": "train_step", "ts": 2.0, "step": 2,
         "restart_count": 0, "node_rank": 0, "source": "trainer"},
        {"type": "rpc_slo_breach", "ts": 1.5, "source": "master",
         "verb": "get.CommWorldRequest", "quantile": "p99",
         "threshold_s": 1.0, "observed_s": 2.5, "count": 40},
    ]
    jt = tl.assemble(events)
    report = tl.to_report(jt)
    assert "rpc SLO breach onsets:" in report
    assert "get.CommWorldRequest" in report


# -- timeline: real-duration hang/straggler buckets ------------------------


def _step(ts, step, rank=0, restart=0):
    return {
        "type": "train_step", "ts": ts, "step": step,
        "restart_count": restart, "node_rank": rank,
        "source": "trainer",
    }


def test_hang_bucket_claims_measured_stall():
    events = []
    for i in range(6):  # steps at t=0..5, 1s cadence
        events.append(_step(float(i), i + 1))
    # stall: silence 5..20; watchdog captured at 12 (6s stall),
    # verdict at 14 (9s stall), restart at 15, resume at 20
    events.append({
        "type": "hang_evidence", "ts": 12.0, "source": "agent",
        "node_rank": 0, "stall_s": 6.0, "last_step": 6,
        "stacks": "s", "workers": "w",
    })
    events.append({
        "type": "diagnosis_verdict", "ts": 14.0, "source": "master",
        "hung": True, "action": "relaunch", "culprit_node": 0,
        "reason": "r", "verdict": "hung", "stall_s": 9.0,
        "duration_s": 9.0, "evidence": "e",
    })
    events.append({
        "type": "worker_restart", "ts": 15.0, "source": "agent",
        "node_rank": 0, "restart_count": 1,
    })
    for i in range(3):
        events.append(_step(20.0 + i, 7 + i, restart=1))
    jt = tl.assemble(events)
    attr = tl.attribute_goodput_loss(jt)
    # lost: (5, 20) = 15s; hang claims (6,12)∪(5,14) -> 9s;
    # restart window (15,20) books under rendezvous
    assert attr["loss_s"] == pytest.approx(15.0)
    assert attr["buckets"][tl.CAUSE_HANG] == pytest.approx(
        9.0, abs=0.01
    )
    assert attr["buckets"][tl.CAUSE_RENDEZVOUS] >= 5.0 - 0.01
    assert sum(attr["buckets"].values()) == pytest.approx(
        attr["loss_s"]
    )
    named = attr["loss_s"] - attr["buckets"][tl.CAUSE_UNATTRIBUTED]
    assert named >= 0.9 * attr["loss_s"]


def test_straggler_bucket_uses_verdict_duration():
    events = [_step(float(i), i + 1) for i in range(4)]  # t=0..3
    events.append(_step(10.0, 5))  # 7s gap: lost (3, 10)
    events.append(_step(11.0, 6))
    events.append({
        "type": "diagnosis_verdict", "ts": 9.0, "source": "master",
        "hung": False, "action": "isolate", "culprit_node": 0,
        "reason": "slow", "verdict": "straggler",
        "stall_s": 0.0, "duration_s": 5.0, "evidence": "",
    })
    jt = tl.assemble(events)
    attr = tl.attribute_goodput_loss(jt)
    # measured claim (4, 9) ∩ lost (3, 10) = 5s — not the legacy 1s
    assert attr["buckets"][tl.CAUSE_STRAGGLER] == pytest.approx(
        5.0, abs=0.01
    )


def test_straggler_bucket_legacy_verdict_falls_back_to_nominal():
    events = [_step(float(i), i + 1) for i in range(4)]
    events.append(_step(10.0, 5))
    events.append({
        "type": "diagnosis_verdict", "ts": 9.0, "source": "master",
        "hung": False, "action": "isolate", "culprit_node": 0,
        "reason": "slow",
    })
    jt = tl.assemble(events)
    attr = tl.attribute_goodput_loss(jt)
    assert attr["buckets"][tl.CAUSE_STRAGGLER] == pytest.approx(
        1.0, abs=0.01
    )


# -- streaming timeline ----------------------------------------------------


def test_iter_collect_events_matches_collect(tmp_path):
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    with open(a, "w") as f:
        for i in range(0, 100, 2):
            f.write(json.dumps({"type": "train_step", "ts": float(i),
                                "step": i}) + "\n")
    with open(b, "w") as f:
        for i in range(1, 100, 2):
            f.write(json.dumps({"type": "train_step", "ts": float(i),
                                "step": i}) + "\n")
    eager = collect_events([str(a), str(b)])
    lazy = list(iter_collect_events([str(a), str(b)]))
    assert [e["ts"] for e in lazy] == [e["ts"] for e in eager]
    assert len(lazy) == 100


def test_iter_collect_events_absorbs_local_disorder(tmp_path):
    path = tmp_path / "log.jsonl"
    order = [0.0, 2.0, 1.0, 3.0, 5.0, 4.0]  # writer interleaving
    with open(path, "w") as f:
        for ts in order:
            f.write(json.dumps({"type": "x", "ts": ts}) + "\n")
    out = [e["ts"] for e in iter_collect_events([str(path)])]
    assert out == sorted(order)


def test_windowed_assembly_bounded_memory_100k_events(tmp_path):
    """PR 5 follow-on regression: a 100k-event log assembles through
    the windowed mode with a fraction of the full-load peak, and
    loses no events."""
    import tracemalloc

    path = tmp_path / "big.jsonl"
    n = 100_000
    with open(path, "w") as f:
        for i in range(n):
            f.write(json.dumps({
                "schema": 1, "ts": i * 0.001, "pid": 1,
                "source": "trainer", "type": "train_step",
                "step": i + 1, "restart_count": 0, "node_rank": 0,
            }) + "\n")

    tracemalloc.start()
    full_events = collect_events([str(path)])
    full_tl = tl.assemble(full_events)
    full_steps = sum(
        len(v) for v in full_tl.steps_by_track.values()
    )
    _, full_peak = tracemalloc.get_traced_memory()
    del full_events, full_tl
    tracemalloc.stop()

    tracemalloc.start()
    stream_steps = 0
    windows = 0
    for _start, wtl in tl.assemble_windows(
        [str(path)], window_s=1.0
    ):
        windows += 1
        stream_steps += sum(
            len(v) for v in wtl.steps_by_track.values()
        )
    _, stream_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()

    assert full_steps == n
    assert stream_steps == n
    assert windows > 10
    # the memory contract: windowed peak is a small fraction of the
    # everything-in-RAM peak
    assert stream_peak < 0.5 * full_peak, (
        f"stream {stream_peak} vs full {full_peak}"
    )


# -- brain feed ------------------------------------------------------------


def test_brain_records_diagnosis_verdicts(tmp_path):
    from dlrover_tpu.brain.cluster_monitor import (
        record_diagnosis_verdicts,
    )
    from dlrover_tpu.brain.datastore import SqliteJobMetricsStore

    store = SqliteJobMetricsStore(str(tmp_path / "brain.db"))
    n = record_diagnosis_verdicts(store, "jobx", [
        {"type": "diagnosis_verdict", "ts": 10.0, "hung": True,
         "action": "relaunch", "culprit_node": 2, "reason": "r",
         "verdict": "hung", "stall_s": 12.5, "duration_s": 12.5},
        {"type": "train_step", "ts": 11.0, "step": 1},
    ])
    assert n == 1
    extras = [
        row for row in store.load_extras("jobx")
        if row.get("event") == "diagnosis_verdict"
    ]
    assert extras
    assert extras[-1]["verdict"] == "hung"
    assert extras[-1]["stall_s"] == pytest.approx(12.5)
    store.close()
