"""Diagnosis + utils tests: collectors produce data, the master
diagnoses a hang with a culprit, timers, numeric checker, muP."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.agent.diagnosis import (
    ChipMetricsCollector,
    LogCollector,
    StackCollector,
)
from dlrover_tpu.common.messages import DiagnosisData
from dlrover_tpu.master.diagnosis import DiagnosisManager
from dlrover_tpu.master.speed_monitor import SpeedMonitor
from dlrover_tpu.utils import Timer, Timers, check_numerics
from dlrover_tpu.utils.mup import (
    mup_adam,
    scale_init,
    width_multipliers,
)
from dlrover_tpu.utils.numeric_checker import compare_pytrees


def test_stack_collector_includes_threads():
    content = StackCollector().collect()
    assert "Thread" in content or "File" in content


def test_log_collector_tails(tmp_path):
    path = tmp_path / "train.log"
    path.write_text("line1\n" * 100 + "THE_END\n")
    content = LogCollector(str(path), tail_bytes=64).collect()
    assert "THE_END" in content
    assert len(content) <= 64


def test_diagnosis_manager_finds_culprit():
    mgr = DiagnosisManager()
    mgr.collect(DiagnosisData(
        node_id=0, data_type="stack", content="state=R running fine",
    ))
    mgr.collect(DiagnosisData(
        node_id=1, data_type="stack",
        content="worker pid 7: state=D wchan=futex_wait barrier",
    ))
    sm = SpeedMonitor()
    sm.add_running_worker(0)
    sm.collect_global_step(5, time.time() - 4000)
    verdict = mgr.diagnose(sm, hang_timeout=1800)
    assert verdict.hung
    assert verdict.culprit_node == 1
    assert verdict.action == "relaunch"


def test_no_hang_when_stepping():
    mgr = DiagnosisManager()
    sm = SpeedMonitor()
    sm.collect_global_step(5, time.time())
    assert not mgr.diagnose(sm).hung


def test_timers_accumulate():
    timers = Timers()
    with timers.scope("phase"):
        time.sleep(0.01)
    with timers.scope("phase"):
        time.sleep(0.01)
    assert timers("phase").count == 2
    assert timers.summary()["phase"] >= 0.01


def test_numeric_checker_flags_nan():
    good = {"w": jnp.ones(4)}
    bad = {"w": jnp.array([1.0, jnp.nan, 2.0, jnp.inf])}
    assert check_numerics(good) == []
    problems = check_numerics(bad)
    assert problems and "non-finite" in problems[0]
    assert compare_pytrees(good, good) == []
    assert compare_pytrees(
        good, {"w": jnp.full(4, 2.0)}
    )


def test_mup_width_multipliers_and_transfer():
    base = {"w": jnp.zeros((8, 8)), "b": jnp.zeros(8)}
    wide = {"w": jnp.ones((32, 8)), "b": jnp.zeros(8)}
    mults = width_multipliers(base, wide)
    assert mults["w"] == 4.0 and mults["b"] == 1.0
    scaled = scale_init(wide, mults)
    np.testing.assert_allclose(
        np.asarray(scaled["w"]), np.full((32, 8), 0.5)
    )
    # matrix lr scaled down by mult, vector lr untouched
    opt = mup_adam(0.1, mults)
    state = opt.init(wide)
    grads = {"w": jnp.ones((32, 8)), "b": jnp.ones(8)}
    updates, _ = opt.update(grads, state, wide)
    w_step = float(np.abs(np.asarray(updates["w"])).mean())
    b_step = float(np.abs(np.asarray(updates["b"])).mean())
    assert w_step == pytest.approx(b_step / 4.0, rel=1e-3)


def test_profiler_trace_capture_and_parse(tmp_path):
    """A traced block holds the program's own ``dlrover.*``
    annotations beside the computation: a span and a step phase, each
    with the wall clock at its entry."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from dlrover_tpu.telemetry.tracing import Tracer
    from dlrover_tpu.trainer.elastic_trainer import StepPhaseProfiler
    from dlrover_tpu.utils.profiler import trace

    @jax.jit
    def f(x):
        return (x @ x).sum()

    x = jnp.ones((256, 256))
    float(f(x))  # compile outside the trace
    phases = StepPhaseProfiler()
    phases.step = 7
    with trace(str(tmp_path)):
        with Tracer().span("ckpt.save", step=7):
            with phases.phase("compute") as p:
                p.block(f(x))
    (path,) = glob.glob(
        str(tmp_path / "**" / "*.xplane.pb"), recursive=True
    )
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("dlrover."):
                    found[event.name] = dict(event.stats)
    assert set(found) >= {"dlrover.ckpt.save", "dlrover.step.compute"}
    for stats in found.values():
        assert int(stats["step"]) == 7
        assert int(stats["wall_ns"]) > 1.6e18


def test_comm_perf_check_reports_bandwidth():
    from dlrover_tpu.agent.node_check import comm_perf_check

    report = comm_perf_check(payload_floats=1 << 16, rounds=2)
    assert report is not None
    assert report["devices"] == 8
    assert report["algbw_gbps"] > 0
    assert report["busbw_gbps"] > report["algbw_gbps"]


def test_inference_chain_reaches_fixpoint_with_dedup():
    """The chain expands problems through operators to a stable
    conclusion set (reference: inference_chain.py infer loop)."""
    from dlrover_tpu.master.diagnosis import (
        DiagnosisContext,
        DiagnosisManager,
        InferAttr,
        Inference,
        InferenceChain,
        InferenceOperator,
        InferName,
    )

    class AtoB(InferenceOperator):
        def is_compatible(self, inf):
            return inf.description == "a"

        def infer(self, inf, ctx):
            return [Inference("x", InferAttr.IS, "b", detail="from-a")]

    class BtoSelfPlusC(InferenceOperator):
        """Re-emits its input alongside a new fact — must converge,
        not spin to the round bound."""

        def is_compatible(self, inf):
            return inf.description == "b"

        def infer(self, inf, ctx):
            return [inf, Inference("x", InferAttr.IS, "c")]

    chain = InferenceChain([AtoB(), BtoSelfPlusC()])
    ctx = DiagnosisContext(manager=DiagnosisManager())
    out = chain.infer(
        [Inference("x", InferAttr.IS_OR_NOT, "a")], ctx
    )
    descs = sorted(i.description for i in out)
    assert descs == ["b", "c"]


def test_straggler_operator_isolates_slow_node():
    from dlrover_tpu.master.diagnosis import DiagnosisManager

    mgr = DiagnosisManager()
    for node, step_s in ((0, 1.0), (1, 1.1), (2, 1.0), (3, 4.8)):
        for _ in range(4):
            mgr.collect(DiagnosisData(
                node_id=node, data_type="step_time",
                content=str(step_s),
            ))
    sm = SpeedMonitor()
    sm.collect_global_step(5, time.time())  # stepping: not hung
    verdict = mgr.diagnose(sm)
    assert not verdict.hung
    assert verdict.culprit_node == 3
    assert verdict.action == "isolate"
    assert "straggler" in verdict.reason


def test_hang_outranks_straggler_action():
    from dlrover_tpu.master.diagnosis import DiagnosisManager

    mgr = DiagnosisManager()
    for node, step_s in ((0, 1.0), (1, 1.0), (2, 5.5)):
        for _ in range(3):
            mgr.collect(DiagnosisData(
                node_id=node, data_type="step_time",
                content=str(step_s),
            ))
    mgr.collect(DiagnosisData(
        node_id=2, data_type="stack",
        content="state=D wchan=futex barrier allreduce",
    ))
    sm = SpeedMonitor()
    sm.add_running_worker(0)
    sm.collect_global_step(5, time.time() - 4000)  # stalled
    verdict = mgr.diagnose(sm, hang_timeout=1800)
    assert verdict.hung
    assert verdict.action == "relaunch"  # outranks isolate
    assert verdict.culprit_node == 2


def test_chain_survives_broken_operator():
    from dlrover_tpu.master.diagnosis import (
        DiagnosisContext,
        DiagnosisManager,
        InferAttr,
        Inference,
        InferenceChain,
        InferenceOperator,
    )

    class Broken(InferenceOperator):
        def is_compatible(self, inf):
            return True

        def infer(self, inf, ctx):
            raise RuntimeError("boom")

    chain = InferenceChain([Broken()])
    ctx = DiagnosisContext(manager=DiagnosisManager())
    problem = Inference("x", InferAttr.IS_OR_NOT, "a")
    assert chain.infer([problem], ctx) == [problem]


def test_no_hang_verdict_before_first_step():
    """A long startup (scheduling, cold compile, restore) must not
    read as a hang: the guard requires registered workers AND at
    least one reported step."""
    from dlrover_tpu.master.diagnosis import DiagnosisManager

    mgr = DiagnosisManager()
    sm = SpeedMonitor()  # last_step_time set at construction...
    sm._start_time = sm._last_step_time = time.time() - 4000
    # ...but no workers registered, no samples: not a hang
    assert not mgr.diagnose(sm, hang_timeout=1800).hung


def test_step_time_collector_reports_delta(tmp_path):
    import json as _json

    from dlrover_tpu.agent.diagnosis import StepTimeCollector

    path = tmp_path / "metrics.json"
    col = StepTimeCollector(str(path))
    assert col.collect() == ""  # no file yet
    path.write_text(_json.dumps(
        {"global_step": 10, "timestamp": 1000.0}
    ))
    assert col.collect() == ""  # first observation: no delta yet
    path.write_text(_json.dumps(
        {"global_step": 14, "timestamp": 1006.0}
    ))
    assert col.collect() == "1.5000"  # 6s over 4 steps
    assert col.collect() == ""  # no progress since
