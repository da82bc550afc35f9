"""The second reducer (``scopes.py``) and the readers built on it: on
hand-made traces and events, and on a small cut of a chip run
(``fixtures/xl12_scopes.xplane.txt`` cut with ``scopes.py cut``,
``fixtures/xl12_save_spans.json`` cut from that run's event log)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import loader  # noqa: E402
import scopes  # noqa: E402
import xplane  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


class Cell:
    name = "xl12_flash_save"


class Run:
    """What ``run.py`` hands a reader, from recorded pieces."""

    def __init__(self, report, events, reduced=None):
        self.cell = Cell()
        self.report = report
        self.events = events
        self.trace = {"steps": 1} if reduced else None
        self.notes = []
        self._reduced = reduced

    def of(self, type_, **match):
        return [
            e for e in self.events if e.get("type") == type_ and all(
                e.get(k) == v for k, v in match.items()
            )
        ]

    def note(self, line):
        self.notes.append(line)


def reader(name):
    return loader.load_module("layer_metrics", name)


@pytest.fixture()
def reduced_for(monkeypatch):
    """Readers take the trace's reduction from ``scopes.of_run``;
    here it is what the test made."""
    monkeypatch.setattr(scopes, "of_run", lambda run: run._reduced)


# -- name stacks ----------------------------------------------------------------


def test_scope_is_found_bare_or_inside_jax_wrappers():
    fwd = "jit(step_fn)/forward_backward/jvp(GPT)/loss_head/wte.attend/dot_general"
    bwd = "jit(step_fn)/forward_backward/transpose(jvp(loss_head))/neg"
    assert scopes.in_scope(fwd, "loss_head")
    assert scopes.in_scope(bwd, "loss_head")
    assert scopes.in_scope(bwd, "forward_backward")
    assert not scopes.in_scope(fwd, "optimizer")
    assert not scopes.in_scope("jit(step_fn)/jit(optimizer_x)/mul", "optimizer")


def test_name_stack_comes_from_the_fixtures_stat_or_the_executables_map():
    assert scopes.name_stack("%fusion.7", {}) == (None, None)
    assert scopes.name_stack(
        "%fusion.7", {}, {"%fusion.7": "a/optimizer/mul"}
    ) == ("a/optimizer/mul", "op_names_map")
    assert scopes.name_stack(
        "%fusion.7", {"tf_op": "a/loss_head/exp"}, {"%fusion.7": "other"}
    ) == ("a/loss_head/exp", "tf_op")


# -- the reduction ----------------------------------------------------------------

HEAD = "jit(step_fn)/forward_backward/jvp(GPT)/loss_head/wte.attend/dot_general"
HEAD_BWD = (
    "jit(step_fn)/forward_backward/transpose(jvp(GPT))/loss_head/"
    "wte.attend/dot_general"
)
OP_MAP = {
    "module": "jit_step_fn",
    "op_names": {
        "%fusion.1": HEAD,
        "%fusion.1.remat2": HEAD,
        "%fusion.2": HEAD_BWD,
        "%fusion.3": "jit(step_fn)/optimizer/mul",
        "%fusion.4": (
            "jit(step_fn)/forward_backward/transpose(jvp(GPT))/"
            "forward_backward/jvp(GPT)/checkpoint/rematted_computation/"
            "block_0/ln_mlp/mul"
        ),
    },
}


def op(name, start, end):
    return (f"{name} = f32[4]{{0}} fusion(f32[4]{{0}} %p), kind=kLoop",
            start, end, {})


def space(device_events, host_events, modules=(("jit_step_fn(123)", 0, 1000),)):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules",
             "events": [(n, s, e, {}) for n, s, e in modules]},
            {"name": "XLA Ops", "events": device_events},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": host_events},
        ]},
    ]


def test_reduce_sums_device_time_by_the_programs_scopes():
    trace = space(
        [op("%fusion.1", 10, 110), op("%fusion.1.remat2", 110, 200),
         op("%fusion.2", 200, 400), op("%fusion.3", 400, 450),
         op("%fusion.4", 450, 480), op("%copy.9", 480, 500),
         # the same instruction name in ANOTHER module is not the
         # step's: the map names only what ran inside its module
         op("%fusion.3", 1500, 1600)],
        [("bench.compute", 0, 2000, {}),
         ("dlrover.step.compute", 5, 1005,
          {"wall_ns": 1_000_000_005, "step": 7}),
         ("dlrover.ckpt.save", 1100, 1900,
          {"wall_ns": 1_000_001_100, "step": 7, "span_id": "sabc"})],
    )
    out = scopes.reduce(trace, OP_MAP)
    assert out["steps"] == 1
    assert out["ops"] == 7 and out["ops_with_stack"] == 5
    assert out["stack_sources"] == {"op_names_map": 5}
    ns = {k: round(v * 1e9) for k, v in out["scope_s"].items()}
    assert ns == {
        "optimizer": 50, "loss_head": 390,
        "forward_backward": 420, "rematted_computation": 30,
    }
    assert out["loss_head_dots"] == {
        "forward": 2, "remat": 0, "backward": 1
    }
    assert out["compiler_remat"]["ops"] == 1
    assert round(out["compiler_remat"]["seconds"] * 1e9) == 90
    # jax's rematted block (30) + the compiler's copy (90)
    assert round(out["recompute_s"] * 1e9) == 120
    assert out["clock_offset_ns"] == 1_000_000_000
    save = [s for s in out["program_spans"] if s["name"] == "ckpt.save"]
    assert save == [{
        "name": "ckpt.save", "start_ns": 1100, "dur_ns": 800,
        "wall_ns": 1_000_001_100, "step": 7, "span_id": "abc",
    }]


def test_reduce_without_the_programs_names_finds_nothing():
    """The parent of PR 25: no map, no annotations."""
    trace = space(
        [op("%fusion.1", 10, 110)], [("bench.compute", 0, 2000, {})]
    )
    out = scopes.reduce(trace)
    assert out["ops_with_stack"] == 0
    assert out["program_spans"] == []
    assert out["clock_offset_ns"] is None


def test_scope_readers_read_zero_under_an_absent_scope_and_none_without_stacks(
    reduced_for,
):
    trace = space(
        [op("%fusion.1", 10, 110), op("%fusion.3", 400, 450)],
        [("bench.compute", 0, 2000, {})],
    )
    run = Run({}, [], scopes.reduce(trace, OP_MAP))
    assert reader("optimizer.ms_per_step").read(run) == pytest.approx(50e-6)
    assert reader("losshead.ms_per_step").read(run) == pytest.approx(100e-6)
    assert "1.0 forward" in run.notes[-1]
    # stacks are there, nothing was computed twice: a reading of 0
    assert reader("model.recompute_ms_per_step").read(run) == 0.0
    bare = Run({}, [], scopes.reduce(trace))
    for name in ("optimizer.ms_per_step", "losshead.ms_per_step",
                 "model.recompute_ms_per_step"):
        assert reader(name).read(bare) is None
    # the rehearsal reduces no trace at all
    for name in ("optimizer.ms_per_step", "losshead.ms_per_step",
                 "model.recompute_ms_per_step"):
        assert reader(name).read(Run({}, [])) is None


# -- the event log's spans ------------------------------------------------------------


def span(name, start, seconds, trace="t1", source="trainer", **attributes):
    return {
        "type": "span", "source": source, "name": name,
        "trace_id": trace, "span_id": name + str(start),
        "parent_id": None, "start_ts": start, "duration_s": seconds,
        "status": "ok", "attributes": attributes,
    }


def save_report(saves, steps=()):
    return {"window": {"saves": saves, "steps": list(steps)}}


def test_unattributed_share_is_what_no_child_covers():
    events = [
        span("ckpt.save", 100.0, 4.0, step=25, storage="memory"),
        span("ckpt.save.lock_wait", 100.0, 0.5, step=25),
        span("ckpt.save.fetch", 100.5, 1.0, step=25, bytes=2e9),
        # children may overlap: a union, not a sum
        span("ckpt.save.memcpy", 101.0, 2.0, step=25, bytes=2e9,
             copy_s=0.25, contiguous_s=1.7),
        # another save's children are not this one's
        span("ckpt.save.fetch", 100.0, 4.0, trace="t2", step=50),
    ]
    run = Run(save_report([
        {"step": 25, "kind": "memory", "stall_s": 4.0},
    ]), events)
    assert reader("ckpt.unattributed_pct").read(run) == pytest.approx(25.0)
    assert "fetch 1.0000" in run.notes[0]
    assert "2.000 GB/s" in run.notes[1]
    assert reader("native.memcpy_GBps").read(run) == pytest.approx(8.0)
    assert "1.700 s making arrays contiguous" in run.notes[-1]
    # no DISK save in this window
    assert reader("ckpt.snapshot_ms").read(run) is None


def test_lock_wait_names_the_persist_it_waited_behind():
    events = [
        span("ckpt.save", 10.0, 0.2, trace="d", step=100, storage="disk"),
        span("ckpt.save.snapshot", 10.0, 0.02, trace="d", step=100),
        span("ckpt.save.d2h_kickoff", 10.02, 0.17, trace="d", step=100),
        span("ckpt.save.enqueue", 10.19, 0.01, trace="d", step=100),
        span("ckpt.save.lock_wait", 10.3, 0.01, trace="d", step=100,
             acquired=True, held_by=None),
        span("ckpt.save", 20.0, 6.0, trace="m", step=125,
             storage="memory"),
        span("ckpt.save.lock_wait", 20.0, 2.0, trace="m", step=125,
             acquired=True, held_by="persist:100"),
        span("ckpt.persist.lock_hold", 19.0, 2.9, trace="d",
             source="agent", step=100, shard=0),
    ]
    run = Run(save_report([
        {"step": 100, "kind": "disk", "stall_s": 0.2},
        {"step": 125, "kind": "memory", "stall_s": 6.0},
    ]), events)
    assert reader("ckpt.lock_wait_ms").read(run) == pytest.approx(2000.0)
    assert "step 125" in run.notes[0] and "persist:100" in run.notes[0]
    assert "1900.0 ms inside the agent's ckpt.persist.lock_hold" in (
        run.notes[0]
    )
    assert reader("ckpt.snapshot_ms").read(run) == pytest.approx(190.0)


def test_save_readers_return_none_for_a_program_without_the_spans():
    """The parent of PR 25 under this PR's benchmark files."""
    old_span = {
        "type": "span", "source": "agent", "name": "rdzv.join",
        "trace_id": "x", "span_id": "y", "parent_id": None,
        "duration_s": 0.1, "status": "ok", "attributes": {},
    }
    run = Run(save_report([
        {"step": 25, "kind": "memory", "stall_s": 3.3},
        {"step": 100, "kind": "disk", "stall_s": 0.2},
    ]), [old_span])
    for name in ("ckpt.unattributed_pct", "ckpt.lock_wait_ms",
                 "ckpt.snapshot_ms", "native.memcpy_GBps"):
        assert reader(name).read(run) is None


def test_window_stall_sums_the_slow_steps_and_names_what_ran_beside_them():
    done = [0.1 * i for i in range(1, 11)]
    done[5:] = [t + 0.4 for t in done[5:]]      # step 6 took 0.5 s
    steps = [{"step": i, "done": t} for i, t in enumerate(done, 1)]
    report = {
        "window_t0": 0.0, "window_t0_epoch": 1000.0,
        "window": {"steps": steps, "saves": [{"step": 2}]},
    }
    events = [
        {"type": "step_phases", "step": 5, "compute": 0.1,
         "report": 0.047, "report.events": 0.04,
         "report.metrics_file": 0.007, "other_s": 0.0},
        {"type": "step_phases", "step": 6, "compute": 0.1,
         "report": 0.003, "gc": 0.35, "other_s": 0.353,
         "total_s": 0.5},
        span("master.goodput_ledger_tick", 1000.55, 0.3,
             source="master", events=9000),
        span("agent.heartbeat", 1000.05, 0.01, source="agent"),
    ]
    run = Run(report, events)
    stall = reader("trainer.stall_ms_in_window").read(run)
    assert stall == pytest.approx(400.0)
    assert "1 of 9 intervals" in run.notes[0]     # step 3 carried a save
    assert "step 6: 500.00 ms" in run.notes[1]
    assert "gc 350.00" in run.notes[1]
    assert "report of step 5 47.00: events 40.00" in run.notes[1]
    assert "master:master.goodput_ledger_tick 300.0 of its 300.0 ms" in (
        run.notes[1]
    )
    assert "agent.heartbeat" not in run.notes[1]
    # a steady window reads 0, not nothing
    even = [{"step": i, "done": 0.1 * i} for i in range(1, 11)]
    report["window"] = {"steps": even, "saves": []}
    assert reader("trainer.stall_ms_in_window").read(Run(report, [])) == 0.0


# -- on a cut of a chip run ---------------------------------------------------------------


def test_chip_fixture_scopes_and_clock():
    """25 ms around a step boundary of ``xl12_flash_save`` (my chip
    run, PR 25): the end of one step's optimizer pass, the report,
    the start of the next step.  Device operations carry their name
    stack (from the executable's map, written into the cut)."""
    trace = xplane.read_space(
        os.path.join(FIXTURES, "xl12_scopes.xplane.txt")
    )
    out = scopes.reduce(trace)
    # (the compiler's own copies and async starts carry no metadata)
    assert out["ops_with_stack"] > 100
    assert out["stack_sources"] == {"tf_op": out["ops_with_stack"]}
    assert out["scope_s"]["optimizer"] > 0
    assert out["scope_s"]["forward_backward"] > 0
    names = {s["name"] for s in out["program_spans"]}
    assert {"step.compute", "step.report", "step.report.events",
            "step.report.metrics_file"} <= names
    # the annotations entered microseconds apart agree on the offset
    offsets = [
        s["wall_ns"] - s["start_ns"] for s in out["program_spans"]
    ]
    assert max(offsets) - min(offsets) < 1e6
    assert abs(out["clock_offset_ns"] - offsets[0]) < 1e6
    # the flash kernels keep the name kernels.py looks for
    import kernels

    reduced = xplane.reduce(trace)
    assert kernels.kernel_ops(reduced, "flash")


def test_chip_fixture_save_spans():
    """One MEMORY save, the DISK save and the MEMORY save that met
    its persist, with the agent's persist spans (my chip run, PR
    25)."""
    with open(os.path.join(FIXTURES, "xl12_save_spans.json")) as f:
        recorded = json.load(f)
    run = Run(recorded["report"], recorded["events"])
    unattributed = reader("ckpt.unattributed_pct").read(run)
    assert 0 <= unattributed < 10
    rate = reader("native.memcpy_GBps").read(run)
    assert 1 < rate < 100
    assert reader("ckpt.snapshot_ms").read(run) > 0
    wait = reader("ckpt.lock_wait_ms").read(run)
    assert wait > 0
    assert any("held by" in n for n in run.notes)


def test_clock_note_lays_the_event_log_on_the_traces_axis(reduced_for):
    reduced = {
        "t0_ns": 1000, "clock_offset_ns": 5_000_000_000,
        "program_spans": [
            {"name": "ckpt.save", "start_ns": 2_000_000,
             "dur_ns": 3_000_000_000, "span_id": "s1"},
            {"name": "step.compute", "start_ns": 1000, "dur_ns": 10},
        ],
    }
    events = [
        dict(span("ckpt.save", 5.002_010, 3.000_020), span_id="s1"),
        span("ckpt.persist", 3.0, 6.0, source="agent", step=100),
    ]
    run = Run({}, events, reduced)
    scopes.clock_note(run)
    (line,) = run.notes
    assert "1 program spans" in line
    assert "at most 10.0 us on start and 20.0 us on duration" in line
    assert "step 100 lies at -2.000 s" in line
    # no trace reduced, or a program without the annotations: silence
    quiet = Run({}, events)
    scopes.clock_note(quiet)
    assert quiet.notes == []


# -- the cells files ------------------------------------------------------------------


def test_rehearsal_spans_is_the_rehearsal_plus_this_benchmarks_metrics():
    """``rehearsal.json`` may not be edited by a PR that adds readers,
    so ``rehearsal_spans.json`` carries them: everything the older
    file has, unchanged and first, and every per-layer metric of
    ``BENCHMARK.json`` with the declarations of its reader."""
    def load(*parts):
        with open(os.path.join(*parts)) as f:
            return json.load(f)

    old = load(BENCH, "rehearsal.json")
    new = load(BENCH, "rehearsal_spans.json")
    for key in ("configs", "workloads", "end_to_end"):
        assert new[key] == old[key]
    assert new["per_layer"][:len(old["per_layer"])] == old["per_layer"]
    listed = {m["name"]: m for m in new["per_layer"]}
    for metric in load(os.path.dirname(BENCH), "BENCHMARK.json")["per_layer"]:
        mine = listed[metric["name"]]
        module = reader(metric["name"])
        for entry in (metric, mine):
            assert (module.NAME, module.UNIT, module.LAYER, module.MOVES,
                    module.SOURCE) == tuple(
                entry[k] for k in ("name", "unit", "layer", "moves", "source")
            )
        # a metric of the save cell alone is one of the toy save cell
        assert ("workloads" in mine) == ("workloads" in metric)
