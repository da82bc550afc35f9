"""The ``launch.*`` readers on a hand-made event list: a launch's
nine spans and three phases, the worker's report, a gap nobody owns,
an overlap counted once, and a program without the spans (the parent
of PR 37), which reads None and never 0."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import loader  # noqa: E402

T = 1000.0


class Run:
    """What ``run.py`` hands a reader, from hand-made pieces."""

    def __init__(self, events, report):
        self.events = events
        self.report = report
        self.t_launch = T
        self.notes = []

    def of(self, type_, **match):
        return [
            e for e in self.events if e.get("type") == type_ and all(
                e.get(k) == v for k, v in match.items()
            )
        ]

    def note(self, line):
        self.notes.append(line)


def span(name, start, end, source="agent", **attributes):
    attributes.setdefault("restart_count", 0)
    return {
        "type": "span", "source": source, "name": name, "ts": T + end,
        "start_ts": T + start, "duration_s": end - start,
        "attributes": attributes,
    }


def phase(name, start, end):
    return {
        "type": "recovery_phase", "source": "trainer", "phase": name,
        "ts": T + end, "seconds": end - start, "restart_count": 0,
    }


def launch():
    """A launch of 40 s: tpurun exists at 0.5 s, the worker at 2 s,
    its imports end at 7 s, the chip is open at 15 s, the step is
    resolved from 16 to 19 s, state and reference take 3 and 8 s,
    five set-up steps from 30 s to the window at 40 s.  15-16 s has
    no name; the master's boot lies inside the launcher's wait."""
    events = [
        span("tpurun.boot", 0.5, 1.0),
        span("tpurun.master_boot", 1.0, 1.6, polls=3, slept_s=0.6),
        span("master.boot", 1.0, 1.4, source="master"),
        span("agent.init", 1.6, 1.7),
        span("rdzv.join", 1.7, 1.9, polls=1, slept_s=0.0),
        span("rdzv.join", 1.75, 1.8, source="master",
             restart_count=None),
        span("agent.spawn_workers", 1.9, 2.0, workers=1),
        phase("import", 2.0, 7.0),
        span("trainer.distributed_init", 7.0, 7.0, source="trainer"),
        span("trainer.backend_open", 7.0, 14.8, source="trainer",
             kind="TPU v5 lite"),
        phase("backend", 7.0, 14.9),
        span("trainer.init", 14.9, 15.0, source="trainer"),
        {"type": "worker_backend", "ts": T + 15.0, "restart_count": 0},
        {"type": "aot_cache", "ts": T + 19.0, "seconds": 2.9},
        phase("first_step", 19.0, 32.0),
    ] + [
        {"type": "step_phases", "step": i + 1, "ts": T + 32.0 + 2 * i,
         "total_s": 2.0, "compute": 1.9, "report": 0.1, "gc": 0.0,
         "other_s": 0.0}
        for i in range(5)
    ]
    report = {
        "window_t0_epoch": T + 40.0, "resolve_step_s": 3.0,
        "init_s": 3.0, "reference_s": 8.0,
    }
    return Run(events, report)


def reader(name):
    return loader.load_module("layer_metrics", name)


def test_the_five_readers_on_a_whole_launch():
    run = launch()
    assert reader("launch.tpurun_boot_s").read(run) == (
        pytest.approx(1.5)
    )
    assert reader("launch.worker_import_s").read(run) == (
        pytest.approx(5.0)
    )
    assert reader("launch.backend_open_s").read(run) == (
        pytest.approx(7.8)
    )
    assert reader("launch.first_step_s").read(run) == 2.0
    # uncovered: 15-16 s (after trainer.init) and nothing else: the
    # harness's 0-0.5 s and 19-30 s are the harness's
    assert reader("launch.unattributed_pct").read(run) == (
        pytest.approx(100 * 1.0 / 40.0)
    )
    notes = "\n".join(run.notes)
    assert "3 polls, slept 0.6" in notes
    assert "spawn 0.100 s" in notes
    assert "98.7% of it under these spans" in notes
    assert "init_s 3.000 + reference_s 8.000 + 2.000" in notes
    assert "uncovered 1.000 s at +15.000: after trainer.init" in notes


def test_setup_splits_into_program_harness_and_uncovered():
    parts = reader("launch.unattributed_pct").split(launch())
    assert parts["setup"] == pytest.approx(40.0)
    # an overlap counts once: master.boot inside tpurun.master_boot,
    # backend beside trainer.backend_open, aot_cache inside
    # resolve_step_s
    assert parts["program"] == pytest.approx(
        (15.0 - 0.5) + 2.9 + (40.0 - 30.0)
    )
    assert parts["harness"] == pytest.approx(0.5 + 0.1 + 11.0)
    assert parts["uncovered"] == pytest.approx(1.0)
    assert (parts["program"] + parts["harness"] + parts["uncovered"]
            == pytest.approx(parts["setup"]))
    assert [(round(s - T, 3), round(e - T, 3))
            for s, e in parts["gaps"]] == [(15.0, 16.0)]


def test_a_gap_between_named_stretches_is_uncovered():
    run = launch()
    run.events = [
        e for e in run.events if e.get("name") != "agent.init"
        and e.get("phase") != "import"
    ]
    parts = reader("launch.unattributed_pct").split(run)
    # 1.6-1.7 s (no agent.init) and 2-7 s (no import phase)
    assert parts["uncovered"] == pytest.approx(1.0 + 0.1 + 5.0)
    assert reader("launch.worker_import_s").read(run) is None
    assert reader("launch.tpurun_boot_s").read(run) == pytest.approx(1.5)
    assert "agent.init missing" in run.notes[-1]


@pytest.mark.parametrize("name", [
    "launch.tpurun_boot_s", "launch.backend_open_s",
    "launch.first_step_s", "launch.unattributed_pct",
])
def test_a_program_without_the_spans_reads_none(name):
    """The parent of PR 37: the recovery phases and the step's
    phases are there, the launch's spans are not."""
    run = launch()
    run.events = [e for e in run.events if e["type"] != "span"]
    assert reader(name).read(run) is None
    assert run.notes == []


def test_a_respawns_spans_are_not_the_launchs():
    run = launch()
    for e in run.events:
        if e["type"] == "span" and e["source"] == "trainer":
            e["attributes"]["restart_count"] = 1
    assert reader("launch.backend_open_s").read(run) is None
    assert reader("launch.first_step_s").read(run) is None


def test_a_save_cells_first_saves_are_the_programs():
    """The set-up's saves and the agent's persist count as named
    (their parts are ``ckpt.*``'s readers' business); a save inside
    the window does not."""
    run = launch()
    run.events = [
        e for e in run.events if e["type"] != "step_phases"
    ] + [
        span("ckpt.save", 32.0, 33.0, source="trainer",
             restart_count=None),
        span("ckpt.save.snapshot", 32.0, 32.5, source="trainer",
             restart_count=None),
        span("ckpt.save.write", 33.0, 36.0, source="trainer",
             restart_count=None),
        span("ckpt.persist", 36.0, 39.0, restart_count=None),
        span("ckpt.save", 41.0, 42.0, source="trainer",
             restart_count=None),
    ]
    launch_pct = reader("launch.unattributed_pct")
    names = [s[3] for s in launch_pct.stretches(run)]
    assert names.count("ckpt.save") == 1
    assert "ckpt.save.snapshot" not in names
    parts = launch_pct.split(run)
    # 15-16 s as before, 30-32 s and 39-40 s where the steps were
    assert parts["uncovered"] == pytest.approx(1.0 + 2.0 + 1.0)
