"""The third reducer (``step_ops.py``) and its two readers: on a
hand-made trace with a loop, and on the cut of a chip run that
``test_scopes.py`` uses (``fixtures/xl12_scopes.xplane.txt``) under a
hand-made map."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import loader  # noqa: E402
import scopes  # noqa: E402
import step_ops  # noqa: E402
import xplane  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
NORM = "jit(step_fn)/forward_backward/jvp(M)/block_0/ssm/ssm_norm"
REMAT = (
    "jit(step_fn)/forward_backward/transpose(jvp(M))/forward_backward/"
    "jvp(M)/checkpoint/rematted_computation/block_0/ssm/ssm_norm"
)
SCOPES = ["forward_backward", "loss_head", "optimizer", "ssm_norm"]


class Run:
    """What ``run.py`` hands a reader: here the reduction is what the
    test made."""

    def __init__(self, reduced):
        self.reduced = reduced
        self.notes = []

    def note(self, line):
        self.notes.append(line)


@pytest.fixture()
def readers(monkeypatch):
    monkeypatch.setattr(step_ops, "of_run", lambda run: run.reduced)
    return [
        loader.load_module("layer_metrics", name) for name in (
            "step.unnamed_ms_per_step", "step.layout_ms_per_step",
        )
    ]


US = 1000  # the hand-made trace's unit is a microsecond


def op(name, start, end, opcode="fusion"):
    return (f"{name} = f32[4]{{0}} {opcode}(f32[4]{{0}} %p)",
            start * US, end * US, {})


def space(device_events):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": device_events},
            {"name": "XLA Modules", "events": [
                ("jit_step_fn(123)", 0, 1000 * US, {}),
            ]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ("bench.compute", 0, 2000 * US, {}),
        ]}]},
    ]


OP_MAP = {
    "module": "jit_step_fn",
    "op_names": {
        "%fusion.1": NORM + "/reduce_sum",
        "%while.2": NORM + "/while",
        "%fusion.3": NORM + "/while/body/mul",
    },
    "inherited": {
        "%copy.4": [NORM + "/reduce_sum", "user"],
        "%copy-start.5": [REMAT + "/reduce_sum", "start"],
        "%copy-done.5": [REMAT + "/reduce_sum", "user"],
        "%copy.6": ["state.opt_state[0].mu['w']", "operand"],
    },
    "containers": ["%while.2"],
    "unnamed": {"%copy.7": "copy f32[]"},
    "scopes": SCOPES,
}


def test_a_loops_body_is_counted_once_and_the_loop_not_at_all(readers):
    trace = space([
        op("%copy.4", 0, 100, "copy"), op("%fusion.1", 100, 300),
        # the loop spans its body's two runs
        op("%while.2", 300, 700, "while"),
        op("%fusion.3", 310, 500), op("%fusion.3", 500, 690),
        op("%copy-start.5", 700, 710, "copy-start"),
        op("%copy-done.5", 710, 750, "copy-done"),
        op("%copy.6", 750, 800, "copy"), op("%copy.7", 800, 803, "copy"),
        # no instruction of the map's at all: unnamed too
        op("%pad.8", 803, 810, "pad"),
        # the same name in ANOTHER module is not the step's
        op("%fusion.1", 1500, 1600),
    ])
    reduced = step_ops.reduce(trace, OP_MAP)
    us = {k: round(v * 1e6) for k, v in reduced["seconds"].items()}
    assert us == {
        "named": 200 + 190 + 190, "inherited": 100 + 10 + 40 + 50,
        "unnamed": 3 + 7, "containers": 400, "outside": 100,
    }
    # no two of the counted operations overlap: their sum is the
    # union of their intervals
    assert us["named"] + us["inherited"] + us["unnamed"] == round(
        reduced["busy_s"] * 1e6
    )
    assert {
        scope: {k: round(v * 1e6) for k, v in parts.items()}
        for scope, parts in reduced["by_scope"].items()
    } == {
        "ssm_norm": {"forward": 100, "remat": 50, "backward": 0},
        step_ops.PARAMETER: {"forward": 50, "remat": 0, "backward": 0},
    }
    assert {k: round(v * 1e6) for k, v in reduced["by_rule"].items()} == {
        "user": 140, "start": 10, "operand": 50,
    }
    assert {k: round(v * 1e6) for k, v in reduced["by_group"].items()} == {
        "%copy": 150, "%copy-start": 10, "%copy-done": 40,
    }
    assert [row[0] for row in reduced["unnamed"]] == ["%pad.8", "%copy.7"]
    assert reduced["unnamed"][1][2] == "copy f32[]"
    unnamed, layout = readers
    run = Run(reduced)
    assert unnamed.read(run) == pytest.approx(0.010)
    assert layout.read(run) == pytest.approx(0.200)
    text = "\n".join(run.notes)
    assert "op_names ALONE leaves 0.210 ms" in text
    assert (
        "named 0.580 + inherited 0.200 + unnamed 0.010 = 0.790 ms a step"
        in text
    )
    assert "0.790 ms busy (100.000% accounted for)" in text
    assert "containers 0.400 ms" in text and "other modules 0.100 ms" in text
    assert "%pad.8 0.0070 (%pad)" in text
    assert "%copy.7 0.0030 (copy f32[])" in text
    assert "ssm_norm 0.100 | 0.050 | 0.000" in text
    assert "%copy 0.150" in text
    assert "by rule: user 0.140, operand 0.050, start 0.010" in text


def test_a_program_without_the_new_keys_reads_nothing(readers):
    """The parent of PR 54 writes ``module`` and ``op_names`` alone:
    no reduction, and both readers are silent."""
    trace = space([op("%fusion.1", 100, 300)])
    old = {k: OP_MAP[k] for k in ("module", "op_names")}
    assert step_ops.reduce(trace, old) is None
    assert step_ops.reduce(trace, None) is None
    for reader in readers:
        assert reader.read(Run(None)) is None


def test_the_innermost_scope_is_a_registered_one():
    names = set(SCOPES)
    assert step_ops.innermost_scope(NORM + "/reduce_sum", names) == "ssm_norm"
    assert step_ops.innermost_scope(
        "jit(step_fn)/forward_backward/transpose(jvp(loss_head))/neg", names
    ) == "loss_head"
    # a flax module's name is no scope
    assert step_ops.innermost_scope(
        "jit(step_fn)/forward_backward/jvp(M)/block_3/attn/mul", names
    ) == "forward_backward"
    assert step_ops.innermost_scope("jit(step_fn)/add", names) == (
        step_ops.NO_SCOPE
    )
    assert step_ops.innermost_scope(
        "state.params['wte']['embedding']", names
    ) == step_ops.PARAMETER
    assert step_ops.phase(REMAT + "/mul") == "remat"
    assert step_ops.phase(
        "jit(step_fn)/forward_backward/transpose(jvp(M))/mul"
    ) == "backward"
    assert step_ops.phase(NORM) == "forward"


def test_the_cut_of_a_chip_run_adds_up_under_a_hand_made_map(readers):
    """``fixtures/xl12_scopes.xplane.txt`` carries each operation's
    name stack in a stat and no modules line: the stacks make the
    map's ``op_names``, the pairs of asynchronous copies inherit, the
    rest stays unnamed; ``named + inherited + unnamed`` is every
    operation of the span once."""
    trace = xplane.read_space(
        os.path.join(FIXTURES, "xl12_scopes.xplane.txt")
    )
    events = xplane.device_ops(trace)["/device:TPU:0"]
    stacks, bare = {}, set()
    for name, _, _, stats in events:
        instruction = xplane.describe(name, stats)[0]
        if stats.get(scopes.STACK_STAT):
            stacks[instruction] = str(stats[scopes.STACK_STAT])
        else:
            bare.add(instruction)
    pairs = {n for n in bare if "-start" in n or "-done" in n}
    assert stacks and pairs and bare - pairs
    op_map = {
        "module": "jit_step_fn", "op_names": stacks,
        "inherited": {
            n: ["jit(step_fn)/optimizer/add", "user"] for n in pairs
        },
        "containers": [],
        "unnamed": {n: "copy f32[1600]" for n in bare - pairs},
        "scopes": SCOPES,
    }
    reduced = step_ops.reduce(trace, op_map)
    spans = xplane.host_spans(trace)
    t0, t1 = min(s[1] for s in spans), max(s[2] for s in spans)
    every = sum(
        min(e, t1) - max(s, t0) for _, s, e, _ in events
        if e > t0 and s < t1
    ) / 1e9
    seconds = reduced["seconds"]
    assert seconds["containers"] == seconds["outside"] == 0
    assert seconds["named"] + seconds["inherited"] + seconds[
        "unnamed"
    ] == pytest.approx(every)
    assert every == pytest.approx(reduced["busy_s"], rel=5e-3)
    assert all(v > 0 for k, v in seconds.items() if k in (
        "named", "inherited", "unnamed"
    ))
    assert set(reduced["by_scope"]) == {"optimizer"}
    unnamed, layout = readers
    run = Run(reduced)
    assert unnamed.read(run) == pytest.approx(
        seconds["unnamed"] / reduced["steps"] * 1e3
    )
    assert layout.read(run) == pytest.approx(
        seconds["inherited"] / reduced["steps"] * 1e3
    )
    assert any("% accounted for" in line for line in run.notes)
