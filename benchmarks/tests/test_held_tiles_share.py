"""The reader of ``moe.held_tiles_share`` (PR 38) against a run that
carries the counter and one that does not, and its entry in
``BENCHMARK.json``, found by name."""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import loader  # noqa: E402

NAME = "moe.held_tiles_share"


class Run:
    report = {"window": {"steps": [{"step": s} for s in (5, 6, 7)]}}

    def __init__(self, events):
        self.events, self.notes = events, []

    def of(self, type_, **match):
        return self.events if type_ == "train_step" else []

    def note(self, line):
        self.notes.append(line)


def test_the_counter_is_read_as_the_windows_median():
    run = Run([
        {"step": 4, NAME: 0.9, "moe.held_rows_share": 0.9},  # set-up
        {"step": 5, NAME: 0.0720, "moe.held_rows_share": 0.0610},
        {"step": 6, NAME: 0.0833, "moe.held_rows_share": 0.0650},
        {"step": 7, NAME: 0.0795, "moe.held_rows_share": 0.0630},
    ])
    reader = loader.load_module("layer_metrics", NAME)
    assert reader.read(run) == 0.0795
    (note,) = run.notes
    assert "0.07950" in note and "3 steps" in note and "0.06300" in note


def test_a_program_without_the_counter_reads_nothing():
    """The parent of PR 38: ``train_step`` events with the rows'
    share and no tiles' share.  None, and no note."""
    run = Run([{"step": 5, "loss": 1.0, "moe.held_rows_share": 0.06}])
    reader = loader.load_module("layer_metrics", NAME)
    assert reader.read(run) is None and not run.notes


def test_the_benchmark_lists_the_reader_by_name():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == ["sarvam_steady_8k"]
    reader = loader.load_module("layer_metrics", NAME)
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES,
            reader.SOURCE) == tuple(
        entry[k] for k in ("name", "unit", "layer", "moves", "source")
    )
    assert entry["better"] == "lower"
