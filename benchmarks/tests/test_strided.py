"""``native.strided_pct`` on recorded span events: a cut of a chip run
of the program with the strided pass
(``fixtures/xl12_strided_spans.json``), the older program's spans
(``fixtures/xl12_save_spans.json``), and hand-made ones."""

import json
import os

import pytest

# the recorded-run stand-in and the hand-made span of the scopes tests
from test_scopes import FIXTURES, Run, span

import loader


def recorded(name):
    with open(os.path.join(FIXTURES, name)) as f:
        data = json.load(f)
    return Run(data["report"], data["events"])


@pytest.fixture()
def reader():
    return loader.load_module("layer_metrics", "native.strided_pct")


def test_share_is_strided_bytes_over_bytes_of_the_memory_saves(reader):
    events = [
        span("ckpt.save", 10.0, 2.0, trace="m", step=25, storage="memory"),
        span("ckpt.save.memcpy", 10.5, 0.2, trace="m", step=25, bytes=300,
             copy_s=0.15, strided_s=0.1, strided_bytes=200,
             strided_leaves=2, contiguous_s=0.0),
        span("ckpt.save.memcpy", 11.0, 0.1, trace="m", step=25, bytes=100,
             copy_s=0.05, strided_s=0.0, strided_bytes=0,
             strided_leaves=0, contiguous_s=0.0),
        # a DISK save's writer thread is not the loop's stall
        span("ckpt.save", 20.0, 0.3, trace="d", step=100, storage="disk"),
        span("ckpt.save.memcpy", 20.5, 0.2, trace="d", step=100, bytes=400,
             copy_s=0.2, strided_s=0.2, strided_bytes=400,
             strided_leaves=4, contiguous_s=0.0),
    ]
    run = Run({"window": {"saves": [
        {"step": 25, "kind": "memory"}, {"step": 100, "kind": "disk"},
    ]}}, events)
    assert reader.read(run) == pytest.approx(50.0)
    (line,) = run.notes
    assert "2 leaves" in line and "0.100 s" in line


def test_a_program_without_the_counter_reports_nothing(reader):
    """The parent's spans (PR 25) carry no ``strided_bytes``."""
    run = recorded("xl12_save_spans.json")
    assert reader.read(run) is None
    assert run.notes == []


def test_no_saves_in_the_window_reports_nothing(reader):
    assert reader.read(Run({"window": {"saves": []}}, [])) is None


def test_chip_fixture_of_the_strided_pass(reader):
    """Two MEMORY saves of a chip run (my chip run, PR 27): the 42
    column-major leaves are 1.230 of every save's 2.706 GB."""
    run = recorded("xl12_strided_spans.json")
    assert reader.read(run) == pytest.approx(45.45, abs=0.05)
    assert "84 leaves" in run.notes[0]
    # the older reader on the same spans: nothing made contiguous
    rate = loader.load_module(
        "layer_metrics", "native.memcpy_GBps"
    ).read(run)
    assert 1 < rate < 100
    assert "0.000 s making arrays contiguous" in run.notes[1]
