"""The trace reduction on hand-made intervals, and on a small trace
recorded on the chip (``fixtures/``, cut with ``xplane.py cut``)."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import xplane  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def test_union_merges_overlaps_and_drops_empty():
    assert xplane.union([(5, 7), (0, 3), (2, 4), (9, 9)]) == [
        (0, 4), (5, 7)
    ]


def test_gaps_are_the_complement():
    assert xplane.gaps([(2, 4), (5, 7)], 0, 10) == [
        (0, 2), (4, 5), (7, 10)
    ]


def test_gaps_booked_to_the_covering_host_span():
    spans = [("compute", 0, 4), ("report", 4, 5), ("checkpoint", 6, 9)]
    booked = xplane.book_gaps([(3, 4.5), (5, 10)], spans)
    assert booked == {
        "compute": 1.0, "report": 0.5, "checkpoint": 3.0,
        "outside": 2.0,
    }


FUSION = (
    "%fusion.1 = bf16[50304,1600]{1,0:T(8,128)(2,1)} fusion(bf16[4,1024,"
    "50304]{2,1,0} %gte), kind=kOutput, calls=%fused_computation.15"
)
FLASH = (
    "%attn.223 = (bf16[100,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, "
    "f32[100,1,1024]{2,1,0:T(1,128)}) custom-call(bf16[100,1024,64]"
    "{2,1,0:T(8,128)(2,1)} %bitcast.5871), "
    'custom_call_target="tpu_custom_call", frontend_attributes={}'
)


def space(device_events, host_events):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_step", 0, 100, {})]},
            {"name": "XLA Ops", "events": device_events},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host_events},
        ]},
    ]


def test_reduce_by_hand():
    s = 1_000_000_000  # one second, in ns
    reduced = xplane.reduce(space(
        [
            (FUSION, 0 * s, 2 * s, {}),
            # overlaps the first: a union, not a sum
            ("%copy.2 = f32[8]{0} copy(f32[8]{0} %p)", 1 * s, 3 * s, {}),
            (FUSION, 6 * s, 8 * s, {}),
            # outside the host spans: not counted
            ("%fusion.9 = f32[8]{0} fusion()", 20 * s, 21 * s, {}),
        ],
        [
            ("bench.compute", 0 * s, 4 * s, {}),
            ("PjitFunction(step)", 0, 1, {}),
            ("bench.checkpoint", 4 * s, 6 * s, {}),
            ("bench.compute", 6 * s, 10 * s, {}),
        ],
    ))
    assert reduced["window_s"] == 10.0
    assert reduced["busy_s"] == 5.0
    assert reduced["steps"] == 2
    assert reduced["idle_s"] == {"compute": 3.0, "checkpoint": 2.0}
    assert reduced["longest_gap_s"] == 3.0
    assert reduced["ops"]["%fusion.1"] == {
        "seconds": 4.0, "count": 2, "group": "%fusion kOutput",
        "target": None,
    }
    assert reduced["ops"]["%copy.2"]["group"] == "%copy"
    assert "%fusion.9" not in reduced["ops"]


def test_describe_reads_the_hlo_text():
    assert xplane.describe(FLASH, {}) == (
        "%attn.223", "%attn custom-call tpu_custom_call",
        "tpu_custom_call",
    )
    # remat's copies of an instruction fall into its group
    assert xplane.describe(
        FUSION.replace("%fusion.1 ", "%fusion.1.remat2 "), {}
    )[:2] == ("%fusion.1.remat2", "%fusion kOutput")
    # the excerpt format: instruction as the name, text in a stat
    assert xplane.describe("%attn.223", {"long_name": FLASH})[2] == (
        "tpu_custom_call"
    )


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce([{"name": "/host:CPU", "lines": [
            {"name": "python", "events": [("bench.compute", 0, 1, {})]},
        ]}])


# -- a trace recorded on the chip ---------------------------------------------
# 16 ms around a step boundary of the 12-layer cell (my chip run, PR 24,
# first call): the end of one step's device operations, the host's
# ``report`` span during which the device idles, the next step's first
# operations.  The ``compute`` span that starts inside the excerpt runs
# on past its end, so the span is 111.6 ms and mostly idle.


@pytest.fixture(scope="module")
def recorded():
    return xplane.read_space(
        os.path.join(FIXTURES, "xl12_step_boundary.xplane.txt")
    )


def test_recorded_trace_against_a_timeline(recorded):
    """busy time by brute force: a 1 ns timeline with every device
    operation painted onto it."""
    import numpy as np

    reduced = xplane.reduce(recorded)
    spans = xplane.host_spans(recorded)
    t0 = min(s[1] for s in spans)
    t1 = max(s[2] for s in spans)
    (events,) = xplane.device_ops(recorded).values()
    timeline = np.zeros(int(t1 - t0) + 1, dtype=bool)
    for _, start, end, _ in events:
        a, b = int(round(start - t0)), int(round(end - t0))
        timeline[max(a, 0):max(b, 0)] = True
    assert reduced["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert reduced["busy_s"] == pytest.approx(
        timeline.sum() / 1e9, abs=2e-6
    )
    assert reduced["busy_s"] == pytest.approx(0.00738, abs=1e-5)
    assert reduced["steps"] == 1
    # the device idles all through the host's report of the step
    report = [s for s in spans if s[0] == "report"]
    assert reduced["idle_s"]["report"] == pytest.approx(
        sum(s[2] - s[1] for s in report) / 1e9, rel=1e-6
    )
    assert sum(reduced["idle_s"].values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-9
    )


def test_recorded_trace_flash_kernels(recorded):
    import kernels

    flash = kernels.kernel_ops(xplane.reduce(recorded), "flash")
    assert sorted(flash) == ["%attn.48", "%attn.49", "%attn.50"]
    # three backward kernels of 0.70 ms each
    assert sum(op["seconds"] for op in flash.values()) == pytest.approx(
        0.002107718, rel=1e-6
    )
