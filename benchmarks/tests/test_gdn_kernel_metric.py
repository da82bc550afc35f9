"""``gdn.kernel_ms_per_step`` on hand-made traces: the rule's Pallas
calls by instruction name, forward and backward apart in the note,
and nothing where the program has no such kernel."""

import pytest

from test_scopes import Run

import loader


def op(seconds, count, target="tpu_custom_call"):
    return {"seconds": seconds, "count": count, "group": "", "target": target}


def traced(ops):
    run = Run({"device": {"kind": "TPU v5 lite", "count": 1}}, [], reduced=True)
    run.trace = {"steps": 2, "ops": ops}
    return run


def reader():
    return loader.load_module("layer_metrics", "gdn.kernel_ms_per_step")


def test_forward_and_backward_kernels_are_summed_and_told_apart():
    run = traced({
        "%jvp_gdn_fwd_.1": op(0.010, 2), "%jvp_gdn_fwd_.2": op(0.012, 2),
        "%gdn_fwd.7": op(0.011, 2),
        "%transpose_jvp_gdn_bwd__.1": op(0.030, 2),
        # the flash kernels and a fusion of the rule's scope are not it
        "%attn.4": op(0.5, 2), "%fusion.3": op(0.2, 2, None),
    })
    assert reader().read(run) == pytest.approx(31.5)
    (line,) = run.notes
    assert "gdn_fwd 16.500 ms in 3.0 calls a step" in line
    assert "gdn_bwd 15.000 ms in 1.0 calls a step" in line


@pytest.mark.parametrize("trace", [
    None, {"steps": 0, "ops": {}}, {"steps": 2, "ops": {}},
    {"steps": 2, "ops": {"%while.7": op(0.05, 2, None)}},
])
def test_a_program_without_the_kernels_reports_nothing(trace):
    run = traced({})
    run.trace = trace
    assert reader().read(run) is None and run.notes == []
