"""``ssm.kernel_ms_per_step`` on hand-made traces: the scan's Pallas
calls by instruction name, forward and backward apart in the note,
and nothing where the program has no such kernel (the XLA form)."""

import pytest

from test_gdn_kernel_metric import op, traced

import loader


def reader():
    return loader.load_module("layer_metrics", "ssm.kernel_ms_per_step")


def test_forward_and_backward_kernels_are_summed_and_told_apart():
    run = traced({
        "%ssd_fwd.1": op(0.010, 2), "%ssd_fwd.2": op(0.012, 2),
        "%checkpoint_ssd_fwd.7": op(0.011, 2),
        "%ssd_bwd.1": op(0.030, 2),
        # the flash kernels, the other rule's kernels and a fusion of
        # the scan's scope are not it
        "%attn.4": op(0.5, 2), "%jvp_gdn_fwd_.1": op(0.1, 2),
        "%fusion.3": op(0.2, 2, None),
    })
    assert reader().read(run) == pytest.approx(31.5)
    (line,) = run.notes
    assert "ssd_fwd 16.500 ms in 3.0 calls a step" in line
    assert "ssd_bwd 15.000 ms in 1.0 calls a step" in line


@pytest.mark.parametrize("trace", [
    None, {"steps": 0, "ops": {}}, {"steps": 2, "ops": {}},
    {"steps": 2, "ops": {"%while.7": op(0.05, 2, None)}},
    {"steps": 2, "ops": {"%jvp_gdn_fwd_.1": op(0.05, 2)}},
])
def test_a_program_without_the_kernels_reports_nothing(trace):
    run = traced({})
    run.trace = trace
    assert reader().read(run) is None and run.notes == []
