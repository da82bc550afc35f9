"""``conv.kernel_ms_per_step`` on hand-made traces: the convolution's
Pallas calls by instruction name, forward and backward apart in the
note, and nothing where the program has no such kernel (the XLA
form)."""

import pytest

from test_gdn_kernel_metric import op, traced

import loader


def reader():
    return loader.load_module("layer_metrics", "conv.kernel_ms_per_step")


def test_forward_and_backward_kernels_are_summed_and_told_apart():
    run = traced({
        "%conv_fwd.3": op(0.004, 2), "%conv_fwd.4": op(0.002, 4),
        "%checkpoint_conv_fwd.7": op(0.003, 2),
        "%conv_bwd.1": op(0.010, 2),
        # the matmuls XLA calls convolutions, the scan's kernels and a
        # fusion of the convolution's scope are not it
        "%convolution_bitcast_fusion.15": op(0.5, 2),
        "%ssd_fwd.1": op(0.1, 2), "%fusion.3": op(0.2, 2, None),
    })
    assert reader().read(run) == pytest.approx(9.5)
    (line,) = run.notes
    assert "conv_fwd 4.500 ms in 4.0 calls a step" in line
    assert "conv_bwd 5.000 ms in 1.0 calls a step" in line


@pytest.mark.parametrize("trace", [
    None, {"steps": 0, "ops": {}}, {"steps": 2, "ops": {}},
    {"steps": 2, "ops": {"%while.7": op(0.05, 2, None)}},
    {"steps": 2, "ops": {"%convolution_add_fusion.1": op(0.05, 2)}},
    {"steps": 2, "ops": {"%ssd_bwd.1": op(0.05, 2)}},
])
def test_a_program_without_the_kernels_reports_nothing(trace):
    run = traced({})
    run.trace = trace
    assert reader().read(run) is None and run.notes == []
