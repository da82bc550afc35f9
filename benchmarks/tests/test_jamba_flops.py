"""``jamba_flops.py`` against a count written out layer by layer, the
cut configuration's ``flops.py`` keys against the counts they stand
for, the roofline's required work, and the new readers against a run
that has nothing for them and against a hand-made trace."""

import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import jamba_flops  # noqa: E402
import loader  # noqa: E402

CUT = loader.load_json(os.path.join(BENCH, "configs", "jamba2_3b_cut.json"))
TOY = loader.load_json(os.path.join(BENCH, "configs", "toy_jamba.json"))
NEW_READERS = (
    "s6.scan_ms_per_step", "s6.kernel_ms_per_step", "s6.scan_roofline_pct",
    "s6.mix_ms_per_step", "s6.proj_ms_per_step", "s6.state_rms_max",
)
SEQ = 8192


def test_the_layers_by_kind():
    assert jamba_flops.layers(CUT, jamba_flops.MAMBA) == 13
    assert jamba_flops.layers(CUT, jamba_flops.ATTENTION) == 1
    assert jamba_flops.layer_types(CUT)[7] == jamba_flops.ATTENTION
    assert jamba_flops.layer_types(TOY) == ["mamba", "attention", "mamba"]
    whole = {**CUT, "num_hidden_layers": CUT["published"]["num_hidden_layers"]}
    assert [
        i for i, k in enumerate(jamba_flops.layer_types(whole))
        if k == jamba_flops.ATTENTION
    ] == [7, 21]


def test_every_parameter_of_the_train_state_layer_by_layer():
    mamba = (
        2560 * 10240            # in_proj
        + 4 * 5120 + 5120       # taps, their bias
        + 5120 * 192            # x_proj
        + 160 * 5120 + 5120     # dt_proj, its bias
        + 5120 * 16 + 5120      # A_log, D
        + 5120 * 2560           # out_proj
        + 160 + 16 + 16         # the three inner norms
    )
    assert mamba == 41_241_792
    swiglu = 3 * 2560 * 8192
    assert mamba + swiglu + 2 * 2560 == 104_161_472
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128
    assert attention + swiglu + 2 * 2560 == 76_682_240
    table = 65536 * 2560
    assert jamba_flops.total_params(CUT) == (
        13 * 104_161_472 + 76_682_240 + table + 2560
    ) == 1_598_556_096
    # 9.59 GB of state at 6 B a parameter
    assert round(6 * 1_598_556_096 / 1e9, 2) == 9.59
    # all 28 layers would not fit: 18.2 GB
    whole = {**CUT, "num_hidden_layers": 28}
    assert jamba_flops.total_params(whole) == 3_029_337_472


def test_the_matmul_parameters_a_token_meets():
    assert jamba_flops.mamba_params(CUT) == (
        2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    ) == 41_123_840
    assert jamba_flops.attention_params(CUT) == 13_762_560
    assert jamba_flops.matmul_params(CUT) == (
        13 * (41_123_840 + 62_914_560) + 13_762_560 + 62_914_560
        + 167_772_160
    ) == 1_596_948_480
    per_token = jamba_flops.train_flops_per_token(CUT, SEQ)
    assert per_token == (
        6 * 1_596_948_480 + 6 * SEQ * 2560 + 18 * 5120 * 16 * 13
    )
    assert per_token * SEQ == pytest.approx(79.68e12, rel=1e-3)
    # the tied head's share of the required FLOPs at 14 of 28 layers
    assert 6 * 167_772_160 / per_token == pytest.approx(0.1035, abs=2e-3)


def test_the_cuts_flops_py_keys_stand_for_these_counts():
    for cfg in (CUT, TOY):
        required = jamba_flops.matmul_params(cfg)
        assert 0 <= required - flops.matmul_params(cfg) < 8 * cfg["n_embd"]
        assert cfg["n_layer"] == jamba_flops.layers(
            cfg, jamba_flops.ATTENTION
        )
        assert cfg["n_embd"] == cfg["hidden_size"]
        assert flops.attention_flops_per_token(cfg, 128) == (
            jamba_flops.attention_flops_per_token(cfg, 128)
        )
    assert flops.matmul_params(CUT) == 1_596_943_360
    ratio = flops.train_flops_per_token(CUT, SEQ) / (
        jamba_flops.train_flops_per_token(CUT, SEQ)
    )
    # flops.py has no key for the recurrence (19.2 MFLOP a token)
    assert ratio == pytest.approx(0.99802, abs=2e-5) and ratio < 1
    assert flops.train_flops_per_token(CUT, SEQ) == pytest.approx(
        9.707e9, rel=1e-3
    )
    # flops.py's attention bytes count k, v and their gradients 2560
    # lanes wide where they are 128: MORE than the step moves, and the
    # attention is bound by FLOPs all the same
    seconds, bound = flops.roofline_seconds(
        flops.attention_flops_per_step(CUT, 1, SEQ),
        flops.attention_bytes_per_step(CUT, 1, SEQ), "TPU v5 lite",
    )
    assert bound == "flops" and seconds == pytest.approx(5.23e-3, rel=1e-2)


def test_the_recurrences_required_work():
    # per token, channel and lane: exp + 3 + 2 forward, twice backward
    assert jamba_flops.SCAN_OPS == 3 * (1 + 3 + 2)
    a_layer = 18 * 5120 * 16 * SEQ
    assert jamba_flops.scan_flops_per_step(CUT, 1, SEQ) == 13 * a_layer
    assert jamba_flops.scan_exps_per_step(CUT, 1, SEQ) == (
        3 * 5120 * 16 * SEQ * 13
    ) == pytest.approx(26.2e9, rel=1e-2)
    # forward x, dt in and y out; backward x, dt, dy in, dx, d dt out
    forward = 5120 * SEQ * (2 + 4 + 2)
    backward = 5120 * SEQ * (2 + 4 + 2 + 2 + 4)
    assert jamba_flops.scan_bytes_per_step(CUT, 1, SEQ) == (
        13 * (forward + backward)
    ) == 11_995_709_440
    least, bound = flops.roofline_seconds(
        jamba_flops.scan_flops_per_step(CUT, 1, SEQ),
        jamba_flops.scan_bytes_per_step(CUT, 1, SEQ), "TPU v5 lite",
    )
    # neither peak is this work's own: the bytes bound, far below it
    assert bound == "bytes" and least == pytest.approx(14.65e-3, rel=1e-3)


def test_the_benchmark_lists_the_cell_and_its_readers():
    bench = loader.load_json(
        os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")
    )
    (cell,) = [
        w for w in bench["workloads"] if w["name"] == "jamba2_steady_8k"
    ]
    assert cell["config"] == "jamba2_3b_cut" and cell["chips"] == 1
    assert cell["traffic"] == "steady_8k" and len(cell["why"]) <= 200
    (config,) = [c for c in bench["configs"] if c["name"] == "jamba2_3b_cut"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["source"] == CUT["source"]
    assert config["file"] == "benchmarks/configs/jamba2_3b_cut.json"
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert listed[name]["workloads"] == ["jamba2_steady_8k"]
        assert listed[name]["layer"] == "selective scan layers"
        reader = loader.load_module("layer_metrics", name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES,
                reader.SOURCE) == tuple(
            listed[name][k]
            for k in ("name", "unit", "layer", "moves", "source")
        )


def fake_run(trace, events, tmp_path=None, stacks=None, config=CUT):
    if stacks is not None:
        (tmp_path / "k.opnames.json").write_text(
            json.dumps({"op_names": stacks})
        )
        events = [
            {"type": "aot_cache", "key": "k", "dir": str(tmp_path)}
        ] + events
    notes = []
    return types.SimpleNamespace(
        config=config, traffic={"batch": 1, "seq": SEQ}, trace=trace,
        report={"window": {"steps": [{"step": 3}, {"step": 4}]},
                "device": {"kind": "TPU v5 lite"}},
        of=lambda type_, **match: [
            e for e in events if e["type"] == type_
        ],
        note=notes.append, flops=flops, notes=notes,
    )


@pytest.mark.parametrize("trace", [None, {"steps": 2, "ops": {}}])
def test_a_program_without_the_family_reports_nothing(trace):
    """The parent of PR 68: no ``s6_*`` scope, kernel or counter."""
    run = fake_run(trace, [{"type": "train_step", "step": 3, "loss": 1.0}])
    for name in NEW_READERS:
        assert loader.load_module("layer_metrics", name).read(run) is None
    assert run.notes == []


def test_another_familys_trace_reports_nothing(tmp_path):
    """A cell of another family run with these readers (the traced
    runs of every cell load every reader): nothing, and no raise."""
    other = loader.load_json(
        os.path.join(BENCH, "configs", "lfm2_24b_a2b_cut.json")
    )
    stacks = {"%fusion.1": "jit(step)/jvp(block_1)/short_conv/sconv_mix/mul"}
    ops = {"%fusion.1": {"seconds": 0.002, "count": 2, "target": ""}}
    run = fake_run(
        {"steps": 2, "ops": ops, "busy_s": 0.002},
        [{"type": "train_step", "step": 3, "loss": 1.0}], tmp_path,
        stacks, config=other,
    )
    for name in NEW_READERS:
        assert loader.load_module("layer_metrics", name).read(run) is None


def test_the_readers_sum_their_scopes_of_a_trace(tmp_path):
    stacks = {
        "%jvp_s6_fwd_.3":
            "jit(step)/jvp(block_1)/mamba/s6_scan/pallas_call",
        "%transpose_jvp_s6_bwd__.1":
            "jit(step)/transpose(jvp(block_1))/mamba/s6_scan/pallas_call",
        "%fusion.1": "jit(step)/jvp(block_1)/mamba/s6_scan/transpose",
        "%conv_fwd.2": "jit(step)/jvp(block_1)/mamba/s6_conv/pallas_call",
        "%conv_fwd.7":
            "jit(step)/rematted_computation/block_1/mamba/s6_conv/pallas_call",
        "%fusion.2": "jit(step)/jvp(block_1)/mamba/s6_params/softplus",
        "%fusion.3": "jit(step)/jvp(block_1)/mamba/s6_gate/mul",
        "%fusion.4": "jit(step)/jvp(block_1)/mamba/s6_in_proj/dot",
        "%fusion.5":
            "jit(step)/transpose(jvp(block_1))/mamba/s6_out_proj/dot",
        "%fusion.6": "jit(step)/jvp(block_1)/mlp/dot",
        # a container: left out, its body counted
        "%while.1": "jit(step)/jvp(loss_head)/while",
        "%copy.4": "",
    }
    ops = {
        name: {"seconds": 0.002, "count": 2, "target": ""}
        for name in stacks
    }
    events = [
        {"type": "train_step", "step": 3, "s6.state_rms_max": 0.004,
         "s6.decay_mean": 0.8, "s6.dt_mean": 0.02},
        {"type": "train_step", "step": 4, "s6.state_rms_max": 0.006,
         "s6.decay_mean": 0.8, "s6.dt_mean": 0.02},
        {"type": "train_step", "step": 9, "s6.state_rms_max": 7.0},
    ]
    run = fake_run(
        {"steps": 2, "ops": ops, "busy_s": 0.024}, events, tmp_path, stacks
    )

    def read(name):
        return loader.load_module("layer_metrics", name).read(run)

    # 0.002 s an operation over 2 traced steps: 1 ms each
    assert read("s6.scan_ms_per_step") == pytest.approx(3.0)
    assert read("s6.kernel_ms_per_step") == pytest.approx(2.0)
    assert read("s6.mix_ms_per_step") == pytest.approx(4.0)
    assert read("s6.proj_ms_per_step") == pytest.approx(2.0)
    assert read("s6.scan_roofline_pct") == pytest.approx(
        100 * 14.65e-3 / 3e-3, rel=1e-3
    )
    # the window's steps alone: step 9 is outside it
    assert read("s6.state_rms_max") == 0.006
    assert any("s6_fwd 1.000 ms in 1.0 calls" in n for n in run.notes)
    assert any("s6_bwd 1.000 ms in 1.0 calls" in n for n in run.notes)
    assert any("s6_conv 1.000 | 1.000 | 0.000" in n for n in run.notes)
    assert any("11.996 GB required" in n for n in run.notes)
    assert any("a lane remembers 5.0 tokens" in n for n in run.notes)
    # other scopes 1 ms (the SwiGLU), no name stack 1 ms, the %while
    # left out: 11 ms of the 12 ms the device was busy
    assert any("= 11.000 (every %while" in n for n in run.notes)
    # no accepted reader's pattern meets the new kernels' names
    for name in stacks:
        if "s6_" in name:
            assert not any(
                other in name for other in (
                    "conv_fwd", "conv_bwd", "kda_fwd", "kda_bwd", "gdn_",
                    "ssd_", "bcx_",
                )
            ) and not name.lstrip("%").startswith("attn")
