"""``lfm2_flops.py`` against a count written out part by part, the cut
configuration's ``flops.py`` keys against the counts they stand for,
and the new readers against a run that has nothing for them and
against a hand-made trace."""

import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import lfm2_flops  # noqa: E402
import loader  # noqa: E402

CUT = loader.load_json(os.path.join(BENCH, "configs", "lfm2_24b_a2b_cut.json"))
TOY = loader.load_json(os.path.join(BENCH, "configs", "toy_lfm2_moe.json"))
NEW_READERS = (
    "sconv.mix_ms_per_step", "sconv.kernel_ms_per_step",
    "sconv.mix_roofline_pct", "sconv.proj_ms_per_step", "sconv.out_rms_max",
)
SEQ = 8192


def test_the_layers_by_kind():
    assert lfm2_flops.conv_layers(CUT) == 7
    assert lfm2_flops.attention_layers(CUT) == 2
    assert lfm2_flops.expert_layers(CUT) == 8
    assert (
        lfm2_flops.conv_layers(TOY), lfm2_flops.attention_layers(TOY),
        lfm2_flops.expert_layers(TOY),
    ) == (3, 1, 3)


def test_the_mixers_required_bytes_are_eleven_arrays_a_layer():
    # forward B, C, u in and y out; backward those and dy in, three out
    forward, backward = 4 * 2048 * 2 * SEQ, 7 * 2048 * 2 * SEQ
    assert (forward, backward) == (134_217_728, 234_881_024)
    assert lfm2_flops.mix_bytes_per_step(CUT, 1, SEQ) == (
        7 * (forward + backward)
    ) == 2_583_691_264
    # 7 operations a channel and token forward, 15 backward, at K = 3
    assert lfm2_flops.mix_flops_per_step(CUT, 1, SEQ) == (
        22 * 2048 * SEQ * 7
    )
    least, bound = flops.roofline_seconds(
        lfm2_flops.mix_flops_per_step(CUT, 1, SEQ),
        lfm2_flops.mix_bytes_per_step(CUT, 1, SEQ), "TPU v5 lite",
    )
    assert bound == "bytes" and least == pytest.approx(3.1547e-3, rel=1e-3)


def test_the_matmul_parameters_a_token_meets():
    assert lfm2_flops.conv_params(CUT) == 2048 * 6144 + 2048 * 2048
    assert lfm2_flops.attention_params(CUT) == (
        2 * 2048 * 2048 + 2 * 2048 * 512
    ) == 10_485_760
    assert lfm2_flops.expected_share(CUT) == 0.25
    assert lfm2_flops.sparse_params(CUT, 0.25) == (
        2048 * 64 + 4 * 0.25 * 3 * 2048 * 1536
    )
    assert lfm2_flops.matmul_params(CUT) == (
        7 * 16_777_216 + 2 * 10_485_760 + 72_351_744
        + 8 * (131_072 + 9_437_184) + 134_217_728
    ) == 421_527_552
    # the counted share in uniform routing's place
    assert lfm2_flops.matmul_params(CUT, 0.3) > 421_527_552
    per_token = lfm2_flops.train_flops_per_token(CUT, SEQ)
    assert per_token == (
        6 * 421_527_552 + 2 * 6 * SEQ * 2048 + 22 * 2048 * 7
    )
    assert per_token * SEQ == pytest.approx(22.37e12, rel=1e-3)
    # the tied head's share of the required FLOPs at 9 of 40 layers
    assert 6 * 134_217_728 / per_token == pytest.approx(0.295, abs=2e-3)


def test_every_parameter_of_the_train_state():
    # 9.32 GB of state at 6 B a parameter
    assert lfm2_flops.total_params(CUT) == 1_554_072_320
    assert lfm2_flops.total_params(CUT) == (
        7 * 16_783_360 + 2 * 10_485_888 + 72_351_744
        + 8 * (150_994_944 + 131_072 + 64) + 134_217_728 + 19 * 2048
    )


def test_the_cuts_flops_py_keys_stand_for_these_counts():
    for cfg in (CUT, TOY):
        required = lfm2_flops.matmul_params(cfg)
        assert 0 <= required - flops.matmul_params(cfg) < 8 * cfg["n_embd"]
        assert cfg["n_layer"] == lfm2_flops.attention_layers(cfg)
        assert cfg["n_embd"] == cfg["hidden_size"]
        assert flops.attention_flops_per_token(cfg, 128) == (
            lfm2_flops.attention_flops_per_token(cfg, 128)
        )
    ratio = flops.train_flops_per_token(CUT, SEQ) / (
        lfm2_flops.train_flops_per_token(CUT, SEQ)
    )
    # flops.py has no key for the mixer's arithmetic (0.3 MFLOP a token)
    assert ratio == pytest.approx(0.99987, abs=2e-5) and ratio < 1
    # the twelve tensors of flops.py's attention bytes are all 2048
    # lanes wide, the kv heads' eight are 512: MORE than the step
    # moves, and the attention is bound by FLOPs all the same
    seconds, bound = flops.roofline_seconds(
        flops.attention_flops_per_step(CUT, 1, SEQ),
        flops.attention_bytes_per_step(CUT, 1, SEQ), "TPU v5 lite",
    )
    assert bound == "flops" and seconds == pytest.approx(8.37e-3, rel=1e-2)


def test_the_benchmark_lists_the_cell_and_its_readers():
    bench = loader.load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    (cell,) = [
        w for w in bench["workloads"] if w["name"] == "lfm2_moe_steady_8k"
    ]
    assert cell["config"] == "lfm2_24b_a2b_cut" and cell["chips"] == 1
    assert cell["traffic"] == "steady_8k"
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert listed[name]["workloads"] == ["lfm2_moe_steady_8k"]
        assert listed[name]["layer"] == "short convolution"
        reader = loader.load_module("layer_metrics", name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES,
                reader.SOURCE) == tuple(
            listed[name][k]
            for k in ("name", "unit", "layer", "moves", "source")
        )


def fake_run(trace, events, tmp_path=None, stacks=None):
    if stacks is not None:
        (tmp_path / "k.opnames.json").write_text(
            json.dumps({"op_names": stacks})
        )
        events = [
            {"type": "aot_cache", "key": "k", "dir": str(tmp_path)}
        ] + events
    notes = []
    return types.SimpleNamespace(
        config=CUT, traffic={"batch": 1, "seq": SEQ}, trace=trace,
        report={"window": {"steps": [{"step": 3}, {"step": 4}]},
                "device": {"kind": "TPU v5 lite"}},
        of=lambda type_, **match: [
            e for e in events if e["type"] == type_
        ],
        note=notes.append, flops=flops, notes=notes,
    )


@pytest.mark.parametrize("trace", [None, {"steps": 2, "ops": {}}])
def test_a_program_without_the_family_reports_nothing(trace):
    """The parent of PR 63: no ``sconv_*`` scope, kernel or counter."""
    run = fake_run(trace, [{"type": "train_step", "step": 3, "loss": 1.0}])
    for name in NEW_READERS:
        assert loader.load_module("layer_metrics", name).read(run) is None
    assert run.notes == []


def test_the_readers_sum_their_scopes_of_a_trace(tmp_path):
    stacks = {
        "%jvp_bcx_fwd_.3":
            "jit(step)/jvp(block_1)/short_conv/sconv_mix/pallas_call",
        "%bcx_fwd.9":
            "jit(step)/checkpoint/block_1/short_conv/sconv_mix/pallas_call",
        "%transpose_jvp_bcx_bwd__.1":
            "jit(step)/transpose(jvp(block_1))/short_conv/sconv_mix/pallas_call",
        "%fusion.1": "jit(step)/jvp(block_1)/short_conv/sconv_mix/reduce_sum",
        "%fusion.2": "jit(step)/jvp(block_1)/short_conv/sconv_proj/dot",
        "%fusion.3":
            "jit(step)/transpose(jvp(block_1))/short_conv/sconv_proj/dot",
        "%fusion.4": "jit(step)/jvp(block_1)/moe/moe_router/dot",
        # the ungated convolution's kernels are another reader's
        "%conv_fwd.2": "jit(step)/jvp(block_1)/mixer/ssm_conv/pallas_call",
        "%copy.4": "",
    }
    ops = {
        name: {"seconds": 0.002, "count": 2, "target": ""}
        for name in stacks
    }
    events = [
        {"type": "train_step", "step": 3, "sconv.out_rms_max": 0.004},
        {"type": "train_step", "step": 4, "sconv.out_rms_max": 0.006},
        {"type": "train_step", "step": 9, "sconv.out_rms_max": 7.0},
    ]
    run = fake_run({"steps": 2, "ops": ops}, events, tmp_path, stacks)

    def read(name):
        return loader.load_module("layer_metrics", name).read(run)

    # 0.002 s an operation over 2 traced steps: 1 ms each
    assert read("sconv.mix_ms_per_step") == pytest.approx(4.0)
    assert read("sconv.kernel_ms_per_step") == pytest.approx(3.0)
    assert read("sconv.proj_ms_per_step") == pytest.approx(2.0)
    assert read("sconv.mix_roofline_pct") == pytest.approx(
        100 * 3.1547e-3 / 4e-3, rel=1e-3
    )
    # the window's steps alone: step 9 is outside it
    assert read("sconv.out_rms_max") == 0.006
    assert any("bcx_fwd 2.000 ms in 2.0 calls" in n for n in run.notes)
    assert any("bcx_bwd 1.000 ms in 1.0 calls" in n for n in run.notes)
    assert any("2.584 GB required" in n for n in run.notes)
    # no accepted reader's pattern meets the new kernels' names
    for name in stacks:
        if "bcx" in name:
            assert not any(
                other in name for other in (
                    "conv_fwd", "conv_bwd", "kda_fwd", "kda_bwd", "gdn_",
                    "ssd_",
                )
            ) and not name.lstrip("%").startswith("attn")
