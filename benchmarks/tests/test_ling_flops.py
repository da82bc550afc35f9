"""``ling_flops.py`` against a count written out part by part, the cut
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
import ling_flops  # noqa: E402
import loader  # noqa: E402

CUT = loader.load_json(os.path.join(BENCH, "configs", "ling_3_flash_cut.json"))
TOY = loader.load_json(os.path.join(BENCH, "configs", "toy_ling.json"))
NEW_READERS = {
    "kda.rule_ms_per_step": "linear attention",
    "kda.kernel_ms_per_step": "linear attention",
    "kda.rule_roofline_pct": "linear attention",
    "kda.mix_ms_per_step": "linear attention",
    "kda.log_decay_min": "linear attention",
    "moe.group_route_ms_per_step": "experts",
    "moe.groups_per_token_mean": "experts",
}
SEQ = 8192


def test_the_layers_by_kind():
    assert ling_flops.kinds(CUT) == ["kda"] * 6 + ["latent"]
    assert ling_flops.kda_layers(CUT) == 6
    assert ling_flops.latent_layers(CUT) == 1
    assert ling_flops.expert_layers(CUT) == 6
    assert ling_flops.kinds(TOY) == ["kda", "kda", "latent"]


def test_the_rules_required_work_is_the_recurrences():
    # a token and head: 6 x 128 x 128 forward, twice that backward
    per_head = 18 * 128 * 128
    assert ling_flops.rule_flops_per_step(CUT, 1, SEQ) == (
        per_head * 32 * SEQ * 6
    ) == 463_856_467_968
    # forward: q, k, v (2 B x 128 each), g (4 B x 128), beta, o;
    # backward: those and do read, the five gradients written
    forward = 3 * 256 + 512 + 4 + 256
    backward = forward + (3 * 256 + 512 + 4)
    assert forward + backward == 4364
    assert ling_flops.rule_bytes_per_step(CUT, 1, SEQ) == (
        4364 * 32 * SEQ * 6
    ) == 6_863_978_496
    least, bound = flops.roofline_seconds(
        ling_flops.rule_flops_per_step(CUT, 1, SEQ),
        ling_flops.rule_bytes_per_step(CUT, 1, SEQ), "TPU v5 lite",
    )
    assert bound == "bytes" and least == pytest.approx(8.381e-3, rel=1e-3)


def test_the_chunk_wise_form_computes_more_than_the_recurrence():
    chunked = ling_flops.chunk_flops_per_step(CUT, 1, SEQ)
    assert chunked == pytest.approx(0.878e12, rel=1e-3)
    assert chunked > ling_flops.rule_flops_per_step(CUT, 1, SEQ)
    # by hand at chunk 128, d 128: pairs below 8128, on and below 8256
    forward = (
        2 * 128 * (8128 + 8256) + 128 ** 3 / 3 + 2 * 8256 * 256
        + 3 * 2 * 128 * 128 * 128 + 2 * 8256 * 128
    )
    assert chunked == 3 * forward * 64 * 32 * 6
    # a smaller chunk computes less inside and hands over as often a token
    assert ling_flops.chunk_flops_per_step(CUT, 1, SEQ, chunk=64) < chunked


def test_the_matmul_parameters_a_token_meets():
    assert ling_flops.kda_params(CUT) == 6 * 2560 * 4096 + 2560 * 32
    assert ling_flops.kda_params(CUT) == 62_996_480
    assert ling_flops.latent_params(CUT) == (
        2560 * 6144 + 2560 * 576 + 512 * 8192 + 2560 * 32 + 4096 * 2560
    ) == 31_965_184
    assert ling_flops.expected_share(CUT) == 1 / 32
    assert ling_flops.sparse_params(CUT, 1 / 32) == (
        2560 * 512 + 3 * 2560 * 768 + 8 / 32 * 3 * 2560 * 768
    )
    assert ling_flops.matmul_params(CUT) == 609_828_864
    # the counted share in uniform routing's place
    assert ling_flops.matmul_params(CUT, 0.04) > 609_828_864
    per_token = ling_flops.train_flops_per_token(CUT, SEQ)
    assert per_token == (
        6 * 609_828_864 + 6 * SEQ * 32 * 160 + 18 * 128 * 128 * 32 * 6
    )
    assert per_token * SEQ == pytest.approx(32.50e12, rel=1e-3)


def test_the_cuts_flops_py_keys_stand_for_these_counts():
    for cfg in (CUT, TOY):
        required = ling_flops.matmul_params(cfg)
        assert 0 <= required - flops.matmul_params(cfg) < 2 * cfg["n_embd"]
        assert cfg["n_layer"] == ling_flops.latent_layers(cfg)
        assert cfg["n_embd"] == ling_flops.latent_lanes(cfg)
        assert flops.attention_flops_per_token(cfg, 128) == (
            ling_flops.attention_flops_per_token(cfg, 128)
        )
    ratio = flops.train_flops_per_token(CUT, SEQ) / (
        ling_flops.train_flops_per_token(CUT, SEQ)
    )
    assert ratio == pytest.approx(0.98572, abs=2e-5)


def test_the_benchmark_lists_the_cell_and_its_readers():
    bench = loader.load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    (cell,) = [
        w for w in bench["workloads"] if w["name"] == "ling_3_steady_8k"
    ]
    assert cell["config"] == "ling_3_flash_cut" and cell["chips"] == 1
    assert cell["traffic"] == "steady_8k"
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name, layer in NEW_READERS.items():
        assert listed[name]["workloads"] == ["ling_3_steady_8k"]
        assert listed[name]["layer"] == layer
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
    """The parent of PR 59: no ``kda_*`` scope, kernel or counter."""
    run = fake_run(trace, [{"type": "train_step", "step": 3, "loss": 1.0}])
    for name in NEW_READERS:
        assert loader.load_module("layer_metrics", name).read(run) is None
    assert run.notes == []


def test_the_readers_sum_their_scopes_of_a_trace(tmp_path):
    stacks = {
        "%fusion.1": "jit(step)/jvp(block_1)/kda/kda_rule/cumsum",
        "%jvp_kda_fwd_.3": "jit(step)/jvp(block_1)/kda/kda_rule/pallas_call",
        "%transpose_jvp_kda_bwd__.1":
            "jit(step)/transpose(jvp(block_1))/kda/kda_rule/pallas_call",
        "%fusion.2": "jit(step)/jvp(block_1)/kda/kda_conv/mul",
        "%fusion.3": "jit(step)/jvp(block_1)/kda/kda_gates/exp",
        "%fusion.4": "jit(step)/transpose(jvp(block_1))/kda/kda_norm/mul",
        "%fusion.5": "jit(step)/jvp(block_1)/moe/moe_router/dot",
        "%fusion.6": "jit(step)/jvp(block_1)/moe/moe_group_select/top_k",
        "%fusion.7": "jit(step)/jvp(block_1)/kda/kda_proj/dot",
        "%copy.4": "",
    }
    ops = {
        name: {"seconds": 0.002, "count": 2, "target": ""}
        for name in stacks
    }
    events = [
        {"type": "train_step", "step": 3, "kda.log_decay_min": -4.5,
         "kda.state_rms_max": 0.2, "moe.groups_per_token_mean": 3.9},
        {"type": "train_step", "step": 4, "kda.log_decay_min": -5.0,
         "kda.state_rms_max": 0.1, "moe.groups_per_token_mean": 4.0},
        {"type": "train_step", "step": 9, "kda.log_decay_min": -7.0,
         "moe.groups_per_token_mean": 1.0},
    ]
    run = fake_run({"steps": 2, "ops": ops}, events, tmp_path, stacks)

    def read(name):
        return loader.load_module("layer_metrics", name).read(run)

    # 0.002 s an operation over 2 traced steps: 1 ms each
    assert read("kda.rule_ms_per_step") == pytest.approx(3.0)
    assert read("kda.kernel_ms_per_step") == pytest.approx(2.0)
    assert read("kda.mix_ms_per_step") == pytest.approx(3.0)
    assert read("moe.group_route_ms_per_step") == pytest.approx(2.0)
    assert read("kda.rule_roofline_pct") == pytest.approx(
        100 * 8.381e-3 / 3e-3, rel=1e-3
    )
    # the window's steps alone: step 9 is outside it
    assert read("kda.log_decay_min") == -5.0
    assert read("moe.groups_per_token_mean") == pytest.approx(3.95)
    assert any("kda_fwd 1.000 ms in 1.0 calls" in n for n in run.notes)
    assert any("kda.state_rms_max at most 0.20000" in n for n in run.notes)
