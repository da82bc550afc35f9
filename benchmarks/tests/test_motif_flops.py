"""``motif_flops.py`` against a count written out part by part, the cut
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
import loader  # noqa: E402
import motif_flops  # noqa: E402

CUT = loader.load_json(os.path.join(BENCH, "configs", "motif_3_beta_cut.json"))
NEW_READERS = {
    "mhc.mix_ms_per_step": "residual streams",
    "mhc.mix_roofline_pct": "residual streams",
    "mhc.res_sum_err_max": "residual streams",
    "gdla.proj_ms_per_step": "differential attention",
    "gdla.diff_ms_per_step": "differential attention",
    "gdla.lambda_mean": "differential attention",
    "moe.polynorm_expert_roofline_pct": "experts",
    "mtp.ms_per_step": "prediction layer",
}
SEQ = 8192
KEYS = (128 * 129 // 2 + (SEQ - 128) * 128) / SEQ


def test_the_streams_mixing_by_sub_layer_and_pass():
    # six blocks (five of the stack, the prediction layer's), two
    # sub-layers each; a pass reads and writes 4 x 4096 bf16 values a
    # token; forward and backward
    assert motif_flops.blocks(CUT) == 6
    a_pass = 2 * 4 * 4096 * 2
    assert motif_flops.mix_bytes_per_step(CUT, 1, SEQ) == (
        12 * 2 * a_pass * SEQ
    ) == 12_884_901_888
    # phi is 16384 x 24; the three mixes are 4 + 16 + 4 multiply-adds
    # over 4096 lanes
    per_token = 6 * 16384 * 24 + 3 * 2 * 4096 * 24
    assert motif_flops.mix_flops_per_step(CUT, 1, SEQ) == 12 * per_token * SEQ
    least, bound = flops.roofline_seconds(
        motif_flops.mix_flops_per_step(CUT, 1, SEQ),
        motif_flops.mix_bytes_per_step(CUT, 1, SEQ), "TPU v5 lite",
    )
    assert bound == "bytes" and least == pytest.approx(15.732e-3, rel=1e-3)


def test_the_differential_attention_by_layer_kind():
    assert KEYS == 127.0078125 == motif_flops.mean_keys(SEQ, 128)
    assert motif_flops.kinds(CUT) == [1, 1, 1, 1, 0, 0]
    # 20 query heads (16 signal, 4 noise: a noise head is a head like
    # any other to the kernels) at (192 + 128) / 2 lanes
    window = 12 * KEYS * 20 * 160
    assert motif_flops.window_flops_per_token(CUT, SEQ) == 4 * window
    full = 6 * SEQ * 20 * 160
    assert motif_flops.full_flops_per_token(CUT, SEQ) == 2 * full
    assert 4 * window + 2 * full == 334_081_200
    # bytes: 20 query heads and 4 latent kv heads, three passes each
    a_layer = (20 + 4) * 3 * (192 + 128) * 2
    assert motif_flops.attention_bytes_per_token(CUT, 1) == 4 * a_layer
    assert motif_flops.attention_bytes_per_token(CUT, 0) == 2 * a_layer
    # one layer's projections at 4 of 16 kv groups
    assert motif_flops.attention_matmul_params(CUT) == (
        4096 * 1024 + 1024 * 20 * 192 + 4096 * (512 + 64)
        + 512 * 4 * 256 + 4096 * 16 + 4096 * 2048 + 2048 * 4096
    ) == 27_852_800


def test_the_polynorm_experts_at_their_counted_rows():
    share = motif_flops.expected_share(CUT)
    assert share == 8 / 384
    rows = share * SEQ * 8
    assert rows == pytest.approx(1365.33, rel=1e-4)
    an_expert = 3 * 4096 * 1280
    assert motif_flops.sparse_layers(CUT) == 5
    assert motif_flops.polynorm_expert_flops_per_step(
        CUT, 1, SEQ, share
    ) == pytest.approx(6 * rows * an_expert * 5)
    per_matrix = 3 * (rows * 4096 + rows * 1280 + 8 * 4096 * 1280)
    assert motif_flops.polynorm_expert_bytes_per_step(
        CUT, 1, SEQ, share
    ) == pytest.approx(3 * per_matrix * 2 * 5)
    # at uniform routing the weights' bytes bound it; at the share
    # the window's end reads (a seventh of the assignments) the FLOPs
    _, bound = flops.roofline_seconds(
        motif_flops.polynorm_expert_flops_per_step(CUT, 1, SEQ, share),
        motif_flops.polynorm_expert_bytes_per_step(CUT, 1, SEQ, share),
        "TPU v5 lite",
    )
    assert bound == "bytes"
    _, bound = flops.roofline_seconds(
        motif_flops.polynorm_expert_flops_per_step(CUT, 1, SEQ, 0.17),
        motif_flops.polynorm_expert_bytes_per_step(CUT, 1, SEQ, 0.17),
        "TPU v5 lite",
    )
    assert bound == "flops"


def test_the_whole_step_and_the_keys_flops_py_reads():
    """``model.mfu_pct`` goes through ``flops.py``'s GPT-2 names: the
    cut's ``n_layer`` / ``n_embd`` / ``n_inner`` are chosen so that
    its two counts land just under what the step requires, the head
    counted TWICE (two passes a step)."""
    share = motif_flops.expected_share(CUT)
    required = motif_flops.matmul_params_per_token(CUT, share)
    assert required == (
        6 * (27_852_800 + 2 * 16384 * 24) + 3 * 4096 * 12288
        + 5 * (4096 * 384 + 3 * 4096 * 1280 * (1 + 8 * 8 / 384))
        + 8192 * 4096 + 2 * 27520 * 4096
    )
    assert round(required) == 681_443_328
    counted = flops.matmul_params(CUT)
    assert 0 < required - counted < 12 * CUT["n_embd"]
    attention = (
        motif_flops.window_flops_per_token(CUT, SEQ)
        + motif_flops.full_flops_per_token(CUT, SEQ)
    )
    assert 0 < attention - flops.attention_flops_per_token(CUT, SEQ) < (
        36 * SEQ
    )
    whole = motif_flops.train_flops_per_token(CUT, SEQ)
    assert whole == 6 * required + attention
    assert 0 < whole - flops.train_flops_per_token(CUT, SEQ) < 1e-4 * whole
    assert round(whole * SEQ / 1e12, 2) == 36.23


@pytest.mark.parametrize("name", list(NEW_READERS))
def test_a_reader_is_silent_where_there_is_nothing_to_read(name):
    """A run of another family, untraced and with no counter (the
    parent of PR 57 on any cell): every new reader returns None and
    raises nothing."""
    reader = loader.load_module("layer_metrics", name)
    run = types.SimpleNamespace(
        config={"model_type": "gpt2"}, traffic={"batch": 1, "seq": 8},
        trace=None, report={"window": {"steps": [{"step": 3}]}},
        of=lambda type_, **match: [{"step": 3, "loss": 1.0}],
        note=lambda line: None, flops=flops,
    )
    assert reader.read(run) is None
    assert reader.LAYER == NEW_READERS[name]
    assert reader.MOVES == "tokens_per_s"


def test_the_readers_sum_their_scopes_of_a_trace(tmp_path):
    stacks = {
        "%fusion.1": "jit(step)/jvp(block_1)/mhc_attn/mhc_coeff/dot",
        "%fusion.2": "jit(step)/jvp(block_1)/mhc_attn/mhc_sinkhorn/div",
        "%fusion.3": "jit(step)/transpose(jvp(block_1))/mhc_mix/mul",
        "%fusion.4": "jit(step)/jvp(block_1)/swa/attn/gdla_diff/sub",
        "%fusion.5": "jit(step)/jvp(block_1)/swa/attn/gdla_gate/mul",
        "%fusion.6": "jit(step)/jvp(block_1)/swa/attn/gdla_kv/dot",
        "%fusion.7": "jit(step)/mtp/jvp(mtp)/block/mhc_attn/mhc_mix/mul",
        "%gmm_up_fwd.1": "jit(step)/jvp(block_1)/moe/moe_experts/pallas_call",
        "%copy.4": "",
    }
    (tmp_path / "k.opnames.json").write_text(
        json.dumps({"op_names": stacks})
    )
    ops = {
        name: {"seconds": 0.002, "count": 2, "target": ""}
        for name in stacks
    }
    events = [
        {"type": "aot_cache", "key": "k", "dir": str(tmp_path)},
        {"type": "train_step", "step": 3, "moe.held_rows_share": 0.05,
         "mhc.res_sum_err_max": 2e-7, "gdla.lambda_mean": 0.5,
         "gdla.noise_share": 0.4, "mtp.loss": 6.0},
    ]
    notes = []
    run = types.SimpleNamespace(
        config=CUT, traffic={"batch": 1, "seq": SEQ},
        trace={"steps": 2, "ops": ops, "busy_s": 0.018},
        report={"window": {"steps": [{"step": 3}]},
                "device": {"kind": "TPU v5 lite"}},
        of=lambda type_, **match: [
            e for e in events if e["type"] == type_
        ],
        note=notes.append, flops=flops,
    )

    def read(name):
        return loader.load_module("layer_metrics", name).read(run)

    # (the prediction layer's mixing counts under both: a scope's
    # metric sums what carries the scope)
    assert read("mhc.mix_ms_per_step") == pytest.approx(4.0)
    assert read("mhc.mix_roofline_pct") == pytest.approx(
        100 * 15.7325e-3 / 4e-3, rel=1e-3
    )
    assert read("gdla.diff_ms_per_step") == pytest.approx(2.0)
    assert read("gdla.proj_ms_per_step") == pytest.approx(1.0)
    assert read("mtp.ms_per_step") == pytest.approx(1.0)
    assert read("mhc.res_sum_err_max") == 2e-7
    assert read("gdla.lambda_mean") == 0.5
    least, _ = flops.roofline_seconds(
        motif_flops.polynorm_expert_flops_per_step(CUT, 1, SEQ, 0.05),
        motif_flops.polynorm_expert_bytes_per_step(CUT, 1, SEQ, 0.05),
        "TPU v5 lite",
    )
    assert read("moe.polynorm_expert_roofline_pct") == pytest.approx(
        100 * least / 1e-3
    )
    assert any("lambda 0.50000" in line for line in notes)
