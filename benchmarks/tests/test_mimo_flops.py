"""``mimo_flops.py`` against a count written out layer by layer, the
cut configuration's ``flops.py`` keys against the counts they stand
for, and the new readers against a run that has nothing for them."""

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import loader  # noqa: E402
import mimo_flops  # noqa: E402

CUT = loader.load_json(os.path.join(BENCH, "configs", "mimo_v2_5_cut.json"))
NEW_READERS = (
    "swa.sink_roofline_pct", "attn.sink_ms_per_step",
    "attn.sink_mass_mean", "attn.qkv_ms_per_step",
)
SEQ = 8192
# keys a query of a window layer meets, mean over 8192 positions:
# rows 0..127 meet 1..128, the other 8064 meet 128
KEYS = (128 * 129 // 2 + (SEQ - 128) * 128) / SEQ


def test_the_window_layers_attention_layer_by_layer():
    assert KEYS == 127.0078125 == mimo_flops.mean_keys(SEQ, 128)
    # a head: QK^T and dP at 192, PV and dV at 128, 2 flops a
    # multiply-add, forward once and backward twice: 12 x keys x 160
    a_layer = 12 * KEYS * 16 * (192 + 128) / 2
    assert a_layer == 3_901_680.0
    assert mimo_flops.window_flops_per_token(CUT, SEQ) == 5 * a_layer
    assert (
        mimo_flops.window_flops_per_step(CUT, 1, SEQ)
        == 5 * a_layer * SEQ == 159_812_812_800
    )
    # bytes a token and layer: 16 query heads (q twice and dq at 192,
    # o twice and do at 128) and 2 kv heads (k, dk; v, dv alike)
    a_layer = (16 + 2) * 3 * (192 + 128) * 2
    assert mimo_flops.attention_bytes_per_token(CUT, 1) == 5 * a_layer
    assert (
        mimo_flops.window_bytes_per_step(CUT, 1, SEQ)
        == 5 * a_layer * SEQ == 1_415_577_600
    )
    # bound by the bytes on a v5e: 1.73 ms against 0.81 ms of FLOPs
    least, bound = flops.roofline_seconds(
        159_812_812_800, 1_415_577_600, "TPU v5 lite"
    )
    assert bound == "bytes" and least == pytest.approx(1.7284e-3, rel=1e-3)


def test_the_full_layers_attention_layer_by_layer():
    a_layer = 6 * SEQ * 16 * 160
    assert mimo_flops.full_flops_per_token(CUT, SEQ) == 2 * a_layer
    assert 2 * a_layer == 251_658_240
    # one kv head in a full layer
    assert mimo_flops.attention_bytes_per_token(CUT, 0) == (
        2 * (16 + 1) * 3 * 320 * 2
    )


def test_the_whole_step_layer_by_layer():
    full = 4096 * (16 * 192 + 192 + 128) + 16 * 128 * 4096
    window = 4096 * (16 * 192 + 2 * 192 + 2 * 128) + 16 * 128 * 4096
    dense = 3 * 4096 * 16384
    expert = 3 * 4096 * 2048
    sparse = 4096 * 256 + 8 * (8 / 256) * expert
    head = 19072 * 4096
    assert (full, window, dense, expert) == (
        22_282_240, 23_592_960, 201_326_592, 25_165_824
    )
    params = 2 * full + 5 * window + dense + 6 * sparse + head
    assert params == 486_014_976
    assert mimo_flops.matmul_params_per_token(CUT, 8 / 256) == params
    assert mimo_flops.expected_share(CUT) == 8 / 256
    attention = 251_658_240 + 5 * 3_901_680
    assert (
        mimo_flops.train_flops_per_token(CUT, SEQ)
        == 6 * params + attention == 3_187_256_496
    )
    # the held experts by COUNTED rows: twice the rows, twice the work
    held = mimo_flops.held_expert_flops_per_step(CUT, 1, SEQ, 8 / 256)
    assert held == 6 * (SEQ * 8 / 32) * expert * 6 == 1_855_425_871_872
    assert mimo_flops.held_expert_flops_per_step(
        CUT, 1, SEQ, 16 / 256
    ) == 2 * held


def test_the_flops_keys_count_just_under_what_the_step_requires():
    """``flops.py`` reads GPT-2's key names: the cut file's stand for
    the required counts from below (no integer width gives them to the
    FLOP: 271,166,640 / (6 x 8192) is 5516.9), so no share of a peak
    built on them can read high."""
    required = mimo_flops.train_flops_per_token(CUT, SEQ)
    counted = flops.train_flops_per_token(CUT, SEQ)
    assert counted == 3_187_163_712
    assert 0 < required - counted < 3e-5 * required
    attention = (
        mimo_flops.window_flops_per_token(CUT, SEQ)
        + mimo_flops.full_flops_per_token(CUT, SEQ)
    )
    assert attention == 271_166_640
    assert flops.attention_flops_per_token(CUT, SEQ) == 6 * SEQ * 5516
    assert 0 < attention - 6 * SEQ * 5516 < 6 * SEQ
    assert flops.matmul_params(CUT) == 486_006_880
    assert 0 < 486_014_976 - flops.matmul_params(CUT) < 14 * 788
    both = sum(mimo_flops.attention_bytes_per_token(CUT, k) for k in (0, 1))
    assert flops.attention_bytes_per_step(CUT, 1, SEQ) < both * SEQ


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_is_silent_where_there_is_nothing_to_read(name):
    """A run of another family, untraced and with no counter: every
    new reader returns None and raises nothing."""
    reader = loader.load_module("layer_metrics", name)
    run = types.SimpleNamespace(
        config={"model_type": "gpt2"}, traffic={"batch": 1, "seq": 8},
        trace=None, report={"window": {"steps": [{"step": 3}]}},
        of=lambda type_, **match: [{"step": 3, "loss": 1.0}],
        note=lambda line: None, flops=flops,
    )
    assert reader.read(run) is None
    assert reader.LAYER == "window attention"


def test_the_step_by_scope_counts_every_operation_once(tmp_path):
    """A loop's ``%while`` holds its body's operations, which the
    trace has too: the container is left out, a flash kernel goes
    under its layer kind and not under a scope its stack also holds,
    and the parts add up to the operations' time."""
    import json

    stacks = {
        "%attn.1": "jit(step)/jvp(block_1)/swa/attn/pallas_call",
        "%attn.2": "jit(step)/jvp(block_0)/full_attn/attn/pallas_call",
        "%fusion.1": "jit(step)/jvp(block_1)/swa/attn/attn_qkv/dot",
        "%fusion.2": "jit(step)/jvp(loss_head)/while/body/dot",
        "%while.1": "jit(step)/jvp(loss_head)/while",
        "%fusion.3": "jit(step)/jvp(block_1)/swa/add",
        "%copy.4": "",
    }
    (tmp_path / "k.opnames.json").write_text(
        json.dumps({"op_names": stacks})
    )
    ops = {
        name: {"seconds": 0.002, "count": 2, "target": (
            "tpu_custom_call" if name.startswith("%attn") else ""
        )} for name in stacks
    }
    ops["%while.1"]["seconds"] = 0.5
    run = types.SimpleNamespace(
        trace={"steps": 2, "ops": ops, "busy_s": 0.012},
        of=lambda type_, **match: [{"key": "k", "dir": str(tmp_path)}],
    )
    assert mimo_flops.step_by_scope(run) == {
        "swa kernels": 1.0, "full_attn kernels": 1.0, "attn_qkv": 1.0,
        "loss_head": 1.0, "other named": 1.0, "unnamed": 1.0,
    }
