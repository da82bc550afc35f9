"""``flops.py`` against values worked by hand from the published
sizes of GPT-2-XL (48 layers, hidden 1600, inner 6400, vocabulary
padded to 50304, 1024 positions)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_matmul_params_by_hand():
    # per block 12 h^2 = 12 x 1600^2 = 30.72 M; head 50304 x 1600
    assert flops.matmul_params(config("gpt2_xl")) == (
        48 * 30_720_000 + 80_486_400
    )
    assert flops.matmul_params(config("gpt2_xl_12l")) == (
        12 * 30_720_000 + 80_486_400
    )


def test_total_params_match_the_model_card():
    # 1,557,686,400 at 48 layers; 451,017,600 at 12
    assert round(flops.total_params(config("gpt2_xl")) / 1e9, 3) == 1.558
    assert round(
        flops.total_params(config("gpt2_xl_12l")) / 1e9, 3
    ) == 0.451


@pytest.mark.parametrize("name,gflop", [
    # 6 x 1,555,046,400 + 6 x 48 x 1024 x 1600 = 9.8021 GFLOP
    ("gpt2_xl", 9.80),
    # 6 x 449,126,400 + 6 x 12 x 1024 x 1600 = 2.8127 GFLOP
    ("gpt2_xl_12l", 2.81),
])
def test_train_flops_per_token(name, gflop):
    got = flops.train_flops_per_token(config(name), 1024)
    assert round(got / 1e9, 2) == gflop


def test_attention_step_by_hand():
    cfg = config("gpt2_xl")
    # 6 x batch x seq^2 x h per layer: 6 x 4 x 1024^2 x 1600 x 48
    assert flops.attention_flops_per_step(cfg, 4, 1024) == (
        6 * 4 * 1024 * 1024 * 1600 * 48
    )
    # 12 tensors of 4 x 1024 x 1600 bf16 per layer
    assert flops.attention_bytes_per_step(cfg, 4, 1024) == (
        12 * 4 * 1024 * 1600 * 2 * 48
    )


def test_roofline_names_the_binding_peak():
    # 197e12 FLOP at the bf16 peak take 1 s; 819e9 bytes take 1 s
    assert flops.roofline_seconds(197e12, 1.0, "TPU v5 lite") == (
        1.0, "flops"
    )
    assert flops.roofline_seconds(1.0, 2 * 819e9, "TPU v5 lite") == (
        2.0, "bytes"
    )
    # flash attention at head size 64 is bound by FLOPs, barely:
    # 40.27 GFLOP / 197e12 = 0.204 ms against 157 MB / 819e9 = 0.192
    cfg = dict(config("gpt2_xl"), n_layer=1)
    seconds, bound = flops.roofline_seconds(
        flops.attention_flops_per_step(cfg, 4, 1024),
        flops.attention_bytes_per_step(cfg, 4, 1024),
        "TPU v5 lite",
    )
    assert bound == "flops" and round(seconds * 1e3, 3) == 0.204


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peak("cpu")
