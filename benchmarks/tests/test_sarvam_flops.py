"""``sarvam_flops.py`` against hand-worked numbers, the cut
configuration's ``flops.py`` keys against the counts they stand for,
and the new readers against a run that has nothing for them."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import loader  # noqa: E402
import sarvam_flops  # noqa: E402

CUT = loader.load_json(
    os.path.join(BENCH, "configs", "sarvam_105b_cut.json")
)
NEW_READERS = (
    "mla.proj_ms_per_step", "mla.rope_ms_per_step",
    "moe.held_expert_ms_per_step", "moe.held_expert_roofline_pct",
    "moe.held_route_ms_per_step", "moe.shared_ms_per_step",
    "moe.held_rows_share", "moe.bias_abs_max",
)


def test_held_expert_work_from_the_counted_share():
    cfg = {
        "hidden_size": 4096, "moe_intermediate_size": 2048,
        "num_experts": 8, "router_outputs": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 5,
        "first_k_dense_replace": 1,
    }
    assert sarvam_flops.expert_layers(cfg) == 4
    assert sarvam_flops.assignments(cfg, 1, 8192) == 65536
    assert sarvam_flops.expected_share(cfg) == 0.0625
    assert sarvam_flops.held_rows(cfg, 1, 8192, 0.0625) == 4096
    # 6 x 4096 rows x 3 x 4096 x 2048 x 4 layers
    assert sarvam_flops.held_expert_flops_per_step(
        cfg, 1, 8192, 0.0625
    ) == 6 * 4096 * 3 * 4096 * 2048 * 4 == 2_473_901_162_496
    # a matrix, three passes: rows x 4096 + rows x 2048 + 8 x 4096 x
    # 2048 elements each; three matrices, 2 bytes, 4 layers
    per_matrix = 3 * (4096 * 4096 + 4096 * 2048 + 8 * 4096 * 2048)
    assert sarvam_flops.held_expert_bytes_per_step(
        cfg, 1, 8192, 0.0625
    ) == 3 * per_matrix * 2 * 4 == 6_643_777_536
    # bound by the FLOPs on a v5e: 12.56 ms against 8.11 ms
    least, bound = flops.roofline_seconds(
        2_473_901_162_496, 6_643_777_536, "TPU v5 lite"
    )
    assert bound == "flops" and least == pytest.approx(0.012558, rel=1e-3)
    # twice the rows: twice the FLOPs, the weights' bytes unchanged
    assert sarvam_flops.held_expert_flops_per_step(
        cfg, 1, 8192, 0.125
    ) == 2 * 2_473_901_162_496
    assert sarvam_flops.held_expert_bytes_per_step(
        cfg, 1, 8192, 0.125
    ) == 3 * 3 * (8192 * 6144 + 8 * 4096 * 2048) * 2 * 4


def test_the_cut_configurations_flops_keys_count_what_they_say():
    """``n_embd`` 2560 = 16 x 160 makes attention's FLOPs and bytes
    exact; ``n_inner`` makes ``matmul_params`` the matmul parameters
    a token requires on this chip (to the rounding of one width)."""
    heads, seq = CUT["num_attention_heads"], 8192
    qk = CUT["qk_nope_head_dim"] + CUT["qk_rope_head_dim"]
    dv = CUT["v_head_dim"]
    layers = CUT["num_hidden_layers"]
    assert CUT["n_embd"] == heads * (qk + dv) // 2 == 2560
    # forward: QK^T 2 seq qk + PV 2 seq dv a head and token; backward
    # twice that; causal halves it
    want = layers * heads * 3 * (2 * seq * qk + 2 * seq * dv) / 2
    assert flops.attention_flops_per_token(CUT, seq) == want
    # q k (192) v o (128) forward; q k v o do, dq dk dv backward
    lanes = (2 * qk + 2 * dv) + (2 * qk + 3 * dv) + (2 * qk + dv)
    assert flops.attention_bytes_per_step(CUT, 1, seq) == (
        layers * heads * seq * lanes * 2
    )
    h = CUT["hidden_size"]
    attention = (
        h * heads * qk + h * (CUT["kv_lora_rank"] + 64)
        + CUT["kv_lora_rank"] * heads * (128 + dv) + heads * dv * h
    )
    assert attention == 25_427_968
    dense = 3 * h * CUT["intermediate_size"]
    expert = 3 * h * CUT["moe_intermediate_size"]
    routed = (
        CUT["num_experts_per_tok"] * expert
        * sarvam_flops.expected_share(CUT)
    )
    required = (
        layers * attention + dense
        + 4 * (expert + h * CUT["router_outputs"] + routed)
        + CUT["vocab_size"] * h
    )
    assert required == 615_776_256
    assert abs(flops.matmul_params(CUT) - required) <= 2 * 5 * 2560
    assert flops.train_flops_per_token(CUT, seq) / 1e9 == pytest.approx(
        4.32, abs=0.01
    )
    assert CUT["reduced"] == [
        "num_hidden_layers", "num_attention_heads", "num_experts",
        "vocab_size",
    ]
    assert CUT["num_experts"] + CUT["first_expert_held"] <= 128


def test_the_benchmark_lists_the_cell_and_its_readers():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [
        w for w in bench["workloads"] if w["name"] == "sarvam_steady_8k"
    ]
    assert cell == bench["workloads"][-1] and cell["chips"] == 1
    assert cell["config"] == "sarvam_105b_cut"
    assert cell["traffic"] == "steady_8k"
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-8:]] == list(NEW_READERS)
    for name in NEW_READERS:
        assert listed[name]["workloads"] == ["sarvam_steady_8k"]
        reader = loader.load_module("layer_metrics", name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES,
                reader.SOURCE) == tuple(
            listed[name][k]
            for k in ("name", "unit", "layer", "moves", "source")
        )


class NoTrace:
    """A run of a program that has none of this: no trace, no
    counter on its events."""

    trace = None
    config = CUT
    traffic = {"batch": 1, "seq": 8192}
    report = {"window": {"steps": [{"step": 5}]},
              "device": {"kind": "TPU v5 lite"}}
    flops = flops

    def of(self, type_, **match):
        return [{"step": 5, "loss": 1.0}] if type_ == "train_step" else []

    def note(self, line):
        raise AssertionError(f"a reader with nothing to read noted {line}")


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert loader.load_module("layer_metrics", name).read(NoTrace()) is None


def test_the_counter_is_read_as_the_windows_median():
    class Run(NoTrace):
        def of(self, type_, **match):
            return [
                {"step": 4, "moe.held_rows_share": 0.9},   # not the window's
                {"step": 5, "moe.held_rows_share": 0.0610},
                {"step": 6, "moe.held_rows_share": 0.0630},
                {"step": 7, "moe.held_rows_share": 0.0650},
            ]

        def note(self, line):
            self.noted = line

    run = Run()
    run.report = {"window": {"steps": [{"step": s} for s in (5, 6, 7)]}}
    reader = loader.load_module("layer_metrics", "moe.held_rows_share")
    assert reader.read(run) == 0.0630
    assert "0.06250" in run.noted
