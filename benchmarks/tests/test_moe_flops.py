"""``moe_flops.py`` against values worked by hand from the published
sizes of OLMoE-1B-7B (hidden 2048, 64 experts of 1024, top-8,
vocabulary 50304) at the cut's 3 layers and the cell's 2 x 4096
tokens; the five GPT-2 keys that ``flops.py`` reads from the new
configuration; and the four ``moe.*`` readers on hand-made traces."""

import json
import os

import pytest

from test_scopes import Run

import flops
import loader
import moe_flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "configs", "olmoe_1b_7b_cut.json")) as f:
    CFG = json.load(f)
with open(os.path.join(BENCH, "traffic", "steady_4k.json")) as f:
    TRAFFIC = json.load(f)


def test_expert_flops_and_bytes_by_hand():
    # 8192 tokens x 8 = 65536 rows a layer; 3 x 2048 x 1024 = 6.29 M
    # parameters a row; x 6 x 3 layers = 7.42 TFLOP
    assert moe_flops.routed_rows(CFG, 2, 4096) == 65536
    assert moe_flops.expert_params_per_row(CFG) == 6_291_456
    assert moe_flops.expert_flops_per_step(CFG, 2, 4096) == (
        6 * 65536 * 6_291_456 * 3
    )
    # a matrix: rows x 2048 + rows x 1024 + 64 x 2048 x 1024 elements,
    # each three times; three matrices, 2 bytes, 3 layers = 18.12 GB
    elements = 65536 * 2048 + 65536 * 1024 + 64 * 2048 * 1024
    assert moe_flops.expert_bytes_per_step(CFG, 2, 4096) == (
        3 * 3 * elements * 2 * 3
    )
    least, bound = flops.roofline_seconds(
        moe_flops.expert_flops_per_step(CFG, 2, 4096),
        moe_flops.expert_bytes_per_step(CFG, 2, 4096), "TPU v5 lite",
    )
    # 7.42e12 / 197e12 = 37.7 ms against 18.1e9 / 819e9 = 22.1 ms
    assert bound == "flops" and round(least * 1e3, 1) == 37.7


def test_flops_py_counts_this_models_active_parameters():
    """``model.mfu_pct`` and ``kernel.flash_roofline_pct`` call
    ``flops.py`` with GPT-2's key names in every cell: the file's
    ``n_*`` keys make it count what a trained token of THIS model
    requires, worked here from the HF keys."""
    h, width = CFG["hidden_size"], CFG["intermediate_size"]
    layers, k = CFG["num_hidden_layers"], CFG["num_experts_per_tok"]
    active = layers * (
        4 * h * h                    # q, k, v, o
        + k * 3 * h * width          # eight SwiGLU experts
        + CFG["num_experts"] * h     # the router
    ) + CFG["vocab_size"] * h        # the untied head
    assert flops.matmul_params(CFG) == active
    seq = TRAFFIC["seq"]
    attention = 6 * layers * seq * (
        CFG["num_attention_heads"] * (h // CFG["num_attention_heads"])
    )
    assert flops.train_flops_per_token(CFG, seq) == 6 * active + attention
    # 16.2 TFLOP a step of 8192 tokens
    assert round(flops.train_flops_per_token(CFG, seq) * 8192 / 1e12, 1) == 16.2
    assert flops.head_dim(CFG) == 128
    assert (CFG["n_layer"], CFG["n_embd"], CFG["n_head"],
            CFG["n_positions"]) == (3, 2048, 16, 4096)
    for key in ("n_layer", "n_embd", "n_head", "n_positions", "n_inner"):
        assert "read by flops.py only" in CFG["assumed"][key]


def test_the_configuration_keeps_every_published_width():
    published = {
        "hidden_size": 2048, "intermediate_size": 1024,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8,
        "vocab_size": 50304, "max_position_embeddings": 4096,
        "rope_theta": 10000, "rms_norm_eps": 1e-05,
        "norm_topk_prob": False, "tie_word_embeddings": False,
        "attention_bias": False, "clip_qkv": None,
        "hidden_act": "silu", "rope_scaling": None,
        "model_type": "olmoe",
    }
    for key, value in published.items():
        assert CFG[key] == value, key
    assert CFG["reduced"] == ["num_hidden_layers"]


# -- the readers -------------------------------------------------------------------

STACKS = {
    "%fusion.1": "jit(step_fn)/forward_backward/jvp(Olmoe)/block_0/moe/moe_router/dot_general",
    "%fusion.2": "jit(step_fn)/forward_backward/jvp(Olmoe)/block_0/moe/moe_dispatch/gather",
    "%fusion.3": "jit(step_fn)/forward_backward/jvp(Olmoe)/block_0/moe/moe_experts/mul",
    "%fusion.4": "jit(step_fn)/forward_backward/transpose(jvp(Olmoe))/block_0/moe/moe_combine/gather",
    "%fusion.5": "jit(step_fn)/optimizer/mul",
    "%gmm_fwd.3": "jit(step_fn)/forward_backward/jvp(Olmoe)/block_0/moe/moe_experts/gmm_fwd",
    "%gmm_drhs.3": "jit(step_fn)/forward_backward/transpose(jvp(Olmoe))/block_0/moe/moe_experts/gmm_drhs",
}


def op(seconds, count=2):
    return {"seconds": seconds, "count": count, "group": "", "target": None}


def traced(tmp_path, monkeypatch, stacks=STACKS):
    path = tmp_path / "k.opnames.json"
    path.write_text(json.dumps({"module": "jit_step_fn", "op_names": stacks}))
    monkeypatch.setattr(
        moe_flops.scopes, "op_names_file", lambda run: str(path)
    )
    run = Run(
        {"device": {"kind": "TPU v5 lite", "count": 1}}, [], reduced=True
    )
    run.config, run.traffic, run.flops = CFG, TRAFFIC, flops
    run.trace = {"steps": 2, "ops": {
        "%fusion.1": op(0.002), "%fusion.2": op(0.004),
        "%fusion.3": op(0.010), "%fusion.4": op(0.006),
        "%fusion.5": op(0.5), "%gmm_fwd.3": op(0.090),
        "%gmm_drhs.3": op(0.100), "%copy.9": op(0.3),
    }}
    return run


def reader(name):
    return loader.load_module("layer_metrics", name)


def test_expert_time_is_what_runs_under_the_experts_scope(
    tmp_path, monkeypatch
):
    run = traced(tmp_path, monkeypatch)
    # (0.010 + 0.090 + 0.100) s over 2 steps
    assert reader("moe.expert_ms_per_step").read(run) == pytest.approx(100.0)
    # least 37.67 ms over 100 ms
    assert reader("moe.expert_roofline_pct").read(run) == pytest.approx(
        37.67, abs=0.01
    )
    assert any("bound by flops" in line for line in run.notes)


def test_route_time_is_router_dispatch_and_combine(tmp_path, monkeypatch):
    run = traced(tmp_path, monkeypatch)
    assert reader("moe.route_ms_per_step").read(run) == pytest.approx(6.0)
    (line,) = run.notes
    assert "moe_dispatch 2.000 ms" in line


@pytest.mark.parametrize("name", [
    "moe.expert_ms_per_step", "moe.expert_roofline_pct",
    "moe.route_ms_per_step",
])
def test_a_program_without_the_layer_reports_nothing(
    name, tmp_path, monkeypatch
):
    """No map (the parent of PR 25), no trace, or a dense model's map:
    None, never an exception."""
    dense = {"%fusion.5": STACKS["%fusion.5"]}
    run = traced(tmp_path, monkeypatch, stacks=dense)
    assert reader(name).read(run) is None
    monkeypatch.setattr(moe_flops.scopes, "op_names_file", lambda run: "")
    assert reader(name).read(run) is None
    run.trace = None
    assert reader(name).read(run) is None


def test_load_is_the_median_of_the_windows_train_step_events():
    events = [
        {"type": "train_step", "step": s, "moe.load_max_over_mean": v}
        for s, v in ((3, 9.0), (4, 1.2), (5, 1.4), (6, 1.3))
    ] + [{"type": "train_step", "step": 7}]
    steps = [{"step": s} for s in (4, 5, 6, 7)]
    run = Run({"window": {"steps": steps, "saves": []}}, events)
    assert reader("moe.load_max_over_mean").read(run) == 1.3
    dense = Run({"window": {"steps": steps, "saves": []}}, events[-1:])
    assert reader("moe.load_max_over_mean").read(dense) is None
