"""``gdn_flops.py`` against values worked by hand from the published
sizes of Olmo-Hybrid-7B (30 heads of 96 keys and 192 values, three
linear layers of four) at the cell's 1 x 8192 tokens; the five GPT-2
keys that ``flops.py`` reads from the new configuration; and the three
``gdn.*`` readers on hand-made traces, one of which holds a scanned
scope (a ``%while`` AND the operations of its body)."""

import json
import os

import pytest

from test_scopes import Run

import flops
import gdn_flops
import loader

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "configs", "olmo_hybrid_7b_cut.json")) as f:
    CFG = json.load(f)
with open(os.path.join(BENCH, "traffic", "steady_8k.json")) as f:
    TRAFFIC = json.load(f)


def test_rule_flops_and_bytes_by_hand():
    assert gdn_flops.linear_layers(CFG) == 3
    # 18 x 96 x 192 = 331776 a token and head; x 30 heads x 8192 tokens
    # x 3 layers = 0.2446 TFLOP
    assert gdn_flops.rule_flops_per_step(CFG, 1, 8192) == (
        18 * 96 * 192 * 30 * 8192 * 3
    )
    assert round(gdn_flops.rule_flops_per_step(CFG, 1, 8192) / 1e12, 4) == 0.2446
    # a token and layer: q, k 2880 each and v, o (or do) 5760 each in
    # bf16 = 34560 B, g and beta 30 float32 each = 240 B, forward and
    # again backward; dq, dk, dv 23040 B + 240 B: 92880 B
    assert gdn_flops.rule_bytes_per_step(CFG, 1, 8192) == (
        (34800 + 34800 + 23280) * 8192 * 3
    )
    least, bound = flops.roofline_seconds(
        gdn_flops.rule_flops_per_step(CFG, 1, 8192),
        gdn_flops.rule_bytes_per_step(CFG, 1, 8192), "TPU v5 lite",
    )
    # 2.283e9 / 819e9 = 2.79 ms against 0.2446e12 / 197e12 = 1.24 ms
    assert bound == "bytes" and round(least * 1e3, 2) == 2.79


def test_flops_py_counts_this_models_matmul_parameters():
    """``model.mfu_pct`` and ``kernel.flash_roofline_pct`` call
    ``flops.py`` with GPT-2's key names in every cell: the file's
    ``n_*`` keys make it count what a trained token of THIS model
    requires, worked here from the HF keys."""
    h, inner = CFG["hidden_size"], CFG["intermediate_size"]
    heads = CFG["linear_num_value_heads"]
    keys = heads * CFG["linear_key_head_dim"]
    values = heads * CFG["linear_value_head_dim"]
    mlp = 3 * h * inner
    linear = 2 * h * keys + 2 * h * values + values * h + 2 * h * heads + mlp
    full = 4 * h * h + mlp
    assert (linear, full) == (215_516_160, 185_794_560)
    blocks = 3 * linear + full
    assert blocks == 832_343_040
    assert flops.matmul_params(CFG) == blocks + CFG["vocab_size"] * h
    assert flops.matmul_params(CFG) == 1_217_694_720
    seq = TRAFFIC["seq"]
    # ONE of the four layers has causal attention, at 30 x 128 = 3840
    attention = 6 * 1 * seq * 3840
    assert flops.train_flops_per_token(CFG, seq) == (
        6 * flops.matmul_params(CFG) + attention
    )
    # 7.306 + 0.189 GFLOP a token; 61.4 TFLOP a step of 8192 tokens
    assert round(flops.train_flops_per_token(CFG, seq) / 1e9, 3) == 7.495
    assert round(flops.train_flops_per_token(CFG, seq) * 8192 / 1e12, 1) == 61.4
    assert flops.head_dim(CFG) == 128
    assert (CFG["n_layer"], CFG["n_embd"], CFG["n_head"],
            CFG["n_positions"]) == (1, 3840, 30, 65536)
    for key in ("n_layer", "n_embd", "n_head", "n_positions", "n_inner"):
        assert "read by flops.py only" in CFG["assumed"][key]
    least, bound = flops.roofline_seconds(
        flops.attention_flops_per_step(CFG, 1, seq),
        flops.attention_bytes_per_step(CFG, 1, seq), "TPU v5 lite",
    )
    assert bound == "flops" and round(least * 1e3, 2) == 7.85


def test_the_configuration_keeps_every_published_width():
    """Every key of the catalog row's ``config`` (``architectures.jsonl``,
    Olmo-Hybrid-7B) but the depth."""
    period = ["linear_attention"] * 3 + ["full_attention"]
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_attention_heads": 30, "num_key_value_heads": 30,
        "hidden_act": "silu", "max_position_embeddings": 65536,
        "attention_bias": False, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None},
    }
    for key, value in published.items():
        assert CFG[key] == value, key
    assert CFG["num_hidden_layers"] == 4 and CFG["layer_types"] == period
    assert CFG["reduced"] == ["num_hidden_layers", "layer_types"]
    assert CFG["source"].endswith("allenai/Olmo-Hybrid-7B/blob/main/config.json")


# -- the readers -------------------------------------------------------------------

FWD = "jit(step_fn)/forward_backward/jvp(OlmoHybrid)/block_0/gdn/"
BWD = "jit(step_fn)/forward_backward/transpose(jvp(OlmoHybrid))/block_0/gdn/"
STACKS = {
    "%fusion.1": FWD + "gdn_conv/mul",
    "%fusion.2": FWD + "gdn_gates/rsqrt",
    "%fusion.3": BWD + "gdn_norm/mul",
    "%fusion.4": FWD + "gdn_rule/checkpoint/nbhid,nbhjd->nbhij/dot_general",
    # the hand-over scan, forward: its %while and two operations of
    # the body, which run INSIDE the %while's interval
    "%while.7": FWD + "gdn_rule/checkpoint/while",
    "%fusion.5": FWD + "gdn_rule/checkpoint/while/body/bhid,bhde->bhie/dot_general",
    "%fusion.6": FWD + "gdn_rule/checkpoint/while/body/closed_call/mul",
    # backward: a body the compiler shares between two loops, their
    # name stacks joined; its %while; and a copy that carries the
    # %while's stack but is an operation of its own, outside the loop
    "%fusion.8": BWD + "gdn_rule/checkpoint/closed_call/" + BWD.replace(
        "block_0", "block_1"
    ) + "gdn_rule/checkpoint/closed_call/while/body/dot_general",
    "%while.8": BWD + "gdn_rule/checkpoint/while",
    "%copy.8": BWD + "gdn_rule/checkpoint/while",
    "%fusion.9": "jit(step_fn)/optimizer/mul",
    # a scan OUTSIDE the scope (the loss head's) is none of the rule's
    "%while.9": "jit(step_fn)/forward_backward/jvp(loss_head)/while",
}


def op(seconds, count=2):
    return {"seconds": seconds, "count": count, "group": "", "target": None}


def traced(tmp_path, monkeypatch, stacks=STACKS):
    path = tmp_path / "k.opnames.json"
    path.write_text(json.dumps({"module": "jit_step_fn", "op_names": stacks}))
    monkeypatch.setattr(
        gdn_flops.scopes, "op_names_file", lambda run: str(path)
    )
    run = Run(
        {"device": {"kind": "TPU v5 lite", "count": 1}}, [], reduced=True
    )
    run.config, run.traffic, run.flops = CFG, TRAFFIC, flops
    run.trace = {"steps": 2, "ops": {
        "%fusion.1": op(0.002), "%fusion.2": op(0.004),
        "%fusion.3": op(0.006), "%fusion.4": op(0.010),
        "%while.7": op(0.050), "%fusion.5": op(0.030, 256),
        "%fusion.6": op(0.015, 256), "%fusion.8": op(0.020, 256),
        "%while.8": op(0.024), "%copy.8": op(0.002),
        "%fusion.9": op(0.5), "%while.9": op(0.3), "%copy.9": op(0.3),
    }}
    return run


def reader(name):
    return loader.load_module("layer_metrics", name)


def test_a_scanned_scope_is_counted_once(tmp_path, monkeypatch):
    """``%while.7`` spans its body: 0.050 s, not 0.050 + 0.030 +
    0.015; likewise ``%while.8`` and the shared body ``%fusion.8``."""
    run = traced(tmp_path, monkeypatch)
    seconds, count, bodies = gdn_flops.seconds_per_step(run, "gdn_rule")
    assert seconds == pytest.approx((0.010 + 0.050 + 0.024 + 0.002) / 2)
    assert bodies == pytest.approx((0.030 + 0.015 + 0.020) / 2)
    assert count == 4
    assert reader("gdn.rule_ms_per_step").read(run) == pytest.approx(43.0)
    (line,) = run.notes
    assert "32.500 ms" in line
    # least 2.787 ms over 43 ms
    assert reader("gdn.rule_roofline_pct").read(run) == pytest.approx(
        6.482, abs=0.01
    )
    assert any("bound by bytes" in line for line in run.notes)


def test_bodies_are_counted_where_the_trace_has_no_while(
    tmp_path, monkeypatch
):
    """A trace without the scope's ``%while`` instructions (a compiler
    that unrolled them, a profiler that left them out): the bodies are
    all there is."""
    run = traced(tmp_path, monkeypatch)
    del run.trace["ops"]["%while.7"], run.trace["ops"]["%while.8"]
    seconds, _, bodies = gdn_flops.seconds_per_step(run, "gdn_rule")
    assert seconds == pytest.approx(
        (0.010 + 0.002 + 0.030 + 0.015 + 0.020) / 2
    )
    assert bodies == 0.0


def test_mix_time_is_conv_gates_and_norm(tmp_path, monkeypatch):
    run = traced(tmp_path, monkeypatch)
    assert reader("gdn.mix_ms_per_step").read(run) == pytest.approx(6.0)
    (line,) = run.notes
    assert "gdn_gates 2.000 ms" in line


def test_in_loop_of_looks_behind_the_scope_only():
    assert not gdn_flops.in_loop_of(STACKS["%while.7"], "gdn_rule")
    assert gdn_flops.in_loop_of(STACKS["%fusion.5"], "gdn_rule")
    assert gdn_flops.in_loop_of(STACKS["%fusion.8"], "gdn_rule")
    assert not gdn_flops.in_loop_of(STACKS["%fusion.4"], "gdn_rule")
    # a while the scope itself sits in (a scanned stack of layers) is
    # not the scope's
    assert not gdn_flops.in_loop_of("jit(f)/while/body/gdn_rule/mul", "gdn_rule")


@pytest.mark.parametrize("name", [
    "gdn.rule_ms_per_step", "gdn.rule_roofline_pct", "gdn.mix_ms_per_step",
])
def test_a_program_without_the_layer_reports_nothing(
    name, tmp_path, monkeypatch
):
    """No map, no trace, or a model without the layer (the parent of
    PR 32 under any cell): None, never an exception."""
    dense = {"%fusion.9": STACKS["%fusion.9"], "%while.9": STACKS["%while.9"]}
    run = traced(tmp_path, monkeypatch, stacks=dense)
    assert reader(name).read(run) is None
    monkeypatch.setattr(gdn_flops.scopes, "op_names_file", lambda run: "")
    assert reader(name).read(run) is None
    run.trace = None
    assert reader(name).read(run) is None
