"""The ``lfm2_moe`` family beside ``test_lfm2_moe.py`` (a file is one
worker's): the shares of all four chips add up to the uncut layer; the
counter on the ``train_step`` event; the cut configuration's
arithmetic, the benchmark's entries and their readers; the harness's
rehearsal, in which the step applies the bias rule."""

import json
import os
import re
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import flops  # noqa: E402  (the benchmark's own)
import lfm2_flops  # noqa: E402
import loader  # noqa: E402

from dlrover_tpu.parallel.moe import DroplessMoE  # noqa: E402
from dlrover_tpu.telemetry.events import read_events  # noqa: E402
from dlrover_tpu.telemetry.schema import validate_event  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer  # noqa: E402

family = loader.load_module("models", "lfm2_moe")
reference = family.reference
CONFIGS = os.path.join(REPO, "benchmarks", "configs")
CUT = loader.load_json(os.path.join(CONFIGS, "lfm2_24b_a2b_cut.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = (
    "sconv.mix_ms_per_step", "sconv.kernel_ms_per_step",
    "sconv.mix_roofline_pct", "sconv.proj_ms_per_step", "sconv.out_rms_max",
)


# -- the shares ---------------------------------------------------------------


def layer(held=None):
    """The family's expert layer at a small size: sigmoid + bias,
    top-4 of 64 weighted by the renormalised scores, no shared
    expert."""
    return DroplessMoE(
        num_experts=64, mlp_dim=24, top_k=4, dtype=jnp.float32,
        held=held, score="sigmoid", select_bias=True, renormalise=True,
        renormalise_eps=1e-6, scale=1.0, shared_dim=0,
    )


def test_the_shares_of_all_four_chips_add_up_to_the_uncut_layer():
    """Four chips, sixteen consecutive experts each: what each
    computes of the routed sum adds up to the layer that holds all 64
    (nothing is computed alike on every chip: no shared expert), which
    is the plain reference's loop over all 64."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 192, 32))
    params = layer().init(jax.random.PRNGKey(1), x)["params"]
    params["select_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (64,)
    )
    whole, stats = jax.jit(layer().apply)({"params": params}, x)
    parts, rows = [], []
    for lo in range(0, 64, 16):
        share = {**params, **{
            k: params[k][lo:lo + 16]
            for k in ("experts_w_gate", "experts_w_in", "experts_w_out")
        }}
        out, said = jax.jit(layer(held=(lo, 16)).apply)(
            {"params": share}, x
        )
        np.testing.assert_array_equal(said["counts"], stats["counts"])
        parts.append(out)
        rows.append(float(said["held_rows"]))
        # the reference is given the same share
        mine, _ = reference._experts(
            x[0], share, top_k=4, first=lo, scale=1.0
        )
        np.testing.assert_allclose(out[0], mine, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-6)
    uncut, counts = reference._experts(
        x[0], params, top_k=4, first=0, scale=1.0
    )
    np.testing.assert_allclose(whole[0], uncut, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(stats["counts"], counts)
    # every assignment lands on exactly one chip
    assert sum(rows) == 192 * 4 and min(rows) > 0


def test_the_counter_rides_on_the_train_step_event(tmp_path, monkeypatch):
    log = tmp_path / "events.jsonl"
    monkeypatch.setenv("DLROVER_EVENT_LOG", str(log))
    trainer = ElasticTrainer(
        global_batch_size=2, micro_batch_size=2, dp_size=1
    )
    trainer.report_step({
        "loss": 1.0, "sconv.out_rms_max": 0.004,
        "moe.held_rows_share": 0.25, "moe.bias_abs_max": 0.002,
        "grad_norm": 2.0,
    })
    (event,) = [e for e in read_events(str(log)) if e["type"] == "train_step"]
    assert validate_event(event) == []
    assert event["sconv.out_rms_max"] == 0.004
    assert event["moe.held_rows_share"] == 0.25
    assert "grad_norm" not in event


# -- the cut, the benchmark's entries -----------------------------------------


def test_the_cut_keeps_every_published_width_and_counts_as_the_issue_says():
    kept = [0, 2, 3, 4, 5, 6, 7, 8, 9]
    reduced = {
        "num_hidden_layers": (40, 9), "num_dense_layers": (2, 1),
        "num_experts": (64, 16),
    }
    assert sorted(CUT["reduced"]) == sorted([*reduced, "layer_types"])
    for key, (published, held) in reduced.items():
        assert CUT["published"][key] == published and CUT[key] == held
    assert CUT["layers_held"] == kept
    assert CUT["layer_types"] == [
        CUT["published"]["layer_types"][i] for i in kept
    ] == ["conv"] + 2 * ["full_attention", "conv", "conv", "conv"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [
                json.loads(line) for line in f if '"LFM2-24B-A2B"' in line
            ]
        assert CUT["source"] == row["source_url"]
        assert CUT["published"]["layer_types"] == row["config"]["layer_types"]
        for key, value in row["config"].items():
            if key not in CUT["reduced"]:
                assert CUT[key] == value, key
    assert CUT["router_outputs"] == 64 and CUT["first_expert_held"] == 0
    assert CUT["tie_word_embeddings"] is True
    for key in "abcdefghij":
        assert any(
            text.startswith(f"({key})") for text in CUT["assumed"].values()
        ), key
    for key in ("source", "published", "reduced", "assumed", "deployment",
                "memory", "recipe", "reference"):
        assert CUT[key], key
    assert "TODO" not in json.dumps(CUT)
    assert CUT["recipe"] == {
        **CUT["recipe"], "optimizer": "adamw_bf16", "attention": "flash",
        "remat": True, "loss_chunks": 8, "bias_update_rate": 0.001,
        "initializer_range": 0.02,
    }
    model, _, _ = family.build(CUT)
    shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), seq_len=128)
    )
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    # 9.32 GB of state at 6 B a parameter
    assert count == lfm2_flops.total_params(CUT) == 1_554_072_320
    mixer = shapes["block_2"]["short_conv"]
    assert mixer["in_proj"]["kernel"].shape == (2048, 6144)
    assert mixer["taps"].shape == (3, 2048)
    assert mixer["out_proj"]["kernel"].shape == (2048, 2048)
    for block in ("block_1", "block_5"):
        attn = shapes[block]["attn"]
        assert attn["q_proj"]["kernel"].shape == (2048, 32 * 64)
        assert attn["k_proj"]["kernel"].shape == (2048, 8 * 64)
        assert attn["v_proj"]["kernel"].shape == (2048, 8 * 64)
        assert attn["out_proj"]["kernel"].shape == (2048, 2048)
        assert attn["q_layernorm"]["scale"].shape == (64,)
        assert attn["k_layernorm"]["scale"].shape == (64,)
        assert "short_conv" not in shapes[block]
    moe = shapes["block_1"]["moe"]
    assert moe["router"].shape == (2048, 64)
    assert moe["select_bias"].shape == (64,)
    assert moe["experts_w_gate"].shape == (16, 2048, 1536)
    assert "shared_gate" not in moe
    assert shapes["block_0"]["mlp"]["gate_proj"]["kernel"].shape == (
        2048, 11776
    )
    # the whole vocabulary, tied: one table and no head beside it
    assert shapes["wte"]["embedding"].shape == (65536, 2048)
    assert "lm_head" not in shapes


def test_flops_py_counts_just_under_what_the_cell_requires():
    """``flops.py`` reads GPT-2's key names; the cut carries them set
    so that its count is just under ``lfm2_flops``'s: the matmul
    parameters by 8192 of 421.5 M, the attention exactly, the whole by
    the mixer's arithmetic, which ``flops.py`` has no key for."""
    required = lfm2_flops.matmul_params(CUT)
    assert required == 421_527_552
    assert required - flops.matmul_params(CUT) == 8192
    assert flops.attention_flops_per_token(CUT, 8192) == (
        lfm2_flops.attention_flops_per_token(CUT, 8192)
    ) == 2 * 6.0 * 8192 * 32 * 64
    ratio = flops.train_flops_per_token(CUT, 8192) / (
        lfm2_flops.train_flops_per_token(CUT, 8192)
    )
    assert 0.9998 < ratio < 1


def test_the_benchmark_gains_one_configuration_one_cell_five_readers():
    bench = loader.load_json(os.path.join(REPO, "BENCHMARK.json"))
    (config,) = [
        c for c in bench["configs"] if c["name"] == "lfm2_24b_a2b_cut"
    ]
    assert config["reduced"] == CUT["reduced"]
    assert config["source"] == CUT["source"]
    assert config["file"] == "benchmarks/configs/lfm2_24b_a2b_cut.json"
    cells = [
        w for w in bench["workloads"] if w["config"] == "lfm2_24b_a2b_cut"
    ]
    assert cells == [{
        "name": "lfm2_moe_steady_8k", "config": "lfm2_24b_a2b_cut",
        "traffic": "steady_8k", "chips": 1, "why": cells[0]["why"],
    }]
    assert len(cells[0]["why"]) <= 200 and len(config["why"]) <= 200
    assert all(w["chips"] == 1 for w in bench["workloads"])
    names = [m["name"] for m in bench["per_layer"]]
    # (five entries in a row; later PRs append after them)
    first = names.index(NEW_READERS[0])
    assert names[first:first + 5] == list(NEW_READERS)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        metric = listed[name]
        assert metric["workloads"] == ["lfm2_moe_steady_8k"]
        assert metric["layer"] == "short convolution"
        assert metric["moves"] == "tokens_per_s"
        reader = loader.load_module("layer_metrics", name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES,
                reader.SOURCE) == tuple(
            metric[k] for k in ("name", "unit", "layer", "moves", "source")
        )
    # nothing that stood is edited: no accepted reader's list is widened
    for metric in bench["per_layer"]:
        if metric["name"] not in NEW_READERS:
            assert "lfm2_moe_steady_8k" not in metric.get("workloads", [])


@pytest.mark.parametrize("leaf, limit", [
    ("['block_0']['short_conv']['taps']", "gradient_tolerance"),
    ("['block_8']['short_conv']['taps']", "gradient_tolerance"),
    ("['block_3']['short_conv']['in_proj']['kernel']", "gradient_tolerance"),
    ("['block_3']['short_conv']['out_proj']['kernel']", "gradient_tolerance"),
    ("['block_1']['attn']['q_layernorm']['scale']", "gradient_tolerance"),
    ("['block_5']['attn']['k_layernorm']['scale']", "gradient_tolerance"),
    ("['block_5']['attn']['q_proj']['kernel']", "gradient_tolerance"),
    ("['block_0']['ffn_norm']['scale']", "gradient_tolerance"),
    ("['block_4']['operator_norm']['scale']", "gradient_tolerance"),
    ("['embedding_norm']['scale']", "gradient_tolerance"),
    ("['wte']['embedding']", "gradient_tolerance"),
    ("['block_4']['ffn_norm']['scale']", "routed_gradient_tolerance"),
    ("['block_2']['moe']['router']", "routed_gradient_tolerance"),
    ("['block_8']['moe']['experts_w_in']", "routed_gradient_tolerance"),
])
def test_a_leaf_is_held_to_its_classs_limit(leaf, limit):
    assert family.kind_of(CUT)(leaf) == limit
    assert family.compared(CUT)(leaf)
    assert limit in CUT["reference"]


def test_the_compared_leaves_leave_the_wide_matrices_out():
    assert "mixer_taps_tolerance" in CUT["reference"]
    pick = family.compared(CUT)
    assert not pick("['block_2']['moe']['experts_w_gate']")
    assert not pick("['block_0']['mlp']['up_proj']['kernel']")
    assert not pick("['block_3']['moe']['select_bias']")


def test_the_harness_rehearses_the_family_on_the_cpu(tmp_path, checkout):
    """``benchmarks/run.py`` end to end on the toy configuration:
    ``tpurun`` -> the worker -> the ``has_aux`` step with the
    interpreted ``bcx_fwd`` / ``bcx_bwd`` kernels and the tied chunked
    head -> the reference's loss and gradients -> the readers; exit
    code 3 (a rehearsal, never a result), ``correct`` true; and the
    step APPLIES the bias rule: the largest bias grows by the rate a
    step."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        # (from a checkout of its own: conftest.py, ROADMAP B7)
        [sys.executable, os.path.join(checkout, "benchmarks", "run.py"),
         "--cells",
         os.path.join(REPO, "benchmarks", "rehearsal_lfm2_moe.json"),
         "--workload", "toy_lfm2_moe_steady", "--seed", "5000000011",
         "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 3, done.stdout[-3000:] + done.stderr[-3000:]
    assert '"correct": true' in done.stdout
    assert "sconv.out_rms_max" in done.stdout
    assert "moe.held_rows_share" in done.stdout
    grown = re.search(
        r"router bias: largest \|b\| ([\d.]+) entering step (\d+)",
        done.stdout,
    )
    # the rule moves an expert by 0.001 a step from a bias of zero
    assert grown and 0.003 <= float(grown[1]) <= 0.001 * int(grown[2])
