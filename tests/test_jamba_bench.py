"""The ``jamba`` family beside ``test_jamba.py`` (a file is one
worker's): the counters on the ``train_step`` event; the cut
configuration's keys, arithmetic and shapes; the benchmark's entries
and their readers; which limit a leaf is held to; the harness's
rehearsal."""

import json
import os
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import flops  # noqa: E402  (the benchmark's own)
import jamba_flops  # noqa: E402
import loader  # noqa: E402

from dlrover_tpu.telemetry.events import read_events  # noqa: E402
from dlrover_tpu.telemetry.schema import validate_event  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer  # noqa: E402

family = loader.load_module("models", "jamba")
CONFIGS = os.path.join(REPO, "benchmarks", "configs")
CUT = loader.load_json(os.path.join(CONFIGS, "jamba2_3b_cut.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = (
    "s6.scan_ms_per_step", "s6.kernel_ms_per_step", "s6.scan_roofline_pct",
    "s6.mix_ms_per_step", "s6.proj_ms_per_step", "s6.state_rms_max",
)


def test_the_counters_ride_on_the_train_step_event(tmp_path, monkeypatch):
    log = tmp_path / "events.jsonl"
    monkeypatch.setenv("DLROVER_EVENT_LOG", str(log))
    trainer = ElasticTrainer(
        global_batch_size=2, micro_batch_size=2, dp_size=1
    )
    trainer.report_step({
        "loss": 1.0, "s6.state_rms_max": 0.004, "s6.decay_mean": 0.86,
        "s6.dt_mean": 0.02, "grad_norm": 2.0,
    })
    (event,) = [e for e in read_events(str(log)) if e["type"] == "train_step"]
    assert validate_event(event) == []
    assert event["s6.state_rms_max"] == 0.004
    assert event["s6.decay_mean"] == 0.86 and event["s6.dt_mean"] == 0.02
    assert "grad_norm" not in event


def test_the_cut_keeps_every_published_key_but_the_depth():
    assert CUT["reduced"] == ["num_hidden_layers"]
    assert CUT["published"] == {"num_hidden_layers": 28}
    assert CUT["num_hidden_layers"] == 14
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [
                json.loads(line) for line in f
                if '"name": "AI21-Jamba2-3B"' in line
            ]
        assert CUT["source"] == row["source_url"]
        assert row["config"]["num_hidden_layers"] == 28
        for key, value in row["config"].items():
            if key not in CUT["reduced"]:
                assert CUT[key] == value, key
    for key in "abcdefg":
        assert any(
            text.startswith(f"({key})") for text in CUT["assumed"].values()
        ), key
    for key in ("source", "published", "reduced", "assumed", "deployment",
                "memory", "recipe", "reference"):
        assert CUT[key], key
    assert "TO FILL" not in json.dumps(CUT)
    assert CUT["recipe"] == {
        **CUT["recipe"], "optimizer": "adamw_bf16", "attention": "flash",
        "remat": True, "loss_chunks": 8, "initializer_range": 0.02,
        "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
    }
    assert "operand_mantissa_bits" not in CUT["recipe"]
    assert "control" not in CUT["recipe"]
    assert "9.59 GB" in CUT["memory"] and "1,598,556,096" in CUT["memory"]
    model, _, _ = family.build(CUT)
    shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), seq_len=128)
    )
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == jamba_flops.total_params(CUT) == 1_598_556_096
    mixer = shapes["block_0"]["mamba"]
    assert mixer["in_proj"]["kernel"].shape == (2560, 10240)
    assert mixer["conv"].shape == (4, 5120)
    assert mixer["conv_bias"].shape == (5120,)
    assert mixer["x_proj"]["kernel"].shape == (5120, 192)
    assert mixer["dt_proj"].shape == (160, 5120)
    assert mixer["A_log"].shape == (5120, 16)
    assert mixer["out_proj"]["kernel"].shape == (5120, 2560)
    for name, lanes in (
        ("dt_layernorm", 160), ("b_layernorm", 16), ("c_layernorm", 16)
    ):
        assert mixer[name]["scale"].shape == (lanes,)
    for name in ("A_log", "D", "dt_bias", "conv_bias"):
        assert mixer[name].dtype == np.float32
    assert mixer["dt_proj"].dtype == mixer["conv"].dtype == "bfloat16"
    attn = shapes["block_7"]["attn"]
    assert attn["q_proj"]["kernel"].shape == (2560, 20 * 128)
    assert attn["k_proj"]["kernel"].shape == (2560, 128)
    assert attn["v_proj"]["kernel"].shape == (2560, 128)
    assert attn["o_proj"]["kernel"].shape == (2560, 2560)
    assert [
        i for i in range(14) if "attn" in shapes[f"block_{i}"]
    ] == [7]
    assert shapes["block_3"]["mlp"]["gate_proj"]["kernel"].shape == (
        2560, 8192
    )
    # the whole vocabulary, tied: one table and no head beside it
    assert shapes["wte"]["embedding"].shape == (65536, 2560)
    assert "lm_head" not in shapes


def test_flops_py_counts_just_under_what_the_cell_requires():
    """``flops.py`` reads GPT-2's key names; the cut carries them set
    so that its count is just under ``jamba_flops``'s: the matmul
    parameters by 5120 of 1,596.9 M, the attention exactly, the whole
    by the recurrence, which ``flops.py`` has no key for."""
    required = jamba_flops.matmul_params(CUT)
    assert required == 1_596_948_480
    assert required - flops.matmul_params(CUT) == 5120
    assert flops.attention_flops_per_token(CUT, 8192) == (
        jamba_flops.attention_flops_per_token(CUT, 8192)
    ) == 6.0 * 8192 * 20 * 128
    ratio = flops.train_flops_per_token(CUT, 8192) / (
        jamba_flops.train_flops_per_token(CUT, 8192)
    )
    assert 0.9975 < ratio < 1


def test_the_benchmark_gains_one_configuration_one_cell_six_readers():
    bench = loader.load_json(os.path.join(REPO, "BENCHMARK.json"))
    (config,) = [c for c in bench["configs"] if c["name"] == "jamba2_3b_cut"]
    assert config["reduced"] == CUT["reduced"] == ["num_hidden_layers"]
    assert config["source"] == CUT["source"]
    assert config["file"] == "benchmarks/configs/jamba2_3b_cut.json"
    cells = [w for w in bench["workloads"] if w["config"] == "jamba2_3b_cut"]
    assert cells == [{
        "name": "jamba2_steady_8k", "config": "jamba2_3b_cut",
        "traffic": "steady_8k", "chips": 1, "why": cells[0]["why"],
    }]
    assert len(cells[0]["why"]) <= 200 and len(config["why"]) <= 200
    assert all(w["chips"] == 1 for w in bench["workloads"])
    names = [m["name"] for m in bench["per_layer"]]
    # (six entries in a row; later PRs append after them)
    first = names.index(NEW_READERS[0])
    assert names[first:first + 6] == list(NEW_READERS)
    for metric in bench["per_layer"]:
        if metric["name"] in NEW_READERS:
            assert metric["workloads"] == ["jamba2_steady_8k"]
            assert metric["layer"] == "selective scan layers"
            assert metric["moves"] == "tokens_per_s"
        else:
            # nothing that stood is edited: no accepted list is widened
            assert "jamba2_steady_8k" not in metric.get("workloads", [])


@pytest.mark.parametrize("leaf, limit", [
    ("['block_0']['mamba']['in_proj']['kernel']", "gradient_tolerance"),
    ("['block_6']['mamba']['conv']", "gradient_tolerance"),
    ("['block_13']['mamba']['conv_bias']", "gradient_tolerance"),
    ("['block_0']['mamba']['x_proj']['kernel']", "gradient_tolerance"),
    ("['block_6']['mamba']['dt_layernorm']['scale']", "gradient_tolerance"),
    ("['block_13']['mamba']['b_layernorm']['scale']", "gradient_tolerance"),
    ("['block_0']['mamba']['c_layernorm']['scale']", "gradient_tolerance"),
    ("['block_6']['mamba']['dt_proj']", "gradient_tolerance"),
    ("['block_13']['mamba']['D']", "gradient_tolerance"),
    ("['block_0']['mamba']['out_proj']['kernel']", "gradient_tolerance"),
    ("['block_7']['attn']['q_proj']['kernel']", "gradient_tolerance"),
    ("['block_7']['attn']['k_proj']['kernel']", "gradient_tolerance"),
    ("['block_4']['input_layernorm']['scale']", "gradient_tolerance"),
    ("['block_9']['pre_ff_layernorm']['scale']", "gradient_tolerance"),
    ("['final_layernorm']['scale']", "gradient_tolerance"),
    ("['wte']['embedding']", "gradient_tolerance"),
    ("['block_0']['mamba']['A_log']", "a_log_gradient_tolerance"),
    ("['block_13']['mamba']['A_log']", "a_log_gradient_tolerance"),
    ("['block_6']['mamba']['dt_bias']", "dt_bias_gradient_tolerance"),
])
def test_a_leaf_is_held_to_its_classs_limit(leaf, limit):
    assert family.kind_of(leaf) == limit
    assert family.compared(CUT)(leaf)
    assert limit in CUT["reference"]


def test_the_compared_leaves_leave_the_wide_matrices_out():
    for limit in ("state_rms_tolerance", "scan_alone_tolerance",
                  "loss_tolerance"):
        assert limit in CUT["reference"]
    pick = family.compared(CUT)
    # the first, the middle and the last state-space layer alone
    assert family.mamba_layers(CUT) == [i for i in range(14) if i != 7]
    assert not pick("['block_3']['mamba']['in_proj']['kernel']")
    assert not pick("['block_8']['mamba']['A_log']")
    assert not pick("['block_0']['mlp']['up_proj']['kernel']")


def test_the_harness_rehearses_the_family_on_the_cpu(tmp_path, checkout):
    """``benchmarks/run.py`` end to end on the toy configuration:
    ``tpurun`` -> the worker -> the ``has_aux`` step with the
    interpreted ``s6_fwd`` / ``s6_bwd`` and ``conv_fwd`` / ``conv_bwd``
    kernels and the tied chunked head -> the reference's loss,
    gradients, final states and the scan alone -> the readers; exit
    code 3 (a rehearsal, never a result), ``correct`` true, the
    counters on the events."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        # (from a checkout of its own: conftest.py, ROADMAP B7)
        [sys.executable, os.path.join(checkout, "benchmarks", "run.py"),
         "--cells", os.path.join(REPO, "benchmarks", "rehearsal_jamba.json"),
         "--workload", "toy_jamba_steady", "--seed", "5000000011",
         "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 3, done.stdout[-3000:] + done.stderr[-3000:]
    assert '"correct": true' in done.stdout
    assert "s6.state_rms_max" in done.stdout
    assert "s6.decay_mean" in done.stdout
