"""What the benchmark has of the ``nemotron_h`` family: the published
sizes' parameter counts, the counters on the ``train_step`` event,
the compared leaves and the limit each comparison is judged by, the
flops keys, the benchmark's cell and its readers, and the harness's
rehearsal.  The family against its reference is ``test_nemotron_h.py``."""

import json
import os
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import loader  # noqa: E402  (the benchmark's own)

from dlrover_tpu.models.gpt import count_params  # noqa: E402
from dlrover_tpu.telemetry.events import read_events  # noqa: E402
from dlrover_tpu.telemetry.schema import validate_event  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer  # noqa: E402

family = loader.load_module("models", "nemotron_h")


def test_published_sizes_give_the_issues_parameter_counts():
    """The cut configuration's tree, by shape alone: 38.74 M a
    state-space layer, 23.40 M an attention layer, 9.978 M an expert
    (two matrices: NO gate), 1.246 B in all."""
    cfg = loader.load_json(os.path.join(
        REPO, "benchmarks", "configs", "nemotron_3_nano_30b_cut.json"
    ))
    model, _, _ = family.build(cfg)
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), seq_len=128)
    )
    assert cfg["hybrid_override_pattern"] == "MEMEM*EMEMEM*EMEME"
    assert count_params(params["block_0"]["ssm"]) == 38_742_208
    assert count_params(params["block_5"]["attn"]) == 23_396_352
    experts = params["block_1"]["moe"]
    assert sorted(experts) == [
        "experts_w_in", "experts_w_out", "router", "select_bias",
        "shared_down", "shared_up",
    ]
    assert experts["experts_w_in"].shape == (8, 2688, 1856)
    assert experts["experts_w_out"].shape == (8, 1856, 2688)
    assert experts["router"].shape == (2688, 128)
    assert count_params(params) == 1_245_843_840
    assert params["block_0"]["ssm"]["A_log"].dtype == jnp.float32


def test_the_counters_ride_on_the_train_step_event(tmp_path, monkeypatch):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("DLROVER_EVENT_LOG", path)
    monkeypatch.setenv(
        "DLROVER_METRICS_FILE", str(tmp_path / "metrics.json")
    )
    trainer = ElasticTrainer(4, 4, dp_size=1)
    trainer.report_step({
        "loss": jnp.float32(1.5), "grad_norm": jnp.float32(0.1),
        "ssm.state_rms_max": jnp.float32(0.25),
        "ssm.decay_mean": jnp.float32(0.875),
        "moe.held_rows_share": jnp.float32(0.0625),
    })
    (event,) = [e for e in read_events(path) if e["type"] == "train_step"]
    assert event["ssm.state_rms_max"] == 0.25
    assert event["ssm.decay_mean"] == 0.875
    assert event["moe.held_rows_share"] == 0.0625
    assert not validate_event(event)


# -- the benchmark's family and harness ---------------------------------------


def test_the_compared_leaves_and_their_limits():
    """Every leaf of the first and the last state-space layer, both
    attention layers, every norm and router, the last expert layer's
    held experts; each judged by its own kind's limit."""
    cfg = loader.load_json(os.path.join(
        REPO, "benchmarks", "configs", "nemotron_3_nano_30b_cut.json"
    ))
    model, _, _ = family.build(cfg)
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), seq_len=128)
    )
    pick = family.compared(cfg)
    names = [
        jax.tree_util.keystr(path)
        for path, _ in jax.tree_util.tree_leaves_with_path(params)
    ]
    picked = [name for name in names if pick(name)]
    kinds = {}
    for name in picked:
        kinds.setdefault(family.kind_of(name), []).append(name)
    assert len(kinds["decay_gradient_tolerance"]) == 2 * 3
    assert len(kinds["routed_gradient_tolerance"]) == 8 + 2
    # 2 x 5 further state-space leaves, 2 x 4 attention, 18 + 1 norms
    assert len(kinds["gradient_tolerance"]) == 10 + 8 + 19
    assert "['block_16']['ssm']['conv_bias']" in picked
    assert "['block_2']['ssm']['A_log']" not in picked
    assert "['block_17']['moe']['experts_w_out']" in picked
    assert "['block_15']['moe']['experts_w_out']" not in picked
    assert set(cfg["reference"]) >= set(kinds) | {
        "loss_tolerance", "router_rms_tolerance", "bias_update_tolerance",
        "state_rms_tolerance",
    }


@pytest.mark.parametrize("moved, inside", [
    ({}, True),
    ({"gradients": {"['block_0']['ssm']['D']": 0.9}}, False),
    ({"gradients": {"['block_5']['attn']['q_proj']['kernel']": 0.3}}, False),
    ({"gradients": {"['block_1']['moe']['router']": 0.3}}, True),
    ({"gradients": {"['block_1']['moe']['router']": float("nan")}}, False),
    ({"routers_rms": 0.46}, False),
    ({"bias": 0.5}, False),
    ({"state_rms": 0.2}, False),
])
def test_every_comparison_is_judged_by_its_own_limit(
    monkeypatch, moved, inside
):
    found = {
        "loss": 9.5, "bias": 0.01, "state_rms": 0.001, "routers_rms": 0.2,
        "gradients": {
            "['block_0']['ssm']['D']": 0.1,
            "['block_5']['attn']['q_proj']['kernel']": 0.05,
            "['block_1']['moe']['router']": 0.2,
        },
    }
    found = {**found, **moved, "gradients": {
        **found["gradients"], **moved.get("gradients", {})
    }}
    monkeypatch.setattr(family, "comparisons", lambda *a: found)
    limits = {"reference": {
        "gradient_tolerance": 0.2, "routed_gradient_tolerance": 0.5,
        "decay_gradient_tolerance": 0.5, "router_rms_tolerance": 0.45,
        "bias_update_tolerance": 0.15, "state_rms_tolerance": 0.05,
    }}
    loss = family.reference_loss(None, None, None, limits)
    assert loss == (9.5 if inside else float("inf"))


def test_the_flops_keys_count_what_the_family_requires():
    """``flops.py`` reads GPT-2's key names: on the cut configuration
    they give the FLOPs a token that ``nemotron_flops.py`` counts
    layer by layer, to the FLOP."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import flops
    import nemotron_flops

    cfg = loader.load_json(os.path.join(
        REPO, "benchmarks", "configs", "nemotron_3_nano_30b_cut.json"
    ))
    assert flops.train_flops_per_token(cfg, 8192) == (
        nemotron_flops.train_flops_per_token(cfg, 8192)
    ) == 4_022_501_376


def test_the_harness_rehearses_the_family_on_the_cpu(tmp_path, checkout):
    """``benchmarks/run.py`` end to end on the toy configuration:
    ``tpurun`` -> the worker -> the ``has_aux`` step over three kinds
    of mixer with its ``state_updates`` -> the reference's loss and
    the family's own comparisons -> the readers; exit code 3 (a
    rehearsal, never a result), ``correct`` true."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        # (from a checkout of its own: conftest.py, ROADMAP B7)
        [sys.executable, os.path.join(checkout, "benchmarks", "run.py"),
         "--cells", os.path.join(
             REPO, "benchmarks", "rehearsal_nemotron_h.json"),
         "--workload", "toy_nemotron_h_steady", "--seed", "4700000007",
         "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 3, done.stdout[-3000:] + done.stderr[-3000:]
    assert '"correct": true' in done.stdout
    assert "ssm.state_rms_max" in done.stdout


def test_the_benchmark_lists_the_cell_and_its_readers():
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    (cell,) = [
        w for w in spec["workloads"] if w["name"] == "nemotron_steady_8k"
    ]
    assert cell == {**cell, "config": "nemotron_3_nano_30b_cut",
                    "traffic": "steady_8k", "chips": 1}
    mine = [
        m["name"] for m in spec["per_layer"]
        if m.get("workloads") == ["nemotron_steady_8k"]
    ]
    assert mine == [
        "ssm.scan_ms_per_step", "ssm.scan_roofline_pct",
        "ssm.mix_ms_per_step", "ssm.proj_ms_per_step",
        "ssm.state_rms_max", "moe.relu2_expert_roofline_pct",
        "ssm.kernel_ms_per_step",
    ]
    for name in mine:
        reader = loader.load_module("layer_metrics", name)
        assert reader.NAME == name and reader.MOVES == "tokens_per_s"
