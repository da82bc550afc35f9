"""What the benchmark has of the ``motif`` family: the counters on the
``train_step`` event, the cut configuration's arithmetic, the
benchmark's entries, the limit each compared leaf is held to, the
controls that take a mechanism out of the system, and the harness's
rehearsal.  The family against its reference is ``test_motif.py``."""

import json
import os
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import loader  # noqa: E402  (the benchmark's own)

from dlrover_tpu.telemetry.events import read_events  # noqa: E402
from dlrover_tpu.telemetry.schema import validate_event  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer  # noqa: E402

from test_motif import toy, toy_cfg  # noqa: E402

family = loader.load_module("models", "motif")
CONFIGS = os.path.join(REPO, "benchmarks", "configs")
CUT = loader.load_json(os.path.join(CONFIGS, "motif_3_beta_cut.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# -- counters, the cut, the benchmark's entries -------------------------------


def test_the_counters_ride_on_the_train_step_event(tmp_path, monkeypatch):
    log = tmp_path / "events.jsonl"
    monkeypatch.setenv("DLROVER_EVENT_LOG", str(log))
    trainer = ElasticTrainer(
        global_batch_size=2, micro_batch_size=2, dp_size=1
    )
    trainer.report_step({
        "loss": 1.0, "mhc.res_sum_err_max": 1e-7, "gdla.lambda_mean": 0.5,
        "gdla.noise_share": 0.4, "mtp.loss": 6.0, "moe.lb_loss": 8.0,
        "grad_norm": 2.0,
    })
    (event,) = [e for e in read_events(str(log)) if e["type"] == "train_step"]
    assert validate_event(event) == []
    assert event["mhc.res_sum_err_max"] == 1e-7 and event["mtp.loss"] == 6.0
    assert event["gdla.noise_share"] == 0.4 and "grad_norm" not in event


def test_the_cut_keeps_every_published_width_and_counts_as_the_issue_says():
    reduced = {
        "num_hidden_layers": (53, 5), "n_dense_first_layers": (2, 1),
        "num_attention_heads": (80, 20), "num_key_value_heads": (16, 4),
        "num_noise_heads": (16, 4), "num_experts": (384, 8),
        "vocab_size": (220160, 27520),
    }
    assert sorted(CUT["reduced"]) == sorted(reduced)
    for key, (published, held) in reduced.items():
        assert CUT["published"][key] == published and CUT[key] == held
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [
                json.loads(line) for line in f if '"Motif-3-Beta"' in line
            ]
        assert CUT["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in reduced and key != "model_type":
                assert CUT[key] == value, key
    assert CUT["router_outputs"] == 384
    assert CUT["layer_kinds"] == [1, 1, 1, 1, 0]
    for key in "abcdefghijk":
        assert any(name.startswith(f"({key})") or f"({key})" in text[:6]
                   for name, text in CUT["assumed"].items()), key
    model, _, _ = family.build(CUT)
    shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), seq_len=128)
    )
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == 1_297_556_848
    attn = shapes["block_4"]["attn"]
    assert attn["q_up"]["kernel"].shape == (1024, 20 * 192)
    assert attn["kv_up"]["kernel"].shape == (512, 4 * 256)
    assert attn["lambda_proj"].shape == (4096, 16)
    assert attn["gate_proj"]["kernel"].shape == (4096, 2048)
    assert shapes["block_1"]["mhc_attn"]["phi"].shape == (16384, 24)
    assert shapes["block_1"]["moe"]["experts_w_gate"].shape == (8, 4096, 1280)
    assert shapes["mtp"]["eh_proj"]["kernel"].shape == (8192, 4096)
    dense = shapes["block_0"]["mlp"]
    assert dense["gate_proj"]["kernel"].shape == (4096, 12288)


def test_the_benchmark_gains_one_configuration_one_cell_eight_readers():
    bench = loader.load_json(os.path.join(REPO, "BENCHMARK.json"))
    # (by name: later PRs append their own entries behind these)
    assert "motif_3_beta_cut" in [c["name"] for c in bench["configs"]]
    (cell,) = [
        w for w in bench["workloads"] if w["name"] == "motif_3_steady_8k"
    ]
    assert cell == {
        "name": "motif_3_steady_8k", "config": "motif_3_beta_cut",
        "traffic": "steady_8k", "chips": 1, "why": cell["why"],
    }
    assert len(cell["why"]) <= 200
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("mhc.mix_ms_per_step")
    added = bench["per_layer"][first:first + 8]
    assert [m["name"] for m in added] == [
        "mhc.mix_ms_per_step", "mhc.mix_roofline_pct", "mhc.res_sum_err_max",
        "gdla.proj_ms_per_step", "gdla.diff_ms_per_step", "gdla.lambda_mean",
        "moe.polynorm_expert_roofline_pct", "mtp.ms_per_step",
    ]
    for metric in added:
        assert metric["workloads"] == ["motif_3_steady_8k"]
        reader = loader.load_module("layer_metrics", metric["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES,
                reader.SOURCE) == tuple(
            metric[k] for k in ("name", "unit", "layer", "moves", "source")
        )


@pytest.mark.parametrize("leaf, limit", [
    ("['block_1']['mhc_attn']['phi']", "mhc_gradient_tolerance"),
    ("['mtp']['block']['mhc_mlp']['alpha']", "mhc_gradient_tolerance"),
    ("['block_2']['moe']['router']", "routed_gradient_tolerance"),
    ("['block_4']['moe']['experts_w_gate']", "routed_gradient_tolerance"),
    ("['block_2']['moe']['experts_polynorm_w']",
     "polynorm_gradient_tolerance"),
    ("['block_2']['moe']['shared_polynorm_b']", "polynorm_gradient_tolerance"),
    ("['block_0']['mlp']['polynorm_w']", "polynorm_gradient_tolerance"),
    ("['block_3']['attn']['lambda_proj']", "gradient_tolerance"),
    ("['block_3']['attn']['q_norm']['scale']", "gradient_tolerance"),
    ("['mtp']['eh_proj']['kernel']", "gradient_tolerance"),
])
def test_a_leaf_is_held_to_its_classs_limit(leaf, limit):
    assert family.limit_of(leaf) == limit
    assert family.compared(CUT)(leaf)
    assert limit in CUT["reference"]


def test_the_leaves_of_a_few_numbers_are_judged_together():
    """A gradient of one or three numbers may come out near zero, and
    its own norm is then no yardstick: every block's same-named
    scalars make ONE unit, its norms over all their numbers."""
    assert family.unit_of("['block_3']['mhc_mlp']['alpha']") == (
        family.unit_of("['mtp']['block']['mhc_attn']['alpha']")
    ) == "every ['mhc_*']['alpha']"
    assert family.unit_of("['block_2']['moe']['experts_polynorm_b']") == (
        family.unit_of("['block_0']['mlp']['polynorm_b']")
    ) == family.unit_of("['block_2']['moe']['shared_polynorm_b']") == (
        "every ['*polynorm_b']"
    )
    assert family.unit_of("['block_3']['moe']['experts_polynorm_w']") == (
        "every ['*polynorm_w']"
    )
    phi = "['block_1']['mhc_attn']['phi']"
    assert family.unit_of(phi) == phi
    got = family.differences({
        "['block_1']['mhc_mlp']['alpha']": (3.0, 0.1),
        "['block_2']['mhc_attn']['alpha']": (4.0, 10.0),
        phi: (1.0, 4.0),
    })
    assert got == {
        "every ['mhc_*']['alpha']": (25.0 / 100.01) ** 0.5, phi: 0.25,
    }
    for unit in got:
        assert family.limit_of(unit) == "mhc_gradient_tolerance"


def test_the_compared_leaves_leave_the_wide_matrices_out():
    pick = family.compared(CUT)
    assert not pick("['block_2']['moe']['experts_w_gate']")
    assert not pick("['block_4']['moe']['experts_w_in']")
    assert not pick("['block_0']['mlp']['up_proj']['kernel']")
    assert not pick("['lm_head']['kernel']")
    assert not pick("['wte']['embedding']")
    assert not pick("['block_1']['moe']['shared_up']['kernel']")


@pytest.fixture(scope="module")
def whole_system():
    """``(params, batch, the whole system's loss)`` on the toy at 64
    tokens, once for the four controls."""
    _, _, loss_fn, params, batch = toy(seq=64)
    whole, _ = jax.jit(loss_fn)(params, batch)
    return params, batch, float(whole)


@pytest.mark.parametrize("control, moved", [
    ("no_noise", 1e-3), ("no_sinkhorn", 1e-3),
    ("no_polynorm_norms", 1e-3), ("no_prediction", 1.0),
])
def test_a_control_takes_its_mechanism_out_of_the_system(
    control, moved, whole_system
):
    """Each control's loss on the toy is another number than the whole
    system's (the chip's comparison holds each outside a limit of the
    first GRADIENT: the configuration's ``reference.why``)."""
    params, batch, whole = whole_system
    _, _, controlled = family.build(toy_cfg(control=control))
    without, _ = jax.jit(controlled)(params, batch)
    assert abs(whole - float(without)) > moved


def test_an_unknown_control_is_refused():
    with pytest.raises(SystemExit, match="unknown control"):
        family.build(toy_cfg(control="no_such_thing"))


def test_the_harness_rehearses_the_family_on_the_cpu(tmp_path, checkout):
    """``benchmarks/run.py`` end to end on the toy configuration:
    ``tpurun`` -> the worker -> the ``has_aux`` step -> the
    reference's loss and gradients -> the readers; exit code 3 (a
    rehearsal, never a result), ``correct`` true."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        # (from a checkout of its own: conftest.py, ROADMAP B7)
        [sys.executable, os.path.join(checkout, "benchmarks", "run.py"),
         "--cells", os.path.join(REPO, "benchmarks", "rehearsal_motif.json"),
         "--workload", "toy_motif_steady", "--seed", "5000000011",
         "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 3, done.stdout[-3000:] + done.stderr[-3000:]
    assert '"correct": true' in done.stdout
    assert "mhc.res_sum_err_max" in done.stdout
    assert "gdla.lambda_mean" in done.stdout
