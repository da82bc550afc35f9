"""The ``bailing_hybrid`` family beside ``test_bailing_hybrid.py`` (a
file is one worker's): group-limited routing in ``parallel/moe.py``
(no token's choices pass ``topk_group`` groups; one group is the
ungrouped router; the shares of all the chips, a group a host, add up
to the uncut layer, the shared expert counted once); the counters on the
``train_step`` event; the cut configuration's arithmetic, the
benchmark's entries and their readers; the harness's rehearsal."""

import json
import os
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import flops  # noqa: E402  (the benchmark's own)
import ling_flops  # noqa: E402
import loader  # noqa: E402

from dlrover_tpu.parallel.moe import DroplessMoE, dropless_moe  # noqa: E402
from dlrover_tpu.telemetry.events import read_events  # noqa: E402
from dlrover_tpu.telemetry.schema import validate_event  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer  # noqa: E402

family = loader.load_module("models", "bailing_hybrid")
reference = family.reference
CONFIGS = os.path.join(REPO, "benchmarks", "configs")
CUT = loader.load_json(os.path.join(CONFIGS, "ling_3_flash_cut.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = {
    "kda.rule_ms_per_step": "linear attention",
    "kda.kernel_ms_per_step": "linear attention",
    "kda.rule_roofline_pct": "linear attention",
    "kda.mix_ms_per_step": "linear attention",
    "kda.log_decay_min": "linear attention",
    "moe.group_route_ms_per_step": "experts",
    "moe.groups_per_token_mean": "experts",
}


# -- group-limited routing ----------------------------------------------------


def layer(e=16, held=None, n_group=4, topk_group=2, top_k=4, shared=24):
    return DroplessMoE(
        num_experts=e, mlp_dim=24, top_k=top_k, dtype=jnp.float32,
        held=held, score="sigmoid", select_bias=True, renormalise=True,
        scale=2.5, shared_dim=shared, n_group=n_group,
        topk_group=topk_group,
    )


def layer_params(seed=0, e=16, d=32):
    whole = layer(e)
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, 96, d))
    params = whole.init(jax.random.PRNGKey(seed + 1), x)["params"]
    params["select_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 2), (e,)
    )
    return x, params


def test_the_shares_of_all_the_chips_add_up_to_the_uncut_layer():
    """Four chips, a GROUP of four experts each (a group a host): what
    each computes of the routed sum, with the shared expert counted
    once, is the layer that holds all sixteen."""
    x, params = layer_params()
    whole, stats = jax.jit(layer().apply)({"params": params}, x)
    parts = []
    for lo in range(0, 16, 4):
        share = {**params, **{
            k: params[k][lo:lo + 4]
            for k in ("experts_w_gate", "experts_w_in", "experts_w_out")
        }}
        out, said = jax.jit(layer(held=(lo, 4)).apply)(
            {"params": share}, x
        )
        np.testing.assert_array_equal(said["counts"], stats["counts"])
        parts.append(out)
    # the shared expert alone: a layer without it, taken from one with
    routed = {k: v for k, v in params.items() if not k.startswith("shared_")}
    without, _ = jax.jit(layer(shared=0).apply)({"params": routed}, x)
    shared = whole - without
    total = sum(parts) - 3 * shared
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-6)
    assert float(stats["groups_per_token"]) <= 2.0


def test_no_tokens_choices_pass_topk_group_groups():
    x, params = layer_params(seed=3)
    tokens = x.reshape(-1, x.shape[-1])
    picked = {}

    def spy(operand, k):
        picked.setdefault(operand.shape, []).append(operand)
        return real(operand, k)

    real = jax.lax.top_k
    jax.lax.top_k = spy
    try:
        _, stats = dropless_moe(
            tokens, params["router"], params["experts_w_gate"],
            params["experts_w_in"], params["experts_w_out"], 4,
            jnp.float32, score="sigmoid",
            select_bias=params["select_bias"], renormalise=True,
            n_group=4, topk_group=2,
        )
    finally:
        jax.lax.top_k = real
    # what stood for the final top-k: two groups of four are finite
    standing = picked[(tokens.shape[0], 16)][-1]
    kept = np.isfinite(np.asarray(standing)).reshape(-1, 4, 4)
    assert (kept.all(axis=-1) | ~kept.any(axis=-1)).all()
    assert (kept.any(axis=-1).sum(axis=-1) == 2).all()
    # and the reference's written-out choice is the same experts
    scores = jax.nn.sigmoid(tokens @ params["router"])
    ids = reference.choose(
        scores, params["select_bias"], top_k=4, n_group=4, topk_group=2
    )
    groups = np.asarray(ids) // 4
    assert max(len(set(row)) for row in groups.tolist()) <= 2
    counts = np.bincount(np.asarray(ids).ravel(), minlength=16)
    np.testing.assert_array_equal(stats["counts"], counts)
    assert 1.0 <= float(stats["groups_per_token"]) <= 2.0


def test_one_group_is_the_ungrouped_router():
    """``n_group = topk_group = 1`` (the fields' defaults) is the
    router ``sarvam_mla`` runs: the same program."""
    x, params = layer_params(seed=5)

    def text(**groups):
        mod = DroplessMoE(
            num_experts=16, mlp_dim=24, top_k=4, dtype=jnp.float32,
            held=(4, 4), score="sigmoid", select_bias=True,
            renormalise=True, scale=2.5, shared_dim=24, **groups,
        )
        share = {**params, **{
            k: params[k][4:8]
            for k in ("experts_w_gate", "experts_w_in", "experts_w_out")
        }}
        return jax.jit(
            lambda p, x: mod.apply({"params": p}, x)
        ).lower(share, x).as_text()

    assert text() == text(n_group=1, topk_group=1)
    assert text() != text(n_group=4, topk_group=2)


@pytest.mark.parametrize("groups, match", [
    (dict(n_group=3, topk_group=2), "do not divide"),
    (dict(n_group=8, topk_group=1), "top-4 of 1 groups of 2"),
    (dict(n_group=4, topk_group=5), "5 of 4 groups"),
])
def test_groups_that_cannot_hold_the_top_k_are_refused(groups, match):
    x, _ = layer_params()
    with pytest.raises(ValueError, match=match):
        layer(**groups).init(jax.random.PRNGKey(0), x)


# -- counters, the cut, the benchmark's entries -------------------------------


def test_the_counters_ride_on_the_train_step_event(tmp_path, monkeypatch):
    log = tmp_path / "events.jsonl"
    monkeypatch.setenv("DLROVER_EVENT_LOG", str(log))
    trainer = ElasticTrainer(
        global_batch_size=2, micro_batch_size=2, dp_size=1
    )
    trainer.report_step({
        "loss": 1.0, "kda.log_decay_min": -4.5, "kda.state_rms_max": 0.03,
        "moe.groups_per_token_mean": 3.9, "moe.held_rows_share": 0.03,
        "moe.bias_abs_max": 0.002, "grad_norm": 2.0,
    })
    (event,) = [e for e in read_events(str(log)) if e["type"] == "train_step"]
    assert validate_event(event) == []
    assert event["kda.log_decay_min"] == -4.5
    assert event["kda.state_rms_max"] == 0.03
    assert event["moe.groups_per_token_mean"] == 3.9
    assert "grad_norm" not in event


def test_the_cut_keeps_every_published_width_and_counts_as_the_issue_says():
    reduced = {
        "num_hidden_layers": (42, 7), "first_k_dense_replace": (2, 1),
        "num_experts": (512, 16), "vocab_size": (157184, 39296),
        "num_nextn_predict_layers": (1, 0),
    }
    assert sorted(CUT["reduced"]) == sorted(reduced)
    for key, (published, held) in reduced.items():
        assert CUT["published"][key] == published and CUT[key] == held
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [
                json.loads(line) for line in f if '"Ling-3.0-flash"' in line
            ]
        assert CUT["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in reduced:
                assert CUT[key] == value, key
    assert CUT["router_outputs"] == 512 and CUT["first_expert_held"] == 0
    assert CUT["layers_held"] == [1, 6, 7, 8, 9, 10, 11]
    assert reference.kinds(CUT) == ("kda",) * 6 + ("latent",)
    for key in "abcdefghijklm":
        assert any(
            text.startswith(f"({key})") for text in CUT["assumed"].values()
        ), key
    for key in ("source", "published", "reduced", "assumed", "deployment",
                "memory", "recipe", "reference"):
        assert CUT[key], key
    assert CUT["recipe"] == {
        **CUT["recipe"], "optimizer": "adamw_bf16", "attention": "flash",
        "remat": True, "loss_chunks": 8, "bias_update_rate": 0.001,
    }
    model, _, _ = family.build(CUT)
    shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), seq_len=128)
    )
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    # 7.61 GB of state at 6 B a parameter
    assert count == 1_268_172_736
    rule = shapes["block_3"]["kda"]
    assert rule["f_proj"]["kernel"].shape == (2560, 4096)
    assert rule["g_proj"]["kernel"].shape == (2560, 4096)
    assert rule["b_proj"]["kernel"].shape == (2560, 32)
    assert rule["q_conv"].shape == (4, 4096)
    assert rule["A_log"].shape == (32,) and rule["dt_bias"].shape == (4096,)
    assert rule["o_norm"].shape == (128,)
    attn = shapes["block_6"]["attn"]
    assert attn["q_proj"]["kernel"].shape == (2560, 32 * 192)
    assert attn["kv_down"]["kernel"].shape == (2560, 512 + 64)
    assert attn["kv_up"]["kernel"].shape == (512, 32 * 256)
    assert attn["g_proj"]["kernel"].shape == (2560, 32)
    assert "attn" not in shapes["block_5"] and "kda" not in shapes["block_6"]
    moe = shapes["block_1"]["moe"]
    assert moe["router"].shape == (2560, 512)
    assert moe["experts_w_gate"].shape == (16, 2560, 768)
    assert shapes["block_0"]["mlp"]["gate_proj"]["kernel"].shape == (
        2560, 6144
    )
    assert "mtp" not in shapes and shapes["lm_head"]["kernel"].shape == (
        2560, 39296
    )


def test_flops_py_counts_just_under_what_the_cell_requires():
    """``flops.py`` reads GPT-2's key names; the cut carries them set
    so that its count is just under ``ling_flops``'s: the matmul
    parameters to 1e-5, the whole by the rule's recurrence (1.4%),
    which ``flops.py`` has no key for."""
    required = ling_flops.matmul_params(CUT)
    assert required == 609_828_864
    assert 0 <= required - flops.matmul_params(CUT) < 2 * CUT["n_embd"]
    assert flops.attention_flops_per_token(CUT, 8192) == (
        ling_flops.attention_flops_per_token(CUT, 8192)
    ) == 6.0 * 8192 * 32 * 160
    ratio = flops.train_flops_per_token(CUT, 8192) / (
        ling_flops.train_flops_per_token(CUT, 8192)
    )
    assert ratio == pytest.approx(0.98572, abs=2e-5) and ratio < 1


def test_the_benchmark_gains_one_configuration_one_cell_seven_readers():
    bench = loader.load_json(os.path.join(REPO, "BENCHMARK.json"))
    (config,) = [
        c for c in bench["configs"] if c["name"] == "ling_3_flash_cut"
    ]
    assert config["reduced"] == CUT["reduced"]
    assert config["source"] == CUT["source"]
    assert config["file"] == "benchmarks/configs/ling_3_flash_cut.json"
    cells = [
        w for w in bench["workloads"] if w["config"] == "ling_3_flash_cut"
    ]
    assert cells == [{
        "name": "ling_3_steady_8k", "config": "ling_3_flash_cut",
        "traffic": "steady_8k", "chips": 1, "why": cells[0]["why"],
    }]
    assert len(cells[0]["why"]) <= 200 and len(config["why"]) <= 200
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name, layer in NEW_READERS.items():
        metric = listed[name]
        assert metric["workloads"] == ["ling_3_steady_8k"]
        assert metric["layer"] == layer
        reader = loader.load_module("layer_metrics", name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES,
                reader.SOURCE) == tuple(
            metric[k] for k in ("name", "unit", "layer", "moves", "source")
        )
    # no copy of another family's readers: their lists are not widened
    for name in ("moe.held_rows_share", "mla.proj_ms_per_step",
                 "attn.gate_ms_per_step", "conv.kernel_ms_per_step"):
        assert "ling_3_steady_8k" not in listed[name]["workloads"]


@pytest.mark.parametrize("leaf, limit", [
    ("['block_0']['kda']['f_proj']['kernel']", "decay_gradient_tolerance"),
    ("['block_5']['kda']['A_log']", "decay_gradient_tolerance"),
    ("['block_5']['kda']['dt_bias']", "decay_gradient_tolerance"),
    ("['block_0']['kda']['b_proj']['kernel']", "gradient_tolerance"),
    ("['block_5']['kda']['q_conv']", "gradient_tolerance"),
    ("['block_5']['kda']['o_norm']", "gradient_tolerance"),
    ("['block_0']['kda']['g_proj']['kernel']", "gradient_tolerance"),
    ("['block_6']['attn']['g_proj']['kernel']", "gradient_tolerance"),
    ("['block_6']['attn']['kv_norm']['scale']", "gradient_tolerance"),
    ("['block_3']['ln_mlp']['scale']", "gradient_tolerance"),
    ("['block_2']['moe']['router']", "routed_gradient_tolerance"),
    ("['block_6']['moe']['experts_w_in']", "routed_gradient_tolerance"),
])
def test_a_leaf_is_held_to_its_classs_limit(leaf, limit):
    assert family.kind_of(leaf) == limit
    assert family.compared(CUT)(leaf)
    assert limit in CUT["reference"]


def test_the_compared_leaves_leave_the_wide_matrices_out():
    pick = family.compared(CUT)
    assert not pick("['block_3']['kda']['q_proj']['kernel']")
    assert not pick("['block_2']['moe']['experts_w_gate']")
    assert not pick("['block_0']['mlp']['up_proj']['kernel']")
    assert not pick("['block_6']['moe']['shared_up']['kernel']")
    assert not pick("['lm_head']['kernel']")
    assert not pick("['wte']['embedding']")


def test_the_harness_rehearses_the_family_on_the_cpu(tmp_path, checkout):
    """``benchmarks/run.py`` end to end on the toy configuration:
    ``tpurun`` -> the worker -> the ``has_aux`` step with the
    interpreted ``kda_fwd`` / ``kda_bwd`` kernels -> the reference's
    loss and gradients -> the readers; exit code 3 (a rehearsal, never
    a result), ``correct`` true."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        # (from a checkout of its own: conftest.py, ROADMAP B7)
        [sys.executable, os.path.join(checkout, "benchmarks", "run.py"),
         "--cells", os.path.join(REPO, "benchmarks", "rehearsal_ling.json"),
         "--workload", "toy_ling_steady", "--seed", "5000000011",
         "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 3, done.stdout[-3000:] + done.stderr[-3000:]
    assert '"correct": true' in done.stdout
    assert "kda.log_decay_min" in done.stdout
    assert "moe.groups_per_token_mean" in done.stdout
