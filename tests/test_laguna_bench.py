"""What the benchmark has of the ``laguna`` family: the cut
configuration's arithmetic and its flops keys, the counter and the
scopes the benchmark's readers join on, the five readers, what
``correct`` compares beside the loss (a sound program, the faulty ones
and the control), and the harness's rehearsal.  The family against
its reference is ``test_laguna.py``."""

import os
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import loader  # noqa: E402  (the benchmark's own)

from dlrover_tpu.models import laguna  # noqa: E402
from dlrover_tpu.models.laguna import (  # noqa: E402
    Laguna,
    LagunaConfig,
    make_laguna_loss,
    window_tiles_share,
)
from dlrover_tpu.optim import adamw_bf16  # noqa: E402
from dlrover_tpu.telemetry.events import read_events  # noqa: E402
from dlrover_tpu.telemetry.schema import validate_event  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import (  # noqa: E402
    ElasticTrainer,
    TrainState,
    make_train_step,
)

from test_laguna import toy  # noqa: E402

reference = loader.load_module("models", "laguna_reference")
CONFIGS = os.path.join(REPO, "benchmarks", "configs")


# -- the cut configuration ----------------------------------------------------


def test_the_cut_keeps_every_published_width_and_counts_as_the_issue_says():
    """``laguna_s_2_1_cut.json`` against the catalog's row: every key
    that is not in ``reduced`` is the published one; the per-layer
    lists are the first five entries; the model it builds has the
    parameters the issue reckons (1.113 B, 6.68 GB of bf16 state)."""
    import json

    cut = loader.load_json(os.path.join(CONFIGS, "laguna_s_2_1_cut.json"))
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(
                r for r in map(json.loads, f) if r["name"] == "Laguna-S-2.1"
            )
        assert cut["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cut["reduced"]:
                assert cut[key] == value, key
            elif isinstance(value, list):
                assert cut[key] == value[:5], key
    assert sorted(cut["reduced"]) == sorted([
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "mlp_layer_types", "gating_types", "num_attention_heads_per_layer",
    ])
    assert (cut["num_hidden_layers"], cut["num_experts"],
            cut["vocab_size"]) == (5, 16, 12544)
    assert cut["published"]["num_experts"] == cut["router_outputs"] == 256
    assert cut["layer_types"].count("sliding_attention") == 3
    family = loader.load_module("models", "laguna")
    model, _, _ = family.build(cut)
    shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), 1, 128)
    )
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree)
    )
    assert count(shapes["block_0"]["attn"]) == 44_187_648
    assert count(shapes["block_1"]["attn"]) == 63_135_744
    assert count(shapes["block_0"]["mlp"]) == 113_246_208
    moe = shapes["block_1"]["moe"]
    assert moe["router"].shape == (3072, 256)
    assert moe["experts_w_gate"].shape == (16, 3072, 1024)
    assert count(moe) == 150_994_944 + 9_437_184 + 786_432
    assert count(shapes) == 1_113_007_104
    assert all(
        x.dtype == jnp.bfloat16 for x in jax.tree.leaves(shapes)
        if x.ndim > 1
    )


def test_the_flops_keys_count_what_the_step_requires():
    """The GPT-2 key names ``flops.py`` reads, against the arithmetic
    in the file's ``assumed`` and ``laguna_flops.py``'s own count of
    the window."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import flops
    import laguna_flops

    cut = loader.load_json(os.path.join(CONFIGS, "laguna_s_2_1_cut.json"))
    assert laguna_flops.mean_keys(8192, 512) == pytest.approx(496.03125)
    assert laguna_flops.mean_keys(256, 512) == pytest.approx(128.5)
    assert laguna_flops.sliding_layers(cut) == [72, 72, 72]
    window = laguna_flops.window_flops_per_token(cut, 8192)
    assert window == pytest.approx(3 * 12 * 496.03125 * 9216)
    required = 2 * 6 * 8192 * 6144 + window
    assert required == pytest.approx(768_551_040)
    counted = flops.attention_flops_per_token(cut, 8192)
    assert 0.9999 * required < counted <= required
    matmul = (
        2 * 44_187_648 + 3 * 63_135_744 + 113_246_208
        + 4 * (9_437_184 + 786_432 + 0.625 * 9_437_184) + 12544 * 3072
    )
    assert matmul == 494_051_328
    assert 0.9999 * matmul < flops.matmul_params(cut) <= matmul
    assert flops.train_flops_per_token(cut, 8192) == pytest.approx(
        3.7329e9, rel=1e-4
    )
    # q o do dq at 9216 lanes, k v dk dv at 1024, three layers, bf16
    assert laguna_flops.window_bytes_per_step(cut, 1, 8192) == (
        8192 * 3 * 6 * (9216 + 1024) * 2
    )
    # the accepted reader's bytes stay under what the step moves
    moved = 8192 * 2 * 6 * (2 * (6144 + 1024) + 3 * (9216 + 1024))
    assert flops.attention_bytes_per_step(cut, 1, 8192) < moved


# -- counters and scopes ------------------------------------------------------


def test_the_window_saves_21_of_36_tiles_at_the_cells_shape():
    cfg = LagunaConfig(attention_impl="flash")
    assert window_tiles_share(cfg, 8192) == pytest.approx(15 / 36)
    assert window_tiles_share(cfg, 1024) == 1.0
    assert window_tiles_share(LagunaConfig(), 8192) is None
    only_full = LagunaConfig(
        attention_impl="flash", layer_types=(laguna.FULL,),
        heads_per_layer=(48,), mlp_layer_types=("dense",),
    )
    assert window_tiles_share(only_full, 8192) is None


def test_the_counters_ride_on_the_train_step_event(tmp_path, monkeypatch):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("DLROVER_EVENT_LOG", path)
    monkeypatch.setenv(
        "DLROVER_METRICS_FILE", str(tmp_path / "metrics.json")
    )
    trainer = ElasticTrainer(4, 4, dp_size=1)
    trainer.report_step({
        "loss": jnp.float32(1.5), "grad_norm": jnp.float32(0.1),
        "moe.held_rows_share": jnp.float32(0.0625),
        "attn.window_tiles_share": jnp.float32(15 / 36),
    })
    (event,) = [e for e in read_events(path) if e["type"] == "train_step"]
    assert event["attn.window_tiles_share"] == pytest.approx(15 / 36)
    assert not validate_event(event)


def test_the_layers_scopes_are_in_the_compiled_step():
    """What the benchmark's readers join on: a sliding layer's
    attention under ``swa``, a full layer's under ``full_attn``, both
    with the module ``attn`` inside; ``attn_rope`` and ``attn_gate``;
    the held layer's scopes."""
    from dlrover_tpu.common.aot_cache import op_names

    _, _, model, loss_fn, params, batch = toy(remat=True)
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    step = make_train_step(loss_fn, optimizer)
    state = TrainState.create(params, optimizer)
    stacks = list(op_names(
        step.lower(state, batch).compile().as_text()
    )["op_names"].values())
    for wanted in (
        "/block_0/full_attn/attn/", "/block_1/swa/attn/",
        "/block_2/swa/attn/", "/attn/attn_rope/", "/attn/attn_gate/",
        "/moe_router/", "/moe_experts/", "/moe_shared/",
    ):
        assert any(wanted in s for s in stacks), wanted
    assert not any("/block_0/swa/" in s for s in stacks)
    assert not any("/block_1/full_attn/" in s for s in stacks)


# -- the benchmark's five readers ----------------------------------------------


class TracedRun:
    """What ``run.py`` hands a reader, for a step whose flash kernels
    took 3 ms in a sliding layer's calls and 4 ms in a full layer's
    over five traced steps, with the op-name map beside the AOT
    entry."""

    traffic = {"batch": 1, "seq": 8192}
    report = {
        "window": {"steps": [{"step": s} for s in (5, 6, 7)]},
        "device": {"kind": "TPU v5 lite"},
    }

    def __init__(self, directory, config, traced=True, counter=True):
        import flops

        self.flops, self.config, self.notes = flops, config, []
        call = "tpu_custom_call"
        self.trace = {"steps": 5, "ops": {
            "%attn.1": {"seconds": 0.010, "count": 5, "target": call},
            "%attn.2": {"seconds": 0.005, "count": 5, "target": call},
            "%attn.3": {"seconds": 0.020, "count": 5, "target": call},
            "%fusion.4": {"seconds": 0.002, "count": 5, "target": ""},
            "%fusion.5": {"seconds": 0.004, "count": 10, "target": ""},
            "%gmm_fwd.6": {"seconds": 0.5, "count": 5, "target": call},
        }} if traced else None
        stack = "jit(step)/jvp(Laguna)/block_{}/{}/attn/{}"
        # a backward kernel's: under the block's ``checkpoint`` and,
        # since the block keeps the forward's results (PR 44), none
        # under ``rematted_computation``
        back = (
            "jit(step)/transpose(jvp(Laguna))/jvp(Laguna)/checkpoint/"
            "block_{}/{}/attn/pallas_call"
        )
        with open(os.path.join(directory, "k.opnames.json"), "w") as f:
            import json

            json.dump({"op_names": {
                "%attn.1": stack.format(1, "swa", "pallas_call"),
                "%attn.2": back.format(1, "swa"),
                "%attn.3": back.format(0, "full_attn"),
                "%fusion.4": stack.format(1, "swa", "attn_gate/mul"),
                "%fusion.5": stack.format(0, "full_attn", "attn_rope/cos"),
                "%gmm_fwd.6": "jit(step)/block_1/moe/moe_experts/gmm",
            }}, f)
        self.events = [{"type": "aot_cache", "key": "k", "dir": directory}]
        if counter:
            self.events += [
                {"type": "train_step", "step": s,
                 "attn.window_tiles_share": 15 / 36} for s in (4, 5, 6, 7)
            ]

    def of(self, type_, **match):
        return [e for e in self.events if e["type"] == type_]

    def note(self, line):
        self.notes.append(line)


READERS = {
    "swa.flash_ms_per_step": 3.0,
    # least: 3 x 12 x 496.03 x 9216 x 8192 FLOPs at 197 TFLOP/s
    "swa.flash_roofline_pct": 100 * (
        3 * 12 * 496.03125 * 9216 * 8192 / 197e12
    ) / 3e-3,
    "swa.tiles_walked_share": 15 / 36,
    "attn.gate_ms_per_step": 0.4,
    "attn.rope_ms_per_step": 0.8,
}


@pytest.mark.parametrize("name", list(READERS))
def test_a_reader_reads_its_scope_and_is_silent_without_it(name, tmp_path):
    """Each of the cell's five readers on a run that carries what it
    reads, on one with no trace, and on the events of a program
    without the counter (the parent's): a number, then nothing."""
    import json

    cut = loader.load_json(os.path.join(CONFIGS, "laguna_s_2_1_cut.json"))
    reader = loader.load_module("layer_metrics", name)
    run = TracedRun(str(tmp_path), cut)
    assert reader.read(run) == pytest.approx(READERS[name])
    assert run.notes
    if name == "swa.flash_ms_per_step":
        # 216 sliding heads in 3 ms, 48 + 48 full heads in 4
        assert "0.333 of a full one" in run.notes[0]
    bare = TracedRun(str(tmp_path), cut, traced=False, counter=False)
    assert reader.read(bare) is None and not bare.notes
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == ["laguna_steady_8k"]
    assert entry["layer"] == "window attention"
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES,
            reader.SOURCE) == tuple(
        entry[k] for k in ("name", "unit", "layer", "moves", "source")
    )


def test_flash_calls_a_step_are_counted_from_the_trace(tmp_path):
    """``kernel.flash_calls_per_step`` (PR 44): the flash kernels'
    executions over the traced steps, the grouped matmuls' left out:
    3 a layer when no forward runs twice.  Silent without a trace or
    without a flash kernel in it; no ``workloads`` list (every cell
    calls the kernels)."""
    import json

    cut = loader.load_json(os.path.join(CONFIGS, "laguna_s_2_1_cut.json"))
    reader = loader.load_module("layer_metrics", "kernel.flash_calls_per_step")
    run = TracedRun(str(tmp_path), cut)
    assert reader.read(run) == 3.0
    run.trace["ops"]["%attn.7"] = {
        "seconds": 0.010, "count": 5, "target": "tpu_custom_call",
    }
    assert reader.read(run) == 4.0
    assert reader.read(TracedRun(str(tmp_path), cut, traced=False)) is None
    run.trace["ops"] = {"%gmm_fwd.6": run.trace["ops"]["%gmm_fwd.6"]}
    assert reader.read(run) is None
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == reader.NAME]
    assert "workloads" not in entry and entry["better"] == "lower"
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES,
            reader.SOURCE) == tuple(
        entry[k] for k in ("name", "unit", "layer", "moves", "source")
    )


def test_the_benchmark_gains_one_configuration_and_one_cell():
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [
        w for w in bench["workloads"] if w["name"] == "laguna_steady_8k"
    ]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna_s_2_1_cut", "steady_8k", 1
    )
    (config,) = [
        c for c in bench["configs"] if c["name"] == "laguna_s_2_1_cut"
    ]
    cut = loader.load_json(os.path.join(REPO, config["file"]))
    assert config["reduced"] == cut["reduced"]
    assert config["source"] == cut["source"]
    assert all(len(x["why"]) <= 200 for x in (cell, config))
    for key in ("published", "assumed", "deployment", "memory", "reference"):
        assert cut[key], key
    assert {"gate", "router", "shared_expert", "qk_norm", "block",
            "rope_lane_pairing", "auxiliary_loss"} <= set(cut["assumed"])


# -- what the benchmark's ``correct`` compares beside the loss ------------------


@pytest.fixture(scope="module")
def toy_cell():
    """The benchmark family on its toy configuration as the harness
    runs it (bf16): ``(family, cfg, params, batch, what a sound
    program reads)``."""
    import worker  # the benchmark's own

    family = loader.load_module("models", "laguna")
    cfg = loader.load_json(os.path.join(CONFIGS, "toy_laguna.json"))
    traffic = loader.load_json(
        os.path.join(REPO, "benchmarks", "traffic", "toy_steady.json")
    )
    seed = 3500000011
    model, _, _ = family.build(cfg)
    params = model.init_params(
        worker.seed_key(seed), seq_len=traffic["seq"]
    )
    batch = jax.tree.map(
        jnp.asarray, worker.fixed_batch(cfg, traffic, seed)
    )
    sound = family.comparisons(params, batch["x"], batch["y"], cfg)
    return family, cfg, params, batch, sound


def test_a_sound_program_reads_the_references_loss(
    toy_cell, monkeypatch, capfd
):
    family, cfg, params, batch, sound = toy_cell
    limits = cfg["reference"]
    for kind, limit in ((True, "routed_gradient_tolerance"),
                        (False, "gradient_tolerance")):
        assert max(
            d for leaf, d in sound["gradients"].items()
            if family.routed(leaf) == kind
        ) < limits[limit]
    leaves = set(sound["gradients"])
    for name in ("q_proj", "k_proj", "v_proj", "g_proj", "o_proj"):
        for block in range(3):
            assert f"['block_{block}']['attn']['{name}']['kernel']" in leaves
    assert "['block_1']['moe']['router']" in leaves
    assert "['block_2']['moe']['experts_w_out']" in leaves
    assert "['block_1']['moe']['experts_w_out']" not in leaves
    monkeypatch.setattr(family, "comparisons", lambda *a: sound)
    got = family.reference_loss(params, batch["x"], batch["y"], cfg)
    assert got == sound["loss"] == reference.loss(
        params, batch["x"], batch["y"], cfg
    )
    assert "first gradient" in capfd.readouterr().err


@pytest.mark.parametrize("fault", [
    "no_window", "no_gate_gradient", "one_rope_rule", "three_bits",
])
def test_a_faulty_program_is_told_apart(toy_cell, monkeypatch, fault):
    """A program whose sliding layers see the whole past, one whose
    gate takes no gradient, one that gives the full layers the sliding
    layers' rope rule, and the lower-precision control
    (``recipe.operand_mantissa_bits`` 3) each read far from a sound
    one; the first three are ``inf`` to the harness."""
    family, cfg, params, batch, sound = toy_cell
    build = family.build

    def faulty(cfg):
        model, optimizer, loss_fn = build(cfg)
        if fault == "no_window":
            import dataclasses

            model = Laguna(dataclasses.replace(
                model.config, sliding_window=cfg["max_position_embeddings"]
            ))
            return model, optimizer, make_laguna_loss(model, 4)
        if fault == "one_rope_rule":
            import dataclasses

            model = Laguna(dataclasses.replace(
                model.config, full_rope=model.config.sliding_rope
            ))
            return model, optimizer, make_laguna_loss(model, 4)

        def loss(params, batch):
            params = dict(params, block_1=dict(
                params["block_1"], attn=dict(
                    params["block_1"]["attn"],
                    g_proj=jax.lax.stop_gradient(
                        params["block_1"]["attn"]["g_proj"]
                    ),
                ),
            ))
            return loss_fn(params, batch)

        return model, optimizer, loss

    if fault == "three_bits":
        cfg = dict(cfg, recipe=dict(cfg["recipe"], operand_mantissa_bits=3))
    else:
        monkeypatch.setattr(family, "build", faulty)
    found = family.comparisons(params, batch["x"], batch["y"], cfg)
    if fault == "three_bits":
        median = np.median(list(found["gradients"].values()))
        assert median > 3 * np.median(list(sound["gradients"].values()))
        return
    if fault == "no_gate_gradient":
        leaf = "['block_1']['attn']['g_proj']['kernel']"
        assert found["gradients"][leaf] == pytest.approx(1.0)
    monkeypatch.setattr(family, "comparisons", lambda *a: found)
    assert family.reference_loss(
        params, batch["x"], batch["y"], cfg
    ) == float("inf")


@pytest.mark.parametrize("gradients, inside", [
    ({"['attn']['q_proj']": 0.1, "['moe']['router']": 0.3}, True),
    ({"['attn']['q_proj']": 0.1, "['moe']['router']": 0.6}, False),
    ({"['attn']['g_proj']": 0.3, "['moe']['router']": 0.3}, False),
    ({"['attn']['q_proj']": float("nan"), "['attn']['o_proj']": 0.1,
      "['moe']['experts_w_in']": 0.3}, False),
])
def test_every_leaf_is_judged_by_its_own_limit(
    monkeypatch, gradients, inside
):
    family = loader.load_module("models", "laguna")
    monkeypatch.setattr(family, "comparisons", lambda *a: {
        "loss": 1.5, "gradients": gradients,
    })
    cfg = {"reference": {
        "gradient_tolerance": 0.2, "routed_gradient_tolerance": 0.5,
    }}
    got = family.reference_loss(None, None, None, cfg)
    assert got == (1.5 if inside else float("inf"))


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
         "--cells", os.path.join(REPO, "benchmarks", "rehearsal_laguna.json"),
         "--workload", "toy_laguna_steady", "--seed", "3500000007",
         "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 3, done.stdout[-3000:] + done.stderr[-3000:]
    assert '"correct": true' in done.stdout
    assert "moe.held_rows_share" in done.stdout
