"""What the benchmark has of the ``sarvam_mla`` family: the published
sizes' parameter counts, the counters on the ``train_step`` event,
what ``correct`` compares beside the loss (a sound program, the faulty
ones and the control, each leaf and the bias by its own limit), and
the harness's rehearsal.  The family against its reference is
``test_sarvam_mla.py``."""

import math
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

from dlrover_tpu.models.sarvam_mla import (  # noqa: E402
    SarvamMla,
    SarvamMlaConfig,
)
from dlrover_tpu.telemetry.events import read_events  # noqa: E402
from dlrover_tpu.telemetry.schema import validate_event  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import (  # noqa: E402
    STATE_UPDATES,
    ElasticTrainer,
)

reference = loader.load_module("models", "sarvam_mla_reference")


def test_published_sizes_give_the_issues_parameter_counts():
    """The cut configuration's share, leaf by leaf from the shapes:
    attention at 16 heads 25,427,968 a layer, the dense SwiGLU
    201,326,592, an expert layer's shared expert 25,165,824, router
    524,288 and 8 held experts 201,326,592, embedding + head
    268,435,456: 1.505 B, 9.03 GB at 6 bytes."""
    model = SarvamMla(SarvamMlaConfig(
        vocab_size=32768, num_layers=5, num_heads_held=16,
        experts_held=(0, 8),
    ))
    shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), seq_len=128)
    )

    def count(tree):
        return sum(math.prod(x.shape) for x in jax.tree.leaves(tree))

    attn = shapes["block_1"]["attn"]
    assert count(attn) - 512 == 25_427_968
    assert attn["q_proj"]["kernel"].shape == (4096, 16 * 192)
    assert attn["kv_down"]["kernel"].shape == (4096, 512 + 64)
    assert attn["kv_up"]["kernel"].shape == (512, 16 * (128 + 128))
    assert attn["o_proj"]["kernel"].shape == (16 * 128, 4096)
    assert count(shapes["block_0"]["mlp"]) == 201_326_592
    expert_layer = shapes["block_4"]["moe"]
    assert expert_layer["router"].shape == (4096, 128)
    assert expert_layer["select_bias"].shape == (128,)
    assert expert_layer["experts_w_gate"].shape == (8, 4096, 2048)
    shared = sum(
        count(expert_layer[f"shared_{n}"]) for n in ("gate", "up", "down")
    )
    assert shared == 25_165_824
    assert count(expert_layer) == 25_165_824 + 524_288 + 128 + 201_326_592
    assert "mlp" not in shapes["block_1"] and "moe" not in shapes["block_0"]
    total = count(shapes)
    assert round(total / 1e6) == 1505 and round(total * 6 / 1e7) == 903


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
        "moe.held_tiles_share": jnp.float32(0.078125),
        "moe.bias_abs_max": jnp.float32(0.003),
    })
    (event,) = [e for e in read_events(path) if e["type"] == "train_step"]
    assert event["moe.held_rows_share"] == 0.0625
    assert event["moe.held_tiles_share"] == 0.078125
    assert event["moe.bias_abs_max"] == pytest.approx(0.003)
    assert not validate_event(event)


# -- what the benchmark's ``correct`` compares beside the loss ------------------


@pytest.fixture(scope="module")
def toy_cell():
    """The benchmark family on its toy configuration: ``(family, cfg,
    params, batch, what a sound program reads)``."""
    import worker  # the benchmark's own

    family = loader.load_module("models", "sarvam_mla")
    cfg = loader.load_json(
        os.path.join(REPO, "benchmarks", "configs", "toy_sarvam_mla.json")
    )
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
    assert sound["bias"] <= limits["bias_update_tolerance"]
    # every block's attention, norms and router; the last block's
    # held experts and no other's
    leaves = set(sound["gradients"])
    assert "['block_0']['attn']['q_proj']['kernel']" in leaves
    assert "['block_1']['moe']['router']" in leaves
    assert "['block_2']['moe']['experts_w_out']" in leaves
    assert "['block_1']['moe']['experts_w_out']" not in leaves
    assert not any("select_bias" in leaf for leaf in leaves)
    # (the comparison itself runs again in the harness's rehearsal)
    monkeypatch.setattr(family, "comparisons", lambda *a: sound)
    got = family.reference_loss(params, batch["x"], batch["y"], cfg)
    assert got == sound["loss"] == reference.loss(
        params, batch["x"], batch["y"], cfg
    )
    assert "first gradient" in capfd.readouterr().err


@pytest.mark.parametrize("fault", ["no_dq", "bias_sign", "three_bits"])
def test_a_faulty_program_is_told_apart(toy_cell, monkeypatch, fault):
    """A program whose query gradient is missing (what a wrong ``dq``
    of the attention kernels would do to ``q_proj``), one whose bias
    rule has the wrong sign, and the lower-precision control
    (``recipe.operand_mantissa_bits`` 3: e4m3's mantissa) each read
    far from a sound one; the first two are ``inf`` to the harness."""
    family, cfg, params, batch, sound = toy_cell
    build = family.build

    def faulty(cfg):
        model, optimizer, loss_fn = build(cfg)

        def loss(params, batch):
            if fault == "no_dq":
                params = dict(params, block_1=dict(
                    params["block_1"], attn=dict(
                        params["block_1"]["attn"],
                        q_proj=jax.lax.stop_gradient(
                            params["block_1"]["attn"]["q_proj"]
                        ),
                    ),
                ))
            value, aux = loss_fn(params, batch)
            if fault == "bias_sign":
                aux = dict(aux, **{STATE_UPDATES: jax.tree.map(
                    jnp.negative, aux[STATE_UPDATES]
                )})
            return value, aux

        return model, optimizer, loss

    if fault == "three_bits":
        cfg = dict(cfg, recipe=dict(cfg["recipe"], operand_mantissa_bits=3))
    else:
        monkeypatch.setattr(family, "build", faulty)
    found = family.comparisons(params, batch["x"], batch["y"], cfg)
    worst = max(found["gradients"].values())
    if fault == "no_dq":
        leaf = "['block_1']['attn']['q_proj']['kernel']"
        assert found["gradients"][leaf] == 1.0
    elif fault == "bias_sign":
        assert found["bias"] > 0.9 and sound["bias"] < 0.1
    else:
        median = np.median(list(found["gradients"].values()))
        assert median > 3 * np.median(list(sound["gradients"].values()))
        assert worst > 2 * max(sound["gradients"].values())
        return
    monkeypatch.setattr(family, "comparisons", lambda *a: found)
    assert family.reference_loss(
        params, batch["x"], batch["y"], cfg
    ) == float("inf")


@pytest.mark.parametrize("gradients, bias, inside", [
    ({"['attn']['q_proj']": 0.1, "['moe']['router']": 0.3}, 0.01, True),
    ({"['attn']['q_proj']": 0.1, "['moe']['router']": 0.6}, 0.01, False),
    ({"['attn']['q_proj']": 0.3, "['moe']['router']": 0.3}, 0.01, False),
    ({"['attn']['q_proj']": 0.1, "['moe']['router']": 0.3}, 0.2, False),
    ({"['attn']['q_proj']": float("nan"), "['attn']['o_proj']": 0.1,
      "['moe']['router']": 0.3}, 0.01, False),
])
def test_every_leaf_and_the_bias_are_judged_by_their_own_limit(
    monkeypatch, gradients, bias, inside
):
    """A routed leaf by the routed limit, any other by the other, the
    bias deltas by theirs; a gradient that is not a number is outside
    whatever the worst of the others reads."""
    family = loader.load_module("models", "sarvam_mla")
    monkeypatch.setattr(family, "comparisons", lambda *a: {
        "loss": 1.5, "gradients": gradients, "bias": bias,
    })
    cfg = {"reference": {
        "gradient_tolerance": 0.2, "routed_gradient_tolerance": 0.5,
        "bias_update_tolerance": 0.15,
    }}
    got = family.reference_loss(None, None, None, cfg)
    assert got == (1.5 if inside else float("inf"))


def test_the_harness_rehearses_the_family_on_the_cpu(tmp_path, checkout):
    """``benchmarks/run.py`` end to end on the toy configuration:
    ``tpurun`` -> the worker -> the ``has_aux`` step with its
    ``state_updates`` -> the reference's loss -> the readers; exit
    code 3 (a rehearsal, never a result), ``correct`` true."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        # (from a checkout of its own: conftest.py, ROADMAP B7)
        [sys.executable, os.path.join(checkout, "benchmarks", "run.py"),
         "--cells", os.path.join(
             REPO, "benchmarks", "rehearsal_sarvam_mla.json"),
         "--workload", "toy_sarvam_mla_steady", "--seed", "3500000007",
         "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 3, done.stdout[-3000:] + done.stderr[-3000:]
    assert '"correct": true' in done.stdout
    assert "moe.held_rows_share" in done.stdout
