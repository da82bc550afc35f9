"""Olmo-Hybrid through the repo's blocks against the plain float32
reference (``benchmarks/models/olmo_hybrid_reference.py``: the rule as
a token-by-token recurrence), the two kinds of block, causality, the
published sizes, and the ``has_aux`` step that carries
``gdn.state_rms_max`` to the ``train_step`` event."""

import functools
import os
import sys

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import loader  # noqa: E402  (the benchmark's own)

from dlrover_tpu.models.gpt import count_params  # noqa: E402
from dlrover_tpu.models.olmo_hybrid import (  # noqa: E402
    FULL,
    LINEAR,
    PERIOD,
    OlmoHybrid,
    OlmoHybridConfig,
    make_olmo_hybrid_loss,
)
from dlrover_tpu.ops.gated_delta_rule import CHUNK  # noqa: E402
from dlrover_tpu.optim import adamw_bf16  # noqa: E402
from dlrover_tpu.telemetry.events import read_events  # noqa: E402
from dlrover_tpu.telemetry.schema import validate_event  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import (  # noqa: E402
    ElasticTrainer,
    TrainState,
    make_train_step,
)

reference = loader.load_module("models", "olmo_hybrid_reference")

# the HF keys of the toy configuration, as the reference reads them
CFG = {
    "layer_types": list(PERIOD), "num_attention_heads": 4,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_allow_neg_eigval": True, "rms_norm_eps": 1e-6,
}
SEQ = 2 * CHUNK + 24  # two chunks of the rule and a ragged tail; 5 x 56


@functools.cache
def toy_weights():
    """The toy's weights, made ONCE a module: the initialisation reads
    neither remat nor the compute dtype."""
    model = OlmoHybrid(OlmoHybridConfig.tiny())
    # (jitted: an eager init runs the whole model op by op)
    params = jax.jit(lambda key: model.init_params(key, seq_len=64))(
        jax.random.PRNGKey(7)
    )
    # at width 64 a head of 0.02 leaves the logits near uniform and the
    # loss blind to the blocks: scale it to the logits' spread at the
    # published width (0.02 x sqrt(3840))
    params["lm_head"]["kernel"] = params["lm_head"]["kernel"] * 8.0
    return params


def toy(dtype=jnp.float32, **kw):
    model = OlmoHybrid(OlmoHybridConfig.tiny(dtype=dtype, **kw))
    # (buffers of its own: a step donates its state)
    params = jax.tree.map(jnp.copy, toy_weights())
    tokens = jax.random.randint(
        jax.random.PRNGKey(8), (2, SEQ + 1), 0, 256
    )
    return model, params, {"x": tokens[:, :-1], "y": tokens[:, 1:]}


def toy_step():
    model, params, batch = toy(remat=True)
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    step = make_train_step(
        make_olmo_hybrid_loss(model, num_chunks=5), optimizer
    )
    return model, step, TrainState.create(params, optimizer), batch


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


# -- the family against the reference -------------------------------------------


def test_float32_loss_and_logits_equal_the_reference():
    model, params, batch = toy()
    loss, aux = jax.jit(make_olmo_hybrid_loss(model, num_chunks=5))(
        params, batch
    )
    want = reference.loss(params, batch["x"], batch["y"], CFG)
    assert abs(float(loss) - want) < 1e-5
    logits = jax.jit(lambda p, x: model.apply({"params": p}, x))(
        params, batch["x"]
    )
    ref_logits = jnp.stack(reference.forward(params, batch["x"], CFG))
    np.testing.assert_allclose(logits, ref_logits, rtol=0, atol=1e-4)
    assert set(aux) == {"gdn.state_rms_max"}
    assert float(aux["gdn.state_rms_max"]) > 0


def test_float32_gradients_equal_the_reference_leaf_by_leaf():
    """Every leaf of ``jax.grad`` of the training loss (projections,
    convolution taps, ``A_log``, ``dt_bias``, the gated norm's scale,
    QK-norms, MLPs, embedding and head), through the chunk-wise rule
    under remat against the recurrence, to 1e-4 of the leaf's largest
    entry."""
    model, params, batch = toy(remat=True)
    loss_fn = make_olmo_hybrid_loss(model, num_chunks=5)
    got = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(params)
    want = jax.jit(jax.grad(
        lambda p: reference.loss_of(p, batch["x"], batch["y"], CFG)
    ))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    # 3 linear blocks of 18 leaves, 1 full block of 11, wte, ln_f, head
    assert len(flat_got) == len(flat_want) == 3 * 18 + 11 + 3
    for (path, g), w in zip(flat_got, flat_want):
        assert np.abs(np.asarray(w)).max() > 0, path
        assert relative(g, w) < 1e-4, jax.tree_util.keystr(path)


def test_bfloat16_loss_is_within_bf16_rounding_of_the_reference():
    """bf16 compute (float32 accumulation, norms, decays, the rule's
    state and inverse, loss) on bf16-rounded weights against the
    float32 reference on the SAME rounded weights.  A bf16 rounding is
    2**-9 relative; over this toy's 556 tokens of a 256-word
    vocabulary the mean loss has been seen to differ by 4-5e-3.
    1.2e-2 is over twice that and far under what a wrong term costs
    (write strengths of 1 for 2 move the loss by 0.1: the last
    lines)."""
    model, params, batch = toy(dtype=jnp.bfloat16)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    loss, _ = jax.jit(make_olmo_hybrid_loss(model, num_chunks=5))(
        params, batch
    )
    want = reference.loss(params, batch["x"], batch["y"], CFG)
    assert abs(float(loss) - want) < 1.2e-2
    wrong = reference.loss(
        params, batch["x"], batch["y"],
        {**CFG, "linear_allow_neg_eigval": False},
    )
    assert abs(wrong - want) > 2e-2


# -- the blocks --------------------------------------------------------------------


def test_layer_types_decides_the_block_kind_and_a_period_has_3_and_1():
    assert PERIOD.count(LINEAR) == 3 and PERIOD.count(FULL) == 1
    published = OlmoHybridConfig()
    assert published.num_layers == 32
    assert published.layer_types == PERIOD * 8
    _, params, _ = toy()
    for i, kind in enumerate(PERIOD):
        block = params[f"block_{i}"]
        assert ("gdn" in block) == (kind == LINEAR), i
        assert ("attn" in block) == (kind == FULL), i
        # nothing else differs between the kinds
        assert set(block) - {"gdn", "attn"} == {
            "ln_mixer", "ln_mlp", "mlp"
        }
    swapped = OlmoHybrid(OlmoHybridConfig.tiny(
        layer_types=(FULL, LINEAR), dtype=jnp.float32
    ))
    params = swapped.init_params(jax.random.PRNGKey(0), seq_len=16)
    assert "attn" in params["block_0"] and "gdn" in params["block_1"]
    with pytest.raises(ValueError, match="unknown layer type"):
        OlmoHybrid(OlmoHybridConfig.tiny(
            layer_types=("sliding_attention",)
        )).init_params(jax.random.PRNGKey(0), seq_len=16)


def test_published_sizes_give_the_published_parameter_counts():
    """Matmul parameters a layer, from the published config: 215.5 M
    for a linear-attention layer, 185.8 M for a full one (their mean
    over a period is the catalog's "about 208M"); the one-period cut
    with the whole vocabulary is the 1.603 B the cell trains."""
    model = OlmoHybrid(OlmoHybridConfig(
        layer_types=PERIOD, param_dtype=jnp.bfloat16
    ))
    shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), seq_len=64)
    )

    def matmul_params(block):
        return sum(
            int(np.prod(leaf.shape))
            for path, leaf in jax.tree_util.tree_leaves_with_path(block)
            if path[-1].key == "kernel"
        )

    assert matmul_params(shapes["block_0"]) == 215_516_160
    assert matmul_params(shapes["block_3"]) == 185_794_560
    taps = (2880 + 2880 + 5760) * 4
    assert sum(
        int(np.prod(shapes["block_0"]["gdn"][name].shape))
        for name in ("q_conv", "k_conv", "v_conv")
    ) == taps
    total = count_params(shapes)
    assert total == (
        3 * 215_516_160 + 185_794_560          # the matrices
        + 3 * (taps + 30 + 30 + 192)           # taps, A_log, dt_bias, o_norm
        + 2 * 3840                             # q_norm, k_norm
        + 4 * 2 * 3840 + 3840                  # block norms, ln_f
        + 2 * 100352 * 3840                    # embedding, untied head
    )
    assert round(total / 1e9, 3) == 1.603


def test_the_whole_model_is_causal():
    """A changed token at position t moves no logit before t, through
    the convolutions, the rule's chunks and full attention; it does
    move logits after t in a LATER chunk of the rule (the state
    carries it)."""
    model, params, batch = toy()
    t = CHUNK + 6  # inside the rule's second chunk
    tokens = batch["x"][:1]
    base = model.apply({"params": params}, tokens)
    other = model.apply(
        {"params": params}, tokens.at[0, t].set((tokens[0, t] + 1) % 256)
    )
    moved = np.abs(np.asarray(other - base)).max(axis=-1)[0]
    assert not moved[:t].any()
    assert moved[t] > 0 and moved[2 * CHUNK + 12] > 0


# -- what the benchmark's readers join on ------------------------------------------


def test_the_layers_scopes_are_in_the_compiled_step():
    """Each of the layer's four device scopes names operations of the
    compiled step, forward and backward (the loss head's are all
    forward: its gradients are formed there); the rule's hand-over is a
    ``while`` under ``gdn_rule`` whose body's operations carry
    ``gdn_rule/../while/body`` (what ``gdn_flops.py`` tells a scan's
    two appearances in a trace by)."""
    from dlrover_tpu.common.aot_cache import op_names

    _, step, state, batch = toy_step()
    compiled = step.lower(state, batch).compile()
    stacks = list(op_names(compiled.as_text())["op_names"].values())
    for scope in ("gdn_conv", "gdn_gates", "gdn_rule", "gdn_norm"):
        named = [s for s in stacks if scope in s]
        assert named, scope
        assert any("transpose(" in s for s in named), scope
    head = [s for s in stacks if "loss_head" in s]
    assert sum(s.endswith("/dot_general") for s in set(head)) == 3
    assert all("jvp(loss_head)" in s for s in head)
    assert not any("rematted_computation" in s for s in head)
    rule = [s for s in stacks if "gdn_rule" in s]
    assert any("/while/body/" in s for s in rule)
    # the full-attention mixer is the module ``attn``, the linear one
    # is not (kernels.py finds flash kernels by ``attn``)
    assert any("/block_3/attn/" in s for s in stacks)
    assert not [s for s in stacks if "gdn" in s and "/attn/" in s]


# -- the step that carries the counter -----------------------------------------


def test_has_aux_puts_the_counter_into_the_metrics():
    model, params, batch = toy()
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    loss_fn = make_olmo_hybrid_loss(model, num_chunks=5)
    assert loss_fn.has_aux  # read by make_train_step: no argument
    step = make_train_step(loss_fn, optimizer)
    # (the step donates its state)
    loss, aux = jax.jit(loss_fn)(params, batch)
    _, metrics = step(TrainState.create(params, optimizer), batch)
    assert set(metrics) == {"loss", "grad_norm", "gdn.state_rms_max"}
    assert float(metrics["loss"]) == pytest.approx(float(loss))
    assert float(metrics["gdn.state_rms_max"]) == pytest.approx(
        float(aux["gdn.state_rms_max"])
    )


def test_state_rms_is_the_largest_linear_layers():
    """The counter against the reference's recurrence: the root mean
    square of each linear layer's state after the last token, the
    largest of the three."""
    model, params, batch = toy()
    _, rms = model.apply(
        {"params": params}, batch["x"], return_hidden=True,
        return_state_rms=True,
    )
    assert float(rms) > 0
    _, alone = OlmoHybrid(OlmoHybridConfig.tiny(
        layer_types=(FULL,), dtype=jnp.float32
    )).apply(
        {"params": {**params, "block_0": params["block_3"]}},
        batch["x"], return_hidden=True, return_state_rms=True,
    )
    assert float(alone) == 0.0  # no linear layer, no state


def test_the_counter_rides_on_the_train_step_event(tmp_path, monkeypatch):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("DLROVER_EVENT_LOG", path)
    monkeypatch.setenv(
        "DLROVER_METRICS_FILE", str(tmp_path / "metrics.json")
    )
    trainer = ElasticTrainer(4, 4, dp_size=1)
    trainer.report_step({
        "loss": jnp.float32(1.5), "grad_norm": jnp.float32(0.1),
        "gdn.state_rms_max": jnp.float32(0.25),
    })
    trainer.report_step({"loss": 1.0})
    first, second = [
        e for e in read_events(path) if e["type"] == "train_step"
    ]
    assert first["gdn.state_rms_max"] == 0.25
    assert "grad_norm" not in first
    assert not [k for k in second if k.startswith("gdn.")]
    assert not validate_event(first) and not validate_event(second)
