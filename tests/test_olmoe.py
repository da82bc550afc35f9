"""OLMoE through the repo's blocks against the plain float32 reference
(``benchmarks/models/olmoe_reference.py``), the dropless expert layer
against its own claims, and the ``has_aux`` step that carries the
routing counters to the ``train_step`` event."""

import functools
import os
import sys

import pytest

jax = pytest.importorskip("jax")

import flax.linen as nn  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import loader  # noqa: E402  (the benchmark's own)
from conftest import primitives_under  # noqa: E402

from dlrover_tpu.models.gpt import GPT, GPTConfig, cross_entropy_loss  # noqa: E402
from dlrover_tpu.models.olmoe import (  # noqa: E402
    Olmoe,
    OlmoeConfig,
    make_olmoe_loss,
    router_losses,
)
from dlrover_tpu.optim import adamw_bf16  # noqa: E402
from dlrover_tpu.parallel.moe import (  # noqa: E402
    DroplessMoE,
    MoEMLP,
    dropless_moe,
)
from dlrover_tpu.telemetry.events import read_events  # noqa: E402
from dlrover_tpu.telemetry.schema import validate_event  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import (  # noqa: E402
    ElasticTrainer,
    TrainState,
    make_train_step,
)

reference = loader.load_module("models", "olmoe_reference")

# the HF keys of a toy configuration, as the reference reads them
CFG = {
    "num_attention_heads": 4, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "num_experts_per_tok": 2, "num_hidden_layers": 2, "num_experts": 8,
    "recipe": {
        "load_balancing_loss_weight": 0.01, "router_z_loss_weight": 0.001,
    },
}


@functools.cache
def toy_weights():
    """The toy's weights, made ONCE a module: the initialisation reads
    neither remat nor the compute dtype."""
    model = Olmoe(OlmoeConfig.tiny())
    # (jitted: an eager init runs the whole model op by op)
    params = jax.jit(lambda key: model.init_params(key, seq_len=64))(
        jax.random.PRNGKey(7)
    )
    # weights at 0.02 leave every router near uniform: scale them up
    # so that routing is decided and the experts' outputs matter
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 if "scale" in str(path[-1]) else 6.0),
        params,
    )


def toy(dtype=jnp.float32, **kw):
    model = Olmoe(OlmoeConfig.tiny(dtype=dtype, **kw))
    # (buffers of its own: a step donates its state)
    params = jax.tree.map(jnp.copy, toy_weights())
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 65), 0, 256)
    return model, params, {"x": tokens[:, :-1], "y": tokens[:, 1:]}


def toy_step():
    """The toy's jitted train step and its arguments."""
    model, params, batch = toy(remat=True)
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    step = make_train_step(make_olmoe_loss(model, num_chunks=4), optimizer)
    return model, step, TrainState.create(params, optimizer), batch


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


# -- the family against the reference -------------------------------------------


def test_float32_loss_and_logits_equal_the_reference():
    model, params, batch = toy()
    loss, aux = jax.jit(make_olmoe_loss(model, num_chunks=4))(
        params, batch
    )
    want = reference.loss(params, batch["x"], batch["y"], CFG)
    assert abs(float(loss) - want) < 1e-5
    logits = jax.jit(lambda p, x: model.apply({"params": p}, x))(
        params, batch["x"]
    )
    ref_logits, _ = reference.forward(params, batch["x"], CFG)
    np.testing.assert_allclose(
        logits, jnp.stack(ref_logits), rtol=0, atol=1e-4
    )
    assert set(aux) == {
        "moe.lb_loss", "moe.z_loss", "moe.load_max_over_mean"
    }


def test_float32_gradients_equal_the_reference_leaf_by_leaf():
    """Router, q/k norms, each expert matrix, embedding and head: every
    leaf of ``jax.grad`` of the training loss, to 1e-4 of the leaf's
    largest entry."""
    model, params, batch = toy(remat=True)
    loss_fn = make_olmoe_loss(model, num_chunks=4)
    got = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(params)
    want = jax.jit(jax.grad(
        lambda p: reference.loss_of(p, batch["x"], batch["y"], CFG)
    ))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want) == 2 * 12 + 3
    for (path, g), w in zip(flat_got, flat_want):
        assert np.abs(np.asarray(w)).max() > 0, path
        assert relative(g, w) < 1e-4, jax.tree_util.keystr(path)


def test_bfloat16_loss_is_within_bf16_rounding_of_the_reference():
    """bf16 compute (8 bits of mantissa, float32 accumulation, norms,
    router and loss) on bf16-rounded weights against the float32
    reference on the SAME rounded weights.  A bf16 rounding is 2**-9
    relative; over this toy's 128 tokens of a 256-word vocabulary the
    mean loss has been seen to differ by 1-3e-3.  1e-2 is three times
    that and far under what a wrong term costs (leaving out the 0.01 x
    load-balancing loss alone moves the loss by 2e-2)."""
    model, params, batch = toy(dtype=jnp.bfloat16)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    loss, _ = jax.jit(make_olmoe_loss(model, num_chunks=4))(params, batch)
    want = reference.loss(params, batch["x"], batch["y"], CFG)
    assert abs(float(loss) - want) < 1e-2
    assert 0.01 * 2.0 > 1e-2  # the lb term at its floor, E * k / E


def test_router_losses_as_hf_computes_them():
    """``load_balancing_loss_func`` of HF ``modeling_olmoe`` on the
    layers' gate logits concatenated, worked in numpy: one-hot of the
    top-k, mean over rows -> ``[k, E]``; mean probability -> ``[E]``;
    ``E * sum(tokens_per_expert * router_prob)``."""
    rng = np.random.default_rng(0)
    layers, t, e, k = 3, 40, 8, 2
    logits = rng.normal(size=(layers, t, e)).astype(np.float32)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    flat = probs.reshape(layers * t, e)
    top = np.argsort(-flat, axis=-1)[:, :k]
    one_hot = np.eye(e)[top]                       # [rows, k, E]
    hf = e * np.sum(one_hot.mean(0) * flat.mean(0)[None, :])
    counts = np.stack([
        np.bincount(np.argsort(-p, axis=-1)[:, :k].ravel(), minlength=e)
        for p in probs
    ]).astype(np.float32)
    lse = np.log(np.exp(logits).sum(-1))
    stats = {
        "counts": jnp.asarray(counts),
        "prob_sum": jnp.asarray(probs.sum(1)),
        "z_loss": jnp.asarray((lse ** 2).mean(1)),
    }
    lb, z, load = router_losses(stats, k)
    assert abs(float(lb) - hf) < 1e-5
    assert abs(float(z) - float((lse ** 2).mean(1).sum())) < 1e-4
    assert float(load) == pytest.approx(
        (counts.max(1) / (t * k / e)).max()
    )


# -- the dropless layer -----------------------------------------------------------


def layer_operands(t=96, d=16, e=64, m=8, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (
        jnp.abs(jax.random.normal(keys[0], (t, d))) + 0.1,
        jax.random.normal(keys[1], (d, e)) * 0.3,
        jax.random.normal(keys[2], (e, d, m)) * 0.3,
        jax.random.normal(keys[3], (e, d, m)) * 0.3,
        jax.random.normal(keys[4], (e, m, d)) * 0.3,
    )


def every_expert_on_every_row(tokens, router, w_gate, w_up, w_down, k):
    probs = jax.nn.softmax(tokens @ router, axis=-1)
    gate, ids = jax.lax.top_k(probs, k)
    out = 0.0
    for j in range(router.shape[1]):
        y = (nn.silu(tokens @ w_gate[j]) * (tokens @ w_up[j])) @ w_down[j]
        out = out + y * jnp.sum(gate * (ids == j), axis=-1)[:, None]
    return out


def test_one_expert_takes_every_token_and_twenty_take_none():
    """No capacity: expert 0 is every token's first choice and gets all
    96 rows, experts 44..63 get none (zero-row groups), nothing is
    dropped, nothing is NaN, and the empty experts' gradients are 0."""
    tokens, router, w_gate, w_up, w_down = layer_operands()
    # (the tokens are positive: a constant column orders the logits)
    router = router.at[:, 0].set(5.0).at[:, 44:].set(-5.0)
    k = 8
    out, stats = dropless_moe(
        tokens, router, w_gate, w_up, w_down, k, jnp.float32
    )
    counts = np.asarray(stats["counts"])
    assert counts[0] == 96 and not counts[44:].any()
    assert counts.sum() == 96 * k  # every assignment has its row
    want = every_expert_on_every_row(
        tokens, router, w_gate, w_up, w_down, k
    )
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)

    def total(*weights):
        return jnp.sum(dropless_moe(
            tokens, router, *weights, k, jnp.float32
        )[0] ** 2)

    grads = jax.grad(total, argnums=(0, 1, 2))(w_gate, w_up, w_down)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
        assert not np.asarray(g[44:]).any()
        assert np.asarray(g[0]).any()


def test_top_k_weights_are_not_renormalised():
    """``norm_topk_prob: false``: with one choice a token the output is
    the expert's output times the router's probability (under 1), not
    times 1."""
    tokens, router, w_gate, w_up, w_down = layer_operands(e=4)
    out, _ = dropless_moe(
        tokens, router, w_gate, w_up, w_down, 1, jnp.float32
    )
    probs = jax.nn.softmax(tokens @ router, axis=-1)
    best = jnp.argmax(probs, axis=-1)
    expert_out = jnp.stack([
        (nn.silu(x @ w_gate[j]) * (x @ w_up[j])) @ w_down[j]
        for x, j in zip(tokens, best)
    ])
    p = probs.max(axis=-1)
    assert float(p.max()) < 0.99
    np.testing.assert_allclose(
        out, expert_out * p[:, None], rtol=1e-5, atol=1e-6
    )


def test_equals_the_gshard_layer_where_no_token_is_dropped():
    """Against ``MoEMLP`` (gated, top-2) at a capacity no expert
    exceeds: the same experts on the same rows; GShard renormalises
    its two weights, so its output times their sum is this layer's."""
    t, d, e, m, k = 64, 16, 4, 8, 2
    x = jax.random.normal(jax.random.PRNGKey(3), (1, t, d))
    gshard = MoEMLP(
        num_experts=e, hidden_dim=d, mlp_dim=m, top_k=k, gated=True,
        capacity_factor=float(e), dtype=jnp.float32,
    )
    params = gshard.init(jax.random.PRNGKey(4), x)["params"]
    want = gshard.apply({"params": params}, x)
    mine = DroplessMoE(
        num_experts=e, mlp_dim=m, top_k=k, dtype=jnp.float32
    )
    got, _ = mine.apply({"params": {
        "router": params["router"]["kernel"],
        "experts_w_gate": params["experts_w_gate"],
        "experts_w_in": params["experts_w_in"],
        "experts_w_out": params["experts_w_out"],
    }}, x)
    probs = jax.nn.softmax(x[0] @ params["router"]["kernel"], axis=-1)
    top_sum = jax.lax.top_k(probs, k)[0].sum(axis=-1)
    np.testing.assert_allclose(
        got[0], want[0] * top_sum[:, None], rtol=1e-4, atol=1e-5
    )


def shapes_in(text):
    import re

    return {
        tuple(int(n) for n in found.split("x") if n)
        for found in re.findall(r"tensor<((?:\d+x)+)[a-z]", text)
    }


def test_no_tokens_by_experts_by_capacity_tensor_in_the_step():
    """The lowered toy step holds no operand whose trailing dims are
    ``experts x capacity`` (GShard's dispatch and combine tensors at
    the factor 1.25 this repo's MoE presets use); the GShard layer's
    own lowering does, so the search would find one."""
    model, step, state, batch = toy_step()
    text = step.lower(state, batch).as_text()
    cfg = model.config
    t = batch["x"].size
    capacity = int(cfg.top_k * t * 1.25 / cfg.num_experts)
    assert not [
        s for s in shapes_in(text)
        if s[-2:] == (cfg.num_experts, capacity)
    ]
    assert not [s for s in shapes_in(text) if len(s) == 3 and s[:2] == (
        t, cfg.num_experts
    )]
    gshard = MoEMLP(
        num_experts=cfg.num_experts, hidden_dim=cfg.hidden_dim,
        mlp_dim=cfg.expert_dim, top_k=cfg.top_k, gated=True,
    )
    x = jnp.zeros((2, 64, cfg.hidden_dim))
    variables = gshard.init(jax.random.PRNGKey(0), x)
    text = jax.jit(gshard.apply).lower(variables, x).as_text()
    assert (t, cfg.num_experts, capacity) in shapes_in(text)


def test_a_gated_expert_is_one_call_of_the_kernels_and_no_pass_beside():
    """The forward of the training loss under ``moe_experts``, a
    layer: ``grouped_expert`` (ONE ``custom_vjp_call``) with the
    three weights' casts, and NOTHING else: the ``silu`` and the
    product are inside the up projections' kernel since PR 52 (before
    it: three grouped matmuls, a ``jit`` and a ``mul`` over the
    padded rows).  Five cells run this path: a change that moves the
    count has to be measured in them."""
    model = Olmoe(OlmoeConfig.tiny())
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), seq_len=64)
    )
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    jaxpr = jax.make_jaxpr(make_olmoe_loss(model, num_chunks=4))(
        params, {"x": tokens, "y": tokens}
    ).jaxpr
    layers = model.config.num_layers
    assert primitives_under(jaxpr, "moe_experts") == {
        "custom_vjp_call": layers, "convert_element_type": 3 * layers,
    }
    assert "experts_w_gate" in params["block_0"]["moe"]


def test_the_layers_scopes_are_in_the_compiled_step():
    """What the benchmark's readers join on: each of the layer's four
    device scopes names operations of the compiled step, forward
    (``jvp(..)``) and backward (``transpose(jvp(..))``); the loss
    head's are all forward, its gradients are formed there."""
    from dlrover_tpu.common.aot_cache import op_names

    _, step, state, batch = toy_step()
    compiled = step.lower(state, batch).compile()
    stacks = list(op_names(compiled.as_text())["op_names"].values())
    for scope in (
        "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
    ):
        named = [s for s in stacks if scope in s]
        assert named, scope
        assert any("transpose(" in s for s in named), scope
    head = [s for s in stacks if "loss_head" in s]
    assert sum(s.endswith("/dot_general") for s in set(head)) == 3
    assert all("jvp(loss_head)" in s for s in head)
    assert not any("rematted_computation" in s for s in head)


# -- the step that carries the counters ----------------------------------------


def gpt_step_text(loss_fn, **kw):
    model = GPT(GPTConfig.tiny())
    params = model.init_params(jax.random.PRNGKey(0))
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    state = TrainState.create(params, optimizer)
    tokens = jnp.zeros((2, 33), jnp.int32)
    batch = {"x": tokens[:, :-1], "y": tokens[:, 1:]}
    return make_train_step(loss_fn(model), optimizer, **kw).lower(
        state, batch
    ).as_text()


def test_has_aux_with_an_empty_aux_lowers_to_the_same_step():
    """``has_aux`` off is the parent's path; on, over a loss that
    returns an empty ``aux``, the toy GPT-2 step lowers to the very
    same text."""
    def scalar(model):
        return lambda p, b: cross_entropy_loss(
            model.apply({"params": p}, b["x"]), b["y"]
        )

    def with_aux(model):
        return lambda p, b: (scalar(model)(p, b), {})

    off = gpt_step_text(scalar)
    assert off == gpt_step_text(scalar, has_aux=False)
    assert off == gpt_step_text(with_aux, has_aux=True)


@pytest.mark.parametrize("grad_accum", [1, 2])
def test_has_aux_puts_the_counters_into_the_metrics(grad_accum):
    model, params, batch = toy()
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    loss_fn = make_olmoe_loss(model, num_chunks=4)
    assert loss_fn.has_aux  # read by make_train_step: no argument
    step = make_train_step(loss_fn, optimizer, grad_accum=grad_accum)
    # (the step donates its state)
    loss, aux = jax.jit(loss_fn)(params, batch)
    _, metrics = step(TrainState.create(params, optimizer), batch)
    assert set(metrics) == {
        "loss", "grad_norm", "moe.lb_loss", "moe.z_loss",
        "moe.load_max_over_mean",
    }
    if grad_accum == 1:
        assert float(metrics["loss"]) == pytest.approx(float(loss))
        for name, value in aux.items():
            assert float(metrics[name]) == pytest.approx(float(value))
    assert float(metrics["moe.load_max_over_mean"]) >= 1.0


def test_the_counters_ride_on_the_train_step_event(
    tmp_path, monkeypatch
):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("DLROVER_EVENT_LOG", path)
    monkeypatch.setenv(
        "DLROVER_METRICS_FILE", str(tmp_path / "metrics.json")
    )
    trainer = ElasticTrainer(4, 4, dp_size=1)
    trainer.report_step({
        "loss": jnp.float32(1.5), "grad_norm": jnp.float32(0.1),
        "moe.load_max_over_mean": jnp.float32(1.25),
        "moe.lb_loss": jnp.float32(8.0), "moe.z_loss": jnp.float32(60.0),
    })
    trainer.report_step({"loss": 1.0})
    first, second = [
        e for e in read_events(path) if e["type"] == "train_step"
    ]
    assert first["moe.load_max_over_mean"] == 1.25
    assert first["moe.lb_loss"] == 8.0 and first["moe.z_loss"] == 60.0
    assert "grad_norm" not in first
    assert not [k for k in second if k.startswith("moe.")]
    assert not validate_event(first) and not validate_event(second)
