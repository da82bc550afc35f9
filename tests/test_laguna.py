"""The window-and-full-attention mixture-of-experts family through the
repo's blocks against the plain float32 reference
(``benchmarks/models/laguna_reference.py``): loss, logits and every
leaf's gradient; the two kinds of layer (head counts, window, rope
rule) and the per-head gate against their own claims; the held-experts
layer with softmax scores (the shares of all chips add up to the whole
layer).  What the benchmark has of the family is
``test_laguna_bench.py``, the cell's offline compile
``test_laguna_tpu.py``."""

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

from dlrover_tpu.models import laguna  # noqa: E402
from dlrover_tpu.models.laguna import (  # noqa: E402
    FULL_ROPE,
    SLIDING_ROPE,
    LagunaConfig,
)
from dlrover_tpu.models.layers import yarn_correction_range  # noqa: E402
from dlrover_tpu.ops.attention import xla_window_attention  # noqa: E402
from dlrover_tpu.parallel.moe import DroplessMoE, dropless_moe  # noqa: E402

reference = loader.load_module("models", "laguna_reference")
CONFIGS = os.path.join(REPO, "benchmarks", "configs")


def toy_cfg(**recipe):
    """The toy configuration's file, in float32 unless told."""
    cfg = loader.load_json(os.path.join(CONFIGS, "toy_laguna.json"))
    cfg["recipe"] = {**cfg["recipe"], **dict(
        param_dtype="float32", compute_dtype="float32",
    ), **recipe}
    return cfg


@functools.cache
def toy_weights(seq, param_dtype):
    """The toy's weights, made ONCE a module: the initialisation reads
    neither the attention, nor remat, nor the compute dtype."""
    family = loader.load_module("models", "laguna")
    model, _, _ = family.build(toy_cfg(param_dtype=param_dtype))
    # (jitted: an eager init runs the whole model op by op)
    params = jax.jit(lambda key: model.init_params(key, seq_len=seq))(
        jax.random.PRNGKey(7)
    )
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 if "scale" in str(path[-1]) else 6.0),
        params,
    )


def toy(seq=128, **recipe):
    """``(family, cfg, model, loss_fn, params, batch)``: weights scaled
    up so that routing is decided, the gates leave 0.5 and the experts'
    outputs matter."""
    family = loader.load_module("models", "laguna")
    cfg = toy_cfg(**recipe)
    model, _, loss_fn = family.build(cfg)
    # (buffers of its own: a step donates its state)
    params = jax.tree.map(
        jnp.copy, toy_weights(seq, cfg["recipe"]["param_dtype"])
    )
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, seq + 1), 0, 512)
    return family, cfg, model, loss_fn, params, {
        "x": tokens[:, :-1], "y": tokens[:, 1:],
    }


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


# -- the family against the reference -----------------------------------------


@pytest.mark.parametrize("attention", ["xla", "flash"])
def test_float32_loss_and_logits_equal_the_reference(attention):
    _, cfg, model, loss_fn, params, batch = toy(attention=attention)
    loss, aux = jax.jit(loss_fn)(params, batch)
    want = reference.loss(params, batch["x"], batch["y"], cfg)
    assert abs(float(loss) - want) < 1e-5
    logits = jax.jit(lambda p, x: model.apply({"params": p}, x))(
        params, batch["x"]
    )
    ref_logits, counts = reference.forward(params, batch["x"], cfg)
    np.testing.assert_allclose(
        logits, jnp.stack(ref_logits), rtol=0, atol=1e-4
    )
    # the counter is the reference's count of what reached experts
    # 4..7 of 16, over both layers' 2 x 128 x 4 assignments each
    held = sum(float(n[4:8].sum()) for n in counts)
    assert float(aux["moe.held_rows_share"]) == pytest.approx(
        held / (2 * 2 * 128 * 4)
    )
    assert ("attn.window_tiles_share" in aux) == (attention == "flash")
    assert set(aux) - {"attn.window_tiles_share"} == {
        "moe.held_rows_share", "moe.held_tiles_share",
    }


def test_float32_gradients_equal_the_reference_leaf_by_leaf():
    """Through the flash kernels (a window of 48 over tiles of 128,
    groups of 2 and 3) and the block's remat: every leaf of
    ``jax.grad`` of the training loss, to 1e-4 of the leaf's largest
    entry."""
    _, cfg, _, loss_fn, params, batch = toy(attention="flash", remat=True)
    got = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(params)
    want = jax.jit(jax.grad(lambda p: reference.loss_and_counts(
        p, batch["x"], batch["y"], cfg
    )[0]))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    # 7 leaves of attention and norms a block, 3 dense, 7 of a sparse
    # layer, embedding, final norm, head
    assert len(flat_got) == len(flat_want) == 3 * 7 + 3 + 2 * 7 + 3
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        assert np.abs(np.asarray(w)).max() > 0, name
        assert relative(g, w) < 1e-4, name


def test_bfloat16_loss_is_within_bf16_rounding_of_the_reference():
    _, cfg, _, loss_fn, params, batch = toy(
        param_dtype="bfloat16", compute_dtype="bfloat16",
    )
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    loss, _ = jax.jit(loss_fn)(params, batch)
    want = reference.loss(params, batch["x"], batch["y"], cfg)
    assert abs(float(loss) - want) < 1e-2


# -- the two kinds of layer ---------------------------------------------------


def test_each_layer_takes_heads_window_and_rope_from_its_kind():
    """A full layer: 4 query heads, no window, yarn on the first half
    of each head.  A sliding layer: 6 heads, the window, the default
    rule on the whole head.  Told apart by the parameter shapes and by
    what each layer's output at a position depends on."""
    _, cfg, model, _, params, batch = toy()
    shapes = jax.tree.map(lambda x: x.shape, params)
    assert shapes["block_0"]["attn"] == {
        "q_proj": {"kernel": (128, 4 * 32)},
        "k_proj": {"kernel": (128, 2 * 32)},
        "v_proj": {"kernel": (128, 2 * 32)},
        "g_proj": {"kernel": (128, 4)},
        "o_proj": {"kernel": (4 * 32, 128)},
    }
    assert shapes["block_1"]["attn"]["q_proj"]["kernel"] == (128, 6 * 32)
    assert shapes["block_1"]["attn"]["g_proj"]["kernel"] == (128, 6)
    assert "mlp" in shapes["block_0"] and "moe" in shapes["block_1"]

    def attention_of(block, x):
        kind = cfg["layer_types"][block]
        sliding = kind == laguna.SLIDING
        module = laguna.LagunaAttention(
            model.config, cfg["num_attention_heads_per_layer"][block],
            cfg["sliding_window"] if sliding else None,
            model.config.sliding_rope if sliding else model.config.full_rope,
        )
        return module.apply(
            {"params": params[f"block_{block}"]["attn"]}, x
        )

    x = jax.random.normal(jax.random.PRNGKey(3), (1, 128, 128))
    moved = x.at[0, 10].add(1.0)
    for block, reach in ((0, 128), (1, 10 + 48)):
        change = np.abs(np.asarray(
            attention_of(block, moved) - attention_of(block, x)
        )).max(axis=-1)[0]
        assert not change[:10].any()
        assert change[10:reach].all()
        # a sliding layer's rows from 10 + window on never see row 10
        assert not change[reach:].any()


def test_rope_rules_against_hand_worked_numbers():
    """Full layers: yarn on 64 of 128 lanes, theta 500000, factor 128,
    original 8192: 64 ln(8192 / (32 x 2 pi)) / (2 ln 500000) = 9.04 ->
    low 9, and for beta 1 17.49 -> high 18; pair 9 keeps its frequency,
    pair 18 takes it over 128, pair 12 blends at 3 / 9.  Sliding
    layers: 64 pairs of the default rule at theta 10000.  The
    reference's own arithmetic agrees."""
    assert yarn_correction_range(64, 500000.0, 8192, 32.0, 1.0) == (9, 18)
    got = FULL_ROPE.inv_freq(128)
    f = 500000.0 ** (-np.arange(32) / 32.0)
    assert got.shape == (32,)
    assert got[:10] == pytest.approx(f[:10], rel=1e-12)
    assert got[18:] == pytest.approx(f[18:] / 128.0, rel=1e-12)
    ramp = 3.0 / 9.0
    assert got[12] == pytest.approx(
        f[12] / 128.0 * ramp + f[12] * (1 - ramp), rel=1e-12
    )
    assert FULL_ROPE.attention_factor == 1.4852030263919618
    sliding = SLIDING_ROPE.inv_freq(128)
    assert sliding == pytest.approx(
        10000.0 ** (-np.arange(64) / 64.0), rel=1e-12
    )
    cut = loader.load_json(os.path.join(CONFIGS, "laguna_s_2_1_cut.json"))
    rules = cut["rope_parameters"]
    np.testing.assert_allclose(
        reference.inv_freq(128, rules["full_attention"]), got, rtol=1e-12
    )
    np.testing.assert_allclose(
        reference.inv_freq(128, rules["sliding_attention"]), sliding,
        rtol=1e-12,
    )
    family = loader.load_module("models", "laguna")
    assert family.rope_rule(rules["full_attention"]) == FULL_ROPE
    assert family.rope_rule(rules["sliding_attention"]) == SLIDING_ROPE


def test_each_head_is_scaled_by_its_own_gate():
    """``g_proj`` zero: every gate is sigmoid(0) = 0.5, the layer's
    output half of the ungated attention's; one head's column large
    and negative: that head alone leaves the output."""
    cfg = LagunaConfig.tiny(dtype=jnp.float32)
    module = laguna.LagunaAttention(cfg, 6, 24, cfg.sliding_rope)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 64))
    params = module.init(jax.random.PRNGKey(1), x)["params"]

    def with_gate(columns):
        kernel = jnp.zeros((64, 6)) + jnp.asarray(columns)[None, :]
        # x has no constant lane: put the bias through a row of ones
        ones = jnp.concatenate([x[..., :63], jnp.ones((1, 64, 1))], -1)
        kernel = kernel * (jnp.arange(64) == 63)[:, None]
        return module.apply(
            {"params": dict(params, g_proj={"kernel": kernel})}, ones
        )

    half = with_gate([0.0] * 6)
    open_ = with_gate([40.0] * 6)
    np.testing.assert_allclose(half, 0.5 * open_, atol=1e-5)
    without_2 = with_gate([40.0, 40.0, -40.0, 40.0, 40.0, 40.0])
    o_proj = params["o_proj"]["kernel"]
    only_2 = with_gate([-40.0, -40.0, 40.0, -40.0, -40.0, -40.0])
    np.testing.assert_allclose(without_2 + only_2, open_, atol=1e-5)
    assert np.abs(np.asarray(only_2)).max() > 1e-3 and o_proj.shape == (96, 64)


@pytest.mark.parametrize("window", [None, 1, 7, 64])
def test_xla_window_attention_is_the_kernels_mask(window):
    from dlrover_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 64, 6, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    np.testing.assert_allclose(
        xla_window_attention(q, k, v, window, jnp.float32),
        flash_attention(q, k, v, window=window, block_q=32, block_k=32),
        atol=2e-5,
    )


# -- the held layer with softmax scores ---------------------------------------


def layer_operands(t, d, m, e, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (
        jax.random.normal(ks[0], (t, d)),
        jax.random.normal(ks[1], (d, e)),
        jax.random.normal(ks[2], (e, d, m)) * 0.2,
        jax.random.normal(ks[3], (e, d, m)) * 0.2,
        jax.random.normal(ks[4], (e, m, d)) * 0.2,
    )


def whole_layer(operands, top_k, scale=2.5):
    """Every expert on every row, softmax scores over all of them."""
    x, router, w_gate, w_up, w_down = operands
    probs = jax.nn.softmax(x @ router, axis=-1)
    chosen, ids = jax.lax.top_k(probs, top_k)
    weights = scale * chosen / chosen.sum(axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(router.shape[1]):
        w = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        y = (nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
        out = out + y * w[:, None]
    return out


@pytest.mark.parametrize("shares, held, top_k", [(4, 4, 4), (16, 16, 10)])
def test_the_shares_add_up_to_the_whole_layer(shares, held, top_k):
    """Every chip of the group routes over all ``shares x held``
    experts by softmax and computes its own: the routed parts summed,
    and the shared expert (which every chip computes alike) counted
    once, equal the uncut layer, every expert on every row.  4 shares
    of 4, and 16 of 16 as the cut configuration's group (256 outputs,
    top-10)."""
    d, m = 32, 16
    e = shares * held
    operands = layer_operands(t=64, d=d, m=m, e=e, seed=2)
    x, router, w_gate, w_up, w_down = operands
    # one share's function, compiled (a range's start is static) and
    # called for each share
    one_share = jax.jit(
        lambda x, router, w_gate, w_up, w_down, lo: dropless_moe(
            x, router, w_gate[lo:lo + held], w_up[lo:lo + held],
            w_down[lo:lo + held], top_k, jnp.float32, held=(lo, held),
            score="softmax", renormalise=True, scale=2.5,
        ),
        static_argnums=5,
    )
    parts = [one_share(*operands, lo) for lo in range(0, e, held)]
    np.testing.assert_allclose(
        sum(out for out, _ in parts),
        jax.jit(whole_layer, static_argnums=1)(operands, top_k),
        atol=2e-5,
    )
    for _, stats in parts:
        assert np.array_equal(stats["counts"], parts[0][1]["counts"])
    assert sum(float(s["held_rows"]) for _, s in parts) == 64 * top_k
    # the layer module as the block builds it: the shared expert rides
    # on every share, so the shares' sum counts it ``shares`` times
    layer = DroplessMoE(
        num_experts=e, mlp_dim=m, top_k=top_k, dtype=jnp.float32,
        held=(held, held), score="softmax", renormalise=True, scale=2.5,
        shared_dim=m,
    )
    variables = jax.jit(layer.init)(jax.random.PRNGKey(0), x[None])
    p = variables["params"]
    assert "select_bias" not in p and p["router"].shape == (d, e)
    out, _ = jax.jit(layer.apply)(variables, x[None])
    routed, _ = jax.jit(lambda x, p: dropless_moe(
        x, p["router"], p["experts_w_gate"], p["experts_w_in"],
        p["experts_w_out"], top_k, jnp.float32, held=(held, held),
        score="softmax", renormalise=True, scale=2.5,
    ))(x, p)
    shared = (
        nn.silu(x @ p["shared_gate"]["kernel"])
        * (x @ p["shared_up"]["kernel"])
    ) @ p["shared_down"]["kernel"]
    np.testing.assert_allclose(out[0], routed + shared, atol=1e-5)
