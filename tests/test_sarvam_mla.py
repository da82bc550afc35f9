"""The latent-attention mixture-of-experts family through the repo's
blocks against the plain float32 reference
(``benchmarks/models/sarvam_mla_reference.py``); the family's router
(chosen by score plus bias, weighted by score alone; the shares of all
chips add up to the whole layer); the router's bias, which no gradient
reaches and the train step moves by the loss's ``state_updates``; a
flash save of the state with its bias; the yarn frequencies against
hand-worked numbers.  The held layer's own claims are
``test_moe_held.py``, what the benchmark has of the family
``test_sarvam_mla_bench.py``, the cell's offline compile
``test_sarvam_mla_tpu.py``."""

import functools
import math
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
from test_moe_held import layer_operands, share  # noqa: E402

from dlrover_tpu.checkpoint.checkpointer import (  # noqa: E402
    Checkpointer,
    StorageType,
    restore_to_template,
)
from dlrover_tpu.checkpoint.saver import (  # noqa: E402
    AsyncCheckpointSaver,
    SaverConfig,
)
from dlrover_tpu.models.layers import (  # noqa: E402
    yarn_correction_range,
    yarn_inv_freq,
)
from dlrover_tpu.models.sarvam_mla import (  # noqa: E402
    SarvamMla,
    SarvamMlaConfig,
    bias_deltas,
    make_sarvam_mla_loss,
    softmax_scale,
)
from dlrover_tpu.ops import grouped_matmul as gmm  # noqa: E402
from dlrover_tpu.optim import adamw_bf16  # noqa: E402
from dlrover_tpu.parallel.moe import DroplessMoE, dropless_moe  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import (  # noqa: E402
    STATE_UPDATES,
    TrainState,
    make_train_step,
)

reference = loader.load_module("models", "sarvam_mla_reference")

SCALING = {
    "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
    "mscale_all_dim": 1, "original_max_position_embeddings": 64,
    "type": "deepseek_yarn",
}
# the HF keys of the tiny configuration, as the reference reads them
CFG = {
    "num_attention_heads": 2, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": SCALING,
    "num_experts_per_tok": 4, "num_hidden_layers": 3,
    "first_expert_held": 4, "routed_scaling_factor": 2.5,
}
EXPERT_LAYERS = (1, 2)


@functools.cache
def toy_weights(seq, param_dtype):
    """The toy's weights, made ONCE a module: the initialisation reads
    neither the attention, nor remat, nor the compute dtype."""
    model = SarvamMla(SarvamMlaConfig.tiny(param_dtype=param_dtype))
    # (jitted: an eager init runs the whole model op by op)
    params = jax.jit(lambda key: model.init_params(key, seq_len=seq))(
        jax.random.PRNGKey(7)
    )
    # weights at 0.02 leave every router near 0.5: scale them up so
    # that routing is decided and the experts' outputs matter; the
    # biases apart, so that score + bias picks other experts than the
    # score alone
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 if "scale" in str(path[-1]) else 6.0),
        params,
    )
    for n, i in enumerate(EXPERT_LAYERS):
        params[f"block_{i}"]["moe"]["select_bias"] = 0.2 * jax.random.normal(
            jax.random.PRNGKey(20 + n), (16,)
        )
    return params


def toy(dtype=jnp.float32, seq=64, **kw):
    model = SarvamMla(SarvamMlaConfig.tiny(dtype=dtype, **kw))
    # (buffers of its own: a step donates its state)
    params = jax.tree.map(
        jnp.copy, toy_weights(seq, model.config.param_dtype)
    )
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, seq + 1), 0, 256)
    return model, params, {"x": tokens[:, :-1], "y": tokens[:, 1:]}


def toy_step(**kw):
    """The toy's jitted train step and its arguments."""
    model, params, batch = toy(remat=True, **kw)
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    step = make_train_step(
        make_sarvam_mla_loss(model, num_chunks=4), optimizer
    )
    return model, step, TrainState.create(params, optimizer), batch


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


# -- the family against the reference -----------------------------------------


@pytest.mark.parametrize("attention", ["xla", "flash"])
def test_float32_loss_and_logits_equal_the_reference(attention):
    model, params, batch = toy(attention_impl=attention)
    loss, aux = jax.jit(make_sarvam_mla_loss(model, num_chunks=4))(
        params, batch
    )
    want = reference.loss(params, batch["x"], batch["y"], CFG)
    assert abs(float(loss) - want) < 1e-5
    logits = jax.jit(lambda p, x: model.apply({"params": p}, x))(
        params, batch["x"]
    )
    ref_logits, counts = reference.forward(params, batch["x"], CFG)
    np.testing.assert_allclose(
        logits, jnp.stack(ref_logits), rtol=0, atol=1e-4
    )
    assert set(aux) == {
        "moe.held_rows_share", "moe.held_tiles_share",
        "moe.bias_abs_max", STATE_UPDATES,
    }
    # the counter is the reference's count of what reached experts
    # 4..7 of 16, over both layers' 2 x 64 x 4 assignments each
    held = sum(float(n[4:8].sum()) for n in counts)
    assert float(aux["moe.held_rows_share"]) == pytest.approx(
        held / (2 * 2 * 64 * 4)
    )
    # ... and each held expert fills ceil(n / tile) row tiles of the
    # layout's 2 x 64 x 4 / tile + 4, an expert without a token one
    tiles = [
        sum(max(1, math.ceil(float(c) / gmm.ROW_TILE)) for c in n[4:8])
        for n in counts
    ]
    assert float(aux["moe.held_tiles_share"]) == pytest.approx(
        np.mean(tiles) / (2 * 64 * 4 / gmm.ROW_TILE + 4)
    )
    assert float(aux["moe.bias_abs_max"]) == pytest.approx(max(
        float(jnp.abs(params[f"block_{i}"]["moe"]["select_bias"]).max())
        for i in EXPERT_LAYERS
    ))


def test_float32_gradients_equal_the_reference_leaf_by_leaf():
    """Both latent projections and the latent's norm, router, shared
    and held experts, the dense block, embedding and head: every leaf
    of ``jax.grad`` of the training loss, to 1e-4 of the leaf's largest
    entry.  The bias takes no gradient on either side."""
    model, params, batch = toy(remat=True, attention_impl="flash")
    loss_fn = make_sarvam_mla_loss(model, num_chunks=4)
    got = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(params)
    want = jax.jit(jax.grad(
        lambda p: reference.loss_of(p, batch["x"], batch["y"], CFG)
    ))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    # 7 leaves of attention and norms a block, 3 dense, 8 of an
    # expert layer, embedding, final norm, head
    assert len(flat_got) == len(flat_want) == 3 * 7 + 3 + 2 * 8 + 3
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        if "select_bias" in name:
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        assert np.abs(np.asarray(w)).max() > 0, name
        assert relative(g, w) < 1e-4, name


def test_bfloat16_loss_is_within_bf16_rounding_of_the_reference():
    """bf16 compute (float32 accumulation, norms, router and loss) on
    bf16-rounded weights against the float32 reference on the SAME
    rounded weights: as the other families' toys, a few 1e-3 at 128
    tokens of a 256-word vocabulary; 1e-2 is far under what a wrong
    term costs (leaving the shared expert out moves this loss by
    2e-2)."""
    model, params, batch = toy(dtype=jnp.bfloat16)
    params = jax.tree.map(
        lambda x: x if x.shape == (16,) else x.astype(jnp.bfloat16),
        params,
    )
    loss, _ = jax.jit(make_sarvam_mla_loss(model, num_chunks=4))(
        params, batch
    )
    want = reference.loss(params, batch["x"], batch["y"], CFG)
    assert abs(float(loss) - want) < 1e-2
    no_shared = jax.tree.map(lambda x: x, params)
    for i in EXPERT_LAYERS:
        no_shared[f"block_{i}"]["moe"]["shared_down"] = jax.tree.map(
            jnp.zeros_like, params[f"block_{i}"]["moe"]["shared_down"]
        )
    assert abs(
        reference.loss(no_shared, batch["x"], batch["y"], CFG) - want
    ) > 1.5e-2


def test_the_whole_model_is_causal():
    model, params, batch = toy(attention_impl="flash")
    tokens = batch["x"]
    changed = tokens.at[:, 40:].set((tokens[:, 40:] + 1) % 256)
    apply = jax.jit(lambda p, x: model.apply({"params": p}, x))
    a = apply(params, tokens)
    b = apply(params, changed)
    assert np.array_equal(np.asarray(a[:, :40]), np.asarray(b[:, :40]))
    assert not np.array_equal(np.asarray(a[:, 40:]), np.asarray(b[:, 40:]))


# -- yarn ---------------------------------------------------------------------


def test_yarn_frequencies_against_hand_worked_numbers():
    """Dim 64, theta 10000, factor 40, original 4096, beta 32 and 1:
    64 ln(4096 / (32 x 2 pi)) / (2 ln 10000) = 10.47 -> low 10, and for
    beta 1 22.51 -> high 23; pair 9 keeps its frequency, pair 23 takes
    it over 40, pair 16 blends at (16 - 10) / 13; the softmax scale is
    192^-1/2 x (0.1 ln 40 + 1)^2."""
    cfg = SarvamMlaConfig()
    assert yarn_correction_range(64, 10000.0, 4096, 32.0, 1.0) == (10, 23)
    got = yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    f = 10000.0 ** (-np.arange(32) / 32.0)
    assert got[:11] == pytest.approx(f[:11], rel=1e-12)
    assert got[23:] == pytest.approx(f[23:] / 40.0, rel=1e-12)
    ramp = 6.0 / 13.0
    assert got[16] == pytest.approx(
        f[16] / 40.0 * ramp + f[16] * (1 - ramp), rel=1e-6
    )
    assert f[16] == pytest.approx(0.01) and got[16] == pytest.approx(
        0.0055, rel=1e-12
    )
    m = 0.1 * math.log(40.0) + 1.0
    assert m == pytest.approx(1.3689, abs=5e-5)
    assert softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    # the reference's own arithmetic agrees
    assert reference.yarn_range(64, 10000.0, dict(
        SCALING, original_max_position_embeddings=4096
    )) == (10, 23)
    np.testing.assert_allclose(
        reference.yarn_inv_freq(64, 10000.0, dict(
            SCALING, original_max_position_embeddings=4096
        )), got, rtol=1e-12,
    )


# -- the router ---------------------------------------------------------------


def whole_layer(operands, top_k, bias, scale=2.5):
    """Every expert on every row, in numpy's order of things."""
    x, router, w_gate, w_up, w_down = operands
    scores = jax.nn.sigmoid(x @ router)
    _, ids = jax.lax.top_k(scores + bias, top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = scale * chosen / chosen.sum(axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(router.shape[1]):
        w = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        y = (nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
        out = out + y * w[:, None]
    return out, scores, ids, weights


def test_chosen_by_score_plus_bias_weighted_by_score_alone():
    operands = layer_operands()
    x, router = operands[:2]
    bias = jnp.zeros((16,)).at[3].set(5.0).at[11].set(-5.0)
    out, stats = jax.jit(
        lambda ops, b: share(ops, (0, 16), bias=b)
    )(operands, bias)
    want, scores, ids, weights = jax.jit(whole_layer, static_argnums=1)(
        operands, 4, bias
    )
    np.testing.assert_allclose(out, want, atol=1e-5)
    # the bias decided: every token takes expert 3, none expert 11
    assert float(stats["counts"][3]) == 96 and stats["counts"][11] == 0
    # and the weights do not know it: 2.5 x s / sum of the chosen s
    assert np.asarray(weights.sum(axis=-1)) == pytest.approx(2.5)
    plain_ids = jax.lax.top_k(scores, 4)[1]
    assert not np.array_equal(np.sort(ids), np.sort(plain_ids))
    s3 = scores[:, 3]
    w3 = jnp.sum(jnp.where(ids == 3, weights, 0.0), axis=-1)
    others = jnp.sum(
        jnp.where(ids != 3, jnp.take_along_axis(scores, ids, -1), 0.0), -1
    )
    np.testing.assert_allclose(w3, 2.5 * s3 / (s3 + others), rtol=1e-5)
    # no gradient reaches the bias
    grad = jax.jit(jax.grad(
        lambda b: share(operands, (0, 16), bias=b)[0].sum()
    ))(bias)
    assert not np.asarray(grad).any()


def test_the_bias_rule_moves_each_expert_by_u_towards_the_mean():
    counts = jnp.array([[10.0, 0.0, 4.0, 2.0], [4.0, 4.0, 4.0, 4.0]])
    np.testing.assert_allclose(
        bias_deltas(counts, 0.001),
        [[-0.001, 0.001, 0.0, 0.001], [0.0, 0.0, 0.0, 0.0]],
    )


@pytest.mark.parametrize("shares, held, width", [(4, 4, (32, 16)),
                                                 (16, 8, (64, 32))])
def test_the_shares_add_up_to_the_whole_layer(shares, held, width):
    """Every chip of the group routes over all ``shares x held``
    experts and computes its own: the routed parts summed, and the
    shared expert (which every chip computes alike) counted once,
    equal the uncut layer, every expert on every row.  4 shares of 4
    experts, and 16 of 8 as the cut configuration's group (128
    outputs, top-8)."""
    d, m = width
    e = shares * held
    top_k = 8 if e == 128 else 4
    operands = layer_operands(t=80, d=d, m=m, e=e, seed=2)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (e,))
    # one share's function, compiled (a range's start is static) and
    # called for each share
    one_share = jax.jit(
        lambda operands, bias, lo: share(
            operands, (lo, held), top_k=top_k, bias=bias
        ),
        static_argnums=2,
    )
    parts = [one_share(operands, bias, lo) for lo in range(0, e, held)]
    routed = sum(out for out, _ in parts)
    want, _, _, _ = jax.jit(whole_layer, static_argnums=1)(
        operands, top_k, bias
    )
    np.testing.assert_allclose(routed, want, atol=2e-5)
    # every share counted the same assignments; their held rows add up
    for _, stats in parts:
        assert np.array_equal(stats["counts"], parts[0][1]["counts"])
    assert sum(float(s["held_rows"]) for _, s in parts) == 80 * top_k
    # the layer module: the shared expert rides on every share
    layer = DroplessMoE(
        num_experts=e, mlp_dim=m, top_k=top_k, dtype=jnp.float32,
        held=(held, held), score="sigmoid", select_bias=True,
        renormalise=True, scale=2.5, shared_dim=m,
    )
    x = operands[0][None]
    variables = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
    p = variables["params"]
    assert p["experts_w_gate"].shape == (held, d, m)
    assert p["router"].shape == (d, e) and p["select_bias"].shape == (e,)
    out, _ = jax.jit(layer.apply)(variables, x)
    routed_part, _ = jax.jit(lambda x, p: dropless_moe(
        x, p["router"], p["experts_w_gate"], p["experts_w_in"],
        p["experts_w_out"], top_k, jnp.float32, held=(held, held),
        score="sigmoid", select_bias=p["select_bias"], renormalise=True,
        scale=2.5,
    ))(x[0], p)
    shared = (
        nn.silu(x[0] @ p["shared_gate"]["kernel"])
        * (x[0] @ p["shared_up"]["kernel"])
    ) @ p["shared_down"]["kernel"]
    np.testing.assert_allclose(out[0], routed_part + shared, atol=1e-5)


def test_a_share_that_no_token_reaches_and_one_that_all_reach():
    """The static layout at its ends: a held range with no assignment
    (every held expert one tile of zero rows: the output is zero and
    so are the gradients), and one that every assignment reaches (all
    ``t x k`` rows used)."""
    operands = layer_operands(t=64, e=16)
    bias = jnp.zeros((16,)).at[:4].set(9.0)   # top-4 = experts 0..3
    out, stats = jax.jit(
        lambda ops: share(ops, (8, 4), bias=bias)
    )(operands)
    assert float(stats["held_rows"]) == 0 and not np.asarray(out).any()
    grads = jax.jit(jax.grad(
        lambda ops: share(ops, (8, 4), bias=bias)[0].sum()
    ))(operands)
    assert not any(np.asarray(g).any() for g in grads)
    out, stats = jax.jit(
        lambda ops: share(ops, (0, 4), bias=bias)
    )(operands)
    assert float(stats["held_rows"]) == 64 * 4
    want, _, _, _ = jax.jit(whole_layer, static_argnums=1)(
        operands, 4, bias
    )
    np.testing.assert_allclose(out, want, atol=1e-5)
    with pytest.raises(ValueError, match="held"):
        dropless_moe(*operands, 4, held=(0, 4))


# -- the step: scopes, counters, the leaf no gradient reaches -----------------


def test_the_step_moves_the_bias_by_its_rule_and_nothing_else_does():
    """After one step each expert layer's bias is ``old + u x
    sign(mean(n) - n)`` EXACTLY: no Adam, no weight decay (0.1 here)
    reached it, while every other leaf moved by the optimizer; the
    deltas are no metric."""
    model, step, state, batch = toy_step()
    loss_fn = make_sarvam_mla_loss(model, num_chunks=4)
    _, aux = jax.jit(loss_fn)(state.params, batch)
    before = jax.tree.map(np.asarray, state.params)
    deltas = jax.tree.map(np.asarray, aux[STATE_UPDATES])
    new_state, metrics = step(state, batch)
    assert set(metrics) == {
        "loss", "grad_norm", "moe.held_rows_share",
        "moe.held_tiles_share", "moe.bias_abs_max",
    }
    for i in EXPERT_LAYERS:
        old = before[f"block_{i}"]["moe"]["select_bias"]
        delta = deltas[f"block_{i}"]["moe"]["select_bias"]
        assert set(np.unique(np.abs(delta))) <= {
            np.float32(0.0), np.float32(0.001)
        }
        assert np.abs(delta).max() == np.float32(0.001)
        new = np.asarray(
            new_state.params[f"block_{i}"]["moe"]["select_bias"]
        )
        assert np.array_equal(new, old + delta)
        moved = np.asarray(new_state.params[f"block_{i}"]["moe"]["router"])
        assert not np.array_equal(moved, before[f"block_{i}"]["moe"]["router"])
    assert "moe" not in before["block_0"]


def test_a_loss_without_state_updates_lowers_to_the_same_step():
    """The mechanism costs a step that does not use it nothing: the
    toy's step over a loss that drops its ``state_updates`` lowers to
    the text it has with the key never looked for."""
    model, _, state, batch = toy_step()
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    inner = make_sarvam_mla_loss(model, num_chunks=4)

    def without(params, batch):
        loss, aux = inner(params, batch)
        return loss, {k: v for k, v in aux.items() if k != STATE_UPDATES}

    def scalars_only(params, batch):
        loss, aux = without(params, batch)
        return loss, dict(aux)

    a = make_train_step(without, optimizer, has_aux=True).lower(
        state, batch).as_text()
    b = make_train_step(scalars_only, optimizer, has_aux=True).lower(
        state, batch).as_text()
    assert a == b
    with_updates = make_train_step(inner, optimizer).lower(
        state, batch).as_text()
    assert with_updates != a


# -- the normal routes: a flash save, the benchmark's harness -----------------


def test_a_flash_save_round_trips_the_state_with_its_bias_bit_for_bit(
    tmp_path,
):
    """``Checkpointer.save_checkpoint`` / ``load_checkpoint`` on the
    tiny model's train state after two steps (the biases no longer
    zero): every leaf, the float32 bias among bf16 weights, comes
    back bit for bit."""
    AsyncCheckpointSaver.reset()
    AsyncCheckpointSaver._instance = AsyncCheckpointSaver(SaverConfig(
        checkpoint_dir=str(tmp_path), local_shard_num=1,
        global_shard_num=1, node_rank=0,
    ))
    try:
        _, step, state, batch = toy_step(
            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16
        )
        for _ in range(2):
            state, _ = step(state, batch)
        host = jax.device_get(state)
        bias = host.params["block_1"]["moe"]["select_bias"]
        assert bias.dtype == np.float32 and np.abs(bias).max() > 0
        ckpt = Checkpointer(
            str(tmp_path), local_rank=0, global_rank=0, world_size=1
        )
        assert ckpt.save_checkpoint(
            2, {"state": state}, storage_type=StorageType.MEMORY
        )
        got_step, restored = ckpt.load_checkpoint()
        assert got_step == 2
        back = restore_to_template(
            host, restored["state"], device_put=False
        )
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(host), jax.tree.leaves(back)
        ):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.tobytes() == b.tobytes(), jax.tree_util.keystr(path)
        ckpt.close()
    finally:
        AsyncCheckpointSaver.reset()
