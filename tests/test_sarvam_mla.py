"""The latent-attention mixture-of-experts family through the repo's
blocks against the plain float32 reference
(``benchmarks/models/sarvam_mla_reference.py``); the held-experts layer
against its own claims (the shares of all chips add up to the whole
layer; the defaults are the layer OLMoE has always run); the router's
bias, which no gradient reaches and the train step moves by the
loss's ``state_updates``; the yarn frequencies against hand-worked
numbers."""

import functools
import math
import os
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

import flax.linen as nn  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import loader  # noqa: E402  (the benchmark's own)
from conftest import (  # noqa: E402
    fill_inside_an_expert, fill_past, primitives_under,
)
from test_moe_held_index import layout_by_sorting  # noqa: E402

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
from dlrover_tpu.parallel import moe  # noqa: E402
from dlrover_tpu.parallel.moe import DroplessMoE, dropless_moe  # noqa: E402
from dlrover_tpu.telemetry.events import read_events  # noqa: E402
from dlrover_tpu.telemetry.schema import validate_event  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import (  # noqa: E402
    STATE_UPDATES,
    ElasticTrainer,
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


def toy(dtype=jnp.float32, seq=64, **kw):
    model = SarvamMla(SarvamMlaConfig.tiny(dtype=dtype, **kw))
    params = model.init_params(jax.random.PRNGKey(7), seq_len=seq)
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
    loss, aux = make_sarvam_mla_loss(model, num_chunks=4)(params, batch)
    want = reference.loss(params, batch["x"], batch["y"], CFG)
    assert abs(float(loss) - want) < 1e-5
    logits = model.apply({"params": params}, batch["x"])
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
    got = jax.grad(lambda p: loss_fn(p, batch)[0])(params)
    want = jax.grad(
        lambda p: reference.loss_of(p, batch["x"], batch["y"], CFG)
    )(params)
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


@pytest.mark.parametrize("attention", ["flash", "xla"])
def test_a_rematted_block_keeps_what_its_flash_backward_reads(
    attention, remat_keeps_what_flash_reads,
    remat_with_xla_attention_is_the_parents,
):
    """One forward kernel a layer (heads of 24 | 16), none of them run
    again for the backward; loss and gradients the parent policy's bit
    for bit.  With XLA attention nothing is named and the program is
    the parent's."""

    model, params, batch = toy(remat=True, attention_impl=attention)
    loss_fn = make_sarvam_mla_loss(model, num_chunks=4)

    def loss(p):
        return loss_fn(p, batch)[0]

    if attention == "xla":
        remat_with_xla_attention_is_the_parents(loss, params)
    else:
        remat_keeps_what_flash_reads(
            loss, params, CFG["num_hidden_layers"]
        )


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
    loss, _ = make_sarvam_mla_loss(model, num_chunks=4)(params, batch)
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
    a = model.apply({"params": params}, tokens)
    b = model.apply({"params": params}, changed)
    assert np.array_equal(np.asarray(a[:, :40]), np.asarray(b[:, :40]))
    assert not np.array_equal(np.asarray(a[:, 40:]), np.asarray(b[:, 40:]))


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


def layer_operands(t=96, d=32, m=16, e=16, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (
        jax.random.normal(ks[0], (t, d)),
        jax.random.normal(ks[1], (d, e)),
        jax.random.normal(ks[2], (e, d, m)) * 0.2,
        jax.random.normal(ks[3], (e, d, m)) * 0.2,
        jax.random.normal(ks[4], (e, m, d)) * 0.2,
    )


def share(operands, held, top_k=4, bias=None, **kw):
    x, router, w_gate, w_up, w_down = operands
    lo, count = held
    kw = {**dict(
        score="sigmoid", select_bias=bias, renormalise=True, scale=2.5,
    ), **kw}
    return dropless_moe(
        x, router, w_gate[lo:lo + count], w_up[lo:lo + count],
        w_down[lo:lo + count], top_k, jnp.float32, held=held, **kw,
    )


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
    out, stats = share(operands, (0, 16), bias=bias)
    want, scores, ids, weights = whole_layer(operands, 4, bias)
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
    grad = jax.grad(
        lambda b: share(operands, (0, 16), bias=b)[0].sum()
    )(bias)
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
    parts = [
        share(operands, (lo, held), top_k=top_k, bias=bias)
        for lo in range(0, e, held)
    ]
    routed = sum(out for out, _ in parts)
    want, _, _, _ = whole_layer(operands, top_k, bias)
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
    variables = layer.init(jax.random.PRNGKey(0), x)
    p = variables["params"]
    assert p["experts_w_gate"].shape == (held, d, m)
    assert p["router"].shape == (d, e) and p["select_bias"].shape == (e,)
    out, _ = layer.apply(variables, x)
    routed_part, _ = dropless_moe(
        x[0], p["router"], p["experts_w_gate"], p["experts_w_in"],
        p["experts_w_out"], top_k, jnp.float32, held=(held, held),
        score="sigmoid", select_bias=p["select_bias"], renormalise=True,
        scale=2.5,
    )
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
    out, stats = share(operands, (8, 4), bias=bias)
    assert float(stats["held_rows"]) == 0 and not np.asarray(out).any()
    grads = jax.grad(
        lambda ops: share(ops, (8, 4), bias=bias)[0].sum()
    )(operands)
    assert not any(np.asarray(g).any() for g in grads)
    out, stats = share(operands, (0, 4), bias=bias)
    assert float(stats["held_rows"]) == 64 * 4
    want, _, _, _ = whole_layer(operands, 4, bias)
    np.testing.assert_allclose(out, want, atol=1e-5)
    with pytest.raises(ValueError, match="held"):
        dropless_moe(*operands, 4, held=(0, 4))


UNWRITTEN = {
    # OLMoE's tiny case: softmax, not renormalised, every expert held
    "every_expert_held": dict(
        operands=dict(t=128, d=64, m=32, e=8, seed=4), held=None,
        top_k=2, tiles=(8, 9),
    ),
    # 4 of 64 experts held: a sixteenth of 2048 assignments has a row
    "most_tiles_empty": dict(
        operands=dict(t=512, e=64, seed=2), held=(8, 4), top_k=4,
        tiles=(4, 12),
    ),
    # ... and the bias keeps every token from held expert 9
    "an_empty_expert": dict(
        operands=dict(t=512, e=64, seed=3), held=(8, 4), top_k=4,
        avoid=9, tiles=(4, 12),
    ),
}


@pytest.mark.parametrize("case", sorted(UNWRITTEN))
def test_no_row_past_tiles_used_reaches_the_layer(case, monkeypatch):
    """The experts' kernels do not write the rows of the tiles past
    ``tiles_used``, forward or backward.  That is safe because
    nothing reads one: with every such row of the experts' result
    and of its gradient to the rows, AND of every array between the
    kernels of ``grouped_expert`` (the hidden rows, the kept
    pre-activations of gate and up, their gradients, every row
    operand of the matrices' gradients) overwritten with NaN, the
    output and all five gradients are finite and bit-equal to the
    run with zeros there (the kernels' contract until PR 36) and to
    the run as it is.  Where the chip holds a range, the
    dispatch's output and the combine's gradient are not written
    there either (PR 38) and are overwritten alike.  A reduction over
    the padded rows, or a gather that names one, fails here."""
    c = UNWRITTEN[case]
    operands = layer_operands(**c["operands"])
    bias = None
    if "avoid" in c:
        bias = jnp.zeros(operands[1].shape[1:]).at[c["avoid"]].set(-9.0)
    real = gmm.grouped_expert
    held_dispatch, held_combine = moe._held_dispatch, moe._held_combine
    seen, kernels = [], []

    def layer(*ops):
        if c["held"] is None:
            return dropless_moe(*ops, c["top_k"], jnp.float32)
        return share(ops, c["held"], c["top_k"], bias=bias)

    def results(fill):
        def experts(rows, w_gate, w_up, w_down, tile_group, tiles_used):
            seen.append((tiles_used, tile_group.shape[0]))
            if fill is None:
                return real(rows, w_gate, w_up, w_down, tile_group, tiles_used)
            # the cotangent's fill first (d_rows), the result's last
            return fill_past(
                real(
                    fill_past(rows, tiles_used, fill), w_gate, w_up, w_down,
                    tile_group, tiles_used,
                ),
                tiles_used, fill,
            )

        def scored(*ops):
            out, stats = layer(*ops)
            return jnp.sum(out * cot), (out, stats)

        def dispatch(tokens, token_of_row, tiles_used, t):
            return fill_past(
                held_dispatch(tokens, token_of_row, tiles_used, t),
                tiles_used, fill,
            )

        def combine(rows, gate_of_row, token_of_row, tiles_used, t):
            # the fill of ``rows`` is the fill of their gradient
            return held_combine(
                fill_past(rows, tiles_used, fill), gate_of_row,
                token_of_row, tiles_used, t,
            )

        monkeypatch.setattr(moe.gmm, "grouped_expert", experts)
        fill_inside_an_expert(monkeypatch, fill, kernels)
        if fill is not None:
            monkeypatch.setattr(moe, "_held_dispatch", dispatch)
            monkeypatch.setattr(moe, "_held_combine", combine)
        cot = jax.random.normal(jax.random.PRNGKey(7), operands[0].shape)
        (_, (out, stats)), grads = jax.value_and_grad(
            scored, argnums=range(5), has_aux=True
        )(*operands)
        return [np.asarray(a) for a in (out, *grads)], stats

    as_it_is, stats = results(None)
    used, tiles = c["tiles"]
    assert [(int(u[0]), n) for u, n in seen] == [(used, tiles)]
    # ONE call, and these its kernels, forward rule and backward
    assert kernels == [
        "gmm_up_fwd", "gmm_fwd", "gmm_down_dlhs", "gmm_up_dlhs",
        "gmm_drhs", "gmm_drhs", "gmm_drhs",
    ]
    if "avoid" in c:
        assert float(stats["counts"][c["avoid"]]) == 0
    with_nan, _ = results(jnp.nan)
    with_zeros, _ = results(0.0)
    for got, zeros, plain in zip(with_nan, with_zeros, as_it_is):
        assert np.isfinite(got).all() and got.any()
        np.testing.assert_array_equal(got, zeros)
        np.testing.assert_array_equal(got, plain)


# The held layer's routing as it stood until PR 38, word for word: the
# plain reference of the row-side movements.  Every array has the
# static size: the dispatch gathers ``[padded rows, d]``, the combine
# gathers ``[t, k, d]`` (a choice held elsewhere reads zeros) and
# weights it.  Its ``source`` and ``slot`` come from the sort of the
# assignments (``test_moe_held_index.py::layout_by_sorting``: the index
# work as it stood until PR 58).


def _rows_at(rows, slot, some_absent: bool):
    if some_absent:
        return rows.at[slot].get(mode="fill", fill_value=0)
    return rows[slot]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_rows_at_pr_37(tokens, source, slot, some_absent=False):
    zero_row = jnp.zeros((1, tokens.shape[1]), tokens.dtype)
    return jnp.concatenate([tokens, zero_row])[source // slot.shape[1]]


def _dispatch_fwd(tokens, source, slot, some_absent):
    return _dispatch_rows_at_pr_37(tokens, source, slot, some_absent), slot


def _dispatch_bwd(some_absent, slot, g):
    return (
        _rows_at(g, slot, some_absent).sum(axis=1).astype(g.dtype),
        None, None,
    )


_dispatch_rows_at_pr_37.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _collect_rows_at_pr_37(rows, source, slot, some_absent=False):
    return _rows_at(rows, slot, some_absent)


def _collect_fwd(rows, source, slot, some_absent):
    return _rows_at(rows, slot, some_absent), source


def _collect_bwd(some_absent, source, g):
    flat = g.reshape((-1, g.shape[-1]))
    return flat.at[source].get(mode="clip"), None, None


_collect_rows_at_pr_37.defvjp(_collect_fwd, _collect_bwd)


def plain_layout(
    expert_ids, gate, lo, count, tile_group, tiles_used, padded_starts
):
    # in ``moe._held_layout``'s place: the sort's ``source`` and
    # ``slot`` where the row side's ``token_of_row`` goes, the weights
    # ``[t, k]`` as they are where ``gate_of_row`` does
    *_, source, slot = layout_by_sorting(
        expert_ids, lo + count + 1, lo, count
    )
    return (source, slot), gate


def plain_dispatch(tokens, source_and_slot, tiles_used, t):
    return _dispatch_rows_at_pr_37(tokens, *source_and_slot, True)


def plain_combine(rows, gate, source_and_slot, tiles_used, t):
    return jnp.einsum(
        "tkd,tk->td",
        _collect_rows_at_pr_37(rows, *source_and_slot, True),
        gate, preferred_element_type=jnp.float32,
    ).astype(rows.dtype)


def plain_routing(patch):
    patch.setattr(moe, "_held_layout", plain_layout)
    patch.setattr(moe, "_held_dispatch", plain_dispatch)
    patch.setattr(moe, "_held_combine", plain_combine)


HELD = {
    **{k: v for k, v in UNWRITTEN.items() if v["held"] is not None},
    # the bias sends every token's four choices to experts 0..3: all
    # 2048 rows land here, two tiles an expert, the 4 spare ones empty
    "every_assignment_lands_here": dict(
        operands=dict(t=512, e=16, seed=5), held=(0, 4), top_k=4,
        towards=slice(0, 4), tiles=(8, 12),
    ),
    # ... and to a range held elsewhere: one empty tile an expert
    "no_token_reaches_the_range": dict(
        operands=dict(t=512, e=16, seed=5), held=(8, 4), top_k=4,
        towards=slice(0, 4), tiles=(4, 12),
    ),
}


def held_case(case):
    c = HELD[case]
    operands = layer_operands(**c["operands"])
    bias = jnp.zeros(operands[1].shape[1:])
    if "avoid" in c:
        bias = bias.at[c["avoid"]].set(-9.0)
    if "towards" in c:
        bias = bias.at[c["towards"]].set(9.0)
    return c, operands, bias


@pytest.mark.parametrize("case", sorted(HELD))
def test_the_row_side_is_the_plain_routing(case, monkeypatch):
    """Where a chip holds a range, dispatch and combine walk the used
    row tiles (PR 38).  Against the routing as it stood, at the static
    size: the same output and the same five gradients, to 1e-6 of
    float32 where a token's held terms are summed in another order
    (by expert, no longer by choice) and BIT-equal where nothing is
    summed differently (the gradients to the experts' weights: the
    rows and the rows' gradients are the same numbers)."""
    c, operands, bias = held_case(case)
    cot = jax.random.normal(jax.random.PRNGKey(7), operands[0].shape)

    def results():
        def scored(*ops):
            out, stats = share(ops, c["held"], c["top_k"], bias=bias)
            return jnp.sum(out * cot), (out, stats)

        (_, (out, stats)), grads = jax.value_and_grad(
            scored, argnums=range(5), has_aux=True
        )(*operands)
        return [np.asarray(a) for a in (out, *grads)], stats

    got, stats = results()
    assert (int(stats["tiles_used"]), int(stats["tiles"])) == c["tiles"]
    plain_routing(monkeypatch)
    want, _ = results()
    for name, a, b in zip(
        ("out", "tokens", "router", "w_gate", "w_up", "w_down"), got, want
    ):
        assert np.isfinite(a).all(), name
        if name.startswith("w_"):
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            assert relative(a, b) < 1e-6, name
    if case == "no_token_reaches_the_range":
        assert not any(a.any() for a in got)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("weighted", [False, True])
def test_the_two_movements_are_each_others_transpose(weighted, dtype):
    """``_rows_from_tokens`` and ``tokens_from_rows`` against numpy's
    loops: 5 row tiles of which 3 are used, a tile's tokens ascending
    and distinct, its padding named past the last token.  The rows of
    the tiles past ``tiles_used`` are neither written by the one nor
    read by the other (NaN there), a row of padding reads zeros and
    adds nothing (NaN there too)."""
    t, d, tile = 300, 128, gmm.ROW_TILE
    rng = np.random.default_rng(3)
    token_of_row = np.full((5 * tile,), -1)
    for i, n in enumerate((tile, 41, 0)):
        token_of_row[i * tile:i * tile + n] = np.sort(
            rng.choice(t, n, replace=False)
        )
    real = token_of_row >= 0
    token_of_row = np.where(real, token_of_row, t + np.arange(5 * tile))
    tiles_used = jnp.array([3], jnp.int32)
    x = jnp.asarray(rng.normal(size=(t, d)), dtype)
    rows = np.asarray(moe._rows_from_tokens(
        x, jnp.asarray(token_of_row, jnp.int32), tiles_used
    ))
    assert np.isnan(rows[3 * tile:].astype(np.float32)).all()
    want = np.where(
        real[:, None], np.asarray(x)[np.minimum(token_of_row, t - 1)], 0
    )
    np.testing.assert_array_equal(rows[:3 * tile], want[:3 * tile])

    y = rng.normal(size=(5 * tile, d)).astype(np.float32)
    y[~real] = np.nan
    y = np.asarray(jnp.asarray(y, dtype))
    weight = rng.uniform(0.5, 2, size=(5 * tile,)).astype(np.float32)
    got = gmm.tokens_from_rows(
        jnp.asarray(y), jnp.asarray(token_of_row, jnp.int32), tiles_used,
        t, jnp.asarray(weight) if weighted else None,
    )
    assert got.dtype == dtype
    want = np.zeros((t, d), np.float32)
    for p in np.flatnonzero(real):
        want[token_of_row[p]] += (
            weight[p] if weighted else 1.0
        ) * y[p].astype(np.float32)
    want = np.asarray(jnp.asarray(want, dtype))
    if weighted:
        # a compiler may fuse the product into the sum
        assert relative(got, want) < (1e-6 if dtype == jnp.float32 else 8e-3)
    else:
        np.testing.assert_array_equal(np.asarray(got), want)


def test_no_tokens_by_choices_by_width_array_in_the_step():
    """The toy's lowered step, forward and backward, holds no array of
    ``tokens x k`` rows of the model's width: no ``[t, k, d]`` and no
    ``[t * k, d]`` (what a gather or scatter of every assignment's row
    would make).  The layer with the plain routing does, so the search
    would find one."""
    from test_olmoe import shapes_in

    model, step, state, batch = toy_step()
    cfg = model.config
    t, k, d = batch["x"].size, cfg.top_k, cfg.hidden_dim

    def every_assignment(text):
        return [
            s for s in shapes_in(text)
            if s[-1] == d and math.prod(s) == t * k * d
        ]

    assert not every_assignment(step.lower(state, batch).as_text())
    c, operands, bias = held_case("most_tiles_empty")
    t, d = operands[0].shape
    k = c["top_k"]

    def lowered():
        return jax.jit(jax.grad(
            lambda *ops: share(ops, c["held"], k, bias=bias)[0].sum(),
            argnums=range(5),
        )).lower(*operands).as_text()

    assert not every_assignment(lowered())
    with pytest.MonkeyPatch.context() as patch:
        plain_routing(patch)
        assert (t, k, d) in every_assignment(lowered())


def dropless_moe_at_pr_33(
    tokens, router_kernel, w_gate, w_up, w_down, top_k, dtype
):
    """``dropless_moe`` as it stood before it learnt of held experts
    and other routers (commit d0cb620), word for word."""
    t, _ = tokens.shape
    e = router_kernel.shape[-1]
    assignments = t * top_k
    logits = jnp.dot(
        tokens.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_ids = jax.lax.top_k(probs, top_k)
    flat_ids = expert_ids.reshape(-1)
    group_sizes = jnp.bincount(flat_ids, length=e).astype(jnp.int32)
    tile_group, tiles_used, padded_starts = gmm.group_layout(
        group_sizes, assignments
    )
    order = jnp.argsort(flat_ids, stable=True).astype(jnp.int32)
    sorted_ids = flat_ids[order]
    starts = jnp.cumsum(group_sizes) - group_sizes
    row = (
        padded_starts[sorted_ids] - starts[sorted_ids]
        + jnp.arange(assignments, dtype=jnp.int32)
    )
    slot = jnp.zeros_like(order).at[order].set(
        row, unique_indices=True
    ).reshape(t, top_k)
    source = jnp.full(
        (tile_group.shape[0] * gmm.ROW_TILE,), assignments, jnp.int32
    ).at[row].set(order, unique_indices=True)
    rows = moe._dispatch_rows(tokens.astype(dtype), source, slot)

    def expert(x, w):
        return gmm.grouped_matmul(
            x, w.astype(dtype), tile_group, tiles_used
        )

    rows = expert(
        nn.silu(expert(rows, w_gate)) * expert(rows, w_up), w_down
    )
    out = jnp.einsum(
        "tkd,tk->td", moe._collect_rows(rows, source, slot), gate,
        preferred_element_type=jnp.float32,
    )
    return out.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_defaults_are_the_layer_olmoe_has_always_run(dtype):
    """OLMoE's tiny case (8 experts, top-2, softmax, not renormalised,
    every expert held): output and all five gradients against the
    function as PR 33 had it, whose activation was XLA's between
    three grouped matmuls.  BIT-equal until PR 52; since then the
    activation is taken inside the kernel from the products' float32
    sums (not from their rounding to ``dtype``), its derivative is
    the down projection's kernel's epilogue and the rows' two
    gradients are summed in float32.  In float32 the two agree to a
    few roundings.  In bf16 the new path is held to being NO LESS
    EXACT than PR 33's: against PR 33's function run in float32 on
    the same operands, the output and every gradient lie closer (by
    their errors' root mean square: 15 to 45% closer here) and none
    of their elements further than 2 ** -6 of the largest."""
    operands = layer_operands(t=128, d=64, m=32, e=8, seed=4)

    def results(fn):
        loss = lambda *ops: fn(*ops).astype(jnp.float32).sum()  # noqa: E731
        return [
            np.asarray(leaf, np.float32) for leaf in jax.tree.leaves((
                jax.jit(fn)(*operands),
                jax.jit(jax.grad(loss, range(5)))(*operands),
            ))
        ]

    new = results(lambda *ops: dropless_moe(*ops, 2, dtype)[0])
    old = results(lambda *ops: dropless_moe_at_pr_33(*ops, 2, dtype))
    assert len(new) == len(old) == 6
    if dtype == jnp.float32:
        for a, b in zip(new, old):
            assert a.shape == b.shape and np.abs(b).max() > 0
            assert np.abs(a - b).max() <= 2.0 ** -20 * np.abs(b).max()
        return
    truth = results(
        lambda *ops: dropless_moe_at_pr_33(*ops, 2, jnp.float32)
    )
    for a, b, true in zip(new, old, truth, strict=True):
        assert a.shape == b.shape == true.shape

        def rms(x, true=true):
            return np.sqrt(np.mean(np.square(x - true)))

        assert 0 < rms(a) <= rms(b)
        assert np.abs(a - true).max() <= 2.0 ** -6 * np.abs(true).max()


# -- the step: scopes, counters, the leaf no gradient reaches -----------------


def test_a_gated_expert_is_one_call_of_the_kernels_and_no_pass_beside():
    """The forward of the training loss under ``moe_experts`` and
    ``moe_shared``, an expert layer: ``grouped_expert`` (ONE
    ``custom_vjp_call``) with the three weights' casts and NOTHING
    else: the ``silu`` and the product are inside the up projections'
    kernel since PR 52 (before it: three grouped matmuls, a ``jit``
    and a ``mul`` over the padded rows); the shared expert three
    plain matmuls, one ``silu``, one product and the sum onto the
    routed output, as it was.  Five cells run this path: a change
    that moves the count has to be measured in them."""
    model = SarvamMla(SarvamMlaConfig.tiny(remat=True))
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), seq_len=64)
    )
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    jaxpr = jax.make_jaxpr(make_sarvam_mla_loss(model, num_chunks=4))(
        params, {"x": tokens, "y": tokens}
    ).jaxpr
    layers = model.config.num_layers - model.config.first_dense
    assert primitives_under(jaxpr, "moe_experts") == {
        "custom_vjp_call": layers, "convert_element_type": 3 * layers,
    }
    assert primitives_under(jaxpr, "moe_shared") == {
        "dot_general": 3 * layers, "convert_element_type": 3 * layers,
        "jit": layers, "mul": layers, "add": layers,
    }
    assert "experts_w_gate" in params["block_1"]["moe"]


def test_the_layers_scopes_are_in_the_compiled_step():
    """What the benchmark's readers join on: latent attention's five
    scopes, the held layer's four and the shared expert's name
    operations of the compiled step, forward (``jvp(..)``) and
    backward (``transpose(jvp(..))``)."""
    from dlrover_tpu.common.aot_cache import op_names

    _, step, state, batch = toy_step()
    compiled = step.lower(state, batch).compile()
    stacks = list(op_names(compiled.as_text())["op_names"].values())
    for scope in (
        "mla_q", "mla_kv_down", "mla_kv_up", "mla_rope", "mla_out",
        "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
        "moe_shared",
    ):
        named = [s for s in stacks if f"/{scope}/" in s]
        assert named, scope
        assert any("transpose(" in s for s in named), scope
    assert any("/attn/" in s for s in stacks)


def test_the_step_moves_the_bias_by_its_rule_and_nothing_else_does():
    """After one step each expert layer's bias is ``old + u x
    sign(mean(n) - n)`` EXACTLY: no Adam, no weight decay (0.1 here)
    reached it, while every other leaf moved by the optimizer; the
    deltas are no metric."""
    model, step, state, batch = toy_step()
    loss_fn = make_sarvam_mla_loss(model, num_chunks=4)
    _, aux = loss_fn(state.params, batch)
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
