"""The Nemotron-H family through the repo's blocks against the plain
float32 reference (``benchmarks/models/nemotron_h_reference.py``): three
kinds of mixer in one stack; the chunked state-space scan
(``ops/ssd.py``: two Pallas kernels, interpreted here) against the
recurrence token by token; the ungated
``relu(.) ** 2`` experts of a held range against their own claims (the
shares of all chips add up to the whole layer, no gate matrix in the
tree, no row past the used tiles read) and against the gated layer's
lowering, which is what it was; an expert width that is no whole
number of lane tiles through the grouped-matmul kernels; the router's
bias and the ``ssm.*`` counters through the train step.  What the
benchmark has of the family is ``test_nemotron_h_bench.py``, the cell's
offline compile ``test_nemotron_h_tpu.py``."""

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
from conftest import (  # noqa: E402
    fill_inside_an_expert, fill_past, jax_internal, primitives_under,
)

from dlrover_tpu.models.nemotron_h import (  # noqa: E402
    NemotronH,
    NemotronHConfig,
    make_nemotron_h_loss,
)
from dlrover_tpu.ops import grouped_matmul as gmm  # noqa: E402
from dlrover_tpu.ops.ssd import ssd_scan  # noqa: E402
from dlrover_tpu.optim import adamw_bf16  # noqa: E402
from dlrover_tpu.parallel import moe  # noqa: E402
from dlrover_tpu.parallel.moe import DroplessMoE, dropless_moe  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import (  # noqa: E402
    STATE_UPDATES,
    TrainState,
    make_train_step,
)

family = loader.load_module("models", "nemotron_h")
reference = family.reference

PATTERN = "ME*EM"
# the HF keys of the tiny configuration, as the reference reads them
CFG = {
    "hybrid_override_pattern": PATTERN, "mamba_num_heads": 4,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "layer_norm_epsilon": 1e-5, "num_experts_per_tok": 3,
    "first_expert_held": 4, "routed_scaling_factor": 2.5,
}
COUNTERS = {
    "ssm.state_rms_max", "ssm.decay_mean", "moe.held_rows_share",
    "moe.held_tiles_share", "moe.bias_abs_max",
}


@functools.cache
def toy_weights(seq):
    """The toy's weights, made ONCE a module: the initialisation reads
    neither the attention, nor remat, nor the scan's chunk, nor the
    compute dtype."""
    model = NemotronH(NemotronHConfig.tiny())
    # (jitted: an eager init runs the whole model op by op)
    params = jax.jit(lambda key: model.init_params(key, seq_len=seq))(
        jax.random.PRNGKey(7)
    )
    # weights at 0.02 leave every router near 0.5 and every state near
    # 0: scale the matrices up so that routing is decided and the
    # recurrence matters; the biases apart, so that score + bias picks
    # other experts than the score alone
    params = jax.tree.map(
        lambda x: x * (6.0 if x.ndim >= 2 else 1.0), params
    )
    for n, i in enumerate(i for i, k in enumerate(PATTERN) if k == "E"):
        params[f"block_{i}"]["moe"]["select_bias"] = 0.2 * jax.random.normal(
            jax.random.PRNGKey(20 + n), (16,)
        )
    return params


def toy(dtype=jnp.float32, seq=48, **kw):
    """Three chunks of 16 tokens through ``M E * E M``; 4 of 16
    experts held."""
    model = NemotronH(NemotronHConfig.tiny(dtype=dtype, **kw))
    # (buffers of its own: a step donates its state)
    params = jax.tree.map(jnp.copy, toy_weights(seq))
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, seq + 1), 0, 256)
    return model, params, {"x": tokens[:, :-1], "y": tokens[:, 1:]}


def toy_step(**kw):
    """The toy's jitted train step and its arguments."""
    model, params, batch = toy(remat=True, **kw)
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    step = make_train_step(
        make_nemotron_h_loss(model, num_chunks=4), optimizer
    )
    return model, step, TrainState.create(params, optimizer), batch


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


# -- the family against the reference -----------------------------------------


@pytest.mark.parametrize("attention", ["xla", "flash"])
def test_float32_loss_logits_and_counters_equal_the_reference(attention):
    model, params, batch = toy(attention_impl=attention)
    loss, aux = jax.jit(make_nemotron_h_loss(model, num_chunks=4))(
        params, batch
    )
    want, said = reference.loss_and_said(
        params, batch["x"], batch["y"], CFG
    )
    assert abs(float(loss) - float(want)) < 1e-5
    logits = jax.jit(lambda p, x: model.apply({"params": p}, x))(
        params, batch["x"]
    )
    np.testing.assert_allclose(
        logits, jnp.stack(reference.forward(params, batch["x"], CFG)),
        rtol=0, atol=2e-4,
    )
    assert set(aux) == COUNTERS | {STATE_UPDATES}
    # the chunked scan's final states are the recurrence's
    rms = np.sqrt(np.mean(np.square(said["state_rms"]), axis=0).max())
    assert float(aux["ssm.state_rms_max"]) == pytest.approx(rms, rel=1e-5)
    assert 0.0 < float(aux["ssm.decay_mean"]) < 1.0
    # the counter is the reference's count of what reached experts
    # 4..7 of 16, over both layers' 2 x 48 x 3 assignments each
    held = float(said["counts"][:, 4:8].sum())
    assert float(aux["moe.held_rows_share"]) == pytest.approx(
        held / (2 * 2 * 48 * 3)
    )


def test_float32_gradients_equal_the_reference_leaf_by_leaf():
    """Every leaf of ``jax.grad`` of the training loss, to 2e-4 of the
    leaf's largest entry: a state-space layer's eight (``A_log``, ``D``
    and ``dt_bias`` among them), an expert layer's five that take a
    gradient, attention's four, the norms, embedding and head.  The
    bias takes no gradient on either side."""
    model, params, batch = toy(remat=True, attention_impl="flash")
    loss_fn = make_nemotron_h_loss(model, num_chunks=4)
    got = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(params)
    want = jax.jit(jax.grad(lambda p: reference.loss_and_said(
        p, batch["x"], batch["y"], CFG
    )[0]))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    # a norm a layer, 8 leaves a state-space mixer, 6 an expert
    # layer, 4 attention; embedding, final norm, head
    assert len(flat_got) == len(flat_want) == 5 + 2 * 8 + 2 * 6 + 4 + 3
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        if "select_bias" in name:
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        assert np.abs(np.asarray(w)).max() > 0, name
        assert relative(g, w) < 2e-4, name


def test_bfloat16_loss_is_within_bf16_rounding_of_the_reference():
    model, params, batch = toy(dtype=jnp.bfloat16)
    loss, _ = jax.jit(make_nemotron_h_loss(model, num_chunks=4))(
        params, batch
    )
    want = reference.loss(params, batch["x"], batch["y"], CFG)
    assert abs(float(loss) - want) < 0.05


def test_the_whole_model_is_causal():
    """A later token moves no earlier logit: the convolution, the
    scan across three chunks, attention and the routing."""
    model, params, batch = toy()
    x = batch["x"]
    apply = jax.jit(lambda p, x: model.apply({"params": p}, x))
    base = apply(params, x)
    moved = apply(params, x.at[:, 30].set((x[:, 30] + 1) % 256))
    np.testing.assert_array_equal(base[:, :30], moved[:, :30])
    assert np.abs(np.asarray(base[:, 30:] - moved[:, 30:])).max() > 1e-3


# -- the chunked scan against the recurrence ----------------------------------


def recurrence(x, dt, A, B, C):
    """One token a step, float32, each head reading its group."""
    b, _, heads, p = x.shape
    repeat = heads // B.shape[2]
    Bh = jnp.repeat(B, repeat, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(C, repeat, axis=2).astype(jnp.float32)

    def token(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (
            jnp.exp(dt_t * A)[..., None, None] * state
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        )
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    state, y = jax.lax.scan(
        token, jnp.zeros((b, heads, p, B.shape[-1]), jnp.float32),
        tuple(a.swapaxes(0, 1) for a in (
            x.astype(jnp.float32), dt, Bh, Ch
        )),
    )
    return y.swapaxes(0, 1), state


def scan_operands(
    seq, dtype, b=2, heads=4, p=8, groups=2, n=16, same_sign=False
):
    """``same_sign``: activations mostly positive, steps of 0.3 to 3
    and ``A`` of -8 to -32, so that a token forgets its neighbour and
    what two tokens exchange is of one sign."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x, dt, A, B, C = (
        jax.random.normal(ks[0], (b, seq, heads, p)),
        0.5 * jax.nn.softplus(jax.random.normal(ks[1], (b, seq, heads))),
        -jnp.exp(jax.random.normal(ks[2], (heads,))),
        jax.random.normal(ks[3], (b, seq, groups, n)),
        jax.random.normal(ks[4], (b, seq, groups, n)),
    )
    if same_sign:
        x, B, C = (jax.nn.silu(0.5 + a) for a in (x, B, C))
        dt, A = 2.0 * dt + 0.2, -8.0 * jnp.arange(1.0, heads + 1)
    return x.astype(dtype), dt, A, B.astype(dtype), C.astype(dtype)


# the cell's group: eight heads of 64 over one B and C of 128, chunks
# of 128 (two and a tail of 44)
GROUP = dict(b=1, heads=8, p=64, groups=1, n=128)


@pytest.mark.parametrize("dtype, seq, chunk, shape, limit", [
    ("float32", 80, 16, {}, 2e-6), ("float32", 70, 16, {}, 2e-6),
    ("bfloat16", 80, 16, {}, 1e-2), ("float32", 300, 128, GROUP, 4e-6),
    ("bfloat16", 80, 16, {"same_sign": True}, 1e-2),
])
def test_the_chunked_scan_is_the_recurrence(dtype, seq, chunk, shape, limit):
    """Five chunks of 16 tokens (and a tail that fills none), two
    heads a group, and the cell's group shape: the outputs, the final
    state and all five gradients, of a loss that reads both results,
    against the recurrence token by token in float32; bf16 operands
    within their rounding, ALSO where tokens decay fast and exchange
    terms of one sign: ``dt A`` at token ``t`` takes only the pairs
    ``i >= t > j``, and the backward has to let every other pair
    cancel (rows and columns from the same rounded numbers), or ``d
    dt`` and ``dA`` read noise (0.21 and 1.7 here; PERF.md, PR 48)."""
    operands = scan_operands(seq, jnp.dtype(dtype), **shape)
    same_sign = shape.get("same_sign", False)
    weight = float(same_sign) + (0.3 if same_sign else 1.0) * (
        jax.random.normal(jax.random.PRNGKey(4), operands[0].shape)
    )

    def scored(rule, *ops):
        y, state = rule(*ops)
        return jnp.sum(y.astype(jnp.float32) * weight) + 0.1 * jnp.sum(
            state ** 2
        ), (y, state)

    chunked = functools.partial(ssd_scan, chunk=chunk)
    (_, got), got_grads = jax.value_and_grad(
        functools.partial(scored, chunked), argnums=range(5), has_aux=True
    )(*operands)
    (_, want), want_grads = jax.value_and_grad(
        functools.partial(scored, recurrence), argnums=range(5),
        has_aux=True,
    )(*operands)
    assert got[0].dtype == operands[0].dtype
    assert got[1].dtype == jnp.float32
    for name, g, w in zip(
        ("y", "state", "dx", "ddt", "dA", "dB", "dC"),
        got + got_grads, want + want_grads,
    ):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert np.linalg.norm(g - w) / np.linalg.norm(w) < limit, name


@pytest.mark.parametrize("remat", [True, False])
def test_a_rematted_block_keeps_what_the_scan_wrote(remat):
    """What the scan's backward reads is said by its ``custom_vjp``:
    the caller's five operands and the float32 state each chunk starts
    from.  Under the block's remat the residuals of the model's loss
    that the scan's file names are what ``ssd_fwd`` WROTE and the
    backward reads, ``y`` (as bits: ``flash_attention._named``) and
    the chunk-start states of both state-space layers, so the block's
    backward does not run ``ssd_fwd`` again, and none of the operands
    (their producers run again); without it the start states and the
    operands are, and nothing ``chunk x chunk`` either way (six chunks
    of 8 here, so that no other array has that shape)."""
    model, params, batch = toy(remat=remat, chunk_size=8)
    saved = jax_internal("ad_checkpoint", "saved_residuals")(
        lambda p: make_nemotron_h_loss(model, num_chunks=4)(p, batch)[0],
        params,
    )
    cfg = model.config
    starts = (
        2, 48 // 8, cfg.ssm_groups, cfg.ssm_state,
        cfg.ssm_inner // cfg.ssm_groups,
    )
    of_the_scan = [a for a, said in saved if "ops/ssd.py" in said]
    y = ((2, 48, cfg.ssm_inner), jnp.uint32)
    assert sorted((a.shape, str(a.dtype)) for a in of_the_scan) == sorted(
        (shape, str(jnp.dtype(dtype)))
        for shape, dtype in [(starts, jnp.float32)] * 2 + [y] * 2 * remat
    )
    shapes = [a.shape for a, _ in saved]
    assert not [s for s in shapes if s[-2:] == (8, 8)]
    # (``A`` is made from a parameter: kept either way)
    x, dt, B = (
        (2, 48, cfg.ssm_heads, cfg.ssm_head_dim), (2, 48, cfg.ssm_heads),
        (2, 48, cfg.ssm_groups, cfg.ssm_state),
    )
    for operand, times in ((x, 2), (dt, 2), (B, 4)):
        assert (shapes.count(operand) >= times) != remat, operand
    operands = scan_operands(64, jnp.float32)
    with pytest.raises(ValueError, match="heads"):
        ssd_scan(*operands[:3], operands[3][:, :, :1].repeat(3, 2),
                 operands[4][:, :, :1].repeat(3, 2))


# -- the ungated experts ------------------------------------------------------


def layer_operands(t=96, d=32, m=24, e=16, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (
        jax.random.normal(ks[0], (t, d)),
        jax.random.normal(ks[1], (d, e)),
        jax.random.normal(ks[2], (e, d, m)) * 0.2,
        jax.random.normal(ks[3], (e, m, d)) * 0.2,
    )


def share(operands, held, top_k=3, bias=None):
    x, router, w_up, w_down = operands
    lo, count = held
    return dropless_moe(
        x, router, None, w_up[lo:lo + count], w_down[lo:lo + count],
        top_k, jnp.float32, held=held, score="sigmoid", select_bias=bias,
        renormalise=True, scale=2.5,
    )


def whole_layer(operands, top_k, bias, scale=2.5):
    """Every expert on every row."""
    x, router, w_up, w_down = operands
    scores = jax.nn.sigmoid(x @ router)
    _, ids = jax.lax.top_k(scores + bias, top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = scale * chosen / chosen.sum(axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(router.shape[1]):
        w = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        y = jnp.square(jax.nn.relu(x @ w_up[e])) @ w_down[e]
        out = out + y * w[:, None]
    return out


def test_the_sixteen_shares_add_up_to_the_whole_layer():
    """Every chip of the group routes over all ``shares x held``
    experts and computes its own: the routed parts summed, and the
    shared expert (which every chip computes alike) counted once,
    equal the uncut layer, every expert on every row: 16 shares of 8
    experts as the cut configuration's group (128 outputs, top-6).
    The layer has no gate matrix: not in the tree, not in the shared
    expert."""
    shares, held, top_k = 16, 8, 6
    e = shares * held
    operands = layer_operands(t=80, e=e, seed=2)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (e,))
    # one share's function, compiled (a range's start is static) and
    # called for each share
    one_share = jax.jit(
        lambda operands, bias, lo: share(
            operands, (lo, held), top_k, bias
        ),
        static_argnums=2,
    )
    parts = [one_share(operands, bias, lo) for lo in range(0, e, held)]
    want = jax.jit(whole_layer, static_argnums=1)(operands, top_k, bias)
    np.testing.assert_allclose(
        sum(out for out, _ in parts), want, atol=2e-5
    )
    assert sum(float(s["held_rows"]) for _, s in parts) == 80 * top_k
    layer = DroplessMoE(
        num_experts=e, mlp_dim=24, top_k=top_k, dtype=jnp.float32,
        held=(held, held), score="sigmoid", select_bias=True,
        renormalise=True, scale=2.5, shared_dim=48,
        expert_form="relu2",
    )
    x = operands[0][None]
    variables = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
    p = variables["params"]
    assert sorted(p) == [
        "experts_w_in", "experts_w_out", "router", "select_bias",
        "shared_down", "shared_up",
    ]
    out, _ = jax.jit(layer.apply)(variables, x)
    routed, _ = jax.jit(lambda x, p: dropless_moe(
        x, p["router"], None, p["experts_w_in"], p["experts_w_out"],
        top_k, jnp.float32, held=(held, held), score="sigmoid",
        select_bias=p["select_bias"], renormalise=True, scale=2.5,
    ))(x[0], p)
    shared = jnp.square(jax.nn.relu(
        x[0] @ p["shared_up"]["kernel"]
    )) @ p["shared_down"]["kernel"]
    np.testing.assert_allclose(out[0], routed + shared, atol=1e-5)


def test_an_ungated_expert_is_one_call_of_the_kernels_with_relu2_inside():
    """The forward of the training loss under ``moe_experts`` and
    ``moe_shared``, an expert layer: ``grouped_expert`` (ONE
    ``custom_vjp_call``: ``relu(.) ** 2`` is inside the up
    projection's kernel since PR 52, no ``max`` and no ``square``
    over the padded rows) and the shared expert's two plain matmuls
    with ``relu(.) ** 2`` between; no gate matrix, no ``silu``.
    (The gated form's count is pinned where its families are tested:
    ``tests/test_olmoe.py``, ``tests/test_moe_held.py``; that the
    call holds two products and not three,
    ``test_no_row_past_tiles_used_reaches_the_ungated_layer``.)"""
    model, params, batch = toy()
    jaxpr = jax.make_jaxpr(make_nemotron_h_loss(model, num_chunks=4))(
        params, batch
    ).jaxpr
    layers = PATTERN.count("E")
    # (float32 weights need no cast)
    assert primitives_under(jaxpr, "moe_experts") == {
        "custom_vjp_call": layers,
    }
    # (``relu`` is a ``custom_jvp_call``)
    assert primitives_under(jaxpr, "moe_shared") == {
        "dot_general": 2 * layers, "custom_jvp_call": layers,
        "square": layers, "add": layers,
    }


def test_no_row_past_tiles_used_reaches_the_ungated_layer(monkeypatch):
    """The rows of the tiles past ``tiles_used`` are not written, and
    the memory may hold anything there: with every such row of the
    experts' result AND of its gradient to the rows, of every array
    between the kernels of ``grouped_expert`` (the hidden rows with
    ``relu(.) ** 2`` taken inside, which are all the forward keeps,
    the gradient of up's product that the derivative makes of their
    root, every row operand of the matrices' gradients), of
    the dispatch's output and of the combine's gradient overwritten
    with NaN, the output and all four gradients are finite and
    bit-equal to the run with zeros there and to the run as it is
    (``tests/test_sarvam_mla.py`` for the gated form)."""
    operands = layer_operands(t=512, e=64, seed=2)
    real = gmm.grouped_expert
    held_dispatch, held_combine = moe._held_dispatch, moe._held_combine
    calls, kernels = [], []

    def results(fill):
        def experts(rows, w_gate, w_up, w_down, tile_group, tiles_used):
            calls.append((w_gate, int(tiles_used[0])))
            if fill is None:
                return real(rows, w_gate, w_up, w_down, tile_group, tiles_used)
            return fill_past(
                real(
                    fill_past(rows, tiles_used, fill), w_gate, w_up, w_down,
                    tile_group, tiles_used,
                ),
                tiles_used, fill,
            )

        def dispatch(tokens, token_of_row, tiles_used, t):
            return fill_past(
                held_dispatch(tokens, token_of_row, tiles_used, t),
                tiles_used, fill,
            )

        def combine(rows, gate_of_row, token_of_row, tiles_used, t):
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
        (_, out), grads = jax.value_and_grad(
            lambda *ops: (lambda out: (jnp.sum(out * cot), out))(
                share(ops, (8, 4), 4)[0]
            ), argnums=range(4), has_aux=True,
        )(*operands)
        return [np.asarray(a) for a in (out, *grads)]

    as_it_is = results(None)
    # one call, with no gate matrix; 4 of the layout's 12 tiles used
    assert calls == [(None, 4)]
    # two products forward, not three, and ``gmm_dlhs`` to the rows
    assert kernels == [
        "gmm_up_fwd", "gmm_fwd", "gmm_down_dlhs", "gmm_dlhs", "gmm_drhs",
        "gmm_drhs",
    ]
    with_nan, with_zeros = results(jnp.nan), results(0.0)
    for got, zeros, plain in zip(with_nan, with_zeros, as_it_is):
        assert np.isfinite(got).all() and got.any()
        np.testing.assert_array_equal(got, zeros)
        np.testing.assert_array_equal(got, plain)


@pytest.mark.parametrize("k, n", [(96, 232), (232, 96)])
def test_a_width_of_no_whole_lane_tile_goes_through_every_kernel(k, n):
    """An expert width of 232 (1856 / 8: one lane tile and 104 lanes)
    as the output of the up matrix and as the contraction of the down
    matrix: ``gmm_fwd``, ``gmm_dlhs``, ``gmm_drhs`` against a loop over
    the groups, and ``gmm_tokens_from_rows`` at that width against a
    scatter-add."""
    sizes = [70, 0, 300, 5]
    layout = gmm.group_layout(jnp.asarray(sizes, jnp.int32), sum(sizes))
    tile_group, tiles_used, starts = layout
    padded = tile_group.shape[0] * gmm.ROW_TILE
    index = np.concatenate([
        int(starts[g]) + np.arange(size) for g, size in enumerate(sizes)
    ])
    ks = jax.random.split(jax.random.PRNGKey(k), 3)
    dense = jax.random.normal(ks[0], (sum(sizes), k))
    weights = jax.random.normal(ks[1], (len(sizes), k, n)) * 0.1
    cot = jax.random.normal(ks[2], (sum(sizes), n))

    def kernels(dense, weights):
        rows = jnp.zeros((padded, k)).at[index].set(dense)
        return gmm.grouped_matmul(rows, weights, tile_group, tiles_used)[
            index
        ]

    def loop(dense, weights):
        group = np.repeat(np.arange(len(sizes)), sizes)
        return jnp.einsum("rk,rkn->rn", dense, weights[group])

    got, pull = jax.vjp(kernels, dense, weights)
    want, pull_want = jax.vjp(loop, dense, weights)
    np.testing.assert_allclose(got, want, atol=1e-4)
    for g, w in zip(pull(cot), pull_want(cot)):
        np.testing.assert_allclose(g, w, atol=1e-4)
    # the rows back to their tokens at the same width
    rows = jnp.zeros((padded, n)).at[index].set(cot)
    token_of_row = jnp.full((padded,), 400, jnp.int32).at[index].set(
        jnp.asarray(np.concatenate([np.arange(s) for s in sizes]))
    )
    back = gmm.tokens_from_rows(rows, token_of_row, tiles_used, 300)
    np.testing.assert_allclose(
        back, jnp.zeros((300, n)).at[token_of_row[index]].add(cot),
        atol=1e-5,
    )


# -- the train step -----------------------------------------------------------


@pytest.fixture(scope="module")
def compiled_step():
    model, step, state, batch = toy_step()
    return model, step.lower(state, batch).compile(), state, batch


def test_the_step_moves_the_bias_by_its_rule_and_carries_the_counters(
    compiled_step,
):
    """After one step each expert layer's bias is ``old + u x
    sign(mean(n) - n)`` EXACTLY (no Adam, no weight decay reached
    it), every other leaf moved by the optimizer, and the step's
    metrics are the loss's counters."""
    model, step, state, batch = compiled_step
    state = jax.tree.map(jnp.copy, state)   # the step donates it
    _, aux = make_nemotron_h_loss(model, num_chunks=4)(state.params, batch)
    before = jax.tree.map(np.asarray, state.params)
    deltas = jax.tree.map(np.asarray, aux[STATE_UPDATES])
    new_state, metrics = step(state, batch)
    assert set(metrics) == COUNTERS | {"loss", "grad_norm"}
    assert sorted(deltas) == ["block_1", "block_3"]
    for name, layer in deltas.items():
        old = before[name]["moe"]["select_bias"]
        delta = layer["moe"]["select_bias"]
        assert np.abs(delta).max() == np.float32(0.001)
        new = np.asarray(new_state.params[name]["moe"]["select_bias"])
        assert np.array_equal(new, old + delta)
        assert not np.array_equal(
            np.asarray(new_state.params[name]["moe"]["router"]),
            before[name]["moe"]["router"],
        )
    moved = np.asarray(new_state.params["block_0"]["ssm"]["A_log"])
    assert not np.array_equal(moved, before["block_0"]["ssm"]["A_log"])


def test_the_layers_scopes_are_in_the_compiled_step(compiled_step):
    """What the benchmark's readers join on: the state-space mixer's
    six scopes, the expert layer's five and ``full_attn`` round the
    module ``attn`` name operations of the compiled step, forward
    (``jvp(..)``) and backward (``transpose(jvp(..))``): the scan's
    backward rule among them."""
    from dlrover_tpu.common.aot_cache import op_names

    _, compiled, _, _ = compiled_step
    stacks = list(op_names(compiled.as_text())["op_names"].values())
    for scope in (
        "ssm_in_proj", "ssm_conv", "ssm_gates", "ssm_scan", "ssm_norm",
        "ssm_out_proj", "moe_router", "moe_dispatch", "moe_experts",
        "moe_combine", "moe_shared", "full_attn",
    ):
        named = [s for s in stacks if f"({scope})" in s or f"/{scope}/" in s]
        assert named, scope
        assert any("transpose(" in s for s in named), scope
    assert any("full_attn" in s and "/attn/" in s for s in stacks)
