"""The channel-gated hybrid family (``model_type: "bailing_hybrid"``,
Ling-3.0-flash) through the repo's blocks against the plain float32
reference (``benchmarks/models/bailing_hybrid_reference.py``): the
channel-wise delta rule's kernels against the token-by-token
recurrence (outputs, the final state, every gradient, a tail that is
no whole chunk, every log-decay AT the bound) and against the scalar
rule where a head's channels share one decay; loss, counters and every
leaf's gradient in float32 and in bf16; the reference's controls;
what the family refuses.  Group-limited
routing, the other families' programs, the harness's rehearsal, the
cut configuration and the benchmark's entries are in
``test_bailing_hybrid_bench.py``, the compiles for a described chip in
``test_bailing_hybrid_tpu.py`` (a file is one worker's)."""

import os
import sys

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import loader  # noqa: E402  (the benchmark's own)

from dlrover_tpu.models import bailing_hybrid  # noqa: E402
from dlrover_tpu.ops import kda  # noqa: E402
from dlrover_tpu.ops.gated_delta_rule import gated_delta_rule  # noqa: E402

family = loader.load_module("models", "bailing_hybrid")
reference = family.reference
CONFIGS = os.path.join(REPO, "benchmarks", "configs")


def toy_cfg(dtype="float32", **recipe):
    """The toy configuration's file (KDA, KDA, latent; 2 heads of
    32 | 32; 4 of 16 experts held, 4 groups, the best 2 kept)."""
    cfg = loader.load_json(os.path.join(CONFIGS, "toy_ling.json"))
    cfg["recipe"] = {**cfg["recipe"], **dict(
        param_dtype=dtype, compute_dtype=dtype,
    ), **recipe}
    return cfg


def toy(dtype="float32", seq=160, seed=0):
    cfg = toy_cfg(dtype)
    model, _, loss_fn = family.build(cfg)
    params = jax.jit(
        lambda key: model.init_params(key, seq_len=seq)
    )(jax.random.PRNGKey(seed))
    # a bias that matters: which experts stand for the top-k
    for i in range(1, cfg["num_hidden_layers"]):
        params[f"block_{i}"]["moe"]["select_bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(100 + i), (cfg["router_outputs"],)
        )
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (2, seq + 1), 0, cfg["vocab_size"]
    )
    return cfg, model, loss_fn, params, {
        "x": tokens[:, :-1], "y": tokens[:, 1:],
    }


# -- the rule -----------------------------------------------------------------


def recurrence(q, k, v, g, beta):
    """``reference.delta_rule`` a sequence at a time: ``(o [b, s, h,
    d_v], S_T [b, h, d_k, d_v])``."""
    with jax.default_matmul_precision("highest"):
        return jax.vmap(reference.delta_rule)(q, k, v, g, beta)


def operands(seed, b, s, h, dk, dv, at_bound=False):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (b, s, h, dk))
    k = jax.random.normal(keys[1], (b, s, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (b, s, h, dv))
    g = kda.LOWER * jax.nn.sigmoid(
        2.0 * jax.random.normal(keys[3], (b, s, h, dk)) - 1.0
    )
    if at_bound:
        g = jnp.full_like(g, kda.LOWER)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, h)))
    return q, k, v, g, beta


def weighted(fn, seed, shapes):
    """``fn``'s two outputs against fixed random cotangents: a scalar
    whose gradient exercises ``do`` and ``dS_T`` together."""
    w_o, w_s = (
        jax.random.normal(jax.random.PRNGKey(seed + i), shape)
        for i, shape in enumerate(shapes)
    )

    def scalar(*args):
        o, state = fn(*args)
        return jnp.sum(o.astype(jnp.float32) * w_o) + jnp.sum(state * w_s)

    return scalar


def relative(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))


@pytest.mark.parametrize("shape, at_bound", [
    ((1, 200, 2, 32, 16), False),   # a tail of 72 tokens, d_v < d_k
    ((1, 256, 2, 32, 32), True),    # every channel at -5, every step
], ids=["tail", "at_the_bound"])
def test_the_rule_is_the_recurrence_in_float32(shape, at_bound):
    b, s, h, dk, dv = shape
    args = operands(3, *shape, at_bound=at_bound)
    o, state = kda.kda_rule(*args)
    o_ref, state_ref = recurrence(*args)
    assert o.shape == (b, s, h, dv) and state.shape == (b, h, dk, dv)
    assert relative(o, o_ref) < 2e-5 and relative(state, state_ref) < 2e-5
    shapes = (o.shape, state.shape)
    got = jax.grad(weighted(kda.kda_rule, 7, shapes), argnums=range(5))(*args)
    want = jax.grad(weighted(recurrence, 7, shapes), argnums=range(5))(*args)
    for name, a, w in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert a.shape == w.shape and bool(jnp.isfinite(a).all()), name
        # (at the bound a step keeps exp(-5) of the state: d g sums
        # terms 150 times smaller than the others', in float32)
        assert relative(a, w) < (5e-4 if at_bound else 5e-5), name


def test_the_rule_in_bf16_stays_near_the_recurrence():
    shape = (1, 256, 2, 32, 32)
    q, k, v, g, beta = operands(5, *shape)
    low = tuple(x.astype(jnp.bfloat16) for x in (q, k, v))
    o, state = kda.kda_rule(*low, g, beta)
    assert o.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    exact = tuple(x.astype(jnp.float32) for x in low)
    o_ref, state_ref = recurrence(*exact, g, beta)
    assert relative(o, o_ref) < 2e-2 and relative(state, state_ref) < 2e-2
    shapes = (o.shape, state.shape)
    got = jax.grad(
        weighted(kda.kda_rule, 9, shapes), argnums=range(5)
    )(*low, g, beta)
    want = jax.grad(
        weighted(recurrence, 9, shapes), argnums=range(5)
    )(*exact, g, beta)
    for name, a, w in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert relative(a, w) < 3e-2, name
    # the decay's gradient is float32 end to end
    assert got[3].dtype == jnp.float32 and got[3].shape == g.shape


def test_one_decay_a_head_is_the_scalar_rule():
    """Where a head's channels share one ``g`` the channel-wise rule
    is ``gated_delta_rule``: outputs, state and gradients (the
    scalar's ``d g`` is the sum of the channels')."""
    shape = (1, 200, 2, 32, 16)
    q, k, v, g, beta = operands(11, *shape)
    one = g[..., 0]

    def channelwise(q, k, v, one, beta):
        return kda.kda_rule(
            q, k, v, jnp.broadcast_to(one[..., None], g.shape), beta
        )

    o, state = channelwise(q, k, v, one, beta)
    o_s, state_s = gated_delta_rule(q, k, v, one, beta)
    assert relative(o, o_s) < 1e-5 and relative(state, state_s) < 1e-5
    shapes = (o.shape, state.shape)
    got = jax.grad(weighted(channelwise, 13, shapes), argnums=range(5))(
        q, k, v, one, beta
    )
    want = jax.grad(
        weighted(gated_delta_rule, 13, shapes), argnums=range(5)
    )(q, k, v, one, beta)
    for name, a, w in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert relative(a, w) < 5e-5, name


def tile_sums(g, reverse):
    """``kda._running_sum``, the kernels' own sum down a chunk's
    tokens, on each ``[CHUNK, d]`` tile of ``g [s, d]`` float32."""
    def kernel(g_ref, out_ref):
        out_ref[...] = kda._running_sum(g_ref[...], reverse=reverse)

    block = pl.BlockSpec((kda.CHUNK, g.shape[1]), lambda i: (i, 0))
    return pl.pallas_call(
        kernel, grid=(g.shape[0] // kda.CHUNK,), in_specs=[block],
        out_specs=block, out_shape=jax.ShapeDtypeStruct(g.shape, g.dtype),
        interpret=True,
    )(g)


@pytest.mark.parametrize("reverse", [False, True], ids=["Gamma", "dg"])
@pytest.mark.parametrize("s", [200, 256], ids=["tail", "whole"])
@pytest.mark.parametrize("at_bound", [False, True], ids=["random", "at_-5"])
def test_the_kernels_running_sum_is_float64s(at_bound, s, reverse):
    """``Gamma = L g`` and ``d g = L^T d Gamma`` as the kernels make
    them (one code for bf16 and float32 callers: float32 adds) against
    numpy's float64 sums a chunk: no further off than twice float32
    ``jnp.cumsum`` (PR 59's program), and exact where every ``g`` is
    -5.  The tail is padded as ``kda._tokens`` pads it, with zeros."""
    d = 32
    rng = np.random.default_rng(60 + s)
    g = np.full((s, d), kda.LOWER) if at_bound else rng.uniform(
        kda.LOWER, 0.0, (s, d)
    )
    g = np.pad(g.astype(np.float32), ((0, -s % kda.CHUNK), (0, 0)))
    chunks = g.reshape(-1, kda.CHUNK, d)
    if reverse:
        chunks = chunks[:, ::-1]

    def back(sums):
        return np.asarray(sums[:, ::-1] if reverse else sums).reshape(g.shape)

    want = back(np.cumsum(chunks.astype(np.float64), axis=1))
    xla = back(jnp.cumsum(jnp.asarray(chunks), axis=1))
    got = np.asarray(tile_sums(jnp.asarray(g), reverse))
    assert got.dtype == np.float32
    error = np.abs(got - want).max()
    if at_bound:
        assert error == 0.0 and np.abs(want).max() == 640.0
    else:
        assert error <= max(
            2 * np.abs(xla - want).max(),
            np.spacing(np.float32(np.abs(want).max())),
        )


def test_what_the_rule_shares_with_the_scalar_rule_is_imported():
    from dlrover_tpu.ops import gated_delta_rule as gdr

    for name in (
        "_inverse_unit_lower", "_solve_bwd", "_heads_lead", "_dot",
        "_lanes", "_as_row",
    ):
        assert getattr(kda, name) is getattr(gdr, name), name
    assert kda.CHUNK == gdr.CHUNK and kda.CHUNK % kda.SUB == 0
    # the positive exponent of a diagonal block stays inside float32
    assert (kda.SUB - 1) * -kda.LOWER < kda.EXP_MAX < 88.7
    assert "ops/kda.py" in gdr.__doc__


# -- the model against the reference ------------------------------------------


def system_and_reference(dtype):
    cfg, _, loss_fn, params, batch = toy(dtype)
    pick = lambda path: "select_bias" not in path  # noqa: E731
    loss, aux, grads = reference.base.gradients_of(
        loss_fn, pick, params, batch
    )
    want_loss, said, want = reference.gradients(
        params, batch["x"], batch["y"], cfg, pick
    )
    return cfg, float(loss), aux, grads, float(want_loss), said, want


def test_float32_loss_counters_and_every_leaf_match_the_reference():
    cfg, loss, aux, grads, want_loss, said, want = system_and_reference(
        "float32"
    )
    assert abs(loss - want_loss) < 2e-5
    assert set(grads) == set(want) and len(grads) > 50
    worst = max((relative(grads[k], want[k]), k) for k in grads)
    assert worst[0] < 2e-4, worst
    # the counters: both sides state them
    np.testing.assert_allclose(
        aux["kda.log_decay_min"], said["log_decay_min"], rtol=1e-5
    )
    rms = np.sqrt(np.max(np.mean(np.square(said["state_rms"]), axis=0)))
    np.testing.assert_allclose(aux["kda.state_rms_max"], rms, rtol=1e-4)
    np.testing.assert_allclose(
        aux["moe.groups_per_token_mean"], said["groups_per_token"],
        rtol=1e-6,
    )
    assert cfg["kda_lower_bound"] <= float(aux["kda.log_decay_min"]) < 0
    assert float(aux["moe.groups_per_token_mean"]) <= cfg["topk_group"]
    # the bias rule on the reference's own counts
    deltas = np.stack([
        aux["state_updates"][f"block_{i}"]["moe"]["select_bias"]
        for i in range(1, cfg["num_hidden_layers"])
    ])
    np.testing.assert_array_equal(deltas, reference.base.bias_deltas(
        said["counts"], cfg["recipe"]["bias_update_rate"]
    ))
    assert np.asarray(said["counts"]).sum(axis=1).tolist() == [
        2 * 160 * cfg["num_experts_per_tok"]
    ] * (cfg["num_hidden_layers"] - 1)


def test_bf16_stays_inside_the_toys_limits():
    cfg, loss, aux, grads, want_loss, _, want = system_and_reference(
        "bfloat16"
    )
    limits = cfg["reference"]
    assert abs(loss - want_loss) < limits["loss_tolerance"]
    for leaf in grads:
        assert relative(grads[leaf], want[leaf]) < limits[
            family.kind_of(leaf)
        ], leaf


def test_a_control_leaves_its_mechanism_out_of_the_reference():
    """Each control's reference is another function: its loss on the
    toy is another number than the whole reference's, which the
    float32 system meets to 2e-5 (the chip's comparison holds each
    outside a limit of the first GRADIENT: the configuration's
    ``reference.why``)."""
    cfg, _, _, params, batch = toy()

    def loss(control):
        return float(reference.loss_and_said(
            params, batch["x"], batch["y"], cfg, control
        )[0])

    whole = loss(None)
    for control in ("channel_decay", "group_mask", "head_gate"):
        assert abs(loss(control) - whole) > 5e-5, control


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="no control"):
        reference.block_kwargs(toy_cfg(), "no_such_thing")


# -- what the family refuses --------------------------------------------------


def test_a_swiglu_clamp_and_a_weighted_prediction_layer_are_refused():
    config = bailing_hybrid.BailingHybridConfig
    with pytest.raises(NotImplementedError, match="SwiGLU clamp"):
        config.tiny(swiglu_limits=(0, 0, 4.0, 0))
    with pytest.raises(NotImplementedError, match="prediction layer"):
        config.tiny(nextn_layers=1, nextn_loss_weight=0.1)
    # the published pair: a prediction layer at weight 0 is not built
    config.tiny(nextn_layers=1, nextn_loss_weight=0.0)
    cfg = toy_cfg()
    cfg["share_expert_swiglu_limit_list"] = [0, 0, 5]
    with pytest.raises(NotImplementedError, match="SwiGLU clamp"):
        family.build(cfg)
    cfg = toy_cfg()
    cfg["kda_safe_gate"] = False
    with pytest.raises(SystemExit, match="kda_safe_gate"):
        family.build(cfg)


def test_a_layers_kind_follows_its_published_index():
    config = bailing_hybrid.BailingHybridConfig
    whole = config()
    kinds = [whole.kind(i) for i in range(whole.num_layers)]
    assert kinds.count("latent") == 7 and kinds.count("kda") == 35
    assert [i for i, k in enumerate(kinds) if k == "latent"] == [
        5, 11, 17, 23, 29, 35, 41,
    ]
    cut = config(num_layers=7, layer_ids=(1, 6, 7, 8, 9, 10, 11))
    assert [cut.kind(i) for i in range(7)] == ["kda"] * 6 + ["latent"]
    with pytest.raises(ValueError, match="layer ids"):
        config(num_layers=7, layer_ids=(1, 2))


def test_the_family_imports_no_sibling():
    source = open(bailing_hybrid.__file__).read()
    imported = [
        line for line in source.splitlines()
        if line.startswith(("from dlrover_tpu", "import dlrover_tpu"))
    ]
    assert sorted(line.split()[1] for line in imported) == [
        "dlrover_tpu.models", "dlrover_tpu.models.losses",
        "dlrover_tpu.ops.causal_conv", "dlrover_tpu.ops.kda",
        "dlrover_tpu.ops.kda_rows", "dlrover_tpu.parallel.moe",
        "dlrover_tpu.telemetry.tracing",
    ]


