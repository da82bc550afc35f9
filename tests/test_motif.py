"""The hyper-connected differential-attention family (``model_type:
"motif"``) through the repo's blocks against the plain float32
reference (``benchmarks/models/motif_reference.py``): loss, counters
and every leaf's gradient in float32 and in bf16; the streams'
``H_res`` doubly stochastic; a noise head's gradient; PolyNorm inside
``grouped_expert`` against the plain form with the padded rows full of
NaN, and a width that does not fit one block refused; the expert
shares (the shared expert counted once) and the head-group shares each
add up to the uncut layer; the prediction loss ignores the last
position.  What the benchmark has of the family is
``test_motif_bench.py``, the cell's offline compile
``test_motif_tpu.py``."""

import os
import sys

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import loader  # noqa: E402  (the benchmark's own)

from dlrover_tpu.models import layers  # noqa: E402
from dlrover_tpu.models import motif  # noqa: E402
from dlrover_tpu.ops import grouped_matmul as gmm  # noqa: E402
from dlrover_tpu.parallel.moe import (  # noqa: E402
    DroplessMoE,
    dropless_moe,
    polynorm_coeffs,
)

family = loader.load_module("models", "motif")
reference = family.reference
CONFIGS = os.path.join(REPO, "benchmarks", "configs")
SCALARS = ("alpha", "bias", "polynorm_")


def toy_cfg(dtype="float32", **recipe):
    """The toy configuration's file (kinds ``[1, 0]``, 2 kv groups
    of 4 signal + 1 noise query heads of 48 | 32, 4 streams, 4 of 16
    experts held, one prediction layer)."""
    cfg = loader.load_json(os.path.join(CONFIGS, "toy_motif.json"))
    cfg["recipe"] = {**cfg["recipe"], **dict(
        param_dtype=dtype, compute_dtype=dtype,
    ), **recipe}
    return cfg


def toy(dtype="float32", seq=128, **recipe):
    """``(cfg, model, loss_fn, params, batch)``: the learned scalars
    moved off their symmetric start, so that every leaf has a
    gradient of its own."""
    cfg = toy_cfg(dtype, **recipe)
    model, _, loss_fn = family.build(cfg)
    # (jitted: an eager init would run the interpreted kernels op by op)
    params = jax.jit(lambda key: model.init_params(key, seq_len=seq))(
        jax.random.PRNGKey(7)
    )

    def moved(path, x):
        name = jax.tree_util.keystr(path)
        if not any(mark in name for mark in SCALARS):
            return x
        key = jax.random.fold_in(
            jax.random.PRNGKey(11), sum(name.encode()) % 9973
        )
        return x + 0.1 * jax.random.normal(key, x.shape, x.dtype)

    params = jax.tree_util.tree_map_with_path(moved, params)
    tokens = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, seq + 1), dtype=np.int32
    )
    batch = {"x": jnp.asarray(tokens[:, :-1]), "y": jnp.asarray(tokens[:, 1:])}
    return cfg, model, loss_fn, params, batch


def relative(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(
        np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30)
    )


def leaves_of(tree):
    return {
        jax.tree_util.keystr(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def both(request):
    """System and reference on the toy: ``(dtype, cfg, loss, aux,
    gradients, reference's loss parts, reference's gradients)``."""
    cfg, _, loss_fn, params, batch = toy(request.param)
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(
            jax.value_and_grad(loss_fn, has_aux=True)
        )(params, batch)
    parts = reference.loss_parts(params, batch["x"], batch["y"], cfg)
    wanted_loss, _, wanted = reference.gradients(
        params, batch["x"], batch["y"], cfg, lambda path: True
    )
    return (
        request.param, cfg, float(loss), jax.device_get(aux),
        leaves_of(grads), parts, (float(wanted_loss), wanted),
    )


def test_loss_and_counters_equal_the_reference(both):
    dtype, cfg, loss, aux, _, parts, (wanted_loss, _) = both
    main, predicted, balance, counts = parts
    tol = 2e-5 if dtype == "float32" else cfg["reference"]["loss_tolerance"]
    assert abs(loss - wanted_loss) < tol
    assert abs(float(aux["mtp.loss"]) - float(predicted)) < 10 * tol
    assert abs(float(aux["moe.lb_loss"]) - float(balance)) < 0.05
    # two sparse layers (one of the stack, the prediction layer's)
    assert counts.shape == (2, cfg["router_outputs"])
    held = counts[:, 4:8].sum(axis=1) / counts.sum(axis=1)
    if dtype == "float32":
        assert abs(float(aux["moe.held_rows_share"]) - held.mean()) < 1e-6
    assert float(aux["mhc.res_sum_err_max"]) < 1e-3
    assert 0.3 < float(aux["gdla.lambda_mean"]) < 0.7
    assert float(aux["gdla.noise_share"]) > 0.1


def test_every_leafs_gradient_equals_the_reference(both):
    """85 leaves, the streams' ``phi`` / ``alpha`` / ``bias``,
    ``lambda_proj``, ``gate_proj``, the query latent, PolyNorm's ``w``
    / ``b`` (routed, shared and dense) and ``eh_proj`` among them: to
    1e-4 a leaf in float32; in bf16 inside the toy's limits, the
    leaves of a few numbers pooled over the blocks as the chip's
    comparison pools them."""
    dtype, cfg, _, _, grads, _, (_, wanted) = both
    assert set(grads) == set(wanted) and len(wanted) == 85
    for name in (
        "['block_1']['mhc_attn']['phi']", "['block_0']['mhc_mlp']['alpha']",
        "['block_1']['attn']['lambda_proj']",
        "['block_1']['attn']['q_down']['kernel']",
        "['block_0']['mlp']['polynorm_w']",
        "['block_1']['moe']['experts_polynorm_b']",
        "['mtp']['eh_proj']['kernel']", "['mtp']['block']['moe']['router']",
    ):
        assert name in wanted
    if dtype == "float32":
        for name, want in wanted.items():
            assert relative(grads[name], want) < 1e-4, name
        return
    units = family.differences(family._norms(
        {name: grads[name] for name in wanted}, wanted
    ))
    assert len(units) == 85 - 22 + 4
    for unit, d in units.items():
        assert d <= cfg["reference"][family.limit_of(unit)], (unit, d)


# -- the streams --------------------------------------------------------------


def test_h_res_is_doubly_stochastic_and_the_gradients_are_autodiffs():
    n, c = 4, 32
    module = layers.StreamCoefficients(
        n, 20, 1e-5, jnp.float32, jnp.float32, 0.1, alpha_init=1.0
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, n * c))
    params = module.init(jax.random.PRNGKey(1), x)
    h_pre, h_post, h_res, err = module.apply(params, x)
    assert h_res.shape == (n, n, 2, 16)
    assert np.abs(np.asarray(h_res.sum(axis=0)) - 1).max() < 1e-3
    assert np.abs(np.asarray(h_res.sum(axis=1)) - 1).max() < 1e-3
    assert float(err) < 1e-3 and (np.asarray(h_res) > 0).all()
    assert float(h_res.max()) > 0.4   # not the uniform start
    # without the iterations the sums are nowhere near one
    raw = layers.StreamCoefficients(
        n, 0, 1e-5, jnp.float32, jnp.float32, 0.1, alpha_init=1.0
    ).apply(params, x)
    assert float(raw[3]) > 0.5
    # the written-out gradients of read and write against autodiff of
    # the plain sums
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 16, c))

    def plain(x, y, h_pre, h_post, h_res):
        streams = jnp.split(x, n, axis=-1)
        u = sum(h[..., None] * s for h, s in zip(h_pre, streams))
        out = jnp.concatenate([
            sum(h[..., None] * s for h, s in zip(row, streams))
            + post[..., None] * y for row, post in zip(h_res, h_post)
        ], axis=-1)
        return jnp.sum(jnp.sin(u)) + jnp.sum(jnp.cos(out) * out)

    def ours(x, y, h_pre, h_post, h_res):
        u = layers.read_streams(x, h_pre)
        out = layers.write_streams(x, y, h_post, h_res)
        return jnp.sum(jnp.sin(u)) + jnp.sum(jnp.cos(out) * out)

    operands = (x, y, h_pre, h_post, h_res)
    for got, want in zip(
        jax.grad(ours, argnums=range(5))(*operands),
        jax.grad(plain, argnums=range(5))(*operands),
    ):
        assert relative(got, want) < 1e-5


def test_a_noise_heads_gradient_is_minus_the_weighted_sum():
    """``d A_noise(g) = - sum_j lambda_gj d o_gj``."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    out = jax.random.normal(keys[0], (2, 8, 3, 5, 16))
    lam = jax.nn.sigmoid(jax.random.normal(keys[1], (2, 8, 3, 4, 1)))
    d_o = jax.random.normal(keys[2], (2, 8, 3, 4, 16))
    d_out = jax.grad(
        lambda a: jnp.sum(motif.differential(a, lam, 4)[0] * d_o)
    )(out)
    np.testing.assert_allclose(
        d_out[..., 4, :], -(lam * d_o).sum(axis=-2), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(d_out[..., :4, :], d_o, rtol=1e-6)


# -- PolyNorm in the kernels --------------------------------------------------


def expert_operands(sizes, d, m, seed=0):
    """Rows laid out as ``group_layout`` says: a group's own rows
    random, its padding ZERO, the tiles of no group NaN."""
    sizes = jnp.asarray(sizes, jnp.int32)
    tile_group, tiles_used, starts = gmm.group_layout(sizes, 1024)
    rows = tile_group.shape[0] * gmm.ROW_TILE
    x = np.full((rows, d), np.nan, np.float32)
    x[:int(tiles_used[0]) * gmm.ROW_TILE] = 0.0
    group, valid = np.zeros(rows, int), np.zeros(rows, bool)
    key = jax.random.PRNGKey(seed)
    for g, (start, size) in enumerate(
        zip(np.asarray(starts), np.asarray(sizes))
    ):
        x[start:start + size] = jax.random.normal(
            jax.random.fold_in(key, g), (size, d)
        )
        group[start:start + size], valid[start:start + size] = g, True
    keys = jax.random.split(key, 4)
    groups = sizes.shape[0]
    weights = [
        jax.random.normal(k, shape) * 0.2 for k, shape in zip(
            keys, [(groups, d, m), (groups, d, m), (groups, m, d)]
        )
    ]
    return jnp.asarray(x), weights, tile_group, tiles_used, group, valid


def test_polynorm_inside_grouped_expert_is_the_plain_form():
    """Rows, the three matrices and the four coefficients (``w`` and
    ``b`` behind them): value and gradient of the kernels' form
    against ``poly_norm`` on plain products, with every row the
    kernels may not read or write full of NaN; a group's padding rows
    are zero in and zero out although ``P(0) = b`` is not zero."""
    x, (w_gate, w_up, w_down), tile_group, tiles_used, group, valid = (
        expert_operands([300, 0, 170], d=64, m=32)
    )
    raw = (jnp.array([0.4, 0.3, 0.2]), jnp.array(0.3))
    cotangent = jnp.where(
        valid[:, None],
        jax.random.normal(jax.random.PRNGKey(9), x.shape), 0.0,
    )

    def kernels(x, w_gate, w_up, w_down, w, b):
        out = gmm.grouped_expert(
            x, w_gate, w_up, w_down, tile_group, tiles_used,
            coeffs=polynorm_coeffs(w, b, 0.5, 0.5),
        )
        return jnp.sum(jnp.where(valid[:, None], out * cotangent, 0.0)), out

    def plain(x, w_gate, w_up, w_down, w, b):
        rows = jnp.where(valid[:, None], x, 0.0)
        gate = jnp.einsum("pd,pdm->pm", rows, w_gate[group])
        up = jnp.einsum("pd,pdm->pm", rows, w_up[group])
        hidden = reference._poly_norm(
            gate, w, b, scale=0.5, clamp=0.5, poly_eps=gmm.POLYNORM_EPS
        ) * up
        out = jnp.einsum("pm,pmd->pd", hidden, w_down[group])
        return jnp.sum(jnp.where(valid[:, None], out * cotangent, 0.0)), out

    operands = (x, w_gate, w_up, w_down, *raw)
    with jax.default_matmul_precision("highest"):
        (got, out), got_grads = jax.value_and_grad(
            kernels, argnums=range(6), has_aux=True
        )(*operands)
        (want, _), want_grads = jax.value_and_grad(
            plain, argnums=range(6), has_aux=True
        )(*operands)
    assert abs(float(got) - float(want)) < 1e-3
    used = int(tiles_used[0]) * gmm.ROW_TILE
    padding = ~valid[:used]
    assert padding.any() and not np.asarray(out[:used])[padding].any()
    for at, (g, w) in enumerate(zip(got_grads, want_grads)):
        g, w = np.asarray(g), np.asarray(w)
        if at == 0:
            g, w = g[valid], w[valid]
        assert not np.isnan(g).any()
        assert relative(g, w) < 1e-5, at
    # the clamp binds: a bias past it moves nothing
    assert float(got_grads[5]) != 0.0
    far = jax.grad(kernels, argnums=5, has_aux=True)(
        x, w_gate, w_up, w_down, raw[0], jnp.array(0.7)
    )[0]
    assert float(far) == 0.0


def test_a_width_past_one_block_is_refused_not_normalised_in_parts():
    assert gmm._fit_tile(1280, 1024) == 640
    assert gmm._fit_tile(1280, 2048, whole=True) == 1280
    with pytest.raises(ValueError, match="whole width"):
        gmm._fit_tile(4096, 2048, whole=True)
    x, (w_gate, w_up, w_down), tile_group, tiles_used, _, _ = (
        expert_operands([10], d=64, m=256)
    )
    coeffs = jnp.ones((4,), jnp.float32)
    with pytest.raises(ValueError, match="whole width"):
        gmm.grouped_expert(
            x, w_gate, w_up, w_down, tile_group, tiles_used,
            tiles=(gmm.ROW_TILE, 64, 128), coeffs=coeffs,
        )
    with pytest.raises(ValueError, match="no gate"):
        gmm.grouped_expert(
            x, None, w_up, w_down, tile_group, tiles_used, coeffs=coeffs
        )


def test_an_unknown_expert_form_is_refused_by_name():
    layer = DroplessMoE(
        num_experts=4, mlp_dim=8, top_k=2, expert_form="gelu"
    )
    with pytest.raises(ValueError, match="swiglu \\| relu2 \\| polynorm"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))


# -- the shares ---------------------------------------------------------------


def test_the_expert_shares_add_up_with_the_shared_expert_once():
    """Every chip of a group routes over all 16 experts by sigmoid,
    top-4 with NO selection bias, renormalised x 2, and computes its
    4 under PolyNorm: the 4 routed parts summed, and the shared expert
    ONCE, equal the uncut reference's layer (all 16 held)."""
    top_k, held, e, d, m = 4, 4, 16, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(2), 9)
    x = jax.random.normal(keys[0], (32, d))
    p = {
        "router": jax.random.normal(keys[1], (d, e)),
        "experts_w_gate": jax.random.normal(keys[2], (e, d, m)) * d ** -0.5,
        "experts_w_in": jax.random.normal(keys[3], (e, d, m)) * d ** -0.5,
        "experts_w_out": jax.random.normal(keys[4], (e, m, d)) * m ** -0.5,
        "experts_polynorm_w": jnp.array([0.5, 0.3, 0.2]),
        "experts_polynorm_b": jnp.array(0.1),
        "shared_gate": {"kernel": jax.random.normal(keys[5], (d, m))},
        "shared_up": {"kernel": jax.random.normal(keys[6], (d, m))},
        "shared_down": {"kernel": jax.random.normal(keys[7], (m, d))},
        "shared_polynorm_w": jnp.array([0.2, 0.3, 0.5]),
        "shared_polynorm_b": jnp.array(-0.2),
    }
    poly = (("scale", 0.5), ("clamp", 0.5), ("poly_eps", gmm.POLYNORM_EPS))
    coeffs = polynorm_coeffs(
        p["experts_polynorm_w"], p["experts_polynorm_b"], 0.5, 0.5
    )

    def share(i):
        mine = [
            jax.lax.dynamic_slice_in_dim(p[name], held * i, held)
            for name in ("experts_w_gate", "experts_w_in", "experts_w_out")
        ]
        shift = held - held * i
        out, stats = dropless_moe(
            x, jnp.roll(p["router"], shift, axis=1), *mine, top_k,
            jnp.float32, held=(held, held), score="sigmoid",
            renormalise=True, scale=2.0, polynorm=coeffs,
        )
        return out, jnp.roll(stats["counts"], -shift), stats["held_rows"]

    with jax.default_matmul_precision("highest"):
        outs, counts, rows = jax.lax.map(share, jnp.arange(e // held))
        shared = reference._glu(
            x, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
            p["shared_down"]["kernel"], p["shared_polynorm_w"],
            p["shared_polynorm_b"], poly,
        )
        whole, (want_counts, _) = reference._experts(
            x, p, top_k=top_k, first=0, scale=2.0, poly=poly
        )
    np.testing.assert_allclose(outs.sum(axis=0) + shared, whole, atol=5e-5)
    for chip in counts:
        assert np.array_equal(chip, want_counts)
    assert float(rows.sum()) == 32 * top_k


def test_the_head_group_shares_add_up_to_the_whole_attention():
    """A host's chips hold whole kv groups (a latent kv head with its
    4 signal and 1 noise query heads, ``W_lambda``, ``W_gate`` and
    ``W_o`` cut with them); both latents, their norms and the one rope
    key are on every chip.  The shares' outputs summed are the uncut
    attention's, window and full."""
    whole = motif.MotifConfig.tiny(
        num_heads=20, num_kv_heads=4, num_noise_heads=4,
        dtype=jnp.float32,
    )
    part = motif.MotifConfig.tiny(
        num_heads=10, num_kv_heads=2, num_noise_heads=2,
        dtype=jnp.float32,
    )
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 64, whole.hidden_dim))
    d, dv = whole.qk_head_dim, whole.v_head_dim
    nope = whole.qk_nope_dim

    def columns(kernel, width, share, groups=2):
        """A share's column blocks of ``width`` of a kernel laid out
        group by group."""
        per = groups * width
        return kernel[..., share * per:(share + 1) * per]

    for window, rope in ((24, whole.swa_rope), (None, whole.full_rope)):
        module = motif.GdlaAttention(whole, window, rope)
        params = module.init(jax.random.PRNGKey(1), u)["params"]
        want, _ = module.apply({"params": params}, u)
        got = 0.0
        for share in range(2):
            mine = jax.tree.map(lambda a: a, params)
            mine["q_up"] = {"kernel": columns(
                params["q_up"]["kernel"], 5 * d, share
            )}
            mine["kv_up"] = {"kernel": columns(
                params["kv_up"]["kernel"], nope + dv, share
            )}
            mine["lambda_proj"] = columns(params["lambda_proj"], 4, share)
            mine["gate_proj"] = {"kernel": columns(
                params["gate_proj"]["kernel"], 4 * dv, share
            )}
            rows = 2 * 4 * dv
            mine["o_proj"] = {"kernel": params["o_proj"]["kernel"][
                share * rows:(share + 1) * rows
            ]}
            out, _ = motif.GdlaAttention(part, window, rope).apply(
                {"params": mine}, u
            )
            got = got + out
        np.testing.assert_allclose(got, want, atol=2e-5)


# -- the prediction layer -----------------------------------------------------


def test_the_prediction_loss_ignores_the_last_position():
    cfg, model, loss_fn, params, batch = toy(seq=64)
    _, aux = jax.jit(loss_fn)(params, batch)
    _, predicted = jax.jit(lambda p, x, y: model.apply(
        {"params": p}, x, next_tokens=y
    ))(params, batch["x"], batch["y"])
    logp = jax.nn.log_softmax(predicted, axis=-1)
    # position t of the prediction layer is asked for token t + 2
    nll = -jnp.take_along_axis(
        logp[:, :-1], batch["y"][:, 1:, None], axis=-1
    )[..., 0]
    assert abs(float(aux["mtp.loss"]) - float(nll.mean())) < 1e-5
    # counting the last position (against any token) is another number
    last = -logp[:, -1, 0]
    counted = (nll.sum() + last.sum()) / batch["y"].size
    assert abs(float(aux["mtp.loss"]) - float(counted)) > 1e-4
