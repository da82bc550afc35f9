"""The window-and-full-attention mixture-of-experts family through the
repo's blocks against the plain float32 reference
(``benchmarks/models/laguna_reference.py``): loss, logits and every
leaf's gradient; the two kinds of layer (head counts, window, rope
rule) and the per-head gate against their own claims; the held-experts
layer with softmax scores (the shares of all chips add up to the whole
layer); the cut configuration's arithmetic; the counter and the scopes
the benchmark's readers join on; the harness's rehearsal."""

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

from dlrover_tpu.models import laguna  # noqa: E402
from dlrover_tpu.models.laguna import (  # noqa: E402
    FULL_ROPE,
    SLIDING_ROPE,
    Laguna,
    LagunaConfig,
    make_laguna_loss,
    window_tiles_share,
)
from dlrover_tpu.models.layers import yarn_correction_range  # noqa: E402
from dlrover_tpu.ops.attention import xla_window_attention  # noqa: E402
from dlrover_tpu.optim import adamw_bf16  # noqa: E402
from dlrover_tpu.parallel.moe import DroplessMoE, dropless_moe  # noqa: E402
from dlrover_tpu.telemetry.events import read_events  # noqa: E402
from dlrover_tpu.telemetry.schema import validate_event  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import (  # noqa: E402
    ElasticTrainer,
    TrainState,
    make_train_step,
)

reference = loader.load_module("models", "laguna_reference")
CONFIGS = os.path.join(REPO, "benchmarks", "configs")


def toy_cfg(**recipe):
    """The toy configuration's file, in float32 unless told."""
    cfg = loader.load_json(os.path.join(CONFIGS, "toy_laguna.json"))
    cfg["recipe"] = {**cfg["recipe"], **dict(
        param_dtype="float32", compute_dtype="float32",
    ), **recipe}
    return cfg


def toy(seq=128, **recipe):
    """``(family, cfg, model, loss_fn, params, batch)``: weights scaled
    up so that routing is decided, the gates leave 0.5 and the experts'
    outputs matter."""
    family = loader.load_module("models", "laguna")
    cfg = toy_cfg(**recipe)
    model, _, loss_fn = family.build(cfg)
    params = model.init_params(jax.random.PRNGKey(7), seq_len=seq)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 if "scale" in str(path[-1]) else 6.0),
        params,
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
    loss, aux = loss_fn(params, batch)
    want = reference.loss(params, batch["x"], batch["y"], cfg)
    assert abs(float(loss) - want) < 1e-5
    logits = model.apply({"params": params}, batch["x"])
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
    got = jax.grad(lambda p: loss_fn(p, batch)[0])(params)
    want = jax.grad(lambda p: reference.loss_and_counts(
        p, batch["x"], batch["y"], cfg
    )[0])(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    # 7 leaves of attention and norms a block, 3 dense, 7 of a sparse
    # layer, embedding, final norm, head
    assert len(flat_got) == len(flat_want) == 3 * 7 + 3 + 2 * 7 + 3
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        assert np.abs(np.asarray(w)).max() > 0, name
        assert relative(g, w) < 1e-4, name


@pytest.mark.parametrize("attention", ["flash", "xla"])
def test_a_rematted_block_keeps_what_its_flash_backward_reads(
    attention, remat_keeps_what_flash_reads,
    remat_with_xla_attention_is_the_parents,
):
    """One forward kernel a layer (a full one, two sliding), none of
    them run again for the backward; loss and gradients the parent
    policy's bit for bit.  With XLA attention nothing is named and the
    program is the parent's."""
    _, cfg, _, loss_fn, params, batch = toy(attention=attention, remat=True)

    def loss(p):
        return loss_fn(p, batch)[0]

    if attention == "xla":
        remat_with_xla_attention_is_the_parents(loss, params)
    else:
        remat_keeps_what_flash_reads(
            loss, params, len(cfg["layer_types"])
        )


def test_bfloat16_loss_is_within_bf16_rounding_of_the_reference():
    _, cfg, _, loss_fn, params, batch = toy(
        param_dtype="bfloat16", compute_dtype="bfloat16",
    )
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    loss, _ = loss_fn(params, batch)
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
    parts = [
        dropless_moe(
            x, router, w_gate[lo:lo + held], w_up[lo:lo + held],
            w_down[lo:lo + held], top_k, jnp.float32, held=(lo, held),
            score="softmax", renormalise=True, scale=2.5,
        ) for lo in range(0, e, held)
    ]
    np.testing.assert_allclose(
        sum(out for out, _ in parts), whole_layer(operands, top_k),
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
    variables = layer.init(jax.random.PRNGKey(0), x[None])
    p = variables["params"]
    assert "select_bias" not in p and p["router"].shape == (d, e)
    out, _ = layer.apply(variables, x[None])
    routed, _ = dropless_moe(
        x, p["router"], p["experts_w_gate"], p["experts_w_in"],
        p["experts_w_out"], top_k, jnp.float32, held=(held, held),
        score="softmax", renormalise=True, scale=2.5,
    )
    shared = (
        nn.silu(x @ p["shared_gate"]["kernel"])
        * (x @ p["shared_up"]["kernel"])
    ) @ p["shared_down"]["kernel"]
    np.testing.assert_allclose(out[0], routed + shared, atol=1e-5)


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
