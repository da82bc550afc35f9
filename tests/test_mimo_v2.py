"""The window-with-a-sink family (``model_type: "mimo_v2"``) through
the repo's blocks against the plain float32 reference
(``benchmarks/models/mimo_v2_reference.py``): loss, logits and every
leaf's gradient, the sinks' included; the flash kernels with a sink
against the plain form; the expert shares (no shared expert) and the
head shares each add up to the whole; a token with no held expert gets
exactly nothing; the bias's rule in the step; the counters, the cut
configuration's arithmetic and the harness's rehearsal."""

import os
import re
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import loader  # noqa: E402  (the benchmark's own)

from dlrover_tpu.common.aot_cache import SOURCE_PACKAGES  # noqa: E402
from dlrover_tpu.models import layers  # noqa: E402
from dlrover_tpu.ops.attention import xla_window_attention  # noqa: E402
from dlrover_tpu.ops.flash_attention import (  # noqa: E402
    block_schedule,
    flash_attention,
)
from dlrover_tpu.parallel.moe import dropless_moe  # noqa: E402
from dlrover_tpu.telemetry.events import read_events  # noqa: E402
from dlrover_tpu.telemetry.schema import validate_event  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import (  # noqa: E402
    STATE_UPDATES,
    ElasticTrainer,
)

reference = loader.load_module("models", "mimo_v2_reference")
CONFIGS = os.path.join(REPO, "benchmarks", "configs")
CUT = loader.load_json(os.path.join(CONFIGS, "mimo_v2_5_cut.json"))


def toy_cfg(**recipe):
    """The toy configuration's file (pattern ``[0, 1, 1, 0]``, 4 query
    heads over 1 | 2 kv heads of 48 | 32), in float32 unless told."""
    cfg = loader.load_json(os.path.join(CONFIGS, "toy_mimo_v2.json"))
    cfg["recipe"] = {**cfg["recipe"], **dict(
        param_dtype="float32", compute_dtype="float32",
    ), **recipe}
    return cfg


def toy(seq=128, **recipe):
    """``(family, cfg, model, loss_fn, params, batch)``: matrices
    scaled up so that routing is decided and the scores spread; the
    sinks stay the normal of 1 they are seeded as."""
    family = loader.load_module("models", "mimo_v2")
    cfg = toy_cfg(**recipe)
    model, _, loss_fn = family.build(cfg)
    # (jitted: an eager init would run the interpreted kernels op by op)
    params = jax.jit(lambda key: model.init_params(key, seq_len=seq))(
        jax.random.PRNGKey(7)
    )
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * (6.0 if x.ndim >= 2 else 1.0), params,
    )
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, seq + 1), 0, 512)
    return family, cfg, model, loss_fn, params, {
        "x": tokens[:, :-1], "y": tokens[:, 1:],
    }


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


# -- the family against the reference -----------------------------------------


@pytest.fixture(scope="module")
def both():
    """Loss, counters and EVERY leaf's gradient of the float32 toy
    through the flash kernels (a window of 48 over tiles of 128 with a
    sink, groups of 4 and 2, heads of 48 | 32) and the block's remat,
    and the plain reference's: one program each."""
    _, cfg, _, loss_fn, params, batch = toy(attention="flash", remat=True)
    (loss, aux), got = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True)
    )(params, batch)
    (want_loss, counts), want = jax.jit(jax.value_and_grad(
        lambda p, x, y: reference.loss_and_counts(p, x, y, cfg),
        has_aux=True,
    ))(params, batch["x"], batch["y"])
    return params, (loss, aux, got), (want_loss, counts, want)


def test_float32_loss_and_counters_equal_the_reference(both):
    params, (loss, aux, _), (want, counts, _) = both
    assert abs(float(loss) - float(want)) < 1e-5
    # the counter is the reference's count of what reached experts
    # 4..7 of 16, over three layers' 2 x 128 x 4 assignments each
    assert float(aux["moe.held_rows_share"]) == pytest.approx(
        float(counts[:, 4:8].sum()) / (3 * 2 * 128 * 4)
    )
    assert set(aux) == {
        "moe.held_rows_share", "moe.held_tiles_share", "moe.bias_abs_max",
        "attn.sink_mass_mean", "attn.sink_abs_max",
        "attn.window_tiles_share", STATE_UPDATES,
    }
    sinks = [params[f"block_{i}"]["attn"]["sink"] for i in (1, 2)]
    assert float(aux["attn.sink_abs_max"]) == pytest.approx(
        float(jnp.abs(jnp.stack(sinks)).max())
    )
    assert 0.0 < float(aux["attn.sink_mass_mean"]) < 0.5
    # the bias's rule, a layer a row, on the reference's own counts
    for j, i in enumerate((1, 2, 3)):
        delta = aux[STATE_UPDATES][f"block_{i}"]["moe"]["select_bias"]
        assert np.array_equal(
            delta, reference.base.bias_deltas(counts, 0.001)[j]
        )


def test_float32_gradients_equal_the_reference_leaf_by_leaf(both):
    """Every leaf of ``jax.grad`` of the training loss, the sinks and
    the fused projection among them, to 1e-4 of the leaf's largest
    entry."""
    _, (_, _, got), (_, _, want) = both
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    # 4 leaves of attention and norms a block and a sink in the two
    # window blocks, 3 dense, 5 of a sparse layer, embedding, final
    # norm, head
    assert len(flat_got) == len(flat_want) == 4 * 4 + 2 + 3 + 3 * 5 + 3
    names = [jax.tree_util.keystr(path) for path, _ in flat_got]
    assert sum("['sink']" in name for name in names) == 2
    for name, (_, g), w in zip(names, flat_got, flat_want):
        if "select_bias" in name:
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        assert np.abs(np.asarray(w)).max() > 0, name
        assert relative(g, w) < 1e-4, name


def test_each_layer_takes_its_heads_rope_and_sink_from_its_kind():
    """The tree by shape: one fused projection a layer, ``(H + G) x 48
    + G x 32`` columns with ``G`` 1 in a full layer and 2 in a window
    layer, a float32 sink a head in the window layers alone."""
    family = loader.load_module("models", "mimo_v2")
    model, _, _ = family.build(toy_cfg())
    params = jax.eval_shape(
        lambda key: model.init_params(key, seq_len=128),
        jax.random.PRNGKey(0),
    )
    attn = {i: params[f"block_{i}"]["attn"] for i in range(4)}
    assert attn[0]["qkv_proj"]["kernel"].shape == (128, 5 * 48 + 32)
    assert jax.tree.map(lambda x: x.shape, attn[1]) == {
        "qkv_proj": {"kernel": (128, 6 * 48 + 2 * 32)},
        "o_proj": {"kernel": (4 * 32, 128)}, "sink": (4,),
    }
    assert attn[1]["sink"].dtype == jnp.float32
    assert "sink" not in attn[0] and "sink" not in attn[3]
    assert model.config.full_rope.theta == 1e7
    assert model.config.swa_rope.theta == 1e4
    # 16 of 48 lanes rotate in both kinds
    for rule in (model.config.full_rope, model.config.swa_rope):
        assert len(rule.inv_freq(48)) == 8
    x = jnp.arange(2 * 48, dtype=jnp.float32).reshape(1, 2, 1, 48)
    cos, sin = model.config.swa_rope.tables(2, 48)
    turned = layers.rotate_partial(x, cos, sin)
    assert np.array_equal(turned[..., 16:], x[..., 16:])
    assert np.array_equal(turned[:, 0], x[:, 0])
    assert not np.allclose(turned[:, 1, :, :16], x[:, 1, :, :16])


# -- the kernels with a sink --------------------------------------------------


def sink_operands(group, d=24, dv=16, s=128, b=2, kv=1):
    keys = jax.random.split(jax.random.PRNGKey(group), 5)
    h = kv * group
    return (
        jax.random.normal(keys[0], (b, s, h, d)),
        jax.random.normal(keys[1], (b, s, kv, d)),
        jax.random.normal(keys[2], (b, s, kv, dv)),
        0.1 * jax.random.normal(keys[3], (h,)),
        jax.random.normal(keys[4], (b, s, h, dv)),
    )


@pytest.mark.parametrize("window, group, level", [
    (16, 1, -3.0), (32, 8, 0.0), (48, 16, 3.0), (None, 8, 3.0),
])
def test_the_flash_kernels_with_a_sink_are_the_plain_form(
    window, group, level
):
    """Forward, ``lse``, ``dq``, ``dk``, ``dv`` and ``d sink`` in
    interpret mode against the plain form with the sink as one more
    column: a window smaller than, equal to and larger than the tile
    of 32 and none, groups of 1, 8 and 16, ``d_qk != d_v``, sinks
    around -3, 0 and +3 (a sink above every score of a row of few keys
    exercises ``m``)."""
    q, k, v, sink, w = sink_operands(group)
    sink = sink + level

    def value_and_grads(attend):
        def loss(q, k, v, sink):
            out, lse = attend(q, k, v, sink)
            return (out * w).sum(), (out, lse)

        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True
        ))(q, k, v, sink)

    (_, (out, lse)), grads = value_and_grads(
        lambda q, k, v, sink: flash_attention(
            q, k, v, block_q=32, window=window, sink=sink,
            return_lse=True,
        )
    )
    (_, (want, want_lse)), wanted = value_and_grads(
        lambda q, k, v, sink: xla_window_attention(
            q, k, v, window, jnp.float32, sink=sink, return_lse=True
        )
    )
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5, rtol=2e-5)
    assert grads[3].dtype == jnp.float32 and grads[3].shape == sink.shape
    for name, g, wanted_g in zip(("dq", "dk", "dv", "dsink"), grads, wanted):
        assert relative(g, wanted_g) < 1e-4, name
    # the sink takes its share: without it the rows differ
    plain = flash_attention(q, k, v, block_q=32, window=window)
    assert not np.allclose(plain, out, atol=1e-3)


def test_a_sink_is_refused_where_no_form_takes_it():
    q, k, v, sink, _ = sink_operands(2)
    with pytest.raises(ValueError, match="sink"):
        layers.attention("ring", q, k, v, sink=sink)
    with pytest.raises(ValueError, match="sink"):
        flash_attention(q, k, v, sink=sink[:1])
    with pytest.raises(ValueError, match="lse"):
        flash_attention(q, k, v, return_lse=True)
    out, mass = layers.attention("xla", q, k, v, window=32, sink=sink)
    assert out.shape == (2, 128, 2, 16) and mass.shape == (2,)


# -- the shares ---------------------------------------------------------------


def layer_operands(t, d, m, e, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (
        jax.random.normal(keys[0], (t, d)),
        jax.random.normal(keys[1], (d, e)),
        jax.random.normal(keys[2], (e, d, m)) * d ** -0.5,
        jax.random.normal(keys[3], (e, d, m)) * d ** -0.5,
        jax.random.normal(keys[4], (e, m, d)) * m ** -0.5,
    )


def test_the_32_expert_shares_add_up_to_the_uncut_reference():
    """Every chip of the cut's group routes over all 256 experts by
    sigmoid + bias, top-8, and computes its 8: the 32 parts summed
    equal the UNCUT reference's layer (all 256 held), with no shared
    expert to count once.  One traced share serves all 32 (32 traces
    of the interpreted kernels cost half a minute to compile): chip
    ``i`` sees the experts renumbered so that its own ``[8 i, 8 i +
    8)`` stand at ``[8, 16)``, which moves no choice and no weight."""
    top_k, held, e = 8, 8, 256
    x, router, w_gate, w_up, w_down = layer_operands(
        t=32, d=16, m=8, e=e, seed=2
    )
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(3), (e,))

    def share(i):
        shift = held - held * i
        mine = [
            jax.lax.dynamic_slice_in_dim(w, held * i, held)
            for w in (w_gate, w_up, w_down)
        ]
        out, stats = dropless_moe(
            x, jnp.roll(router, shift, axis=1), *mine, top_k,
            jnp.float32, held=(held, held), score="sigmoid",
            select_bias=jnp.roll(bias, shift), renormalise=True,
            scale=1.0,
        )
        return out, jnp.roll(stats["counts"], -shift), stats["held_rows"]

    outs, counts, rows = jax.lax.map(share, jnp.arange(e // held))
    whole, want_counts = reference._experts(x, {
        "router": router, "select_bias": bias, "experts_w_gate": w_gate,
        "experts_w_in": w_up, "experts_w_out": w_down,
    }, top_k=top_k, first=0, scale=1.0)
    np.testing.assert_allclose(outs.sum(axis=0), whole, atol=2e-5)
    for chip in counts:
        assert np.array_equal(chip, want_counts)
    assert float(rows.sum()) == 32 * top_k
    assert rows.max() < 32 * top_k


def test_the_four_head_shares_add_up_to_the_whole_attention():
    """A host's four chips hold 4 of 16 query heads each with their kv
    heads (kv-head-major: a full layer's 4 kv heads one a chip, a
    window layer's 8 two a chip): the shares' ``o W_o`` parts summed
    are the uncut reference's attention, sinks and all."""
    seq, hidden, d, dv, heads = 64, 32, 12, 8, 16
    for kv, window, sinked in ((4, None, False), (8, 16, True)):
        keys = jax.random.split(jax.random.PRNGKey(kv), 4)
        p = {
            "qkv_proj": {"kernel": jax.random.normal(
                keys[0], (hidden, (heads + kv) * d + kv * dv)
            ) * 0.3},
            "o_proj": {"kernel": jax.random.normal(
                keys[1], (heads * dv, hidden)
            )},
            "sink": jax.random.normal(keys[2], (heads,)),
        }
        x = jax.random.normal(keys[3], (seq, hidden))
        kwargs = dict(
            d=d, dv=dv, window=window, theta=1e4, rotated=4,
            value_scale=0.707, sinked=sinked,
        )
        attend = jax.jit(
            reference._attention, static_argnames=("heads", "kv", *kwargs)
        )
        whole = attend(x, p, heads=heads, kv=kv, **kwargs)
        q, k, v = jnp.split(
            p["qkv_proj"]["kernel"], (heads * d, (heads + kv) * d), axis=-1
        )

        def share(columns, i, width):
            return columns.reshape(hidden, 4, -1)[:, i]

        parts = [
            attend(x, {
                "qkv_proj": {"kernel": jnp.concatenate(
                    [share(t, i, 4) for t in (q, k, v)], axis=-1
                )},
                "o_proj": {"kernel": p["o_proj"]["kernel"].reshape(
                    4, -1, hidden
                )[i]},
                "sink": p["sink"].reshape(4, -1)[i],
            }, heads=heads // 4, kv=kv // 4, **kwargs)
            for i in range(4)
        ]
        np.testing.assert_allclose(sum(parts), whole, atol=2e-5)


def test_a_token_with_no_held_expert_gets_exactly_nothing():
    """No shared expert: a token none of whose top-k is held gets
    exactly zero from the layer, its row carries no gradient into the
    held experts, and the layer's input none from it."""
    top_k, e, held = 2, 16, (4, 4)
    x, router, w_gate, w_up, w_down = layer_operands(
        t=32, d=16, m=8, e=e, seed=5
    )
    mine = tuple(w[4:8] for w in (w_gate, w_up, w_down))

    def layer(x, *weights):
        return dropless_moe(
            x, router, *weights, top_k, jnp.float32, held=held,
            score="sigmoid", select_bias=jnp.zeros((e,)),
            renormalise=True, scale=1.0,
        )

    out, _ = jax.jit(layer)(x, *mine)
    _, ids = jax.lax.top_k(jax.nn.sigmoid(x @ router), top_k)
    unheld = np.asarray(((ids < 4) | (ids >= 8)).all(axis=1))
    assert unheld.any() and not unheld.all()
    assert not np.asarray(out)[unheld].any()
    assert np.asarray(out)[~unheld].any(axis=1).all()
    # a cotangent on the unheld rows alone reaches no expert and no row
    cotangent = jnp.where(unheld[:, None], 1.0, 0.0)
    grads = jax.jit(jax.grad(
        lambda x, *w: (layer(x, *w)[0] * cotangent).sum(),
        argnums=(0, 1, 2, 3),
    ))(x, *mine)
    for g in grads:
        assert not np.asarray(g).any()


# -- the counters ---------------------------------------------------------------


def test_the_counters_ride_on_the_train_step_event(tmp_path, monkeypatch):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("DLROVER_EVENT_LOG", path)
    monkeypatch.setenv(
        "DLROVER_METRICS_FILE", str(tmp_path / "metrics.json")
    )
    trainer = ElasticTrainer(4, 4, dp_size=1)
    trainer.report_step({
        "loss": jnp.float32(1.5), "grad_norm": jnp.float32(0.1),
        "attn.sink_mass_mean": jnp.float32(0.0126),
        "attn.sink_abs_max": jnp.float32(2.5),
    })
    (event,) = [e for e in read_events(path) if e["type"] == "train_step"]
    assert event["attn.sink_mass_mean"] == pytest.approx(0.0126)
    assert event["attn.sink_abs_max"] == pytest.approx(2.5)
    assert not validate_event(event)


# -- the cut configuration and the benchmark ----------------------------------


def test_the_cut_keeps_every_published_width_and_counts_as_the_issue_says():
    family = loader.load_module("models", "mimo_v2")
    model, _, _ = family.build(CUT)
    cfg = model.config
    assert (
        cfg.hidden_dim, cfg.head_dim, cfg.v_head_dim, cfg.sliding_window,
        cfg.dense_dim, cfg.expert_dim, cfg.num_experts, cfg.top_k,
        cfg.rms_eps, cfg.value_scale, cfg.routed_scale,
    ) == (4096, 192, 128, 128, 16384, 2048, 256, 8, 1e-5, 0.707, 1.0)
    assert len(cfg.full_rope.inv_freq(192)) == 32
    assert (cfg.full_rope.theta, cfg.swa_rope.theta) == (1e7, 1e4)
    assert cfg.experts_held == (0, 8) and cfg.sink_layers == (1, 2, 3, 4, 5)
    assert CUT["reduced"] == list(CUT["published"])
    params = jax.eval_shape(
        lambda key: model.init_params(key, seq_len=128),
        jax.random.PRNGKey(0),
    )
    sizes = jax.tree.map(lambda x: int(np.prod(x.shape)), params)
    count = lambda tree: sum(jax.tree.leaves(tree))  # noqa: E731
    assert count(sizes["block_0"]["attn"]) == 22_282_240
    assert count(sizes["block_1"]["attn"]) == 23_592_960 + 16
    assert count(sizes["block_0"]["mlp"]) == 201_326_592
    assert sizes["block_6"]["moe"]["experts_w_in"] == 8 * 4096 * 2048
    assert sizes["block_6"]["moe"]["router"] == 4096 * 256
    assert "shared_up" not in sizes["block_6"]["moe"]
    assert 1.733e9 < count(sizes) < 1.735e9
    # the walk of a window narrower than the kernels' chunk
    walk = block_schedule(8192, 1024, 1024, True, 128)
    assert CUT["window_walk"]["computed"] == walk["computed"]
    assert walk["computed"] / CUT["window_walk"]["required"] == (
        pytest.approx(3.968, abs=1e-3)
    )


def test_the_benchmark_gains_one_configuration_one_cell_four_readers():
    spec = loader.load_json(os.path.join(REPO, "BENCHMARK.json"))
    # (by name: later PRs append their own entries behind these)
    (config,) = [c for c in spec["configs"] if c["name"] == "mimo_v2_5_cut"]
    assert config["reduced"] == CUT["reduced"]
    (cell,) = [
        w for w in spec["workloads"] if w["name"] == "mimo_v2_5_steady"
    ]
    assert cell == {
        **cell, "config": "mimo_v2_5_cut", "traffic": "steady_8k",
        "chips": 1,
    }
    mine = [
        m["name"] for m in spec["per_layer"]
        if m.get("workloads") == ["mimo_v2_5_steady"]
    ]
    assert mine == [
        "swa.sink_roofline_pct", "attn.sink_ms_per_step",
        "attn.sink_mass_mean", "attn.qkv_ms_per_step",
    ]
    for name in mine:
        reader = loader.load_module("layer_metrics", name)
        assert reader.NAME == name and reader.MOVES == "tokens_per_s"
    assert "models" in SOURCE_PACKAGES and "ops" in SOURCE_PACKAGES


def test_the_compared_leaves_are_the_issues():
    """``qkv_proj``, ``o_proj`` and the norms of every block, EVERY
    window layer's sink, every router, the last block's held
    experts, each kind under its own limit."""
    family = loader.load_module("models", "mimo_v2")
    model, _, _ = family.build(CUT)
    params = jax.eval_shape(
        lambda key: model.init_params(key, seq_len=128),
        jax.random.PRNGKey(0),
    )
    pick = family.compared(CUT)
    leaves = {
        name for name in (
            jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_leaves_with_path(params)
        ) if pick(name)
    }
    for block in range(7):
        for name in ("qkv_proj", "o_proj"):
            assert f"['block_{block}']['attn']['{name}']['kernel']" in leaves
        for norm in ("ln_attn", "ln_mlp"):
            assert f"['block_{block}']['{norm}']['scale']" in leaves
        assert (f"['block_{block}']['attn']['sink']" in leaves) == (
            1 <= block <= 5
        )
        assert (f"['block_{block}']['moe']['router']" in leaves) == (
            block >= 1
        )
        for name in ("experts_w_gate", "experts_w_in", "experts_w_out"):
            assert (f"['block_{block}']['moe']['{name}']" in leaves) == (
                block == 6
            )
    # (and the final norm)
    assert len(leaves) == 7 * 4 + 5 + 6 + 3 + 1
    routed = family.routed_in(CUT)
    held_to = {leaf: family.limit_of(leaf, routed) for leaf in leaves}
    assert set(held_to.values()) == {
        "gradient_tolerance", "routed_gradient_tolerance",
        "sink_gradient_tolerance",
    }
    assert held_to["['block_1']['attn']['sink']"] == (
        "sink_gradient_tolerance"
    )
    # no shared expert: a sparse block's second norm learns through
    # the router and the held rows alone, and is held to their limit
    assert held_to["['block_0']['ln_mlp']['scale']"] == "gradient_tolerance"
    assert held_to["['block_1']['ln_attn']['scale']"] == "gradient_tolerance"
    for block in range(1, 7):
        assert held_to[f"['block_{block}']['ln_mlp']['scale']"] == (
            "routed_gradient_tolerance"
        )
    assert sum(
        limit == "routed_gradient_tolerance" for limit in held_to.values()
    ) == 6 + 6 + 3


@pytest.mark.parametrize("control", ["no_sink_forward", "no_sink_gradient"])
def test_a_control_of_the_sink_reaches_every_sink_and_nothing_else(control):
    """The two controls the limits of the sink are shown against on
    the chip (``recipe.control``): the loss sees sinks that take
    nothing of any softmax, or sinks whose gradient is stopped; every
    other leaf is as it was."""
    family = loader.load_module("models", "mimo_v2")

    def loss_fn(params, batch):
        attn = params["block_1"]["attn"]
        return (
            jnp.exp(attn["sink"]).sum() + (attn["o_proj"] ** 2).sum(),
            {"seen": attn["sink"]},
        )

    params = {"block_1": {"attn": {
        "sink": jnp.array([0.5, -1.0]), "o_proj": jnp.array([2.0]),
    }}}
    controlled = family._sink_control(loss_fn, control)
    assert controlled.has_aux
    (loss, aux), grads = jax.value_and_grad(controlled, has_aux=True)(
        params, None
    )
    attn = grads["block_1"]["attn"]
    assert not np.asarray(attn["sink"]).any()
    assert float(attn["o_proj"][0]) == 4.0
    if control == "no_sink_forward":
        assert float(loss) == 4.0 and float(aux["seen"].max()) < -9e29
    else:
        assert np.array_equal(aux["seen"], params["block_1"]["attn"]["sink"])


@pytest.mark.parametrize("gradients, bias, inside", [
    ({"['attn']['qkv_proj']": 0.1, "['moe']['router']": 0.3,
      "['attn']['sink']": 0.3}, 0.1, True),
    ({"['attn']['qkv_proj']": 0.1, "['attn']['sink']": 0.6}, 0.1, False),
    ({"['attn']['qkv_proj']": 0.3, "['attn']['sink']": 0.3}, 0.1, False),
    ({"['attn']['qkv_proj']": 0.1, "['moe']['router']": 0.6}, 0.1, False),
    ({"['block_0']['ln_mlp']['scale']": 0.3}, 0.1, False),
    ({"['block_1']['ln_mlp']['scale']": 0.3}, 0.1, True),
    ({"['attn']['qkv_proj']": 0.1, "['attn']['sink']": 0.1}, 0.2, False),
    ({"['attn']['sink']": float("nan"), "['attn']['o_proj']": 0.1}, 0.0,
     False),
])
def test_every_leaf_and_the_bias_are_judged_by_their_own_limit(
    monkeypatch, gradients, bias, inside
):
    family = loader.load_module("models", "mimo_v2")
    monkeypatch.setattr(family, "comparisons", lambda *a: {
        "loss": 1.5, "gradients": gradients, "bias": bias,
    })
    cfg = {"moe_layer_freq": [0, 1], "reference": {
        "gradient_tolerance": 0.2, "routed_gradient_tolerance": 0.5,
        "sink_gradient_tolerance": 0.5, "bias_update_tolerance": 0.15,
    }}
    got = family.reference_loss(None, None, None, cfg)
    assert got == (1.5 if inside else float("inf"))


def test_the_harness_rehearses_the_family_on_the_cpu(tmp_path, checkout):
    """``benchmarks/run.py`` end to end on the toy configuration:
    ``tpurun`` -> the worker -> the ``has_aux`` step with its
    ``state_updates`` -> the reference's loss, gradients (sinks
    included) and bias rule -> the readers; exit code 3 (a rehearsal,
    never a result), ``correct`` true."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        # (from a checkout of its own: conftest.py, ROADMAP B7)
        [sys.executable, os.path.join(checkout, "benchmarks", "run.py"),
         "--cells", os.path.join(REPO, "benchmarks", "rehearsal_mimo_v2.json"),
         "--workload", "toy_mimo_v2_steady", "--seed", "5000000011",
         "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 3, done.stdout[-3000:] + done.stderr[-3000:]
    assert '"correct": true' in done.stdout
    assert "attn.sink_mass_mean" in done.stdout
    # the step applied the bias's rule: the counter on the parameters
    # a step STARTS from has moved, by no more than u a step
    moved, most = re.search(
        r"router bias: largest \|b\| ([0-9.]+) entering step \d+; .* at "
        r"most ([0-9.]+) by then", done.stdout,
    ).groups()
    assert 0.0 < float(moved) <= float(most)
