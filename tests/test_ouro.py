"""The looped family (a block stack that runs ``R`` times over the same
weights, an exit gate after every pass, the expected-exit loss through
one weighted chunked head) against the plain float32 reference
(``benchmarks/models/ouro_reference.py``): loss and every leaf's
gradient; the TIE (the reference with ``R x L`` independent copies of
the blocks gives per-copy gradients whose sum over the passes is the
system's gradient for that block), which the loss cannot see and a
dropped contribution fails; one pass is the plain model; the weighted
head against the unweighted one, whose lowering is pinned; the cut
configuration's arithmetic; counters, scopes and the benchmark's
readers; the harness's rehearsal."""

import functools
import hashlib
import json
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

from dlrover_tpu.models.losses import (  # noqa: E402
    chunked_cross_entropy,
    weighted_chunked_cross_entropy,
)
from dlrover_tpu.models.ouro import Ouro, exit_distribution  # noqa: E402
from dlrover_tpu.optim import adamw_bf16  # noqa: E402
from dlrover_tpu.telemetry.events import read_events  # noqa: E402
from dlrover_tpu.telemetry.schema import validate_event  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import (  # noqa: E402
    ElasticTrainer,
    TrainState,
    make_train_step,
)

reference = loader.load_module("models", "ouro_reference")
CONFIGS = os.path.join(REPO, "benchmarks", "configs")
COUNTERS = {
    "loop.expected_exit", "loop.exit_entropy", "loop.nll_first",
    "loop.nll_last",
}


def toy_cfg(passes=3, **recipe):
    """The toy configuration's file, in float32 unless told."""
    cfg = loader.load_json(os.path.join(CONFIGS, "toy_ouro.json"))
    cfg["total_ut_steps"] = passes
    cfg["recipe"] = {**cfg["recipe"], **dict(
        param_dtype="float32", compute_dtype="float32",
    ), **recipe}
    return cfg


@functools.cache
def toy_weights(seq, passes, param_dtype):
    """The toy's weights, made ONCE a module: the initialisation reads
    neither the attention, nor remat, nor the compute dtype."""
    family = loader.load_module("models", "ouro")
    model, _, _ = family.build(toy_cfg(passes, param_dtype=param_dtype))
    # (jitted: an eager init runs the whole model op by op)
    params = jax.jit(lambda key: model.init_params(key, seq_len=seq))(
        jax.random.PRNGKey(7)
    )
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 if "scale" in str(path[-1]) else 4.0),
        params,
    )
    if passes > 1:
        params["exit_gate"]["bias"] = jnp.asarray([0.3])
    return params


def toy(seq=128, passes=3, **recipe):
    """``(family, cfg, model, loss_fn, params, batch)``: weights
    scaled up and the gate's bias off 0, so that the exits differ, the
    gates leave 0.5 and every leaf's gradient matters."""
    family = loader.load_module("models", "ouro")
    cfg = toy_cfg(passes, **recipe)
    model, _, loss_fn = family.build(cfg)
    # (buffers of its own: a step donates its state)
    params = jax.tree.map(
        jnp.copy, toy_weights(seq, passes, cfg["recipe"]["param_dtype"])
    )
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, seq + 1), 0, 512)
    return family, cfg, model, loss_fn, params, {
        "x": tokens[:, :-1], "y": tokens[:, 1:],
    }


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


def reference_grads(params, batch, cfg):
    return jax.jit(jax.grad(lambda p: reference.loss_and_aux(
        p, batch["x"], batch["y"], cfg
    )[0]))(params)


# -- (i) the family against the reference ---------------------------------------


@pytest.mark.parametrize("attention", ["xla", "flash"])
def test_float32_loss_logits_and_counters_equal_the_reference(attention):
    _, cfg, model, loss_fn, params, batch = toy(attention=attention)
    loss, aux = jax.jit(loss_fn)(params, batch)
    want, want_aux = reference.loss_and_aux(
        params, batch["x"], batch["y"], cfg
    )
    assert abs(float(loss) - float(want)) < 1e-5
    assert set(aux) == COUNTERS
    for name in COUNTERS:
        assert float(aux[name]) == pytest.approx(
            float(want_aux[name]), abs=1e-5
        ), name
    logits, gate_logits = jax.jit(
        lambda p, x: model.apply({"params": p}, x)
    )(params, batch["x"])
    ref_logits, ref_p = reference.exit_logits(params, batch["x"], cfg)
    assert logits.shape == (3, 2, 128, 512) and gate_logits.shape == (
        2, 2, 128
    )
    np.testing.assert_allclose(logits, ref_logits, rtol=0, atol=2e-4)
    np.testing.assert_allclose(
        exit_distribution(gate_logits)[0], ref_p, rtol=0, atol=1e-6
    )
    # the exits differ and the gates have left 0.5
    assert np.abs(np.asarray(logits[0] - logits[2])).max() > 0.1
    assert np.abs(np.asarray(ref_p[0]) - 0.5).max() > 0.05


def test_float32_gradients_equal_the_reference_leaf_by_leaf():
    """Through the flash kernels, the scan over the passes, the block's
    remat (one module instance, rematerialised in each) and the
    weighted head: every
    leaf of ``jax.grad`` of the training loss, to 1e-4 of the leaf's
    largest entry."""
    _, cfg, _, loss_fn, params, batch = toy(attention="flash", remat=True)
    got = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(params)
    want = reference_grads(params, batch, cfg)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    # 11 leaves a block (4 attention, 3 feed-forward, 4 norms), TWO
    # blocks though six are applied; embedding, final norm, the gate's
    # kernel and bias, head
    assert len(flat_got) == len(flat_want) == 2 * 11 + 5
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
    assert abs(float(loss) - want) < 2e-2


# -- (ii) the tie, and (vi) the control that fails it ------------------------------


def untied_block_gradients(params, batch, cfg):
    """The reference with a copy of its own for every application:
    a list of ``L`` blocks' gradients, ``[R, ...]`` a leaf, pass
    ``t``'s at ``[t]``."""
    return jax.jit(jax.grad(lambda copies: reference.loss_and_aux(
        params, batch["x"], batch["y"], cfg, copies=copies
    )[0]))(reference.copies_of(params, cfg))


def drops_pass_one(loss_fn):
    """The control: ``loss_fn`` with a ``stop_gradient`` round pass 1's
    output (its exit and its gate logit): the loss is the same, and
    every block weight loses the contribution of its first
    application.  The model's passes are one scan, so the faulty
    forward writes them out, pass by pass, from the model's own
    :meth:`Ouro.one_pass`."""

    def faulty(params, batch):
        def stop_first(next_fun, args, kwargs, context):
            model = context.module
            if not (
                isinstance(model, Ouro) and context.method_name == "__call__"
            ):
                return next_fun(*args, **kwargs)
            assert kwargs == {"return_hidden": True}
            x = model.wte(*args)
            exits, logits = [], []
            for t in range(model.config.ut_steps):
                x, logit = model.one_pass(x)
                if t == 0:
                    x, logit = jax.lax.stop_gradient((x, logit))
                exits.append(x)
                logits.append(logit)
            return jnp.stack(exits), jnp.stack(logits[:-1])

        with nn.intercept_methods(stop_first):
            return loss_fn(params, batch)

    faulty.has_aux = True
    return faulty


def test_a_blocks_gradient_is_the_sum_of_its_applications_gradients():
    """The loss cannot see a dropped contribution; this can.  Each of
    the three passes' copies takes a gradient of its own size, their
    sum is the system's gradient of the tied block, leaf by leaf; the
    same program with pass 1's output under ``stop_gradient`` reads
    the same loss and FAILS the sum by what pass 1 gave."""
    _, cfg, _, loss_fn, params, batch = toy(attention="flash", remat=True)
    value, system = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, batch)[0])
    )(params)
    copies = untied_block_gradients(params, batch, cfg)
    faulty_fn = drops_pass_one(loss_fn)
    faulty_value, faulty = jax.jit(
        jax.value_and_grad(lambda p: faulty_fn(p, batch)[0])
    )(params)
    assert float(faulty_value) == float(value)
    for layer, block in enumerate(("block_0", "block_1")):
        per_pass = [
            jax.tree.map(lambda g: g[t], copies[layer]) for t in range(3)
        ]
        summed = jax.tree.map(lambda *g: sum(g), *per_pass)
        leaves = jax.tree_util.tree_leaves_with_path(system[block])
        assert len(leaves) == 11
        for (path, got), want, first, bad in zip(
            leaves, jax.tree.leaves(summed),
            jax.tree.leaves(per_pass[0]),
            jax.tree.leaves(faulty[block]),
        ):
            name = block + jax.tree_util.keystr(path)
            assert relative(got, want) < 1e-4, name
            # no pass's share is negligible, the first's least of all
            assert relative(first, want) > 0.05, name
            # the control keeps passes 2 and 3 and nothing of pass 1
            assert relative(bad, want) > 0.05, name
            assert relative(bad, jax.tree.map(
                lambda a, b: a - b, want, first
            )) < 1e-4, name


# -- (iii) one pass is the plain model ------------------------------------------------


def test_one_pass_is_the_plain_sandwich_norm_model():
    """``R = 1``: no gate in the tree, ``p_1 = 1``, entropy 0, and the
    loss is the cross entropy of the one exit through the unweighted
    head."""
    _, cfg, model, loss_fn, params, batch = toy(passes=1)
    assert "exit_gate" not in params
    loss, aux = jax.jit(loss_fn)(params, batch)
    assert float(aux["loop.expected_exit"]) == 1.0
    assert float(aux["loop.exit_entropy"]) == 0.0
    assert float(aux["loop.nll_first"]) == float(aux["loop.nll_last"])
    exits, gate_logits = jax.jit(lambda p, x: model.apply(
        {"params": p}, x, return_hidden=True
    ))(params, batch["x"])
    assert exits.shape == (1, 2, 128, 128) and gate_logits.shape == (
        0, 2, 128
    )
    plain = chunked_cross_entropy(
        exits[0], params["lm_head"]["kernel"], batch["y"], num_chunks=4
    )
    assert float(loss) == pytest.approx(float(plain), abs=1e-6)
    assert float(loss) == pytest.approx(
        reference.loss(params, batch["x"], batch["y"], cfg), abs=1e-5
    )
    got = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(params)
    want = reference_grads(params, batch, cfg)
    for (path, g), w in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)
    ):
        assert relative(g, w) < 1e-4, jax.tree_util.keystr(path)


# -- (iv) the exit distribution and the weighted head -----------------------------------


def test_the_exit_distribution_sums_to_one_a_token():
    z = 4.0 * jax.random.normal(jax.random.PRNGKey(0), (3, 2, 64))
    p, log_p = exit_distribution(z)
    assert p.shape == (4, 2, 64)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    lam = np.asarray(jax.nn.sigmoid(z), np.float64)
    np.testing.assert_allclose(p[0], lam[0], atol=1e-6)
    np.testing.assert_allclose(
        p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]), atol=1e-6
    )
    np.testing.assert_allclose(
        p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), atol=1e-6
    )
    np.testing.assert_allclose(np.exp(log_p), p, atol=1e-6)
    # every gate at 0.5: (1/2, 1/4, 1/8, 1/8), expected exit 1.875
    p, _ = exit_distribution(jnp.zeros((3, 1)))
    assert float(jnp.sum(jnp.arange(1, 5)[:, None] * p)) == 1.875
    # a saturated gate: finite logs, no nan in p log p
    p, log_p = exit_distribution(jnp.asarray([[200.0], [-200.0]]))
    assert np.isfinite(np.asarray(p * log_p)).all()
    np.testing.assert_allclose(p[:, 0], [1.0, 0.0, 0.0], atol=1e-30)


def head_operands(n=4, s=64, h=32, v=96, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    return (
        jax.random.normal(ks[0], (n, s, h), dtype),
        (0.3 * jax.random.normal(ks[1], (h, v))).astype(dtype),
        jax.random.randint(ks[2], (n, s), 0, v),
        jax.random.uniform(ks[3], (n, s)),
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_weighted_head_is_the_plain_weighted_sum(dtype):
    """Value, the per-row nll and all three gradients (rows, kernel,
    weights) against the full logits; in bf16 within its rounding."""
    hidden, kernel, targets, weights = head_operands(dtype=jnp.dtype(dtype))
    exact = dtype == "float32"

    def plain(hidden, kernel, weights):
        logits = hidden.astype(jnp.float32) @ kernel.astype(jnp.float32)
        nll = -jnp.take_along_axis(
            jax.nn.log_softmax(logits), targets[..., None], -1
        )[..., 0]
        return (weights * nll).sum(), nll

    def chunked(hidden, kernel, weights):
        return weighted_chunked_cross_entropy(
            hidden, kernel, targets, weights, num_chunks=4
        )

    (want, want_nll), want_grads = jax.value_and_grad(
        plain, argnums=(0, 1, 2), has_aux=True
    )(hidden, kernel, weights)
    (got, got_nll), got_grads = jax.value_and_grad(
        chunked, argnums=(0, 1, 2), has_aux=True
    )(hidden, kernel, weights)
    assert float(got) == pytest.approx(
        float(want), rel=1e-6 if exact else 1e-3
    )
    np.testing.assert_allclose(
        got_nll, want_nll, atol=1e-5 if exact else 2e-2
    )
    for g, w in zip(got_grads, want_grads):
        assert g.dtype == w.dtype
        assert relative(g, w) < (1e-5 if exact else 2e-2)
    # the gradient with respect to the weights IS the per-row nll
    np.testing.assert_allclose(got_grads[2], got_nll, atol=1e-6)
    # the value alone (no gradient formed) is the same number
    assert float(chunked(hidden, kernel, weights)[0]) == float(got)


def test_equal_weights_over_stacked_exits_are_the_mean_of_unweighted_calls():
    """``R`` exits stacked, every weight ``1 / R`` of a token's ``1 /
    (b s)``: the mean of ``R`` unweighted calls, in value and in both
    gradients."""
    steps, b = 4, 2
    hidden, kernel, targets, _ = head_operands(n=steps * b)
    weights = jnp.full(targets.shape, 1.0 / (steps * b * 64))

    def stacked(hidden, kernel):
        return weighted_chunked_cross_entropy(
            hidden, kernel, targets, weights, num_chunks=4
        )[0]

    def apart(hidden, kernel):
        return sum(
            chunked_cross_entropy(
                hidden[t * b:(t + 1) * b], kernel,
                targets[t * b:(t + 1) * b], num_chunks=4,
            ) for t in range(steps)
        ) / steps

    got, got_grads = jax.value_and_grad(stacked, argnums=(0, 1))(
        hidden, kernel
    )
    want, want_grads = jax.value_and_grad(apart, argnums=(0, 1))(
        hidden, kernel
    )
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for g, w in zip(got_grads, want_grads):
        assert relative(g, w) < 1e-5


def test_the_per_row_nll_carries_no_gradient_of_its_own():
    hidden, kernel, targets, weights = head_operands()
    grads = jax.grad(lambda h: weighted_chunked_cross_entropy(
        h, kernel, targets, weights, num_chunks=4
    )[1].sum())(hidden)
    assert not np.asarray(grads).any()
    with pytest.raises(ValueError, match="not divisible"):
        weighted_chunked_cross_entropy(
            hidden, kernel, targets, weights, num_chunks=5
        )


# -- (v) the unweighted head is what it was -----------------------------------------------

# sha256 (16 hex digits) of the lowered text of the unweighted head at
# [2, 64, 32] x 96 words in 4 chunks, value-and-gradient then value
# alone: recorded on the PARENT of the PR that added the weighted head
# (commit 35dc3a2, jax 0.9.0).  Four cells call this form; a change
# that moves a hash has to be measured in them.
PINNED = {
    (False, "bfloat16"): ("87b10c6c3ea2052c", "d7118622916d99d6"),
    (False, "float32"): ("bfd3a46637089453", "8cc548891cc08820"),
    (True, "bfloat16"): ("84069e156ef9525d", "7578503231cc692f"),
    (True, "float32"): ("c8979b53486f7c9d", "b74cc9e1ca0aae4b"),
}


@pytest.mark.parametrize("transpose, dtype", list(PINNED))
def test_the_unweighted_head_lowers_to_what_it_was(transpose, dtype):
    if jax.__version__ != "0.9.0":
        pytest.skip("the hashes were recorded under jax 0.9.0")
    dtype = jnp.dtype(dtype)
    hidden = jax.ShapeDtypeStruct((2, 64, 32), dtype)
    kernel = jax.ShapeDtypeStruct(
        (96, 32) if transpose else (32, 96), dtype
    )
    targets = jax.ShapeDtypeStruct((2, 64), jnp.int32)

    def head(h, w, t):
        return chunked_cross_entropy(
            h, w, t, num_chunks=4, transpose=transpose
        )

    texts = (
        jax.jit(jax.value_and_grad(head, argnums=(0, 1))).lower(
            hidden, kernel, targets
        ).as_text(),
        jax.jit(head).lower(hidden, kernel, targets).as_text(),
    )
    assert tuple(
        hashlib.sha256(text.encode()).hexdigest()[:16] for text in texts
    ) == PINNED[(transpose, dtype.name)]


def test_the_unweighted_head_is_the_mean_cross_entropy():
    hidden, kernel, targets, _ = head_operands()
    logits = hidden @ kernel
    want = -jnp.take_along_axis(
        jax.nn.log_softmax(logits), targets[..., None], -1
    ).mean()
    got = chunked_cross_entropy(hidden, kernel, targets, num_chunks=4)
    assert float(got) == pytest.approx(float(want), rel=1e-6)


# -- the cut configuration ----------------------------------------------------------------


def cut_cfg():
    return loader.load_json(os.path.join(CONFIGS, "ouro_2_6b_cut.json"))


def test_the_cut_keeps_every_published_width_and_counts_as_the_issue_says():
    """``ouro_2_6b_cut.json`` against the catalog's row: every key
    that is not in ``reduced`` is the published one; the model it
    builds holds TWELVE blocks (not 48) and the parameters the issue
    reckons (818.0 M, 4.91 GB of bf16 state)."""
    cut = cut_cfg()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(
                r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B"
            )
        assert cut["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cut["reduced"]:
                assert cut[key] == value, key
            elif isinstance(value, list):
                assert cut[key] == value[:12], key
    assert cut["reduced"] == ["num_hidden_layers", "layer_types"]
    assert (cut["num_hidden_layers"], cut["total_ut_steps"]) == (12, 4)
    assert cut["published"]["num_hidden_layers"] == 48
    assert {"norms", "final_norm", "exit_gate", "biases", "beta",
            "objective", "initializer_range"} <= set(cut["assumed"])
    for letter, key in zip("abcdefg", (
        "norms", "final_norm", "exit_gate", "biases", "beta", "objective",
        "initializer_range",
    )):
        assert cut["assumed"][key].startswith(f"({letter})"), key
    for key in ("published", "deployment", "memory", "reference", "paper"):
        assert cut[key], key
    family = loader.load_module("models", "ouro")
    model, _, _ = family.build(cut)
    shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), 1, 128)
    )
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree)
    )
    assert sorted(k for k in shapes if k.startswith("block_")) == sorted(
        f"block_{i}" for i in range(12)
    )
    # 4 x 2048^2 + 3 x 2048 x 5632 + four norms
    assert count(shapes["block_0"]) == 51_380_224 + 4 * 2048 == 51_388_416
    assert shapes["exit_gate"]["kernel"].shape == (2048, 1)
    assert shapes["exit_gate"]["bias"].shape == (1,)
    assert count(shapes["wte"]) == count(shapes["lm_head"]) == 100_663_296
    assert count(shapes) == (
        12 * 51_388_416 + 2 * 100_663_296 + 2048 + 2049
    ) == 817_991_681
    assert all(
        x.dtype == jnp.bfloat16 for x in jax.tree.leaves(shapes)
        if x.ndim > 1
    )


def test_the_flops_keys_count_applications_and_exits():
    """The GPT-2 key names ``flops.py`` reads (a FIFTH configuration
    with invented ones: ``n_layer`` 48 applications, ``n_inner`` 9984)
    against ``ouro_flops.py``'s own count, exactly."""
    import flops
    import ouro_flops

    cut = cut_cfg()
    assert ouro_flops.applications(cut) == 48 == cut["n_layer"]
    assert ouro_flops.block_matmul_params(cut) == 51_380_224
    assert flops.matmul_params(cut) == (
        48 * 51_380_224 + 4 * 100_663_296
    ) == 2_868_903_936
    assert flops.train_flops_per_token(cut, 4096) == (
        ouro_flops.train_flops_per_token(cut, 4096)
    ) == 19_629_342_720
    assert flops.attention_flops_per_step(cut, 1, 4096) == (
        6 * 48 * 4096 * 2048 * 4096
    )
    toy = loader.load_json(os.path.join(CONFIGS, "toy_ouro.json"))
    required = ouro_flops.train_flops_per_token(toy, 128)
    assert 0.999 * required < flops.train_flops_per_token(toy, 128) <= (
        required
    )


# -- counters and scopes ------------------------------------------------------------------


def test_the_counters_ride_on_the_train_step_event(tmp_path, monkeypatch):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("DLROVER_EVENT_LOG", path)
    monkeypatch.setenv(
        "DLROVER_METRICS_FILE", str(tmp_path / "metrics.json")
    )
    trainer = ElasticTrainer(4, 4, dp_size=1)
    trainer.report_step({
        "loss": jnp.float32(1.5), "grad_norm": jnp.float32(0.1),
        "loop.expected_exit": jnp.float32(1.875),
        "loop.exit_entropy": jnp.float32(1.2130),
        "loop.nll_first": jnp.float32(10.8),
        "loop.nll_last": jnp.float32(10.7),
    })
    (event,) = [e for e in read_events(path) if e["type"] == "train_step"]
    assert event["loop.expected_exit"] == pytest.approx(1.875)
    assert COUNTERS <= set(event)
    assert not validate_event(event)


def test_the_passes_scopes_are_in_the_compiled_step():
    """What the benchmark's readers join on: a pass's operations under
    ``ut`` with the block's module INSIDE it, forward and backward, in
    the bodies of the passes' two scans; the gate under ``exit_gate``;
    the head under ``loss_head``; and the step's metrics carry the four
    counters."""
    from dlrover_tpu.common.aot_cache import op_names

    _, _, _, loss_fn, params, batch = toy(remat=True)
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    step = make_train_step(loss_fn, optimizer)
    state = TrainState.create(params, optimizer)
    compiled = step.lower(state, batch).compile()
    stacks = list(op_names(compiled.as_text())["op_names"].values())
    for block in range(2):
        for wrapper in ("jvp(", "transpose("):
            assert any(
                wrapper in s and "/while/body/" in s.split("/ut/")[0]
                and f"block_{block}/" in s.rsplit("/ut/", 1)[1]
                for s in stacks if "/ut/" in s
            ), (block, wrapper)
    assert any(
        "/ut/" in s and "rematted_computation" in s for s in stacks
    )
    assert any("exit_gate" in s for s in stacks)
    assert any("loss_head" in s for s in stacks)
    _, metrics = compiled(state, batch)
    assert COUNTERS <= set(metrics)


# -- the benchmark's five readers ------------------------------------------------------------


class TracedRun:
    """What ``run.py`` hands a reader, for a step whose operations
    took 100 ms under the passes' scope (and their scans' containers
    as much again), 8 under the head (its scan's container 8 more), 2
    under the optimizer, 0.5 under the gate and 1.5 elsewhere over five
    traced steps."""

    traffic = {"batch": 1, "seq": 4096}
    report = {
        "window": {"steps": [{"step": s} for s in (5, 6, 7)]},
        "device": {"kind": "TPU v5 lite"},
    }

    def __init__(self, directory, config, traced=True, counter=True):
        import flops

        self.flops, self.config, self.notes = flops, config, []
        ops = {
            "%fusion.1": (0.100, "jvp(Ouro)/while/body/ut/checkpoint/"
                          "block_0/mlp"),
            # a block's remat copy holds no kernel since it keeps the
            # forward's ``out`` and ``lse`` (PR 44): projections, norms
            "%fusion.2": (0.050, "transpose(jvp(Ouro))/while/body/ut/"
                          "checkpoint/rematted_computation/block_0/"
                          "attn/q_proj/dot_general"),
            "%attn.3": (0.020, "transpose(jvp(Ouro))/while/body/ut/"
                        "checkpoint/block_0/attn/pallas_call"),
            "%fusion.3": (0.030, "transpose(jvp(Ouro))/while/body/ut/"
                          "checkpoint/block_0/mlp"),
            "%fusion.4": (0.150, "jvp(Ouro)/while/body/ut/checkpoint/"
                          "block_1/mlp"),
            "%fusion.5": (0.100, "transpose(jvp(Ouro))/while/body/ut/"
                          "block_1/mlp"),
            "%fusion.6": (0.050, "transpose(jvp(Ouro))/while/body/ut/"
                          "ln_f/mul"),
            "%while.13": (0.250, "jvp(Ouro)/while"),
            "%while.14": (0.250, "transpose(jvp(Ouro))/while"),
            "%fusion.7": (0.040, "jvp(loss_head)/while/body/dot_general"),
            "%while.8": (0.040, "jvp(loss_head)/while"),
            "%fusion.9": (0.010, "optimizer/mul"),
            "%fusion.10": (0.0025, "jvp(Ouro)/while/body/exit_gate/"
                           "exit_gate/dot"),
            "%fusion.11": (0.0050, "jit(step)/convert_element_type"),
            "%copy.12": (0.0025, None),
        }
        self.trace = {"steps": 5, "busy_s": 0.565, "ops": {
            name: {"seconds": seconds, "count": 5, "target": ""}
            for name, (seconds, _) in ops.items()
        }} if traced else None
        with open(os.path.join(directory, "k.opnames.json"), "w") as f:
            json.dump({"op_names": {
                name: stack for name, (_, stack) in ops.items() if stack
            }}, f)
        self.events = [{"type": "aot_cache", "key": "k", "dir": directory}]
        if counter:
            self.events += [
                {"type": "train_step", "step": s,
                 "loop.expected_exit": 1.8 + s / 100,
                 "loop.exit_entropy": 1.2 + s / 100,
                 "loop.nll_first": 10.9, "loop.nll_last": 10.8}
                for s in (4, 5, 6, 7)
            ]

    def of(self, type_, **match):
        return [e for e in self.events if e["type"] == type_]

    def note(self, line):
        self.notes.append(line)


READERS = {
    "loop.blocks_ms_per_step": 100.0,
    # least: 48 applications x 358,612,992 FLOPs x 4096 tokens
    "loop.blocks_peak_pct": 100 * (
        48 * 358_612_992 * 4096 / 197e12
    ) / 0.1,
    "loop.exit_gate_ms_per_step": 0.5,
    # the run's first step's, not the window's
    "loop.expected_exit": 1.84,
    "loop.exit_entropy": 1.24,
}


@pytest.mark.parametrize("name", list(READERS))
def test_a_reader_reads_its_scope_and_is_silent_without_it(name, tmp_path):
    """Each of the cell's five readers on a run that carries what it
    reads, and on one with no trace and the events of a program
    without the counters (the parent's): a number, then nothing."""
    reader = loader.load_module("layer_metrics", name)
    run = TracedRun(str(tmp_path), cut_cfg())
    assert reader.read(run) == pytest.approx(READERS[name])
    if name == "loop.blocks_ms_per_step":
        assert (
            "over 4 passes, forward | remat copy | backward: 50.00 | "
            "10.00 | 40.00; a pass 12.50 | 2.50 | 10.00"
        ) in run.notes[0]
        # a scan's body counted once, its container not at all
        assert "loss_head 8.00 + optimizer 2.00" in run.notes[1]
        assert "other 1.00 + unnamed 0.50 = 112.00 ms" in run.notes[1]
        assert "99.1% accounted for" in run.notes[1]
    bare = TracedRun(str(tmp_path), cut_cfg(), traced=False, counter=False)
    assert reader.read(bare) is None and not bare.notes
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == ["ouro_steady_1x4k"]
    assert entry["layer"] == "looped stack"
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES,
            reader.SOURCE) == tuple(
        entry[k] for k in ("name", "unit", "layer", "moves", "source")
    )


def test_the_benchmark_gains_one_configuration_and_one_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [
        w for w in bench["workloads"] if w["name"] == "ouro_steady_1x4k"
    ]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro_2_6b_cut", "steady_1x4k", 1
    )
    (config,) = [c for c in bench["configs"] if c["name"] == "ouro_2_6b_cut"]
    cut = loader.load_json(os.path.join(REPO, config["file"]))
    assert config["reduced"] == cut["reduced"]
    assert config["source"] == cut["source"]
    assert all(len(x["why"]) <= 200 for x in (cell, config))
    traffic = loader.load_json(os.path.join(
        REPO, "benchmarks", "traffic", cell["traffic"] + ".json"
    ))
    assert (traffic["batch"], traffic["seq"], traffic["saves"]) == (
        1, 4096, None
    )
    assert (traffic["warmup_steps"], traffic["trace"]) == (3, {"steps": 5})


# -- what the benchmark's ``correct`` compares beside the loss --------------------------------


@pytest.fixture(scope="module")
def toy_cell():
    """The benchmark family on its toy configuration as the harness
    runs it (bf16): ``(family, cfg, params, batch, what a sound
    program reads)``."""
    import worker  # the benchmark's own

    family = loader.load_module("models", "ouro")
    cfg = loader.load_json(os.path.join(CONFIGS, "toy_ouro.json"))
    traffic = loader.load_json(
        os.path.join(REPO, "benchmarks", "traffic", "toy_steady.json")
    )
    seed = 4300000013
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
    limit = cfg["reference"]["gradient_tolerance"]
    assert max(sound["gradients"].values()) < limit
    leaves = set(sound["gradients"])
    # both ends of the stack whole, the norm every pass closes with,
    # the gate's kernel, the head; not the gate's bias (one number that
    # can cancel to nothing), not the embedding
    assert len(leaves) == 2 * 11 + 3
    for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
        for block in (0, 1):
            assert f"['block_{block}']['attn']['{name}']['kernel']" in leaves
    for name in ("ln_attn", "ln_attn_out", "ln_mlp", "ln_mlp_out"):
        assert f"['block_0']['{name}']['scale']" in leaves
    assert {"['ln_f']['scale']", "['exit_gate']['kernel']",
            "['lm_head']['kernel']"} <= leaves
    assert "['exit_gate']['bias']" not in leaves
    for name, (got, want) in sound["counters"].items():
        assert got == pytest.approx(want, abs=2e-2), name
    monkeypatch.setattr(family, "comparisons", lambda *a: sound)
    got = family.reference_loss(params, batch["x"], batch["y"], cfg)
    assert got == sound["loss"] == reference.loss(
        params, batch["x"], batch["y"], cfg
    )
    err = capfd.readouterr().err
    assert "first gradient" in err and "loop.expected_exit" in err


@pytest.mark.parametrize("fault", ["drops_pass_one", "three_bits"])
def test_a_faulty_program_is_told_apart(toy_cell, monkeypatch, fault):
    """A program that drops pass 1's contribution to every gradient
    (the same loss: ``inf`` to the harness through block 0's leaves)
    and the lower-precision control (``recipe.operand_mantissa_bits``
    3) each read far from a sound one."""
    family, cfg, params, batch, sound = toy_cell
    build = family.build

    def faulty(cfg):
        model, optimizer, loss_fn = build(cfg)
        return model, optimizer, drops_pass_one(loss_fn)

    if fault == "three_bits":
        cfg = dict(cfg, recipe=dict(cfg["recipe"], operand_mantissa_bits=3))
    else:
        monkeypatch.setattr(family, "build", faulty)
    found = family.comparisons(params, batch["x"], batch["y"], cfg)
    if fault == "three_bits":
        median = np.median(list(found["gradients"].values()))
        assert median > 3 * np.median(list(sound["gradients"].values()))
        return
    leaf = "['block_0']['attn']['q_proj']['kernel']"
    assert found["gradients"][leaf] > cfg["reference"]["gradient_tolerance"]
    monkeypatch.setattr(family, "comparisons", lambda *a: found)
    assert family.reference_loss(
        params, batch["x"], batch["y"], cfg
    ) == float("inf")


@pytest.mark.parametrize("gradients, inside", [
    ({"['block_0']['attn']['q_proj']": 0.1, "['exit_gate']['kernel']": 0.19},
     True),
    ({"['block_0']['attn']['q_proj']": 0.1, "['exit_gate']['kernel']": 0.3},
     False),
    ({"['lm_head']['kernel']": float("nan"), "['ln_f']['scale']": 0.1},
     False),
])
def test_every_leaf_is_judged_by_the_limit(monkeypatch, gradients, inside):
    family = loader.load_module("models", "ouro")
    monkeypatch.setattr(family, "comparisons", lambda *a: {
        "loss": 1.5, "gradients": gradients, "counters": {},
    })
    cfg = {"reference": {"gradient_tolerance": 0.2}}
    got = family.reference_loss(None, None, None, cfg)
    assert got == (1.5 if inside else float("inf"))


def test_the_harness_rehearses_the_family_on_the_cpu(tmp_path, checkout):
    """``benchmarks/run.py`` end to end on the toy configuration:
    ``tpurun`` -> the worker -> the ``has_aux`` step (two blocks called
    three times) -> the reference's loss and gradients -> the readers;
    exit code 3 (a rehearsal, never a result), ``correct`` true, the
    ``loop.*`` counters read.  From a checkout of its own, so that the
    compile cache ``run.py`` fixes at its checkout's root is this
    test's (ROADMAP B7)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(checkout, "benchmarks", "run.py"),
         "--cells", os.path.join(REPO, "benchmarks", "rehearsal_ouro.json"),
         "--workload", "toy_ouro_steady", "--seed", "4300000007",
         "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 3, done.stdout[-3000:] + done.stderr[-3000:]
    assert '"correct": true' in done.stdout
    assert "'loop.exit_entropy', 'loop.expected_exit'" in done.stdout
    assert "exits: expected exit" in done.stdout
