"""The gated-short-convolution family (``model_type: "lfm2_moe"``,
LFM2-24B-A2B) through the repo's blocks against the plain float32
reference (``benchmarks/models/lfm2_moe_reference.py``): loss, logits,
the counter and every leaf's gradient in float32 and in bf16; the
mixer alone against its bf16 control; the whole model's causality;
the tied table's gradient from both its ends; the reference's two mixers against the family's OWN code on this
machine (``transformers``' ``Lfm2DecoderLayer``); what the family
refuses.  The kernels alone are in ``test_short_conv.py``, the cut
configuration, the shares, the harness's rehearsal (where the step
applies the bias rule) and the benchmark's entries in
``test_lfm2_moe_bench.py``, the compiles for a described chip in
``test_lfm2_moe_tpu.py`` (a file is one worker's)."""

import os
import sys

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import loader  # noqa: E402  (the benchmark's own)

from dlrover_tpu.models import lfm2_moe  # noqa: E402
from dlrover_tpu.models.losses import chunked_cross_entropy  # noqa: E402

family = loader.load_module("models", "lfm2_moe")
reference = family.reference
CONFIGS = os.path.join(REPO, "benchmarks", "configs")
SEQ = 160


def toy_cfg(dtype="float32", **recipe):
    """The toy configuration's file (conv with the dense feed-forward,
    attention, conv, conv; 4 heads of 32 over 2; 4 of 16 experts
    held)."""
    cfg = loader.load_json(os.path.join(CONFIGS, "toy_lfm2_moe.json"))
    cfg["recipe"] = {**cfg["recipe"], **dict(
        param_dtype=dtype, compute_dtype=dtype,
    ), **recipe}
    return cfg


def toy(dtype="float32", seed=0):
    cfg = toy_cfg(dtype)
    model, _, loss_fn = family.build(cfg)
    params = jax.jit(
        lambda key: model.init_params(key, seq_len=SEQ)
    )(jax.random.PRNGKey(seed))
    # a bias that matters: which experts stand for the top-k
    for i in range(cfg["num_dense_layers"], cfg["num_hidden_layers"]):
        params[f"block_{i}"]["moe"]["select_bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(100 + i), (cfg["router_outputs"],)
        )
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (2, SEQ + 1), 0, cfg["vocab_size"]
    )
    return cfg, model, loss_fn, params, {
        "x": tokens[:, :-1], "y": tokens[:, 1:],
    }


def relative(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))


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


def test_float32_loss_counter_and_every_leaf_match_the_reference():
    cfg, loss, aux, grads, want_loss, said, want = system_and_reference(
        "float32"
    )
    assert abs(loss - want_loss) < 2e-5
    assert set(grads) == set(want) and len(grads) > 35
    worst = max((relative(grads[k], want[k]), k) for k in grads)
    assert worst[0] < 2e-4, worst
    # every class of leaf the chip's comparison names is among them
    for leaf in (
        "['short_conv']['taps']", "['short_conv']['in_proj']",
        "['short_conv']['out_proj']", "['q_layernorm']", "['k_layernorm']",
        "['q_proj']", "['experts_w_gate']", "['router']", "['wte']",
    ):
        assert any(leaf in k for k in grads), leaf
    # the counter: the system's is the rms over the batch together
    rms = np.sqrt(np.max(np.mean(np.square(said["out_rms"]), axis=0)))
    np.testing.assert_allclose(aux["sconv.out_rms_max"], rms, rtol=1e-5)
    assert np.asarray(said["out_rms"]).shape == (
        2, cfg["layer_types"].count("conv"),
    )
    # the bias rule on the reference's own counts
    sparse = range(cfg["num_dense_layers"], cfg["num_hidden_layers"])
    deltas = np.stack([
        aux["state_updates"][f"block_{i}"]["moe"]["select_bias"]
        for i in sparse
    ])
    np.testing.assert_array_equal(deltas, reference.base.bias_deltas(
        said["counts"], cfg["recipe"]["bias_update_rate"]
    ))
    assert np.asarray(said["counts"]).sum(axis=1).tolist() == [
        2 * SEQ * cfg["num_experts_per_tok"]
    ] * len(sparse)
    held = float(aux["moe.held_rows_share"])
    assert 0.1 < held < 0.5  # a quarter in expectation, under a bias


def test_bf16_stays_inside_the_toys_limits():
    cfg, loss, aux, grads, want_loss, said, want = system_and_reference(
        "bfloat16"
    )
    limits = cfg["reference"]
    assert abs(loss - want_loss) < limits["loss_tolerance"]
    kind = family.kind_of(cfg)
    for leaf in grads:
        assert relative(grads[leaf], want[leaf]) < limits[kind(leaf)], leaf
    rms = np.sqrt(np.max(np.mean(np.square(said["out_rms"]), axis=0)))
    assert abs(float(aux["sconv.out_rms_max"]) / rms - 1) < limits[
        "out_rms_tolerance"
    ]


def test_the_mixer_in_bf16_fails_the_mixers_own_limit():
    """The first conv layer's mixer alone on its own bf16 operands:
    the kernels sum the float32 terms the plain float32 form sums
    (the taps' gradient to float32's last bits); the control, every
    product and sum of the mixer in bf16, stands seventy times the
    limit off."""
    cfg, _, _, params, batch = toy("bfloat16")
    limit = cfg["reference"]["mixer_taps_tolerance"]
    assert family.mixer_alone(params, batch["x"], cfg) < 0.05 * limit
    control = toy_cfg("bfloat16", control="sconv_mix_bf16")
    assert family.mixer_alone(params, batch["x"], control) > 50 * limit


def test_logits_causality_and_the_tied_tables_two_ends():
    """The float32 logits are the reference's; a token changes no
    logit before it (the whole model: the convolutions' halo, the
    attention's mask, a row-wise feed-forward); the tied table's
    gradient is the head's plus the lookup's, the second on the rows
    of the tokens that were looked up and nowhere else."""
    cfg, model, loss_fn, params, batch = toy()
    logits = jax.jit(lambda p, x: model.apply({"params": p}, x))
    got = logits(params, batch["x"])
    assert got.dtype == jnp.float32
    want = jnp.stack(reference.forward(params, batch["x"], cfg))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    at = 97
    later = batch["x"].at[:, at].add(1) % cfg["vocab_size"]
    moved = np.abs(np.asarray(logits(params, later) - got)).max(axis=-1)
    assert np.all(moved[:, :at] == 0.0) and np.all(moved[:, at] > 0.0)

    # both ends of the one table
    whole = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(params)[
        "wte"
    ]["embedding"]
    hidden = model.apply(
        {"params": params}, batch["x"], return_hidden=True
    )
    head = jax.grad(lambda table: chunked_cross_entropy(
        hidden, table, batch["y"], num_chunks=4, transpose=True
    ))(params["wte"]["embedding"])
    lookup = np.asarray(whole - head)
    seen = np.zeros(cfg["vocab_size"], bool)
    seen[np.asarray(batch["x"]).ravel()] = True
    scale = np.abs(np.asarray(head)).max()
    assert np.abs(lookup[~seen]).max() < 1e-6 * scale
    assert np.all(np.abs(lookup[seen]).max(axis=-1) > 1e-3 * scale)
    assert np.all(np.abs(np.asarray(head)).max(axis=-1) > 0)


# -- the reference against the family's own code ------------------------------


def test_the_references_mixers_are_transformers_lfm2_layers():
    """Seeded weights copied into ``transformers``' ``Lfm2DecoderLayer``
    of each kind (``block_auto_adjust_ff_dim`` false) at a tiny size:
    its float32 output is the reference's conv block and attention
    block.  The slicing order of ``B | C | u``, the causal padding,
    the taps' order, the per-head norms BEFORE rope, ``rotate_half``
    and the kv heads' repeat are then not a reading of the reference's
    author."""
    torch = pytest.importorskip("torch")
    modeling = pytest.importorskip("transformers.models.lfm2.modeling_lfm2")
    from transformers.models.lfm2.configuration_lfm2 import Lfm2Config

    cfg = toy_cfg()
    hidden, seq = cfg["hidden_size"], 40
    kinds = ["conv", "full_attention"]
    theirs = Lfm2Config(
        vocab_size=cfg["vocab_size"], hidden_size=hidden,
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=2,
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        norm_eps=cfg["norm_eps"], conv_bias=False,
        conv_L_cache=cfg["conv_L_cache"],
        block_auto_adjust_ff_dim=False, layer_types=kinds,
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
    )
    theirs._attn_implementation = "eager"
    params = toy()[3]
    x = jax.random.normal(jax.random.PRNGKey(5), (seq, hidden))

    def tensor(a):
        return torch.tensor(np.asarray(a, np.float32))

    def linear(module, leaf):
        # nn.Linear keeps [out, in]
        module.weight.data = tensor(leaf["kernel"]).T.contiguous()

    rope = modeling.Lfm2RotaryEmbedding(theirs)
    position = torch.arange(seq)[None]
    mask = torch.full((seq, seq), float("-inf")).triu(1)[None, None]
    for index, (kind, block) in enumerate(zip(kinds, ("block_0", "block_1"))):
        p = dict(params[block])
        # both kinds under the dense feed-forward, as the dense
        # sibling's layer has it
        p.pop("moe", None)
        p["mlp"] = params["block_0"]["mlp"]
        layer = modeling.Lfm2DecoderLayer(theirs, index).float().eval()
        layer.operator_norm.weight.data = tensor(p["operator_norm"]["scale"])
        layer.ffn_norm.weight.data = tensor(p["ffn_norm"]["scale"])
        for name, ours in (
            ("w1", "gate_proj"), ("w3", "up_proj"), ("w2", "down_proj"),
        ):
            linear(getattr(layer.feed_forward, name), p["mlp"][ours])
        if kind == "conv":
            mixer = p["short_conv"]
            linear(layer.conv.in_proj, mixer["in_proj"])
            linear(layer.conv.out_proj, mixer["out_proj"])
            # Conv1d keeps [channels, 1, K]; ours [K, channels]
            layer.conv.conv.weight.data = tensor(
                mixer["taps"]
            ).T[:, None, :].contiguous()
        else:
            mixer = p["attn"]
            for name in ("q_proj", "k_proj", "v_proj", "out_proj"):
                linear(getattr(layer.self_attn, name), mixer[name])
            for name in ("q_layernorm", "k_layernorm"):
                getattr(layer.self_attn, name).weight.data = tensor(
                    # (a scale that is not all ones)
                    mixer[name]["scale"]
                )
        with torch.no_grad():
            want = layer(
                tensor(x)[None],
                position_embeddings=rope(tensor(x)[None], position),
                attention_mask=mask, position_ids=position,
            )[0].numpy()
        got, _, _ = reference._block(
            x, p, kind=kind, **reference.block_kwargs(cfg)
        )
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=1e-5, atol=1e-5, err_msg=kind
        )


def test_what_the_family_refuses():
    for key, value in (
        ("conv_bias", True), ("tie_word_embeddings", False),
        ("use_expert_bias", False), ("norm_topk_prob", False),
    ):
        with pytest.raises(SystemExit, match=key):
            family.build({**toy_cfg(), key: value})
    with pytest.raises(SystemExit, match="layer_types lists"):
        family.build({**toy_cfg(), "num_hidden_layers": 5})
    with pytest.raises(SystemExit, match="pass the router"):
        family.build({**toy_cfg(), "first_expert_held": 14})
    with pytest.raises(SystemExit, match="no control"):
        family.build(toy_cfg(control="no_such_thing"))
    with pytest.raises(ValueError, match="unknown layer types"):
        lfm2_moe.Lfm2MoeConfig.tiny(layer_types=("conv", "window"))
    with pytest.raises(ValueError, match="query heads"):
        lfm2_moe.Lfm2MoeConfig.tiny(num_heads=4, num_kv_heads=3)
