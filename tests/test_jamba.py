"""The Jamba family (``model_type: "jamba"``, AI21-Jamba2-3B) through
the repo's blocks against the plain float32 reference
(``benchmarks/models/jamba_reference.py``): loss, the counters and
every leaf's gradient in float32 and in bf16; the reference against
the family's OWN code on this machine (``transformers``'
``JambaForCausalLM``, slow path, float32, copied weights): loss,
logits and every leaf's gradient, and the two noted departures shown
to be invisible in float32; the layer types of the published keys;
what the family refuses.  The kernels alone are in
``test_selective_scan.py``, the cut configuration, the harness's
rehearsal and the benchmark's entries in ``test_jamba_bench.py``, the
compiles for a described chip in ``test_jamba_tpu.py`` (a file is one
worker's)."""

import os
import sys

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import loader  # noqa: E402  (the benchmark's own)

from dlrover_tpu.models import jamba  # noqa: E402

family = loader.load_module("models", "jamba")
reference = family.reference
CONFIGS = os.path.join(REPO, "benchmarks", "configs")
SEQ = 72


def toy_cfg(dtype="float32", **recipe):
    """The toy configuration's file (mamba, attention, mamba; 128
    channels x 16 lanes, rank-8 dt; 4 heads of 16 over 1)."""
    cfg = loader.load_json(os.path.join(CONFIGS, "toy_jamba.json"))
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
    # scales, biases and a skip that matter: not the initial 1 and 0
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 50), 64))

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if name.endswith(("['scale']", "['D']")):
            return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)
        if name.endswith("['conv_bias']"):
            return 0.1 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    params = jax.tree_util.tree_map_with_path(moved, params)
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
    pick = lambda path: True  # noqa: E731
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
    assert set(grads) == set(want) and len(grads) == 45
    worst = max((relative(grads[k], want[k]), k) for k in grads)
    assert worst[0] < 2e-4, worst
    # every class of leaf the chip's comparison names is among them
    for leaf in (
        "['in_proj']", "['conv']", "['conv_bias']", "['x_proj']",
        "['dt_layernorm']", "['b_layernorm']", "['c_layernorm']",
        "['dt_proj']", "['dt_bias']", "['A_log']", "['D']", "['out_proj']",
        "['q_proj']", "['k_proj']", "['v_proj']", "['o_proj']",
        "['input_layernorm']", "['pre_ff_layernorm']",
        "['final_layernorm']", "['wte']",
    ):
        assert any(leaf in k for k in grads), leaf
    # the counter: the system's is the rms over the batch together
    rms = np.sqrt(np.max(np.mean(np.square(said["state_rms"]), axis=0)))
    np.testing.assert_allclose(aux["s6.state_rms_max"], rms, rtol=1e-5)
    assert np.asarray(said["state_rms"]).shape == (2, 2)
    assert 0.5 < float(aux["s6.decay_mean"]) < 1.0
    assert 1e-3 < float(aux["s6.dt_mean"]) < 1e-1


def test_bf16_stays_inside_the_toys_limits_and_the_controls_do_not():
    """The family's whole comparison (``comparisons`` + ``worst_of``,
    what ``reference_loss`` judges) on the toy in bf16: inside every
    limit; with the state and ``exp`` in bf16 the scan alone is a
    thousand times outside its own; with 3 bits of mantissa the
    gradient's limits fail."""
    cfg, _, _, params, batch = toy("bfloat16")
    limits = cfg["reference"]
    found = family.comparisons(params, batch["x"], batch["y"], cfg)
    assert abs(found["system_loss"] - found["loss"]) < limits[
        "loss_tolerance"
    ]
    worst = family.worst_of(found)
    assert set(worst) == {
        "gradient_tolerance", "a_log_gradient_tolerance",
        "dt_bias_gradient_tolerance", "state_rms_tolerance",
        "scan_alone_tolerance",
    }
    for key, (value, what) in worst.items():
        assert value <= limits[key], (key, value, what)
    assert not family.not_finite(found)
    assert worst["scan_alone_tolerance"][0] < 1e-5

    low = toy_cfg("bfloat16", control="s6_state_bf16")
    scan = family.scan_alone(params, batch["x"], low)
    assert max(scan.values()) > 10 * limits["scan_alone_tolerance"], scan

    bits = toy_cfg("bfloat16", operand_mantissa_bits=3)
    found = family.comparisons(params, batch["x"], batch["y"], bits)
    failed = [
        key for key, (value, _) in family.worst_of(found).items()
        if not value <= limits[key]
    ]
    assert "gradient_tolerance" in failed, family.worst_of(found)


def test_a_reading_that_is_not_finite_fails_and_names_its_leaf(capsys):
    cfg, _, _, params, batch = toy("float32")
    params["block_2"]["mamba"]["A_log"] = params["block_2"]["mamba"][
        "A_log"
    ].at[3, 5].set(jnp.nan)
    got = family.reference_loss(params, batch["x"], batch["y"], cfg)
    assert got == float("inf")
    said = capsys.readouterr().err
    assert "NOT FINITE" in said and "['block_2']['mamba']['A_log']" in said


def test_the_published_keys_put_attention_at_7_and_21():
    hf = loader.load_json(os.path.join(CONFIGS, "jamba2_3b_cut.json"))
    whole = {**hf, "num_hidden_layers": hf["published"]["num_hidden_layers"]}
    config = jamba.JambaConfig.from_hf(whole)
    kinds = config.layer_types
    assert len(kinds) == 28
    assert [i for i, k in enumerate(kinds) if k == jamba.ATTENTION] == [7, 21]
    assert kinds == jamba.JambaConfig().layer_types
    assert reference.layer_types(whole) == list(kinds)
    # the cut: one whole period
    cut = jamba.JambaConfig.from_hf(hf).layer_types
    assert len(cut) == 14 and cut.count(jamba.MAMBA) == 13
    assert cut[7] == jamba.ATTENTION
    assert (config.ssm_inner, config.ssm_state, config.dt_rank) == (
        5120, 16, 160
    )
    assert (config.num_heads, config.num_kv_heads, config.head_dim) == (
        20, 1, 128
    )
    transformers = pytest.importorskip("transformers")
    theirs = transformers.JambaConfig(**{
        k: whole[k] for k in (
            "num_hidden_layers", "attn_layer_period", "attn_layer_offset",
            "expert_layer_period", "expert_layer_offset", "num_experts",
        )
    })
    assert tuple(theirs.layers_block_type) == kinds
    assert set(theirs.layers_num_experts) == {1}


@pytest.mark.parametrize("key, value", [
    ("num_experts", 16), ("mamba_proj_bias", True),
    ("sliding_window", 4096), ("tie_word_embeddings", False),
])
def test_what_the_family_does_not_build_is_refused_with_the_reason(
    key, value
):
    hf = loader.load_json(os.path.join(CONFIGS, "jamba2_3b_cut.json"))
    with pytest.raises(ValueError, match=f"no {key} = "):
        jamba.JambaConfig.from_hf({**hf, key: value})
    with pytest.raises(SystemExit, match=f"no {key} = "):
        family.build({**hf, key: value})


# -- the reference against the family's own code ------------------------------


def _theirs(cfg, params, torch, modeling):
    """``transformers``' ``JambaForCausalLM`` (slow path, float32,
    eager attention) with ``params`` copied in."""
    from transformers import JambaConfig

    theirs = JambaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        rms_norm_eps=cfg["rms_norm_eps"],
        attn_layer_period=cfg["attn_layer_period"],
        attn_layer_offset=cfg["attn_layer_offset"],
        num_experts=1, num_experts_per_tok=1,
        mamba_d_state=cfg["mamba_d_state"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"],
        mamba_dt_rank=cfg["mamba_dt_rank"], mamba_conv_bias=True,
        mamba_proj_bias=False, use_mamba_kernels=False,
        tie_word_embeddings=True, pad_token_id=None,
    )
    theirs._attn_implementation = "eager"
    model = modeling.JambaForCausalLM(theirs).float().eval()

    def tensor(a):
        return torch.tensor(np.asarray(a, np.float32))

    def linear(module, leaf):
        # nn.Linear keeps [out, in]
        module.weight.data = tensor(leaf["kernel"]).T.contiguous()

    # (name in their model -> path in ours), for the gradients
    names = {"model.embed_tokens.weight": "['wte']['embedding']"}
    model.model.embed_tokens.weight.data = tensor(params["wte"]["embedding"])
    model.model.final_layernorm.weight.data = tensor(
        params["final_layernorm"]["scale"]
    )
    names["model.final_layernorm.weight"] = "['final_layernorm']['scale']"
    for i, layer in enumerate(model.model.layers):
        p = params[f"block_{i}"]
        at, ours = f"model.layers.{i}.", f"['block_{i}']"
        for norm in ("input_layernorm", "pre_ff_layernorm"):
            getattr(layer, norm).weight.data = tensor(p[norm]["scale"])
            names[f"{at}{norm}.weight"] = f"{ours}['{norm}']['scale']"
        for name in ("gate_proj", "up_proj", "down_proj"):
            linear(getattr(layer.feed_forward, name), p["mlp"][name])
            names[f"{at}feed_forward.{name}.weight"] = (
                f"{ours}['mlp']['{name}']['kernel']"
            )
        if "mamba" in p:
            m, q = layer.mamba, p["mamba"]
            for name in ("in_proj", "x_proj", "out_proj"):
                linear(getattr(m, name), q[name])
                names[f"{at}mamba.{name}.weight"] = (
                    f"{ours}['mamba']['{name}']['kernel']"
                )
            # Conv1d keeps [channels, 1, K]; ours [K, channels]
            m.conv1d.weight.data = tensor(q["conv"]).T[:, None].contiguous()
            m.conv1d.bias.data = tensor(q["conv_bias"])
            m.dt_proj.weight.data = tensor(q["dt_proj"]).T.contiguous()
            m.dt_proj.bias.data = tensor(q["dt_bias"])
            m.A_log.data = tensor(q["A_log"])
            m.D.data = tensor(q["D"])
            for norm in ("dt_layernorm", "b_layernorm", "c_layernorm"):
                getattr(m, norm).weight.data = tensor(q[norm]["scale"])
                names[f"{at}mamba.{norm}.weight"] = (
                    f"{ours}['mamba']['{norm}']['scale']"
                )
            for theirs_name, our_name in (
                ("conv1d.weight", "conv"), ("conv1d.bias", "conv_bias"),
                ("dt_proj.weight", "dt_proj"), ("dt_proj.bias", "dt_bias"),
                ("A_log", "A_log"), ("D", "D"),
            ):
                names[f"{at}mamba.{theirs_name}"] = (
                    f"{ours}['mamba']['{our_name}']"
                )
        else:
            for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
                linear(getattr(layer.self_attn, name), p["attn"][name])
                names[f"{at}self_attn.{name}.weight"] = (
                    f"{ours}['attn']['{name}']['kernel']"
                )
    return model, names


def test_the_reference_is_transformers_jamba_for_causal_lm():
    """Seeded weights copied into ``transformers``' ``JambaForCausalLM``
    (``use_mamba_kernels=False``, float32, eager attention) at the
    toy's size: its logits, its loss and EVERY parameter's gradient
    are the reference's to 1e-5.  The order of ``x | z`` and of ``dt_r
    | B | C``, the causal padding and the taps' order, the three inner
    norms, ``dt_proj``'s bias, the skip and the gate, the kv head's
    repeat and the tied head are then not a reading of the reference's
    author; and the reference's two departures (the float32 read-out
    where the slow path rounds the state to the activations' type, the
    norm's scale before the cast back) are shown to change nothing in
    float32."""
    torch = pytest.importorskip("torch")
    modeling = pytest.importorskip(
        "transformers.models.jamba.modeling_jamba"
    )
    cfg, _, _, params, batch = toy("float32", seed=3)
    model, names = _theirs(cfg, params, torch, modeling)
    tokens = torch.tensor(np.asarray(batch["x"]), dtype=torch.long)
    targets = torch.tensor(np.asarray(batch["y"]), dtype=torch.long)
    logits = model(input_ids=tokens, use_cache=False).logits
    ours = reference.forward(params, batch["x"], cfg)
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(ours[b]), logits[b].detach().numpy(),
            rtol=1e-4, atol=2e-6,
        )
    loss = torch.nn.functional.cross_entropy(
        logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
    )
    loss.backward()
    want_loss, _, want = reference.gradients(
        params, batch["x"], batch["y"], cfg, lambda path: True
    )
    assert abs(float(want_loss) - float(loss.detach())) < 1e-5
    theirs = dict(model.named_parameters())
    theirs.pop("lm_head.weight", None)  # tied: the table's own
    assert set(names) == set(theirs) and set(names.values()) == set(want)
    for name, path in names.items():
        grad = theirs[name].grad.numpy()
        if name.endswith(("_proj.weight",)) and grad.ndim == 2:
            grad = grad.T
        if name.endswith("conv1d.weight"):
            grad = grad[:, 0].T
        assert relative(np.asarray(want[path]), grad) < 1e-5, (
            name, relative(np.asarray(want[path]), grad)
        )
