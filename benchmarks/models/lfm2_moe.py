"""The ``lfm2_moe`` family (LFM2-24B-A2B): how the benchmark builds the
system's model, optimizer and loss from a configuration file with
``model_type: "lfm2_moe"`` (the HF key names plus the ``recipe``), and
the plain reference's loss for it (``lfm2_moe_reference.py``, beside
this file).

A configuration of this family states a chip's SHARE of a layer and a
pipeline stage's layers: ``num_experts`` counts the experts held
here, ``router_outputs`` the experts the router scores (all of the
layer's), ``first_expert_held`` where the held range starts;
``layer_types`` lists the mixer of each layer BUILT
(``num_hidden_layers`` entries; ``layers_held`` gives their published
indices, for the reader), the first ``num_dense_layers`` of which have
the dense feed-forward.

The loss returns ``(loss, aux)`` and says so itself
(``loss_fn.has_aux``): ``make_train_step`` puts the ``sconv.*`` and
``moe.*`` counters of ``aux`` into the step's metrics and adds its
``state_updates`` (the router bias's rule) to the parameters,
``worker.py`` unchanged.

**What ``correct`` compares.**  The harness compares one number, the
step program's first loss with :func:`reference_loss`'s.  As in the
``sarvam_mla`` family that number cannot tell bf16 from fewer bits, so
:func:`reference_loss` makes further comparisons itself, each against
a limit of the configuration's ``reference``, and answers ``inf``
where one fails: the system's first GRADIENT against the reference's,
leaf by leaf (:func:`compared`; the worst leaf of each of two kinds,
:func:`kind_of`, and the routers' leaves together), the bias deltas
the loss hands the step against the rule applied to the reference's
own counts, the ``sconv.out_rms_max`` counter against the reference's
largest rms of ``y``, and the first conv layer's mixer ALONE on its
own operands (:func:`mixer_alone`): in the whole model every leaf's
gradient, the taps' too, stands 0.07-0.13 from the reference's for
the top-k choices that bf16 flips in eight expert layers, which hides
what the mixer's own arithmetic does to it.

``recipe.operand_mantissa_bits`` (absent in every cell) builds the
lower-precision CONTROL of the matmuls' operands, as the
``sarvam_mla`` family's does (its ``_in_fewer_bits``);
``recipe.control: "sconv_mix_bf16"`` (absent in every cell) builds the
control of the mixer's own arithmetic: everything between ``W_in`` and
``W_out`` in the compute type (bf16) where the configuration states
float32, the products, the taps' sums and the taps' gradient's sums
(:func:`_mix_in_low_precision`).
"""

import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

import loader
from dlrover_tpu.models import lfm2_moe as system
from dlrover_tpu.optim import adamw_bf16

sarvam = loader.load_module("models", "sarvam_mla")
nemotron = loader.load_module("models", "nemotron_h")
reference = loader.load_module("models", "lfm2_moe_reference")
DTYPES = sarvam.DTYPES


def build(cfg):
    """``(model, optimizer, loss_fn)`` of the system under test."""
    recipe = cfg["recipe"]
    if recipe["optimizer"] != "adamw_bf16":
        raise SystemExit(f"unknown optimizer {recipe['optimizer']!r}")
    for key, value in (
        ("conv_bias", False), ("norm_topk_prob", True),
        ("use_expert_bias", True), ("tie_word_embeddings", True),
        ("rope_parameters", {
            "rope_theta": cfg["rope_parameters"]["rope_theta"],
            "rope_type": "default",
        }),
    ):
        if cfg[key] != value:
            raise SystemExit(
                f"the lfm2_moe family has no {key} = {cfg[key]!r}"
            )
    depth = cfg["num_hidden_layers"]
    for key in ("layer_types", "layers_held"):
        if len(cfg[key]) != depth:
            raise SystemExit(f"{key} lists {len(cfg[key])} of {depth} layers")
    first, held = cfg["first_expert_held"], cfg["num_experts"]
    if first + held > cfg["router_outputs"]:
        raise SystemExit("the held experts pass the router's outputs")
    model = system.Lfm2Moe(system.Lfm2MoeConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        hidden_dim=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        conv_kernel=cfg["conv_L_cache"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        dense_dim=cfg["intermediate_size"],
        expert_dim=cfg["moe_intermediate_size"],
        num_experts=cfg["router_outputs"],
        experts_held=(first, held),
        top_k=cfg["num_experts_per_tok"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        bias_update_rate=recipe["bias_update_rate"],
        rms_eps=cfg["norm_eps"],
        init_std=recipe["initializer_range"],
        attention_impl=recipe["attention"],
        remat=recipe["remat"],
        dtype=DTYPES[recipe["compute_dtype"]],
        param_dtype=DTYPES[recipe["param_dtype"]],
    ))
    optimizer = adamw_bf16(
        learning_rate=recipe["learning_rate"],
        weight_decay=recipe["weight_decay"],
    )
    loss_fn = system.make_lfm2_moe_loss(
        model, num_chunks=recipe["loss_chunks"]
    )
    if "operand_mantissa_bits" in recipe:
        loss_fn = sarvam._in_fewer_bits(
            loss_fn, recipe["operand_mantissa_bits"]
        )
    if "control" in recipe:
        if recipe["control"] != "sconv_mix_bf16":
            raise SystemExit(f"no control {recipe['control']!r}")
        loss_fn = _mix_in_low_precision(loss_fn)
    return model, optimizer, loss_fn


def _low_short_conv(bcu, taps, *, dtype):
    """``short_conv``'s plain form with NOTHING in float32: the two
    products, the shifted adds and (by autodiff) every sum of the
    backward in ``dtype``."""
    k, c = taps.shape
    s = bcu.shape[1]
    gate_b, gate_c, u = (
        bcu[..., w * c:(w + 1) * c].astype(dtype) for w in range(3)
    )
    v = jnp.pad(gate_b * u, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(v[:, j:j + s] * taps[j].astype(dtype) for j in range(k))
    return gate_c * conv


def _mix_in_low_precision(loss_fn):
    """The control: ``loss_fn`` traced with the model's ``short_conv``
    replaced by :func:`_low_short_conv`."""

    def control(params, batch):
        with mock.patch.object(system, "short_conv", _low_short_conv):
            return loss_fn(params, batch)

    control.has_aux = True
    return control


# The flash backward at 32 query heads of 64 over 8 at 8192 tokens asks
# for 62.84 MB of scoped VMEM; in the step's program the compiler grants
# it, in the gradient alone it stops at 62.00 ("Scoped allocation with
# size 62.84M and limit 62.00M": offline compile, PR 63; the hybrid's
# family met the same at 30 x 8192 x 128, PERF.md section 7).  The
# comparison's program asks for the room by name.
SCOPED_VMEM_KIB = 98304


def system_gradients_of(loss_fn, pick, params, batch):
    """``sarvam_mla_reference.gradients_of`` for the SYSTEM's loss:
    ``(loss, aux, {path: gradient})`` for the leaves ``pick`` names, in
    one program, on the chip compiled with ``SCOPED_VMEM_KIB`` of
    scoped VMEM."""
    paths, tree = jax.tree_util.tree_flatten_with_path(params)
    names = [jax.tree_util.keystr(path) for path, _ in paths]
    picked = [pick(name) for name in names]

    def of(some, rest, batch):
        some, rest = iter(some), iter(rest)
        return loss_fn(jax.tree_util.tree_unflatten(tree, [
            next(some) if mine else next(rest) for mine in picked
        ]), batch)

    options = {}
    if jax.default_backend() == "tpu":
        options = {"xla_tpu_scoped_vmem_limit_kib": str(SCOPED_VMEM_KIB)}
    leaves = [leaf for _, leaf in paths]
    (value, aux), grads = jax.jit(
        jax.value_and_grad(of, has_aux=True), compiler_options=options
    )(
        [leaf for leaf, mine in zip(leaves, picked) if mine],
        [leaf for leaf, mine in zip(leaves, picked) if not mine],
        batch,
    )
    return value, aux, dict(zip(
        [name for name, mine in zip(names, picked) if mine], grads
    ))


def compared(cfg):
    """Picks the leaves whose first gradient is compared: every conv
    mixer's ``taps``, ``in_proj`` (the kernel's three gradients in one
    array reach it) and ``out_proj``; every attention layer (the flash
    kernels at 32 heads of 64 over 8, the per-head norms' scales, the
    four projections); every block's norms and the final one; every
    router; the LAST block's held experts; the TIED table (the head's
    gradient and the lookup's, summed).  The other sparse layers'
    experts and the dense feed-forward are left out for room: both
    sets of gradients stand on the chip beside the train state."""
    last = f"['block_{cfg['num_hidden_layers'] - 1}']"

    def pick(path: str) -> bool:
        return (
            "['short_conv']" in path or "['attn']" in path
            or "_norm']" in path or "['router']" in path
            or "['wte']" in path
            or (last in path and "['experts_w_" in path)
        )

    return pick


def kind_of(cfg):
    """``kind(path)``: the limit a leaf's gradient is held to.
    ``routed``: a router's or a held expert's (``sarvam_mla.routed``:
    its gradient sums over the tokens that CHOSE an expert, so every
    top-k choice a bf16 rounding flips moves it whole) and, because
    this family's sparse layers have NO shared expert, a sparse
    block's ``ffn_norm`` scale too (``mimo_v2.routed_in``'s reason).
    ``gradient``: the rest."""
    sparse = tuple(
        f"['block_{i}']['ffn_norm']" for i in range(
            cfg["num_dense_layers"], cfg["num_hidden_layers"]
        )
    )

    def kind(path: str) -> str:
        if sarvam.routed(path) or path.startswith(sparse):
            return "routed_gradient_tolerance"
        return "gradient_tolerance"

    return kind


def mixer_alone(params, tokens, cfg) -> float:
    """The first conv layer's mixer ALONE: the system's
    ``short_conv`` (the form ``build(cfg)``'s loss calls: a control's
    stands in for it) against the plain float32 form on the SAME
    operands, ``|difference| / |reference|`` of the TAPS' gradient.
    The operands are that layer's own: the first sequence's embedding
    rows through its norm and ``W_in``, rounded once to the compute
    type; the cotangent is the ``B`` window a row on; the taps in
    float32 so that their gradient leaves the kernel unrounded.  The
    configuration states every product and sum between ``W_in`` and
    ``W_out`` in float32: then the two sides sum the same float32
    terms, where a mixer in bf16 rounds each of them."""
    recipe = cfg["recipe"]
    dtype = DTYPES[recipe["compute_dtype"]]
    block = params[f"block_{cfg['layer_types'].index('conv')}"]
    hidden = cfg["hidden_size"]
    with jax.default_matmul_precision("highest"):
        x = reference.base._rms_norm(
            reference.base._embed(params["wte"]["embedding"], tokens[0]),
            block["operator_norm"]["scale"], cfg["norm_eps"],
        )
        bcu = (
            x @ block["short_conv"]["in_proj"]["kernel"].astype(jnp.float32)
        ).astype(dtype)
    taps = block["short_conv"]["taps"].astype(jnp.float32)
    dy = jnp.roll(bcu[:, :hidden], 1, axis=0)
    mixer = (
        _low_short_conv if recipe.get("control") == "sconv_mix_bf16"
        else system.short_conv
    )
    _, back = jax.vjp(
        lambda t: mixer(bcu[None], t, dtype=dtype), taps
    )
    _, wanted = jax.vjp(
        lambda t: reference.mix(bcu.astype(jnp.float32), t), taps
    )
    (got,), (want,) = back(dy[None]), wanted(dy.astype(jnp.float32))
    return float(
        jnp.linalg.norm(got.astype(jnp.float32) - want)
        / jnp.linalg.norm(want)
    )


def comparisons(params, tokens, targets, cfg) -> dict:
    """The system (``build(cfg)``'s loss, as the step program runs
    it) against the plain reference on ``params`` and the batch:
    ``loss`` (the reference's), ``gradients`` (:func:`compared` leaf
    -> ``|system - reference| / |reference|`` of the first gradient),
    ``routers_rms`` (``nemotron_h.routers_rms`` of them), ``bias``
    (the share of the routers' bias deltas that differ from the rule
    applied to the reference's own counts), ``out_rms``
    (``|sconv.out_rms_max / the reference's largest rms of y - 1|``),
    ``out_rms_max`` (system, reference) and ``mixer_taps``
    (:func:`mixer_alone`); ``system_loss`` is the gradient program's
    own."""
    _, _, loss_fn = build(cfg)
    system_loss, aux, got = system_gradients_of(
        loss_fn, compared(cfg), params, {"x": tokens, "y": targets}
    )
    loss, said, wanted = reference.gradients(
        params, tokens, targets, cfg, compared(cfg)
    )
    differences = sarvam._differences(got, wanted)
    deltas = np.stack([
        np.asarray(layer["moe"]["select_bias"])
        for _, layer in sorted(
            aux["state_updates"].items(),
            key=lambda item: int(item[0].rpartition("_")[2]),
        )
    ])
    # the system's is the rms over the batch's sequences together
    wanted_rms = float(np.sqrt(np.max(np.mean(
        np.square(np.asarray(said["out_rms"])), axis=0
    ))))
    gradients = {k: float(d) for k, d in differences.items()}
    return {
        "loss": float(loss),
        "system_loss": float(system_loss),
        "gradients": gradients,
        "routers_rms": nemotron.routers_rms(gradients),
        "bias": float(np.mean(deltas != reference.base.bias_deltas(
            said["counts"], cfg["recipe"]["bias_update_rate"]
        ))),
        "out_rms": abs(
            float(aux["sconv.out_rms_max"]) / wanted_rms - 1.0
        ),
        "out_rms_max": (float(aux["sconv.out_rms_max"]), wanted_rms),
        "mixer_taps": mixer_alone(params, tokens, cfg),
    }


def worst_of(found: dict, cfg) -> dict:
    """``{limit's key: (reading, what read it)}`` of
    :func:`comparisons`' result: the worst leaf of each
    :func:`kind_of`, the routers together, the bias rule, the mixers'
    output and the mixer alone."""
    kind = kind_of(cfg)
    worst = {}
    for leaf, d in found["gradients"].items():
        if not d <= worst.get(kind(leaf), (-1.0, ""))[0]:
            worst[kind(leaf)] = (d, leaf)
    worst["router_rms_tolerance"] = (found["routers_rms"], "the routers")
    worst["bias_update_tolerance"] = (found["bias"], "share of the deltas")
    worst["out_rms_tolerance"] = (found["out_rms"], "sconv.out_rms_max")
    worst["mixer_taps_tolerance"] = (
        found["mixer_taps"], "the first conv mixer alone"
    )
    return worst


def reference_loss(params, tokens, targets, cfg) -> float:
    """The plain reference's loss of ``params`` on the batch, or
    ``inf`` where the system is further from the reference than
    ``cfg["reference"]`` allows: the worst leaf of each
    :func:`kind_of` of the first gradient, ``router_rms_tolerance``,
    ``bias_update_tolerance``, ``out_rms_tolerance``,
    ``mixer_taps_tolerance``; the numbers and their limits go to
    stderr either way."""
    limits = cfg["reference"]
    found = comparisons(params, tokens, targets, cfg)
    leaves = found["gradients"]
    worst = worst_of(found, cfg)
    print(
        f"lfm2_moe reference: first gradient over {len(leaves)} "
        "leaves, |difference| / |reference|, the bias rule and the "
        "conv mixers' output: " + "; ".join(
            f"{key} {value:.3g} at {what} (limit {limits[key]})"
            for key, (value, what) in sorted(worst.items())
        ) + "; sconv.out_rms_max {:.6f} | {:.6f} (system | "
        "reference)".format(*found["out_rms_max"])
        + f"; the reference's loss {found['loss']:.6f}",
        file=sys.stderr, flush=True,
    )
    # every leaf, not the worst alone: a gradient that is not a
    # number is larger than nothing
    kind = kind_of(cfg)
    inside = all(
        value <= limits[key] for key, (value, _) in worst.items()
    ) and all(d <= limits[kind(leaf)] for leaf, d in leaves.items())
    return found["loss"] if inside else float("inf")
