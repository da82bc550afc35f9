"""The ``nemotron_h`` family: how the benchmark builds the system's
model, optimizer and loss from a configuration file with ``model_type:
"nemotron_h"`` (the HF key names plus the ``recipe``), and the plain
reference's loss for it (``nemotron_h_reference.py``, beside this
file).

A configuration of this family states a chip's SHARE of a layer:
``n_routed_experts`` counts the experts held here, ``router_outputs``
the experts the router scores (all of the layer's),
``first_expert_held`` where the held range starts;
``hybrid_override_pattern`` has one character a layer (``M`` | ``E`` |
``*``) and is ``num_hidden_layers`` long.

The loss returns ``(loss, aux)`` and says so itself
(``loss_fn.has_aux``): ``make_train_step`` puts the ``ssm.*`` and
``moe.*`` counters of ``aux`` into the step's metrics and adds its
``state_updates`` (the router bias's rule) to the parameters,
``worker.py`` unchanged.

**What ``correct`` compares.**  The harness compares one number, the
step program's first loss with :func:`reference_loss`'s.  As in the
``sarvam_mla`` family that number cannot tell bf16 from fewer bits, so
:func:`reference_loss` makes further comparisons itself, each against
a limit of the configuration's ``reference``, and answers ``inf``
where one fails: the system's first GRADIENT against the reference's,
leaf by leaf (:func:`compared`; the worst leaf of each of three kinds,
:func:`kind_of`, and the routers' leaves together, :func:`routers_rms`),
the bias deltas the loss hands the step against the rule applied to
the reference's own counts, and the ``ssm.*`` counter of the final
states against the token-by-token recurrence's.

``recipe.operand_mantissa_bits`` (absent in every cell) builds the
lower-precision CONTROL the limits are set against, as the
``sarvam_mla`` family's does (its ``_in_fewer_bits``).
"""

import sys

import numpy as np

import loader
from dlrover_tpu.models.nemotron_h import (
    NemotronH,
    NemotronHConfig,
    make_nemotron_h_loss,
)
from dlrover_tpu.optim import adamw_bf16

sarvam = loader.load_module("models", "sarvam_mla")
reference = loader.load_module("models", "nemotron_h_reference")
DTYPES = sarvam.DTYPES


def build(cfg):
    """``(model, optimizer, loss_fn)`` of the system under test."""
    recipe = cfg["recipe"]
    if recipe["optimizer"] != "adamw_bf16":
        raise SystemExit(f"unknown optimizer {recipe['optimizer']!r}")
    for key, value in (
        ("mamba_hidden_act", "silu"), ("mlp_hidden_act", "relu2"),
        ("attention_bias", False), ("mamba_proj_bias", False),
        ("mlp_bias", False), ("use_bias", False),
        ("use_conv_bias", True), ("tie_word_embeddings", False),
        ("n_group", 1), ("topk_group", 1), ("norm_topk_prob", True),
        ("n_shared_experts", 1), ("sliding_window", None),
        ("norm_eps", cfg["layer_norm_epsilon"]),
    ):
        if cfg[key] != value:
            raise SystemExit(
                f"the nemotron_h family has no {key} = {cfg[key]!r}"
            )
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"] or set(pattern) - set("ME*"):
        raise SystemExit(
            f"hybrid_override_pattern {pattern!r} does not name "
            f"{cfg['num_hidden_layers']} layers of M, E and *"
        )
    first, held = cfg["first_expert_held"], cfg["n_routed_experts"]
    if first + held > cfg["router_outputs"]:
        raise SystemExit("the held experts pass the router's outputs")
    model = NemotronH(NemotronHConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        pattern=pattern,
        hidden_dim=cfg["hidden_size"],
        ssm_heads=cfg["mamba_num_heads"],
        ssm_head_dim=cfg["mamba_head_dim"],
        ssm_groups=cfg["n_groups"],
        ssm_state=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"],
        chunk_size=cfg["chunk_size"],
        time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        expert_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["moe_shared_expert_intermediate_size"],
        num_experts=cfg["router_outputs"],
        experts_held=(first, held),
        top_k=cfg["num_experts_per_tok"],
        routed_scale=cfg["routed_scaling_factor"],
        bias_update_rate=recipe["bias_update_rate"],
        rms_eps=cfg["layer_norm_epsilon"],
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
    loss_fn = make_nemotron_h_loss(
        model, num_chunks=recipe["loss_chunks"]
    )
    if "operand_mantissa_bits" in recipe:
        loss_fn = sarvam._in_fewer_bits(
            loss_fn, recipe["operand_mantissa_bits"]
        )
    return model, optimizer, loss_fn


def _last(pattern: str, kind: str) -> str:
    return f"['block_{pattern.rindex(kind)}']"


def compared(cfg):
    """Picks the leaves whose first gradient is compared: every leaf
    of the FIRST and the LAST state-space layer (``A_log``, ``D``,
    ``dt_bias``, the convolution and its bias, the grouped norm, both
    projections: the scan's five gradients reach them all), every
    attention layer (the flash kernels at 32 heads over 2), every
    layer's norm, every router, and the LAST expert layer's held
    experts (the ungated grouped matmuls and the held range's dispatch
    and combine).  The other state-space layers', the other expert
    layers', the shared experts' and the vocabulary's leaves are left
    out for room: both sets of gradients stand on the chip beside the
    train state."""
    pattern = cfg["hybrid_override_pattern"]
    ends = (f"['block_{pattern.index('M')}']", _last(pattern, "M"))
    last_experts = _last(pattern, "E")

    def pick(path: str) -> bool:
        return (
            "['attn']" in path or "['norm']['scale']" in path
            or "['norm_f']" in path or "['router']" in path
            or ("['ssm']" in path and path.startswith(ends))
            or (last_experts in path and "['experts_w_" in path)
        )

    return pick


def kind_of(path: str) -> str:
    """The limit a leaf's gradient is held to.  ``routed``: a router's
    or a held expert's, which sums over the tokens that CHOSE an
    expert, so every top-k choice that a bf16 rounding flips moves it
    whole (as in the ``sarvam_mla`` family).  ``decay``: the three
    per-head vectors of a state-space layer that reach the loss
    through ``exp(dt A)`` alone (``A_log``, ``dt_bias``) or through
    one multiply-add (``D``): 64 numbers each, every one a sum over
    all tokens of terms of both signs.  ``gradient``: the rest."""
    if sarvam.routed(path):
        return "routed_gradient_tolerance"
    if path.endswith(("['A_log']", "['dt_bias']", "['D']")):
        return "decay_gradient_tolerance"
    return "gradient_tolerance"


def routers_rms(gradients: dict) -> float:
    """The root mean square of the routers' differences.  ONE router's
    reading swings with the seed (0.07 to 0.55 in bf16 at the cell's
    sizes: which of a layer's choices a rounding flips), the eight
    together do not (0.21 to 0.30, where 3 bits of mantissa read 0.66
    to 0.71): this is the number that tells the precisions apart, the
    leaf's own limit the one that finds a missing gradient."""
    routers = [d for leaf, d in gradients.items() if "['router']" in leaf]
    return float(np.sqrt(np.mean(np.square(routers))))


def comparisons(params, tokens, targets, cfg) -> dict:
    """The system (``build(cfg)``'s loss, as the step program runs
    it) against the plain reference on ``params`` and the batch:
    ``loss`` (the reference's), ``gradients`` (:func:`compared` leaf
    -> ``|system - reference| / |reference|`` of the first gradient),
    ``routers_rms`` (:func:`routers_rms` of them), ``bias`` (the share
    of the routers' bias deltas that differ from the rule applied to
    the reference's own counts) and ``state_rms``
    (``ssm.state_rms_max`` over the token-by-token recurrence's
    largest final-state rms, less 1)."""
    _, _, loss_fn = build(cfg)
    _, aux, system = reference.base.gradients_of(
        loss_fn, compared(cfg), params, {"x": tokens, "y": targets}
    )
    loss, said, wanted = reference.gradients(
        params, tokens, targets, cfg, compared(cfg)
    )
    differences = sarvam._differences(system, wanted)
    deltas = np.stack([
        np.asarray(layer["moe"]["select_bias"])
        for _, layer in sorted(
            aux["state_updates"].items(),
            key=lambda item: int(item[0].rpartition("_")[2]),
        )
    ])
    # the system's is the rms over the batch's sequences together
    wanted_rms = float(np.sqrt(np.max(np.mean(
        np.square(np.asarray(said["state_rms"])), axis=0
    ))))
    gradients = {k: float(d) for k, d in differences.items()}
    return {
        "loss": float(loss),
        "gradients": gradients,
        "routers_rms": routers_rms(gradients),
        "bias": float(np.mean(deltas != reference.base.bias_deltas(
            said["counts"], cfg["recipe"]["bias_update_rate"]
        ))),
        "state_rms": abs(
            float(aux["ssm.state_rms_max"]) / wanted_rms - 1.0
        ),
    }


def reference_loss(params, tokens, targets, cfg) -> float:
    """The plain reference's loss of ``params`` on the batch, or
    ``inf`` where the system is further from the reference than
    ``cfg["reference"]`` allows: the worst leaf of each
    :func:`kind_of` of the first gradient, ``router_rms_tolerance``,
    ``bias_update_tolerance``, ``state_rms_tolerance``; the numbers
    and their limits go to stderr either way."""
    limits = cfg["reference"]
    found = comparisons(params, tokens, targets, cfg)
    leaves = found["gradients"]
    worst = {}
    for leaf, d in leaves.items():
        if not d <= worst.get(kind_of(leaf), (-1.0, ""))[0]:
            worst[kind_of(leaf)] = (d, leaf)
    worst["router_rms_tolerance"] = (found["routers_rms"], "the routers")
    worst["bias_update_tolerance"] = (found["bias"], "share of the deltas")
    worst["state_rms_tolerance"] = (
        found["state_rms"], "ssm.state_rms_max"
    )
    print(
        f"nemotron_h reference: first gradient over {len(leaves)} "
        "leaves, |difference| / |reference|, the bias rule and the "
        "final states: " + "; ".join(
            f"{what} {value:.4f} (limit {limits[key]})"
            for key, (value, what) in sorted(worst.items())
        ) + f"; the reference's loss {found['loss']:.6f}",
        file=sys.stderr, flush=True,
    )
    # every leaf, not the worst alone: a gradient that is not a
    # number is larger than nothing
    inside = all(
        value <= limits[key] for key, (value, _) in worst.items()
    ) and all(d <= limits[kind_of(leaf)] for leaf, d in leaves.items())
    return found["loss"] if inside else float("inf")
