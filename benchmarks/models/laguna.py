"""The ``laguna`` family: how the benchmark builds the system's model,
optimizer and loss from a configuration file with ``model_type:
"laguna"`` (the HF key names plus the ``recipe``), and the plain
reference's loss for it (``laguna_reference.py``, beside this file).

A configuration of this family states a chip's SHARE of a layer:
``num_experts`` counts the experts held here, ``router_outputs`` the
experts the router scores (all of the layer's), ``first_expert_held``
where the held range starts.  The per-layer lists (``layer_types``,
``mlp_layer_types``, ``num_attention_heads_per_layer``,
``gating_types``) are as long as ``num_hidden_layers``.

The loss returns ``(loss, aux)`` and says so itself
(``loss_fn.has_aux``): ``make_train_step`` puts the ``moe.*`` and
``attn.*`` counters of ``aux`` into the step's metrics, ``worker.py``
unchanged.

**What ``correct`` compares.**  The harness compares one number, the
step program's first loss with :func:`reference_loss`'s.  As in the
``sarvam_mla`` family that number cannot tell bf16 from fewer bits, so
:func:`reference_loss` also compares the system's first GRADIENT with
the reference's, leaf by leaf (:func:`compared`: every block's
attention, so both kinds of layer with their windows, rope rules and
gates, its norms and router, and the held experts of the last block),
the worst leaf of each of two kinds against a limit of the
configuration's ``reference``, and answers ``inf`` where one fails.

``recipe.operand_mantissa_bits`` (absent in every cell) builds the
lower-precision CONTROL the limits are set against, as the
``sarvam_mla`` family's does (its ``_in_fewer_bits``).
"""

import sys

import loader
from dlrover_tpu.models.laguna import (
    Laguna,
    LagunaConfig,
    RopeRule,
    make_laguna_loss,
)
from dlrover_tpu.optim import adamw_bf16

sarvam = loader.load_module("models", "sarvam_mla")
reference = loader.load_module("models", "laguna_reference")
DTYPES = sarvam.DTYPES
routed = sarvam.routed


def rope_rule(rule: dict) -> RopeRule:
    """``rope_parameters[kind]`` as the model's rule."""
    own = dict(
        theta=float(rule["rope_theta"]),
        rotated=float(rule["partial_rotary_factor"]),
    )
    if rule["rope_type"] == "default":
        return RopeRule(**own)
    if rule["rope_type"] != "yarn":
        raise SystemExit(f"the laguna family has no rope {rule['rope_type']!r}")
    return RopeRule(
        factor=float(rule["factor"]),
        original_len=rule["original_max_position_embeddings"],
        beta_fast=float(rule["beta_fast"]),
        beta_slow=float(rule["beta_slow"]),
        attention_factor=float(rule["attention_factor"]), **own,
    )


def build(cfg):
    """``(model, optimizer, loss_fn)`` of the system under test."""
    recipe = cfg["recipe"]
    if recipe["optimizer"] != "adamw_bf16":
        raise SystemExit(f"unknown optimizer {recipe['optimizer']!r}")
    layers = cfg["num_hidden_layers"]
    for key, value in (
        ("attention_bias", False), ("tie_word_embeddings", False),
        ("norm_topk_prob", True), ("decoder_sparse_step", 1),
        ("gating", "per-head"), ("gating_types", ["per_head"] * layers),
        ("moe_apply_router_weight_on_input", False),
        ("moe_router_logit_softcapping", 0),
        ("mlp_only_layers", [
            i for i, kind in enumerate(cfg["mlp_layer_types"])
            if kind == "dense"
        ]),
    ):
        if cfg[key] != value:
            raise SystemExit(
                f"the laguna family has no {key} = {cfg[key]!r}"
            )
    for key in (
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
    ):
        if len(cfg[key]) != layers:
            raise SystemExit(f"{key} lists {len(cfg[key])} of {layers} layers")
    first, held = cfg["first_expert_held"], cfg["num_experts"]
    if first + held > cfg["router_outputs"]:
        raise SystemExit("the held experts pass the router's outputs")
    rules = cfg["rope_parameters"]
    model = Laguna(LagunaConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        hidden_dim=cfg["hidden_size"],
        head_dim=cfg["head_dim"],
        num_kv_heads=cfg["num_key_value_heads"],
        layer_types=tuple(cfg["layer_types"]),
        heads_per_layer=tuple(cfg["num_attention_heads_per_layer"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        sliding_window=cfg["sliding_window"],
        full_rope=rope_rule(rules["full_attention"]),
        sliding_rope=rope_rule(rules["sliding_attention"]),
        dense_dim=cfg["intermediate_size"],
        expert_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["shared_expert_intermediate_size"],
        num_experts=cfg["router_outputs"],
        experts_held=(first, held),
        top_k=cfg["num_experts_per_tok"],
        routed_scale=cfg["moe_routed_scaling_factor"],
        rms_eps=cfg["rms_norm_eps"],
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
    loss_fn = make_laguna_loss(model, num_chunks=recipe["loss_chunks"])
    if "operand_mantissa_bits" in recipe:
        loss_fn = sarvam._in_fewer_bits(
            loss_fn, recipe["operand_mantissa_bits"]
        )
    return model, optimizer, loss_fn


def compared(cfg):
    """Picks the leaves whose first gradient is compared: every
    block's attention (the flash kernels' three gradients, with and
    without a window and at both group sizes, reach ``q_proj``,
    ``k_proj`` and ``v_proj``; the gate's ``g_proj``; ``o_proj``), its
    norms and router, and the LAST block's held experts.  The other
    sparse layers' experts, the dense and shared feed-forwards and the
    vocabulary's leaves are left out for room: both sets of gradients
    stand on the chip beside the train state."""
    last = f"['block_{cfg['num_hidden_layers'] - 1}']"

    def pick(path: str) -> bool:
        return (
            "['attn']" in path or "['ln_" in path or "['router']" in path
            or (last in path and "['experts_w_" in path)
        )

    return pick


def comparisons(params, tokens, targets, cfg) -> dict:
    """The system (``build(cfg)``'s loss, as the step program runs
    it) against the plain reference on ``params`` and the batch:
    ``loss`` (the reference's) and ``gradients`` (:func:`compared`
    leaf -> ``|system - reference| / |reference|`` of the first
    gradient)."""
    _, _, loss_fn = build(cfg)
    batch = {"x": tokens, "y": targets}
    _, _, system = reference.base.gradients_of(
        loss_fn, compared(cfg), params, batch
    )
    loss, _, wanted = reference.gradients(
        params, tokens, targets, cfg, compared(cfg)
    )
    differences = sarvam._differences(system, wanted)
    return {
        "loss": float(loss),
        "gradients": {k: float(d) for k, d in differences.items()},
    }


def reference_loss(params, tokens, targets, cfg) -> float:
    """The plain reference's loss of ``params`` on the batch, or
    ``inf`` where the system's first gradient is further from the
    reference's than ``cfg["reference"]`` allows
    (``gradient_tolerance`` for the worst attention or norm leaf,
    ``routed_gradient_tolerance`` for the worst :func:`routed` leaf);
    the numbers and their limits go to stderr either way."""
    limits = cfg["reference"]
    found = comparisons(params, tokens, targets, cfg)
    leaves = found["gradients"]

    def key_of(leaf):
        return (
            "routed_gradient_tolerance" if routed(leaf)
            else "gradient_tolerance"
        )

    worst = {}
    for leaf, d in leaves.items():
        if not d <= worst.get(key_of(leaf), (-1.0, ""))[0]:
            worst[key_of(leaf)] = (d, leaf)
    print(
        f"laguna reference: first gradient over {len(leaves)} leaves, "
        "|difference| / |reference|: " + "; ".join(
            f"{leaf} {value:.4f} (limit {limits[key]})"
            for key, (value, leaf) in sorted(worst.items())
        ),
        file=sys.stderr, flush=True,
    )
    # every leaf, not the worst alone: a gradient that is not a
    # number is larger than nothing
    inside = all(d <= limits[key_of(leaf)] for leaf, d in leaves.items())
    return found["loss"] if inside else float("inf")
