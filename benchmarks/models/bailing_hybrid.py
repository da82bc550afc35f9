"""The ``bailing_hybrid`` family (Ling-3.0-flash): how the benchmark
builds the system's model, optimizer and loss from a configuration
file with ``model_type: "bailing_hybrid"`` (the HF key names plus the
``recipe``), and the plain reference's loss for it
(``bailing_hybrid_reference.py``, beside this file).

A configuration of this family states a chip's SHARE of a layer:
``num_experts`` counts the experts held here, ``router_outputs`` the
experts the router scores (all of the layer's, in ``n_group`` groups),
``first_expert_held`` where the held range starts; ``layers_held``
gives the PUBLISHED index of each layer built (a pipeline stage's own
layers), which sets the layer's kind: latent attention where ``(index
+ 1) % layer_group_size == 0``, Kimi Delta Attention elsewhere; the
first ``first_k_dense_replace`` of the layers built have the dense
feed-forward.

The loss returns ``(loss, aux)`` and says so itself
(``loss_fn.has_aux``): ``make_train_step`` puts the ``kda.*`` and
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
:func:`kind_of`, and the routers' leaves together), the bias deltas
the loss hands the step against the rule applied to the reference's
own counts, and the ``kda.state_rms_max`` counter of the rule's final
states against the token-by-token recurrence's.

``recipe.operand_mantissa_bits`` (absent in every cell) builds the
lower-precision CONTROL the limits are set against, as the
``sarvam_mla`` family's does (its ``_in_fewer_bits``); ``control`` of
:func:`comparisons` names a MECHANISM the reference then leaves out
(``bailing_hybrid_reference.block_kwargs``): each must fall outside a
limit too.
"""

import sys

import numpy as np

import loader
from dlrover_tpu.models.bailing_hybrid import (
    BailingHybrid,
    BailingHybridConfig,
    make_bailing_hybrid_loss,
)
from dlrover_tpu.optim import adamw_bf16

sarvam = loader.load_module("models", "sarvam_mla")
nemotron = loader.load_module("models", "nemotron_h")
reference = loader.load_module("models", "bailing_hybrid_reference")
DTYPES = sarvam.DTYPES


def layer_ids(cfg):
    ids = cfg.get("layers_held") or range(cfg["num_hidden_layers"])
    if len(ids) != cfg["num_hidden_layers"]:
        raise SystemExit("layers_held does not name every layer built")
    return tuple(ids)


def build(cfg):
    """``(model, optimizer, loss_fn)`` of the system under test."""
    recipe = cfg["recipe"]
    if recipe["optimizer"] != "adamw_bf16":
        raise SystemExit(f"unknown optimizer {recipe['optimizer']!r}")
    for key, value in (
        ("hidden_act", "silu"), ("tie_word_embeddings", False),
        ("use_qk_norm", True), ("moe_router_enable_expert_bias", True),
        ("score_function", "sigmoid"), ("topk_method", "noaux_tc"),
        ("norm_topk_prob", True), ("kda_safe_gate", True),
        ("no_kda_lora", True), ("use_kda_lora", False),
        ("linear_silu", True), ("group_norm_size", 1),
        ("num_kv_heads_for_linear_attn", 0), ("q_lora_rank", None),
        ("rope_scaling", None), ("rope_interleave", True),
        ("gated_attention_proj_granularity_type", "head_wise"),
        ("use_bias", False), ("use_qkv_bias", False),
        ("use_nGPT", False), ("value_norm", False),
        ("up_proj_norm", False), ("scale_router_input", False),
        ("use_mla_nope", False),
        ("num_key_value_heads", cfg["num_attention_heads"]),
        ("qk_head_dim", cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]),
        ("rotary_dim", cfg["qk_rope_head_dim"]),
    ):
        if cfg[key] != value:
            raise SystemExit(
                f"the bailing_hybrid family has no {key} = {cfg[key]!r}"
            )
    first, held = cfg["first_expert_held"], cfg["num_experts"]
    if first + held > cfg["router_outputs"]:
        raise SystemExit("the held experts pass the router's outputs")
    ids = layer_ids(cfg)
    # the clamp of each layer built, the routed experts' and the
    # shared one's (the model raises on a non-zero entry)
    limits = tuple(
        limit for key in (
            "expert_swiglu_limit_list", "share_expert_swiglu_limit_list",
        ) for limit in (cfg[key][i] for i in ids)
    )
    model = BailingHybrid(BailingHybridConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        num_layers=cfg["num_hidden_layers"],
        layer_group_size=cfg["layer_group_size"],
        layer_ids=ids,
        first_dense=cfg["first_k_dense_replace"],
        num_heads=cfg["num_attention_heads"],
        hidden_dim=cfg["hidden_size"],
        head_dim=cfg["head_dim"],
        conv_kernel=cfg["short_conv_kernel_size"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        kv_lora_rank=cfg["kv_lora_rank"],
        rope_theta=float(cfg["rope_theta"]),
        dense_dim=cfg["intermediate_size"],
        expert_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["moe_shared_expert_intermediate_size"],
        shared_experts=cfg["num_shared_experts"],
        num_experts=cfg["router_outputs"],
        experts_held=(first, held),
        top_k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scale=cfg["routed_scaling_factor"],
        bias_update_rate=recipe["bias_update_rate"],
        swiglu_limits=limits,
        nextn_layers=cfg["num_nextn_predict_layers"],
        nextn_loss_weight=cfg["mtp_loss_scaling_factor"],
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
    loss_fn = make_bailing_hybrid_loss(
        model, num_chunks=recipe["loss_chunks"]
    )
    if "operand_mantissa_bits" in recipe:
        loss_fn = sarvam._in_fewer_bits(
            loss_fn, recipe["operand_mantissa_bits"]
        )
    return model, optimizer, loss_fn


def compared(cfg):
    """Picks the leaves whose first gradient is compared: every leaf
    of the FIRST and the LAST Kimi-Delta-Attention layer (the three
    projections and their convolutions' taps, ``f_proj``, ``A_log``,
    ``dt_bias``, ``b_proj``, the head norm, the output gate ``g_proj``
    and ``o_proj``: the rule's five gradients reach them all), every
    latent-attention layer (the flash kernels at 192 | 128 and the
    head-wise gate), every block's norms, every router (under the
    group mask), and the LAST expert layer's held experts.  The other
    KDA layers', the other expert layers', the dense and shared
    feed-forwards' and the vocabulary's leaves are left out for room:
    both sets of gradients stand on the chip beside the train state."""
    kinds = reference.kinds(cfg)
    ends = (
        f"['block_{kinds.index(reference.KDA)}']",
        f"['block_{len(kinds) - 1 - kinds[::-1].index(reference.KDA)}']",
    )
    last = f"['block_{cfg['num_hidden_layers'] - 1}']"

    def pick(path: str) -> bool:
        return (
            "['attn']" in path or "['ln_" in path or "['router']" in path
            or ("['kda']" in path and path.startswith(ends))
            or (last in path and "['experts_w_" in path)
        )

    return pick


def kind_of(path: str) -> str:
    """The limit a leaf's gradient is held to.  ``routed``: a router's
    or a held expert's, which sums over the tokens that CHOSE an
    expert, so every choice that a bf16 rounding flips moves it whole
    (as in the ``sarvam_mla`` family; here a flipped GROUP moves up to
    all eight).  ``decay``: the leaves that reach the loss through the
    log-decay alone (``A_log``, ``dt_bias``, ``f_proj``): every entry
    a sum over all tokens of terms of both signs, through a sigmoid
    that is flat at either end.  ``gradient``: the rest."""
    if sarvam.routed(path):
        return "routed_gradient_tolerance"
    if path.endswith(("['A_log']", "['dt_bias']")) or "['f_proj']" in path:
        return "decay_gradient_tolerance"
    return "gradient_tolerance"


def comparisons(params, tokens, targets, cfg, control=None) -> dict:
    """The system (``build(cfg)``'s loss, as the step program runs
    it) against the plain reference on ``params`` and the batch:
    ``loss`` (the reference's), ``gradients`` (:func:`compared` leaf
    -> ``|system - reference| / |reference|`` of the first gradient),
    ``routers_rms`` (``nemotron_h.routers_rms`` of them), ``bias``
    (the share of the routers' bias deltas that differ from the rule
    applied to the reference's own counts), ``state_rms``
    (``kda.state_rms_max`` over the token-by-token recurrence's
    largest final-state rms, less 1) and the counters both sides
    state (``log_decay_min``, ``groups_per_token``: system,
    reference).  ``control``: the mechanism the reference leaves out
    (None: none)."""
    _, _, loss_fn = build(cfg)
    _, aux, system = reference.base.gradients_of(
        loss_fn, compared(cfg), params, {"x": tokens, "y": targets}
    )
    loss, said, wanted = reference.gradients(
        params, tokens, targets, cfg, compared(cfg), control
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
        "routers_rms": nemotron.routers_rms(gradients),
        "bias": float(np.mean(deltas != reference.base.bias_deltas(
            said["counts"], cfg["recipe"]["bias_update_rate"]
        ))),
        "state_rms": abs(
            float(aux["kda.state_rms_max"]) / wanted_rms - 1.0
        ),
        "log_decay_min": (
            float(aux["kda.log_decay_min"]), float(said["log_decay_min"])
        ),
        "groups_per_token": (
            float(aux["moe.groups_per_token_mean"]),
            float(said["groups_per_token"]),
        ),
    }


def worst_of(found: dict) -> dict:
    """``{limit's key: (reading, what read it)}`` of
    :func:`comparisons`' result: the worst leaf of each
    :func:`kind_of`, the routers together, the bias rule and the
    final states."""
    worst = {}
    for leaf, d in found["gradients"].items():
        if not d <= worst.get(kind_of(leaf), (-1.0, ""))[0]:
            worst[kind_of(leaf)] = (d, leaf)
    worst["router_rms_tolerance"] = (found["routers_rms"], "the routers")
    worst["bias_update_tolerance"] = (found["bias"], "share of the deltas")
    worst["state_rms_tolerance"] = (
        found["state_rms"], "kda.state_rms_max"
    )
    return worst


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
    worst = worst_of(found)
    print(
        f"bailing_hybrid reference: first gradient over {len(leaves)} "
        "leaves, |difference| / |reference|, the bias rule and the "
        "rule's final states: " + "; ".join(
            f"{what} {value:.4f} (limit {limits[key]})"
            for key, (value, what) in sorted(worst.items())
        ) + "; least log-decay {:.4f} | {:.4f}, groups a token {:.4f} | "
        "{:.4f} (system | reference)".format(
            *found["log_decay_min"], *found["groups_per_token"]
        ) + f"; the reference's loss {found['loss']:.6f}",
        file=sys.stderr, flush=True,
    )
    # every leaf, not the worst alone: a gradient that is not a
    # number is larger than nothing
    inside = all(
        value <= limits[key] for key, (value, _) in worst.items()
    ) and all(d <= limits[kind_of(leaf)] for leaf, d in leaves.items())
    return found["loss"] if inside else float("inf")
