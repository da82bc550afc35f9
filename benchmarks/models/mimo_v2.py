"""The ``mimo_v2`` family: how the benchmark builds the system's model,
optimizer and loss from a configuration file with ``model_type:
"mimo_v2"`` (the HF key names plus the ``recipe``), and the plain
reference's loss for it (``mimo_v2_reference.py``, beside this file).

A configuration of this family states a chip's SHARE of a layer: the
four head counts (``num_attention_heads`` | ``num_key_value_heads`` in
a full layer, ``swa_*`` in a window layer) and ``n_routed_experts``
count what is held here, ``router_outputs`` the experts the router
scores (all of the layer's), ``first_expert_held`` where the held
range starts.  ``hybrid_layer_pattern`` and ``moe_layer_freq`` are as
long as ``num_hidden_layers``.

The loss returns ``(loss, aux)`` and says so itself
(``loss_fn.has_aux``): ``make_train_step`` puts the ``moe.*`` and
``attn.*`` counters of ``aux`` into the step's metrics and adds its
``state_updates`` (the router bias's rule) to the parameters,
``worker.py`` unchanged.

**What ``correct`` compares.**  The harness compares one number, the
step program's first loss with :func:`reference_loss`'s.  As in the
``sarvam_mla`` family that number cannot tell bf16 from fewer bits,
and a sink that takes a hundredth of a row's softmax it cannot see at
all, so :func:`reference_loss` also compares the system's first
GRADIENT with the reference's, leaf by leaf (:func:`compared`), the
worst leaf of each of three kinds against a limit of the
configuration's ``reference`` (attention and norms; the leaves
:func:`routed_in` names; the SINKS, whose gradient is zero in a program whose forward
has no sink and in one whose backward forgets it), and the bias deltas
against the rule on the reference's own counts, and answers ``inf``
where one fails.

``recipe.operand_mantissa_bits`` (absent in every cell) builds the
lower-precision CONTROL the limits are set against, as the
``sarvam_mla`` family's does (its ``_in_fewer_bits``);
``recipe.control`` (absent in every cell) builds the two controls of
the sink: ``"no_sink_forward"`` (every sink at -1e30: it takes nothing
of any softmax) and ``"no_sink_gradient"`` (the sink's gradient
stopped).
"""

import sys

import jax
import numpy as np

import loader
from dlrover_tpu.models import layers
from dlrover_tpu.models.mimo_v2 import (
    MiMoV2,
    MiMoV2Config,
    make_mimo_v2_loss,
)
from dlrover_tpu.optim import adamw_bf16

sarvam = loader.load_module("models", "sarvam_mla")
reference = loader.load_module("models", "mimo_v2_reference")
DTYPES = sarvam.DTYPES


def build(cfg):
    """``(model, optimizer, loss_fn)`` of the system under test."""
    recipe = cfg["recipe"]
    if recipe["optimizer"] != "adamw_bf16":
        raise SystemExit(f"unknown optimizer {recipe['optimizer']!r}")
    depth = cfg["num_hidden_layers"]
    for key, value in (
        ("attention_bias", False), ("tie_word_embeddings", False),
        ("hidden_act", "silu"), ("norm_topk_prob", True),
        ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
        ("n_group", 1), ("topk_group", 1), ("n_shared_experts", None),
        ("attention_projection_layout", "fused_qkv"),
        ("swa_head_dim", cfg["head_dim"]),
        ("swa_v_head_dim", cfg["v_head_dim"]),
        ("sliding_window_size", cfg["sliding_window"]),
        ("rope_scaling", {"rope_type": "default", "type": "default"}),
    ):
        if cfg[key] != value:
            raise SystemExit(
                f"the mimo_v2 family has no {key} = {cfg[key]!r}"
            )
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        if len(cfg[key]) != depth:
            raise SystemExit(f"{key} lists {len(cfg[key])} of {depth} layers")
    first, held = cfg["first_expert_held"], cfg["n_routed_experts"]
    if first + held > cfg["router_outputs"]:
        raise SystemExit("the held experts pass the router's outputs")
    rotated = float(cfg["partial_rotary_factor"])
    model = MiMoV2(MiMoV2Config(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        hidden_dim=cfg["hidden_size"],
        head_dim=cfg["head_dim"],
        v_head_dim=cfg["v_head_dim"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        swa_num_heads=cfg["swa_num_attention_heads"],
        swa_num_kv_heads=cfg["swa_num_key_value_heads"],
        layer_pattern=tuple(cfg["hybrid_layer_pattern"]),
        moe_layers=tuple(cfg["moe_layer_freq"]),
        sliding_window=cfg["sliding_window"],
        full_rope=layers.RopeRule(
            theta=float(cfg["rope_theta"]), rotated=rotated
        ),
        swa_rope=layers.RopeRule(
            theta=float(cfg["swa_rope_theta"]), rotated=rotated
        ),
        value_scale=float(cfg["attention_value_scale"]),
        full_sink=cfg["add_full_attention_sink_bias"],
        swa_sink=cfg["add_swa_attention_sink_bias"],
        sink_init_std=recipe["sink_init_std"],
        dense_dim=cfg["intermediate_size"],
        expert_dim=cfg["moe_intermediate_size"],
        num_experts=cfg["router_outputs"],
        experts_held=(first, held),
        top_k=cfg["num_experts_per_tok"],
        routed_scale=reference.routed_scale(cfg),
        bias_update_rate=recipe["bias_update_rate"],
        rms_eps=cfg["layernorm_epsilon"],
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
    loss_fn = make_mimo_v2_loss(model, num_chunks=recipe["loss_chunks"])
    if "operand_mantissa_bits" in recipe:
        loss_fn = sarvam._in_fewer_bits(
            loss_fn, recipe["operand_mantissa_bits"]
        )
    if "control" in recipe:
        loss_fn = _sink_control(loss_fn, recipe["control"])
    return model, optimizer, loss_fn


def sink(path: str) -> bool:
    return "['sink']" in path


def routed_in(cfg):
    """``routed(path)`` for this configuration: a router's or a held
    expert's leaf (``sarvam_mla.routed``: its gradient sums over the
    tokens that CHOSE an expert, so every top-k choice a bf16 rounding
    flips moves it whole) and, because this family's sparse layers
    have NO shared expert, a sparse block's ``ln_mlp`` scale too: all
    of its gradient comes back through the router and the rows of the
    held experts (in the families with a shared expert most of it
    comes through that expert, from every token)."""
    sparse = tuple(
        f"['block_{i}']['ln_mlp']"
        for i, moe in enumerate(cfg["moe_layer_freq"]) if moe
    )

    def routed(path: str) -> bool:
        return sarvam.routed(path) or path.startswith(sparse)

    return routed


def _sink_control(loss_fn, control: str):
    """``loss_fn`` on parameters whose sinks take nothing of any
    softmax (``no_sink_forward``) or whose gradient is stopped
    (``no_sink_gradient``)."""
    change = {
        "no_sink_forward": lambda x: x * 0.0 - 1e30,
        "no_sink_gradient": jax.lax.stop_gradient,
    }[control]

    def controlled(params, batch):
        return loss_fn(jax.tree_util.tree_map_with_path(
            lambda path, x: change(x) if sink(
                jax.tree_util.keystr(path)
            ) else x, params,
        ), batch)

    controlled.has_aux = True
    return controlled


def compared(cfg):
    """Picks the leaves whose first gradient is compared: every
    block's attention (the flash kernels' three gradients, with the
    window and the sink and without, at heads of 192 | 128 and both
    group sizes, reach the fused ``qkv_proj``; ``o_proj``; every
    sinked layer's ``sink``), its norms and router, and the LAST
    block's held experts.  The other sparse layers' experts, the dense
    feed-forward and the vocabulary's leaves are left out for room:
    both sets of gradients stand on the chip beside the train
    state."""
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
    ``loss`` (the reference's), ``gradients`` (:func:`compared` leaf
    -> ``|system - reference| / |reference|`` of the first gradient)
    and ``bias`` (the share of the routers' bias deltas that differ
    from the rule applied to the reference's own counts)."""
    _, _, loss_fn = build(cfg)
    _, aux, system = reference.base.gradients_of(
        loss_fn, compared(cfg), params, {"x": tokens, "y": targets}
    )
    loss, counts, wanted = reference.gradients(
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
    return {
        "loss": float(loss),
        "gradients": {k: float(d) for k, d in differences.items()},
        "bias": float(np.mean(deltas != reference.base.bias_deltas(
            counts, cfg["recipe"]["bias_update_rate"]
        ))),
    }


def limit_of(leaf: str, routed) -> str:
    """The limit of ``cfg["reference"]`` a leaf is held to; ``routed``
    is :func:`routed_in` of the configuration."""
    if sink(leaf):
        return "sink_gradient_tolerance"
    return (
        "routed_gradient_tolerance" if routed(leaf)
        else "gradient_tolerance"
    )


def reference_loss(params, tokens, targets, cfg) -> float:
    """The plain reference's loss of ``params`` on the batch, or
    ``inf`` where the system's first gradient or its bias deltas are
    further from the reference's than ``cfg["reference"]`` allows
    (``gradient_tolerance`` for the worst attention or norm leaf,
    ``routed_gradient_tolerance`` for the worst :func:`routed_in` leaf,
    ``sink_gradient_tolerance`` for the worst sink,
    ``bias_update_tolerance``); the numbers and their limits go to
    stderr either way."""
    limits = cfg["reference"]
    found = comparisons(params, tokens, targets, cfg)
    leaves = found["gradients"]
    routed = routed_in(cfg)
    held_to = {leaf: limit_of(leaf, routed) for leaf in leaves}
    worst = {}
    for leaf, d in leaves.items():
        if not d <= worst.get(held_to[leaf], (-1.0, ""))[0]:
            worst[held_to[leaf]] = (d, leaf)
    worst["bias_update_tolerance"] = (found["bias"], "share of the deltas")
    print(
        f"mimo_v2 reference: first gradient over {len(leaves)} leaves, "
        "|difference| / |reference|, and the bias rule: " + "; ".join(
            f"{key} {value:.4f} at {leaf} (limit {limits[key]})"
            for key, (value, leaf) in sorted(worst.items())
        ),
        file=sys.stderr, flush=True,
    )
    # every leaf, not the worst alone: a gradient that is not a
    # number is larger than nothing
    inside = found["bias"] <= limits["bias_update_tolerance"] and all(
        d <= limits[held_to[leaf]] for leaf, d in leaves.items()
    )
    return found["loss"] if inside else float("inf")
