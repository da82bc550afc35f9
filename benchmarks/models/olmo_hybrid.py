"""The ``olmo_hybrid`` family: how the benchmark builds the system's
model, optimizer and loss from a configuration file with ``model_type:
"olmo_hybrid"`` (the HF key names plus the ``recipe``), and the plain
reference's loss for it (``olmo_hybrid_reference.py``, beside this
file).

The loss returns ``(loss, aux)`` and says so itself
(``loss_fn.has_aux``): ``make_train_step`` puts the
``gdn.state_rms_max`` counter of ``aux`` into the step's metrics,
``worker.py`` unchanged.
"""

import jax.numpy as jnp

import loader
from dlrover_tpu.models.olmo_hybrid import (
    OlmoHybrid,
    OlmoHybridConfig,
    make_olmo_hybrid_loss,
)
from dlrover_tpu.optim import adamw_bf16

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
reference = loader.load_module("models", "olmo_hybrid_reference")


def build(cfg):
    """``(model, optimizer, loss_fn)`` of the system under test."""
    recipe = cfg["recipe"]
    if recipe["optimizer"] != "adamw_bf16":
        raise SystemExit(f"unknown optimizer {recipe['optimizer']!r}")
    for key, value in (
        ("num_key_value_heads", cfg["num_attention_heads"]),
        ("linear_num_value_heads", cfg["linear_num_key_heads"]),
        ("hidden_act", "silu"), ("attention_bias", False),
        ("tie_word_embeddings", False),
        ("rope_parameters", {"rope_theta": None}),
    ):
        if cfg[key] != value:
            raise SystemExit(
                f"the olmo_hybrid family has no {key} = {cfg[key]!r}"
            )
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise SystemExit("layer_types does not name every layer")
    model = OlmoHybrid(OlmoHybridConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        layer_types=tuple(cfg["layer_types"]),
        num_heads=cfg["num_attention_heads"],
        hidden_dim=cfg["hidden_size"],
        mlp_dim=cfg["intermediate_size"],
        linear_heads=cfg["linear_num_key_heads"],
        linear_key_dim=cfg["linear_key_head_dim"],
        linear_value_dim=cfg["linear_value_head_dim"],
        conv_kernel=cfg["linear_conv_kernel_dim"],
        allow_neg_eigval=cfg["linear_allow_neg_eigval"],
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
    loss_fn = make_olmo_hybrid_loss(
        model, num_chunks=recipe["loss_chunks"]
    )
    return model, optimizer, loss_fn


def reference_loss(params, tokens, targets, cfg) -> float:
    return reference.loss(params, tokens, targets, cfg)
