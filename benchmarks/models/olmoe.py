"""The ``olmoe`` family: how the benchmark builds the system's model,
optimizer and loss from a configuration file with ``model_type:
"olmoe"`` (the HF key names plus the ``recipe``), and the plain
reference's loss for it (``olmoe_reference.py``, beside this file).

The loss returns ``(loss, aux)`` and says so itself
(``loss_fn.has_aux``): ``make_train_step`` puts the ``moe.*``
counters of ``aux`` into the step's metrics, ``worker.py`` unchanged.
"""

import jax.numpy as jnp

import loader
from dlrover_tpu.models.olmoe import Olmoe, OlmoeConfig, make_olmoe_loss
from dlrover_tpu.optim import adamw_bf16

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
reference = loader.load_module("models", "olmoe_reference")


def build(cfg):
    """``(model, optimizer, loss_fn)`` of the system under test."""
    recipe = cfg["recipe"]
    if recipe["optimizer"] != "adamw_bf16":
        raise SystemExit(f"unknown optimizer {recipe['optimizer']!r}")
    for key, value in (
        ("num_key_value_heads", cfg["num_attention_heads"]),
        ("hidden_act", "silu"), ("norm_topk_prob", False),
        ("attention_bias", False), ("clip_qkv", None),
        ("rope_scaling", None), ("tie_word_embeddings", False),
    ):
        if cfg[key] != value:
            raise SystemExit(
                f"the olmoe family has no {key} = {cfg[key]!r}"
            )
    model = Olmoe(OlmoeConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        hidden_dim=cfg["hidden_size"],
        expert_dim=cfg["intermediate_size"],
        num_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        rope_theta=float(cfg["rope_theta"]),
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
    loss_fn = make_olmoe_loss(
        model,
        lb_weight=recipe["load_balancing_loss_weight"],
        z_weight=recipe["router_z_loss_weight"],
        num_chunks=recipe["loss_chunks"],
    )
    return model, optimizer, loss_fn


def reference_loss(params, tokens, targets, cfg) -> float:
    return reference.loss(params, tokens, targets, cfg)
