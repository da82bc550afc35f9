"""The ``gpt2`` family: how the benchmark builds the system's model,
optimizer and loss from a configuration file with ``model_type:
"gpt2"`` (the HF key names plus the ``recipe``), and the plain
reference's loss for it (``gpt2_reference.py``, beside this file).

``worker.py`` finds this file by the configuration's ``model_type``;
a later family adds a file of its own here.
"""

import jax.numpy as jnp

import loader
from dlrover_tpu.models.gpt import GPT, GPTConfig, cross_entropy_loss
from dlrover_tpu.optim import adamw_bf16

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
reference = loader.load_module("models", "gpt2_reference")


def build(cfg):
    """``(model, optimizer, loss_fn)`` of the system under test."""
    recipe = cfg["recipe"]
    if recipe["optimizer"] != "adamw_bf16":
        raise SystemExit(f"unknown optimizer {recipe['optimizer']!r}")
    model = GPT(GPTConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["n_positions"],
        num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"],
        hidden_dim=cfg["n_embd"],
        mlp_ratio=cfg["n_inner"] // cfg["n_embd"],
        ln_eps=cfg["layer_norm_epsilon"],
        tie_embeddings=cfg["tie_word_embeddings"],
        attention_impl=recipe["attention"],
        remat=recipe["remat"],
        dtype=DTYPES[recipe["compute_dtype"]],
        param_dtype=DTYPES[recipe["param_dtype"]],
    ))
    optimizer = adamw_bf16(
        learning_rate=recipe["learning_rate"],
        weight_decay=recipe["weight_decay"],
    )

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["x"])
        return cross_entropy_loss(logits, batch["y"])

    return model, optimizer, loss_fn


def reference_loss(params, tokens, targets, cfg) -> float:
    return reference.loss(params, tokens, targets, cfg)
