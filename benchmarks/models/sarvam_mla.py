"""The ``sarvam_mla`` family: how the benchmark builds the system's
model, optimizer and loss from a configuration file with ``model_type:
"sarvam_mla"`` (the HF key names plus the ``recipe``), and the plain
reference's loss for it (``sarvam_mla_reference.py``, beside this
file).

A configuration of this family states a chip's SHARE of a layer:
``num_attention_heads`` and ``num_experts`` count what is held here,
``router_outputs`` the experts the router scores (all of the layer's),
``first_expert_held`` where the held range starts.

The loss returns ``(loss, aux)`` and says so itself
(``loss_fn.has_aux``): ``make_train_step`` puts the ``moe.*`` counters
of ``aux`` into the step's metrics and adds its ``state_updates`` (the
router bias's rule) to the parameters, ``worker.py`` unchanged.

**What ``correct`` compares.**  The harness compares one number, the
step program's first loss with :func:`reference_loss`'s.  At this
family's sizes that number cannot tell 8 bits of mantissa from 3 (the
two differences are signed and of one size), so :func:`reference_loss`
makes two further comparisons itself, each against a limit of the
configuration's ``reference``, and answers ``inf`` (which the harness
reports as not correct) where one fails: the system's first GRADIENT
against the reference's, leaf by leaf (:func:`compared`: every
attention leaf, norm and router, and the held experts of the last
block; the worst leaf of each of two kinds, :func:`routed`), and the
bias deltas the loss hands the step against the rule
applied to the reference's own counts.

``recipe.operand_mantissa_bits`` (absent in every cell) builds the
lower-precision CONTROL that the limits are set against: every weight
matrix and the input of every projection and expert layer rounded to
that many bits of mantissa (3 = e4m3 under ideal scaling), gradients
straight through.
"""

import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

import loader
from dlrover_tpu.models.sarvam_mla import (
    SarvamMla,
    SarvamMlaConfig,
    make_sarvam_mla_loss,
)
from dlrover_tpu.optim import adamw_bf16
from dlrover_tpu.parallel.moe import DroplessMoE

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
reference = loader.load_module("models", "sarvam_mla_reference")


def build(cfg):
    """``(model, optimizer, loss_fn)`` of the system under test."""
    recipe = cfg["recipe"]
    if recipe["optimizer"] != "adamw_bf16":
        raise SystemExit(f"unknown optimizer {recipe['optimizer']!r}")
    scaling = cfg["rope_scaling"]
    for key, value in (
        ("hidden_act", "silu"), ("tie_word_embeddings", False),
        ("use_qk_norm", True), ("moe_router_enable_expert_bias", True),
        ("attn_implementation", None),
        ("q_head_dim", cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]),
        ("head_dim", cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]),
        ("default_theta", cfg["rope_theta"]),
    ):
        if cfg[key] != value:
            raise SystemExit(
                f"the sarvam_mla family has no {key} = {cfg[key]!r}"
            )
    if scaling["type"] != "deepseek_yarn":
        raise SystemExit(
            f"the sarvam_mla family has no rope {scaling['type']!r}"
        )
    first, held = cfg["first_expert_held"], cfg["num_experts"]
    if first + held > cfg["router_outputs"]:
        raise SystemExit("the held experts pass the router's outputs")
    model = SarvamMla(SarvamMlaConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        num_layers=cfg["num_hidden_layers"],
        first_dense=cfg["first_k_dense_replace"],
        num_heads_held=cfg["num_attention_heads"],
        hidden_dim=cfg["hidden_size"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        kv_lora_rank=cfg["kv_lora_rank"],
        dense_dim=cfg["intermediate_size"],
        expert_dim=cfg["moe_intermediate_size"],
        shared_experts=cfg["num_shared_experts"],
        num_experts=cfg["router_outputs"],
        experts_held=(first, held),
        top_k=cfg["num_experts_per_tok"],
        routed_scale=cfg["routed_scaling_factor"],
        bias_update_rate=recipe["bias_update_rate"],
        rope_theta=float(cfg["rope_theta"]),
        rope_factor=float(scaling["factor"]),
        rope_original_len=scaling["original_max_position_embeddings"],
        rope_beta_fast=float(scaling["beta_fast"]),
        rope_beta_slow=float(scaling["beta_slow"]),
        rope_mscale=float(scaling["mscale"]),
        rope_mscale_all_dim=float(scaling["mscale_all_dim"]),
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
    loss_fn = make_sarvam_mla_loss(
        model, num_chunks=recipe["loss_chunks"]
    )
    if "operand_mantissa_bits" in recipe:
        loss_fn = _in_fewer_bits(loss_fn, recipe["operand_mantissa_bits"])
    return model, optimizer, loss_fn


def _round_to(x, bits: int):
    """``x`` at ``bits`` bits of mantissa (through bf16's 7, to the
    nearest; by bits, a convert pair may be simplified away), its
    gradient straight through."""
    drop = 7 - bits
    raw = jax.lax.bitcast_convert_type(x.astype(jnp.bfloat16), jnp.uint16)
    raw = (raw + jnp.uint16(1 << (drop - 1))) & jnp.uint16(
        0xFFFF ^ ((1 << drop) - 1)
    )
    rounded = jax.lax.bitcast_convert_type(raw, jnp.bfloat16)
    return x + jax.lax.stop_gradient(rounded.astype(x.dtype) - x)


def _in_fewer_bits(loss_fn, bits: int):
    """The control: ``loss_fn`` with every weight matrix (the
    embedding is no matmul's operand) and the input of every
    ``nn.Dense`` and expert layer rounded to ``bits`` bits."""

    def round_inputs(next_fun, args, kwargs, context):
        if context.method_name == "__call__" and isinstance(
            context.module, (nn.Dense, DroplessMoE)
        ):
            args = tuple(_round_to(a, bits) for a in args)
        return next_fun(*args, **kwargs)

    def control(params, batch):
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: _round_to(x, bits) if x.ndim >= 2 and (
                "wte" not in jax.tree_util.keystr(path)
            ) else x, params,
        )
        with nn.intercept_methods(round_inputs):
            return loss_fn(params, batch)

    control.has_aux = True
    return control


def compared(cfg):
    """Picks the leaves whose first gradient is compared: every
    block's attention (the flash kernels' three gradients reach
    ``q_proj``, ``kv_down``, ``kv_norm``, ``kv_up``), its norms and
    router, and the LAST block's held experts (the grouped matmuls'
    gradients and the held range's dispatch and combine).  The other
    expert layers', the dense and shared feed-forwards' and the
    vocabulary's leaves are left out for room: both sets of gradients
    stand on the chip beside the train state."""
    last = f"['block_{cfg['num_hidden_layers'] - 1}']"

    def pick(path: str) -> bool:
        return (
            "['attn']" in path or "['ln_" in path or "['router']" in path
            or (last in path and "['experts_w_" in path)
        )

    return pick


@jax.jit
def _differences(system, wanted):
    """Leaf by leaf ``|system - wanted| / |wanted|`` in float32."""
    def one(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(
            b.ravel()
        )

    return jax.tree.map(one, system, wanted)


def comparisons(params, tokens, targets, cfg) -> dict:
    """The system (``build(cfg)``'s loss, as the step program runs
    it) against the plain reference on ``params`` and the batch:
    ``loss`` (the reference's), ``gradients`` (:func:`compared` leaf
    -> ``|system - reference| / |reference|`` of the first gradient)
    and ``bias`` (the share of the routers' bias deltas that differ
    from the rule applied to the reference's own counts; flipped
    top-k choices move a count near the mean across it)."""
    _, _, loss_fn = build(cfg)
    _, aux, system = reference.gradients_of(
        loss_fn, compared(cfg), params, {"x": tokens, "y": targets}
    )
    loss, counts, wanted = reference.gradients(
        params, tokens, targets, cfg, compared(cfg)
    )
    differences = _differences(system, wanted)
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
        "bias": float(np.mean(deltas != reference.bias_deltas(
            counts, cfg["recipe"]["bias_update_rate"]
        ))),
    }


def routed(path: str) -> bool:
    """A router's or a held expert's leaf: its gradient sums over the
    tokens that CHOSE an expert, so every top-k choice that a bf16
    rounding flips moves it whole (0.2 to 0.3 of its norm at the
    cell's sizes, where an attention leaf differs by 0.04 to 0.08):
    these leaves take a limit of their own."""
    return "['router']" in path or "['experts_w_" in path


def reference_loss(params, tokens, targets, cfg) -> float:
    """The plain reference's loss of ``params`` on the batch, or
    ``inf`` where the system's first gradient or its bias deltas are
    further from the reference's than ``cfg["reference"]`` allows
    (``gradient_tolerance`` for the worst attention or norm leaf,
    ``routed_gradient_tolerance`` for the worst :func:`routed` leaf,
    ``bias_update_tolerance``); the numbers and their limits go to
    stderr either way."""
    limits = cfg["reference"]
    found = comparisons(params, tokens, targets, cfg)
    leaves = found["gradients"]
    read = {
        "gradient_tolerance": max(
            (d, leaf) for leaf, d in leaves.items() if not routed(leaf)
        ),
        "routed_gradient_tolerance": max(
            (d, leaf) for leaf, d in leaves.items() if routed(leaf)
        ),
        "bias_update_tolerance": (found["bias"], "share of the deltas"),
    }
    print(
        f"sarvam_mla reference: first gradient over {len(leaves)} "
        "leaves, |difference| / |reference|, and the bias rule: "
        + "; ".join(
            f"{what} {value:.4f} (limit {limits[key]})"
            for key, (value, what) in read.items()
        ),
        file=sys.stderr, flush=True,
    )
    # every leaf, not the worst alone: a gradient that is not a
    # number is larger than nothing
    inside = found["bias"] <= limits["bias_update_tolerance"] and all(
        d <= limits[
            "routed_gradient_tolerance" if routed(leaf)
            else "gradient_tolerance"
        ] for leaf, d in leaves.items()
    )
    return found["loss"] if inside else float("inf")
