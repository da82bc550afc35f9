"""The ``ouro`` family: how the benchmark builds the system's model,
optimizer and loss from a configuration file with ``model_type:
"ouro"`` (the HF key names plus the ``recipe``), and the plain
reference's loss for it (``ouro_reference.py``, beside this file).

``num_hidden_layers`` counts the blocks the tree HOLDS; the stack runs
``total_ut_steps`` times over them.  ``early_exit_threshold``,
``max_window_layers`` and the sliding-window keys are carried unread
(inference's, or off).

The loss returns ``(loss, aux)`` and says so itself
(``loss_fn.has_aux``): ``make_train_step`` puts the ``loop.*`` counters
of ``aux`` into the step's metrics, ``worker.py`` unchanged.

**What ``correct`` compares.**  The harness compares one number, the
step program's first loss with :func:`reference_loss`'s.  As in the
``sarvam_mla`` and ``laguna`` families that number cannot tell bf16
from fewer bits, so :func:`reference_loss` also compares the system's
first GRADIENT with the reference's, leaf by leaf (:func:`compared`:
every leaf of the first and of the last block, the final norm, the
exit gate's kernel and the head), each against the configuration's
``reference.gradient_tolerance``, and answers ``inf`` where one
fails.  Block 0's leaves carry all ``R`` passes' contributions through
``R x L - 1`` later applications: a dropped or half-precision pass
fails there.

``recipe.operand_mantissa_bits`` (absent in every cell) builds the
lower-precision CONTROL the limits are set against, as the
``sarvam_mla`` family's does (its ``_in_fewer_bits``).
"""

import sys

import loader
from dlrover_tpu.models.ouro import Ouro, OuroConfig, make_ouro_loss
from dlrover_tpu.optim import adamw_bf16

sarvam = loader.load_module("models", "sarvam_mla")
reference = loader.load_module("models", "ouro_reference")
DTYPES = sarvam.DTYPES


def build(cfg):
    """``(model, optimizer, loss_fn)`` of the system under test."""
    recipe = cfg["recipe"]
    if recipe["optimizer"] != "adamw_bf16":
        raise SystemExit(f"unknown optimizer {recipe['optimizer']!r}")
    layers = cfg["num_hidden_layers"]
    for key, value in (
        ("hidden_act", "silu"), ("tie_word_embeddings", False),
        ("rope_scaling", None), ("use_sliding_window", False),
        ("num_key_value_heads", cfg["num_attention_heads"]),
        ("layer_types", ["full_attention"] * layers),
    ):
        if cfg[key] != value:
            raise SystemExit(f"the ouro family has no {key} = {cfg[key]!r}")
    model = Ouro(OuroConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        num_layers=layers,
        ut_steps=cfg["total_ut_steps"],
        num_heads=cfg["num_attention_heads"],
        head_dim=cfg["head_dim"],
        hidden_dim=cfg["hidden_size"],
        dense_dim=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=cfg["rms_norm_eps"],
        entropy_weight=recipe["entropy_weight"],
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
    loss_fn = make_ouro_loss(model, num_chunks=recipe["loss_chunks"])
    if "operand_mantissa_bits" in recipe:
        loss_fn = sarvam._in_fewer_bits(
            loss_fn, recipe["operand_mantissa_bits"]
        )
    return model, optimizer, loss_fn


def compared(cfg):
    """Picks the leaves whose first gradient is compared: every leaf
    of block 0 (all ``R`` contributions, the first of them through
    every later application) and of the last block, the final norm
    (applied ``R`` times), the exit gate's kernel and the head (``R``
    exits' rows through the weighted chunk).  The gate's BIAS is left
    out: its gradient is one number, a sum over tokens of differences
    between exits' cross entropies that can cancel to next to nothing,
    and a relative difference of next to nothing says nothing (the
    CPU tests compare it).  The blocks between and the embedding are
    left out for room: both sets of gradients stand on the chip beside
    the train state."""
    ends = ("['block_0']", f"['block_{cfg['num_hidden_layers'] - 1}']")

    def pick(path: str) -> bool:
        return path.startswith(ends + (
            "['ln_f']", "['exit_gate']['kernel']", "['lm_head']",
        ))

    return pick


def comparisons(params, tokens, targets, cfg) -> dict:
    """The system (``build(cfg)``'s loss, as the step program runs
    it) against the plain reference on ``params`` and the batch:
    ``loss`` (the reference's), ``gradients`` (:func:`compared` leaf
    -> ``|system - reference| / |reference|`` of the first gradient)
    and ``counters`` (the ``loop.*`` counters, system beside
    reference)."""
    _, _, loss_fn = build(cfg)
    _, aux, system = reference.gradients_of(
        loss_fn, compared(cfg), params, {"x": tokens, "y": targets}
    )
    loss, wanted_aux, wanted = reference.gradients(
        params, tokens, targets, cfg, compared(cfg)
    )
    differences = sarvam._differences(system, wanted)
    return {
        "loss": float(loss),
        "gradients": {k: float(d) for k, d in differences.items()},
        "counters": {
            k: (float(aux[k]), float(v)) for k, v in wanted_aux.items()
        },
    }


def reference_loss(params, tokens, targets, cfg) -> float:
    """The plain reference's loss of ``params`` on the batch, or
    ``inf`` where a leaf of the system's first gradient is further
    from the reference's than ``cfg["reference"]["gradient_tolerance"]``;
    the numbers and the limit go to stderr either way."""
    limit = cfg["reference"]["gradient_tolerance"]
    found = comparisons(params, tokens, targets, cfg)
    leaves = found["gradients"]
    value, leaf = max((d, leaf) for leaf, d in leaves.items())
    print(
        f"ouro reference: first gradient over {len(leaves)} leaves, "
        f"|difference| / |reference|: worst {leaf} {value:.4f} (limit "
        f"{limit}); counters, system | reference: " + "; ".join(
            f"{k} {a:.5f} | {b:.5f}"
            for k, (a, b) in sorted(found["counters"].items())
        ),
        file=sys.stderr, flush=True,
    )
    # every leaf, not the worst alone: a gradient that is not a
    # number is larger than nothing
    inside = all(d <= limit for d in leaves.values())
    return found["loss"] if inside else float("inf")
