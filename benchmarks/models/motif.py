"""The ``motif`` family: how the benchmark builds the system's model,
optimizer and loss from a configuration file with ``model_type:
"motif"`` (the HF key names plus the ``recipe``), and the plain
reference's loss for it (``motif_reference.py``, beside this file).

A configuration of this family states a chip's SHARE of a layer:
``num_attention_heads`` | ``num_key_value_heads`` | ``num_noise_heads``
and ``num_experts`` count what is held here (whole kv groups: a kv
head with its signal and noise query heads), ``router_outputs`` the
experts the router scores, ``first_expert_held`` where the held range
starts.  ``layer_kinds`` (0 full, 1 window) is as long as
``num_hidden_layers`` and is the cut's reading of
``sliding_window_pattern`` / ``sliding_window_period`` (``assumed``).

The loss returns ``(loss, aux)`` and says so itself
(``loss_fn.has_aux``): ``make_train_step`` puts the ``moe.*``,
``mhc.*``, ``gdla.*`` and ``mtp.*`` counters of ``aux`` into the
step's metrics, ``worker.py`` unchanged.

**What ``correct`` compares.**  The harness compares one number, the
step program's first loss with :func:`reference_loss`'s.  As in the
``sarvam_mla`` family that number cannot tell bf16 from fewer bits,
nor see a mechanism that moves the loss in its fifth digit, so
:func:`reference_loss` also compares the system's first GRADIENT with
the reference's, leaf by leaf (:func:`compared`; the leaves of a few
numbers pooled over the blocks, :func:`unit_of`), the worst unit of
each class (:func:`limit_of`) against a limit of the configuration's
``reference``, and answers ``inf`` where one fails.

``recipe.operand_mantissa_bits`` (absent in every cell) builds the
lower-precision CONTROL the limits are set against
(``sarvam_mla._in_fewer_bits``); ``recipe.control`` (absent in every
cell) builds one control a mechanism, each a change of the SYSTEM that
the reference does not follow (:func:`control_loss`).
"""

import dataclasses
import sys

import jax
import jax.numpy as jnp

import loader
from dlrover_tpu.models import layers
from dlrover_tpu.models.motif import Motif, MotifConfig, make_motif_loss
from dlrover_tpu.ops import grouped_matmul
from dlrover_tpu.optim import adamw_bf16

sarvam = loader.load_module("models", "sarvam_mla")
reference = loader.load_module("models", "motif_reference")
DTYPES = sarvam.DTYPES


def build(cfg):
    """``(model, optimizer, loss_fn)`` of the system under test."""
    recipe = cfg["recipe"]
    if recipe["optimizer"] != "adamw_bf16":
        raise SystemExit(f"unknown optimizer {recipe['optimizer']!r}")
    scaling = cfg["rope_scaling"]
    for key, value in (
        ("attention_cls", "gdla"), ("diff_v2", True),
        ("elementwise_attn_output_gate", True),
        ("headwise_attn_output_gate", False),
        ("hidden_act", "poly_norm"), ("mhc_enabled", True),
        ("mhc_identity_init", False), ("score_func", "sigmoid"),
        ("route_norm", True), ("score_before_experts", False),
        ("interleave_moe_layer_step", 1), ("use_sliding_window", True),
        ("sliding_window_pattern", "interleave"),
        ("tie_word_embeddings", False), ("num_nextn_predict_layers", 1),
        ("polynorm_output_scale_per_layer", {}),
        ("swa_rope_theta", cfg["rope_theta"]),
        ("rope_factor", scaling["factor"]),
        ("original_seq_len", scaling["original_max_position_embeddings"]),
        ("polynorm_eps", grouped_matmul.POLYNORM_EPS),
    ):
        if cfg[key] != value:
            raise SystemExit(
                f"the motif family has no {key} = {cfg[key]!r}"
            )
    if scaling["rope_type"] != "yarn" or scaling["apply_yarn_scaling"]:
        raise SystemExit(f"the motif family has no rope {scaling!r}")
    if len(cfg["layer_kinds"]) != cfg["num_hidden_layers"]:
        raise SystemExit("layer_kinds lists another depth")
    first, held = cfg["first_expert_held"], cfg["num_experts"]
    if first + held > cfg["router_outputs"]:
        raise SystemExit("the held experts pass the router's outputs")
    model = Motif(MotifConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        hidden_dim=cfg["hidden_size"],
        streams=cfg["mhc_expansion_rate"],
        sinkhorn_iters=cfg["mhc_sinkhorn_iters"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        num_noise_heads=cfg["num_noise_heads"],
        qk_nope_dim=cfg["head_dim"] - cfg["qk_rope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        layer_pattern=tuple(cfg["layer_kinds"]),
        first_dense=cfg["n_dense_first_layers"],
        sliding_window=cfg["sliding_window"],
        full_rope=layers.RopeRule(
            theta=float(scaling["rope_theta"]),
            factor=float(scaling["factor"]),
            original_len=scaling["original_max_position_embeddings"],
            beta_fast=float(scaling["beta_fast"]),
            beta_slow=float(scaling["beta_slow"]),
        ),
        swa_rope=layers.RopeRule(theta=float(cfg["swa_rope_theta"])),
        dense_dim=cfg["intermediate_size"],
        expert_dim=cfg["moe_intermediate_size"],
        shared_experts=cfg["num_shared_experts"],
        num_experts=cfg["router_outputs"],
        experts_held=(first, held),
        top_k=cfg["experts_top_k"],
        routed_scale=float(cfg["route_scale"]),
        balance_coeff=cfg["load_balance_coeff"],
        polynorm_scale=cfg["polynorm_output_scale"],
        polynorm_clamp=cfg["polynorm_bias_clamp"],
        mtp_layers=cfg["num_nextn_predict_layers"],
        mtp_weight=recipe["mtp_weight"],
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
    if "control" in recipe:
        loss_fn = control_loss(
            recipe["control"], model, recipe["loss_chunks"]
        )
    else:
        loss_fn = make_motif_loss(model, num_chunks=recipe["loss_chunks"])
    if "operand_mantissa_bits" in recipe:
        loss_fn = sarvam._in_fewer_bits(
            loss_fn, recipe["operand_mantissa_bits"]
        )
    return model, optimizer, loss_fn


# -- one control a mechanism -------------------------------------------------


def _on_leaves(loss_fn, named, change):
    def controlled(params, batch):
        return loss_fn(jax.tree_util.tree_map_with_path(
            lambda path, x: change(x) if named(
                jax.tree_util.keystr(path)
            ) else x, params,
        ), batch)

    controlled.has_aux = True
    return controlled


def _without_norms(loss_fn):
    def parts(z, eps=None):
        one = jnp.ones(z.shape[:-1] + (1,), z.dtype)
        return [(z * z * z, one), (z * z, one), (z, one)]

    def controlled(params, batch):
        kept = grouped_matmul._poly_parts
        grouped_matmul._poly_parts = parts
        try:
            return loss_fn(params, batch)
        finally:
            grouped_matmul._poly_parts = kept

    controlled.has_aux = True
    return controlled


def control_loss(name: str, model, num_chunks: int):
    """The SYSTEM's loss with one mechanism taken out, which the
    reference does not follow, so that the comparison lands outside a
    limit: ``no_noise`` (``lambda_proj`` at -1e4: ``lambda`` 0, the
    noise term dropped), ``no_sinkhorn`` (``H_res = M_0``, the
    exponential without its normalisation), ``no_polynorm_norms``
    (``N(a) = a``, in the kernels' epilogues too: they call the same
    function) and ``no_prediction`` (the prediction term dropped from
    the loss)."""
    changed = {
        "no_sinkhorn": dict(sinkhorn_iters=0),
        "no_prediction": dict(mtp_weight=0.0),
    }
    if name in changed:
        return make_motif_loss(Motif(dataclasses.replace(
            model.config, **changed[name]
        )), num_chunks=num_chunks)
    loss_fn = make_motif_loss(model, num_chunks=num_chunks)
    if name == "no_noise":
        return _on_leaves(
            loss_fn, lambda path: "['lambda_proj']" in path,
            lambda x: x * 0.0 - 1e4,
        )
    if name == "no_polynorm_norms":
        return _without_norms(loss_fn)
    raise SystemExit(f"unknown control {name!r}")


# -- the comparison -----------------------------------------------------------

# a class's limit in ``cfg["reference"]``, first match
CLASSES = (
    ("mhc_gradient_tolerance", ("['mhc_",)),
    ("routed_gradient_tolerance", ("['router']", "['experts_w_")),
    ("polynorm_gradient_tolerance", ("polynorm_",)),
    ("gradient_tolerance", ("",)),
)
# leaves of a few numbers are judged TOGETHER, all blocks' at once: a
# gradient of one or three numbers is a sum of signed terms that may
# come out near zero, and its own norm is then no yardstick
POOLED = (
    ("every ['mhc_*']['alpha']", ("['alpha']",)),
    ("every ['mhc_*']['bias']", (
        "['mhc_attn']['bias']", "['mhc_mlp']['bias']",
    )),
    # (routed, shared and dense together: the routed experts' are sums
    # over the 1400 rows a layer that reach a held expert, a twentieth
    # to a fiftieth of the others' size; judged alone, even all five
    # layers' twenty numbers together, they read 0.09 to 0.41 of their
    # own norm over 16 seeds in bf16, and the bias alone 0.04 to 4.8)
    ("every ['*polynorm_w']", ("polynorm_w']",)),
    ("every ['*polynorm_b']", ("polynorm_b']",)),
)


def unit_of(leaf: str) -> str:
    """What a leaf's difference is judged in: the leaf itself, or for
    a leaf of a few numbers (:data:`POOLED`) the same-named leaves of
    every block together."""
    return next(
        (unit for unit, marks in POOLED if leaf.endswith(marks)), leaf
    )


def limit_of(unit: str) -> str:
    """The limit of ``cfg["reference"]`` a leaf or a pooled unit is
    held to: the streams' ``phi`` / ``alpha`` / ``bias``; the routed
    leaves (a router, a held expert: flipped top-k choices move them
    whole, ``sarvam_mla.routed``); PolyNorm's ``w`` / ``b``, every
    module's together;
    everything else (attention with the query latent,
    ``lambda_proj``, ``gate_proj``, the norms, ``eh_proj``)."""
    return next(
        limit for limit, marks in CLASSES
        if any(mark in unit for mark in marks)
    )


@jax.jit
def _norms(system, wanted):
    """Leaf by leaf ``(|system - wanted|, |wanted|)`` in float32."""
    def one(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.linalg.norm((a - b).ravel()), jnp.linalg.norm(b.ravel())

    return jax.tree.map(one, system, wanted)


def differences(norms: dict) -> dict:
    """``{unit: |difference| / |reference|}`` from ``{leaf:
    (|difference|, |reference|)}``, a pooled unit's norms taken over
    all its leaves' numbers."""
    squares = {}
    for leaf, (d, n) in norms.items():
        total = squares.setdefault(unit_of(leaf), [0.0, 0.0])
        total[0] += float(d) ** 2
        total[1] += float(n) ** 2
    return {unit: (d / n) ** 0.5 for unit, (d, n) in squares.items()}


def compared(cfg):
    """Picks the leaves whose first gradient is compared: every
    block's (the prediction layer's too) attention, norms, streams'
    coefficients, router and PolyNorm scalars, ``eh_proj``, and the
    LAST stack block's held experts' GATE matrices (``rows^T x d
    gate``, ``d gate`` the PolyNorm derivative that
    ``gmm_down_dlhs``' epilogue writes).  The other expert and
    feed-forward matrices and the vocabulary's leaves are left out
    for room: both sets of gradients, the reference's float32
    accumulators and six copies of a sequence's float32 streams (0.5
    GB each) stand on the chip beside the train state."""
    last = f"['block_{cfg['num_hidden_layers'] - 1}']"

    def pick(path: str) -> bool:
        return (
            "['attn']" in path or "['ln_" in path or "['router']" in path
            or "['mhc_" in path or "polynorm_" in path
            or "['eh_proj']" in path
            or (last in path and "['experts_w_gate']" in path)
        )

    return pick


def comparisons(params, tokens, targets, cfg) -> dict:
    """The system (``build(cfg)``'s loss, as the step program runs
    it) against the plain reference on ``params`` and the batch:
    ``loss`` (the reference's) and ``gradients`` (:func:`unit_of` a
    :func:`compared` leaf -> ``|system - reference| / |reference|``
    of the first gradient)."""
    _, _, loss_fn = build(cfg)
    _, _, system = reference.base.gradients_of(
        loss_fn, compared(cfg), params, {"x": tokens, "y": targets}
    )
    loss, _, wanted = reference.gradients(
        params, tokens, targets, cfg, compared(cfg)
    )
    return {
        "loss": float(loss),
        "gradients": differences(_norms(system, wanted)),
    }


def reference_loss(params, tokens, targets, cfg) -> float:
    """The plain reference's loss of ``params`` on the batch, or
    ``inf`` where a leaf (or a pooled unit) of the system's first
    gradient is further from the reference's than its class's limit
    in ``cfg["reference"]`` allows; the worst of each class and its
    limit go to stderr either way."""
    limits = cfg["reference"]
    found = comparisons(params, tokens, targets, cfg)
    leaves = found["gradients"]
    worst = {}
    for leaf, d in leaves.items():
        if not d <= worst.get(limit_of(leaf), (-1.0, ""))[0]:
            worst[limit_of(leaf)] = (d, leaf)
    print(
        f"motif reference: first gradient in {len(leaves)} units, "
        "|difference| / |reference|: " + "; ".join(
            f"{key} {value:.4f} at {leaf} (limit {limits[key]})"
            for key, (value, leaf) in sorted(worst.items())
        ),
        file=sys.stderr, flush=True,
    )
    # every leaf, not the worst alone: a gradient that is not a
    # number is larger than nothing
    inside = all(
        d <= limits[limit_of(leaf)] for leaf, d in leaves.items()
    )
    return found["loss"] if inside else float("inf")
