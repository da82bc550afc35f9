"""The ``jamba`` family (AI21-Jamba2-3B): how the benchmark builds the
system's model, optimizer and loss from a configuration file with
``model_type: "jamba"`` (the HF key names plus the ``recipe``), and
the plain reference's loss for it (``jamba_reference.py``, beside this
file).

A configuration of this family states a pipeline stage's layers:
``num_hidden_layers`` counts the layers BUILT, from published layer 0
on, their kinds by ``attn_layer_period`` and ``attn_layer_offset`` as
``transformers``' ``JambaConfig.layers_block_type`` has them.

The loss returns ``(loss, aux)`` and says so itself
(``loss_fn.has_aux``): ``make_train_step`` puts the ``s6.*`` counters
of ``aux`` into the step's metrics, ``worker.py`` unchanged.

**What ``correct`` compares.**  The harness compares one number, the
step program's first loss with :func:`reference_loss`'s.  That number
cannot tell bf16 from fewer bits, so :func:`reference_loss` makes
further comparisons itself, each against a limit of the
configuration's ``reference``, and answers ``inf`` where one fails:
the system's first GRADIENT against the reference's, leaf by leaf
(:func:`compared`; the worst leaf of each of three kinds,
:func:`kind_of`: ``A_log`` and ``dt_bias`` see the recurrence and
nothing else and have limits of their own), the ``s6.state_rms_max``
counter against the token-by-token recurrence's final states, and the
first state-space layer's scan ALONE on its own operands
(:func:`scan_alone`).  A reading that is not finite fails by its own
rule, whatever the limits, and the line names it.

``recipe.operand_mantissa_bits`` (absent in every cell) builds the
lower-precision CONTROL of the matmuls' operands, as the
``sarvam_mla`` family's does (its ``_in_fewer_bits``);
``recipe.control: "s6_state_bf16"`` (absent in every cell) builds the
control of the recurrence's own arithmetic: the state and ``exp(dt
A)`` rounded to bf16 at every row (:func:`_low_selective_scan`).
"""

import math
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

import loader
from dlrover_tpu.models import jamba as system
from dlrover_tpu.optim import adamw_bf16

sarvam = loader.load_module("models", "sarvam_mla")
lfm2 = loader.load_module("models", "lfm2_moe")
reference = loader.load_module("models", "jamba_reference")
DTYPES = sarvam.DTYPES
F32 = jnp.float32
CONTROL = "s6_state_bf16"


def build(cfg):
    """``(model, optimizer, loss_fn)`` of the system under test."""
    recipe = cfg["recipe"]
    if recipe["optimizer"] != "adamw_bf16":
        raise SystemExit(f"unknown optimizer {recipe['optimizer']!r}")
    try:
        config = system.JambaConfig.from_hf(
            cfg,
            chunk_size=recipe["scan_chunk"],
            init_std=recipe["initializer_range"],
            attention_impl=recipe["attention"],
            remat=recipe["remat"],
            dtype=DTYPES[recipe["compute_dtype"]],
            param_dtype=DTYPES[recipe["param_dtype"]],
        )
    except ValueError as e:
        raise SystemExit(str(e))
    model = system.Jamba(config)
    optimizer = adamw_bf16(
        learning_rate=recipe["learning_rate"],
        weight_decay=recipe["weight_decay"],
    )
    loss_fn = system.make_jamba_loss(
        model, num_chunks=recipe["loss_chunks"]
    )
    if "operand_mantissa_bits" in recipe:
        loss_fn = sarvam._in_fewer_bits(
            loss_fn, recipe["operand_mantissa_bits"]
        )
    if "control" in recipe:
        if recipe["control"] != CONTROL:
            raise SystemExit(f"no control {recipe['control']!r}")
        loss_fn = _state_in_low_precision(loss_fn)
    return model, optimizer, loss_fn


def _low_selective_scan(x, dt, A, B, C, D=None, *, chunk):
    """``selective_scan``'s plain form with the state and ``exp(dt
    A)`` rounded to bf16 at every row (every product and sum still
    float32): a ``lax.scan`` a token, in segments of ``chunk`` rows
    under ``jax.checkpoint``."""
    low = jnp.bfloat16
    x32 = x.astype(F32)

    def one(x, dt, b, c):
        def token(h, at):
            x_t, dt_t, b_t, c_t = at
            decay = jnp.exp(dt_t[:, None] * A).astype(low).astype(F32)
            h = (
                decay * h.astype(F32) + (dt_t * x_t)[:, None] * b_t
            ).astype(low)
            return h, jnp.sum(h.astype(F32) * c_t, axis=1)

        def segment(h, ats):
            return jax.lax.scan(token, h, ats)

        pad = -x.shape[0] % chunk
        ats = tuple(
            jnp.pad(a, ((0, pad), (0, 0))).reshape(
                (-1, chunk) + a.shape[1:]
            ) for a in (x, dt, b, c)
        )
        h, y = jax.lax.scan(
            jax.checkpoint(segment), jnp.zeros(A.shape, low), ats
        )
        return y.reshape(-1, x.shape[1])[:x.shape[0]], h.astype(F32)

    y, h = jax.vmap(one)(x32, dt, B.astype(F32), C.astype(F32))
    if D is not None:
        y = y + D * x32
    return y.astype(x.dtype), h


def _state_in_low_precision(loss_fn):
    """The control: ``loss_fn`` traced with the model's
    ``selective_scan`` replaced by :func:`_low_selective_scan`."""

    def control(params, batch):
        with mock.patch.object(
            system, "selective_scan", _low_selective_scan
        ):
            return loss_fn(params, batch)

    control.has_aux = True
    return control


def mamba_layers(cfg):
    return [
        i for i, kind in enumerate(reference.layer_types(cfg))
        if kind == reference.MAMBA
    ]


def compared(cfg):
    """Picks the leaves whose first gradient is compared: every leaf
    of the FIRST, the MIDDLE and the LAST state-space layer's mixer
    (``in_proj``, the taps and their bias, ``x_proj``, the three inner
    norms' scales, ``dt_proj``, ``dt_bias``, ``A_log``, ``D``,
    ``out_proj``: the scan's six gradients reach them all), the
    attention layers' four projections (the flash kernels at 20 heads
    over 1), every norm's scale (each block's two, EVERY state-space
    layer's inner three: a few hundred numbers that see all of their
    layer's scan, and the final one), and the TIED table (the head's
    gradient and the lookup's, summed).  The other state-space layers'
    matrices and vectors and the SwiGLUs are left out for room: both
    sets of gradients stand on the chip beside the train state."""
    layers = mamba_layers(cfg)
    mixers = tuple(
        f"['block_{i}']['mamba']"
        for i in {layers[0], layers[len(layers) // 2], layers[-1]}
    )

    def pick(path: str) -> bool:
        return (
            path.startswith(mixers) or "['attn']" in path
            or "_layernorm']['scale']" in path or "['wte']" in path
        )

    return pick


def kind_of(path: str) -> str:
    """The limit a leaf's gradient is held to.  ``A_log`` and
    ``dt_bias`` reach the loss through ``exp(dt A)`` and ``dt`` alone:
    every one of their numbers is a sum over all tokens of terms of
    both signs that only the recurrence makes, so each has a limit of
    its own.  ``gradient``: the rest."""
    if path.endswith("['A_log']"):
        return "a_log_gradient_tolerance"
    if path.endswith("['dt_bias']"):
        return "dt_bias_gradient_tolerance"
    return "gradient_tolerance"


def scan_alone(params, tokens, cfg) -> dict:
    """The first state-space layer's scan ALONE: the system's
    ``selective_scan`` (the form ``build(cfg)``'s loss calls: a
    control's stands in for it) against the plain float32 recurrence
    on the SAME operands, ``|difference| / |reference|`` of ``y`` and
    of the gradients of ``dt`` and ``A``.  The operands are that
    layer's own: the first sequence's embedding rows through the
    block's norm, ``W_in``, the convolution, ``W_x``, the inner norms
    and ``dt_proj`` in float32, ``x`` rounded once to the compute
    type and handed over in float32 (so that ``y`` leaves the kernel
    unrounded); the cotangent is ``x`` a row on.  The kernels sum the
    same float32 terms in another order, so the readings are
    float32's last bits where a state in bf16 reads a thousand times
    that."""
    recipe = cfg["recipe"]
    dtype = DTYPES[recipe["compute_dtype"]]
    eps = cfg["rms_norm_eps"]
    block = params[f"block_{mamba_layers(cfg)[0]}"]
    p = block["mamba"]
    n, rank = p["A_log"].shape[1], p["dt_proj"].shape[0]
    norm = reference.base._rms_norm
    with jax.default_matmul_precision("highest"):
        u = norm(
            reference.base._embed(params["wte"]["embedding"], tokens[0]),
            block["input_layernorm"]["scale"], eps,
        )
        xz = u @ p["in_proj"]["kernel"].astype(F32)
        x = jax.nn.silu(reference.causal_conv(
            xz[:, :xz.shape[1] // 2], p["conv"].astype(F32),
            p["conv_bias"].astype(F32),
        )).astype(dtype).astype(F32)
        sel = x @ p["x_proj"]["kernel"].astype(F32)
        dt = jax.nn.softplus(
            norm(sel[:, :rank], p["dt_layernorm"]["scale"], eps)
            @ p["dt_proj"].astype(F32) + p["dt_bias"].astype(F32)
        )
        B = norm(sel[:, rank:rank + n], p["b_layernorm"]["scale"], eps)
        C = norm(sel[:, rank + n:], p["c_layernorm"]["scale"], eps)
    A = -jnp.exp(p["A_log"].astype(F32))
    scan = (
        _low_selective_scan if recipe.get("control") == CONTROL
        else system.selective_scan
    )

    # (the operands as arguments: closed over, 168 MB arrays would be
    # constants of the two programs)
    @jax.jit
    def got(x, dt, A, B, C):
        (y, _), back = jax.vjp(lambda dt, A: scan(
            x[None], dt[None], A, B[None], C[None],
            chunk=recipe["scan_chunk"],
        ), dt, A)
        d_dt, d_a = back((
            jnp.roll(x, 1, axis=0)[None], jnp.zeros((1,) + A.shape, F32)
        ))
        return {"y": y[0], "d dt": d_dt, "dA": d_a}

    @jax.jit
    def want(x, dt, A, B, C):
        y, back = jax.vjp(
            lambda dt, A: reference.recurrence(x, dt, A, B, C)[0], dt, A
        )
        d_dt, d_a = back(jnp.roll(x, 1, axis=0))
        return {"y": y, "d dt": d_dt, "dA": d_a}

    operands = (x, dt, A, B, C)
    return {
        name: float(d) for name, d in sarvam._differences(
            got(*operands), want(*operands)
        ).items()
    }


def comparisons(params, tokens, targets, cfg) -> dict:
    """The system (``build(cfg)``'s loss, as the step program runs
    it) against the plain reference on ``params`` and the batch:
    ``loss`` (the reference's), ``gradients`` (:func:`compared` leaf
    -> ``|system - reference| / |reference|`` of the first gradient),
    ``state_rms`` (``|s6.state_rms_max / the token-by-token
    recurrence's largest final-state rms - 1|``), ``state_rms_max``
    (system, reference) and ``scan`` (:func:`scan_alone`);
    ``system_loss`` is the gradient program's own."""
    _, _, loss_fn = build(cfg)
    system_loss, aux, got = lfm2.system_gradients_of(
        loss_fn, compared(cfg), params, {"x": tokens, "y": targets}
    )
    loss, said, wanted = reference.gradients(
        params, tokens, targets, cfg, compared(cfg)
    )
    differences = sarvam._differences(got, wanted)
    # the system's is the rms over the batch's sequences together
    wanted_rms = float(np.sqrt(np.max(np.mean(
        np.square(np.asarray(said["state_rms"])), axis=0
    ))))
    return {
        "loss": float(loss),
        "system_loss": float(system_loss),
        "gradients": {k: float(d) for k, d in differences.items()},
        "state_rms": abs(
            float(aux["s6.state_rms_max"]) / wanted_rms - 1.0
        ),
        "state_rms_max": (float(aux["s6.state_rms_max"]), wanted_rms),
        "scan": scan_alone(params, tokens, cfg),
    }


def worst_of(found: dict) -> dict:
    """``{limit's key: (reading, what read it)}`` of
    :func:`comparisons`' result: the worst leaf of each
    :func:`kind_of` (a reading that is not a number is worse than
    any), the final states and the scan alone."""
    worst = {}
    for leaf, d in found["gradients"].items():
        if not d <= worst.get(kind_of(leaf), (-1.0, ""))[0]:
            worst[kind_of(leaf)] = (d, leaf)
    worst["state_rms_tolerance"] = (found["state_rms"], "s6.state_rms_max")
    what, d = max(
        found["scan"].items(),
        key=lambda item: item[1] if math.isfinite(item[1]) else math.inf,
    )
    worst["scan_alone_tolerance"] = (d, f"the first scan alone, {what}")
    return worst


def not_finite(found: dict) -> list:
    """What :func:`comparisons` read that is no finite number."""
    readings = {
        **found["gradients"],
        **{f"scan alone {k}": d for k, d in found["scan"].items()},
        "s6.state_rms_max": found["state_rms"],
        "the reference's loss": found["loss"],
        "the system's loss": found["system_loss"],
    }
    return [what for what, d in readings.items() if not math.isfinite(d)]


def reference_loss(params, tokens, targets, cfg) -> float:
    """The plain reference's loss of ``params`` on the batch, or
    ``inf`` where the system is further from the reference than
    ``cfg["reference"]`` allows (the worst leaf of each
    :func:`kind_of` of the first gradient, ``state_rms_tolerance``,
    ``scan_alone_tolerance``) or a reading is not finite; the numbers
    and their limits go to stderr either way."""
    limits = cfg["reference"]
    found = comparisons(params, tokens, targets, cfg)
    leaves = found["gradients"]
    worst = worst_of(found)
    broken = not_finite(found)
    print(
        f"jamba reference: first gradient over {len(leaves)} leaves, "
        "|difference| / |reference|, the final states and the scan "
        "alone: " + "; ".join(
            f"{key} {value:.3g} at {what} (limit {limits[key]})"
            for key, (value, what) in sorted(worst.items())
        ) + "; scan alone " + ", ".join(
            f"{k} {d:.3g}" for k, d in found["scan"].items()
        ) + "; s6.state_rms_max {:.6f} | {:.6f} (system | "
        "reference)".format(*found["state_rms_max"])
        + f"; the reference's loss {found['loss']:.6f}"
        + (f"; NOT FINITE: {', '.join(broken)}" if broken else ""),
        file=sys.stderr, flush=True,
    )
    # every leaf, not the worst alone
    inside = not broken and all(
        value <= limits[key] for key, (value, _) in worst.items()
    ) and all(d <= limits[kind_of(leaf)] for leaf, d in leaves.items())
    return found["loss"] if inside else float("inf")
