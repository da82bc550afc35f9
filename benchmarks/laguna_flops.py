"""The benchmark's arithmetic for a model whose attention layers are
of two kinds, full and windowed, and how its readers find the sliding
layers' device operations.

Sizes come from a configuration file of the ``laguna`` family
(``layer_types``, ``num_attention_heads_per_layer``,
``num_key_value_heads``, ``head_dim``, ``sliding_window``) and the
traffic's ``batch`` and ``seq``.  Required means what forward and
backward need once under the mask: a query at position ``i`` of a
sliding layer meets ``min(i + 1, window)`` keys, whatever tiles the
kernels walk to reach them; the remat copy of the forward is NOT
counted.  So a share of a peak built on these numbers cannot pass
100%.

The program names the parts itself (``jax.named_scope``): a sliding
layer's attention module sits under ``swa`` (a full layer's under
``full_attn``), and inside the module ``attn_rope`` (both rope tables,
the rotation, the layouts into the kernels) and ``attn_gate`` (the
gate's matmul, sigmoid and the scaling of the heads' outputs).  A
reader joins the reduced trace's operations with the step executable's
instruction -> name-stack map (``moe_flops.seconds_per_step``).

Checked against hand-worked values and a recorded run's shape in
``tests/test_laguna.py``.
"""

import moe_flops
import scopes
from kernels import kernel_ops

SLIDING = "sliding_attention"
SWA_SCOPE = "swa"
FULL_SCOPE = "full_attn"
ROPE_SCOPE = "attn_rope"
GATE_SCOPE = "attn_gate"
COUNTER = "attn.window_tiles_share"

seconds_per_step = moe_flops.seconds_per_step


def sliding_layers(cfg: dict):
    """The query heads of each sliding layer."""
    return [
        heads for kind, heads in zip(
            cfg["layer_types"], cfg["num_attention_heads_per_layer"]
        ) if kind == SLIDING
    ]


def mean_keys(seq: int, window: int) -> float:
    """Keys a query meets, mean over the sequence's positions:
    ``min(i + 1, window)``."""
    w = min(window, seq)
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def window_flops_per_token(cfg: dict, seq: int) -> float:
    """Windowed attention, forward + backward, per token, all sliding
    layers: QK^T and PV forward are ``2 x 2 x keys x lanes``, the
    backward twice that (dQ, dK, dV, dP): ``12 x keys x lanes``."""
    keys = mean_keys(seq, cfg["sliding_window"])
    return sum(
        12.0 * keys * heads * cfg["head_dim"]
        for heads in sliding_layers(cfg)
    )


def window_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    return window_flops_per_token(cfg, seq) * batch * seq


def window_bytes_per_step(
    cfg: dict, batch: int, seq: int, itemsize: int = 2
) -> float:
    """HBM traffic the sliding layers' attention cannot avoid: the
    forward reads q and writes o, the backward reads q, o, do and
    writes dq (6 tensors of the layer's query lanes); k and v are read
    forward and backward and dk, dv written (6 of the kv lanes).  The
    per-row statistics are left out."""
    kv_lanes = cfg["num_key_value_heads"] * cfg["head_dim"]
    lanes = sum(
        6 * heads * cfg["head_dim"] + 6 * kv_lanes
        for heads in sliding_layers(cfg)
    )
    return float(batch * seq * lanes * itemsize)


def kernel_seconds_by_scope(run, scope):
    """``(seconds a traced step, calls a step)`` of the flash kernels'
    operations whose name stack holds ``scope``, or None: no trace, no
    instruction -> name-stack map, or no such call."""
    trace = run.trace
    if not trace or not trace.get("steps"):
        return None
    stacks = moe_flops._stacks_of(scopes.op_names_file(run))
    if stacks is None:
        return None
    found = [
        op for name, op in kernel_ops(trace, "flash").items()
        if scopes.in_scope(stacks.get(name, ""), scope)
    ]
    if not found:
        return None
    return (
        sum(op["seconds"] for op in found) / trace["steps"],
        sum(op["count"] for op in found) / trace["steps"],
    )


def scope_ms_per_step(run, scope, what):
    """Device milliseconds per traced step under ``scope``; None
    where no operation carries it."""
    found = seconds_per_step(run, scope)
    if not found:
        return None
    run.note(
        f"{what}: {scope} {found[0] * 1e3:.3f} ms "
        f"({found[1]:.0f} operations)"
    )
    return found[0] * 1e3
