"""The benchmark's arithmetic for a model of the ``mimo_v2`` family:
window layers whose softmax has a sink beside full layers, heads of
``head_dim`` | ``v_head_dim`` over kv heads that differ by kind, and
expert layers that hold a range of their experts with no shared one;
and how its readers find the parts' device operations.

Sizes come from a configuration file of the family
(``hybrid_layer_pattern``: 0 full, 1 window; the four head counts;
``head_dim``, ``v_head_dim``, ``sliding_window``; ``moe_layer_freq``,
``n_routed_experts`` = the experts HELD here, ``router_outputs``) and
the traffic's ``batch`` and ``seq``.  Required means what forward and
backward need once under the mask: a query at position ``i`` of a
window layer meets ``min(i + 1, window)`` keys, whatever tiles the
kernels walk to reach them (the sink is one more column of the
statistics, no matmul); the remat copy of the forward is NOT counted.
So a share of a peak built on these numbers cannot pass 100%.  The
held experts' work is reckoned from the program's own counter
(``moe.held_rows_share``), as ``sarvam_flops.py`` does.

The program names the parts itself (``jax.named_scope``): a window
layer's attention module sits under ``swa`` (a full layer's under
``full_attn``), and inside the module ``attn_qkv`` (the fused
projection, the split, the value scale), ``attn_rope``, ``attn_sink``
(the sink's gradient and counter) and ``attn_out``.

Checked against a count written out layer by layer in
``benchmarks/tests/test_mimo_flops.py``.
"""

import laguna_flops
import moe_flops
import ouro_flops
import sarvam_flops
import scopes
from kernels import kernel_ops

WINDOW = 1
SWA_SCOPE = laguna_flops.SWA_SCOPE
FULL_SCOPE = laguna_flops.FULL_SCOPE
QKV_SCOPE = "attn_qkv"
OUT_SCOPE = "attn_out"
ROPE_SCOPE = laguna_flops.ROPE_SCOPE
SINK_SCOPE = "attn_sink"
# the step by scope, for the note that accounts for the busy time
# (``mlp``, ``ln_*`` and ``wte`` are flax modules' names, which stand
# in an operation's name stack like a scope's)
STEP_SCOPES = (
    QKV_SCOPE, ROPE_SCOPE, SINK_SCOPE, OUT_SCOPE, "moe_router",
    "moe_dispatch", "moe_experts", "moe_combine", "mlp", "ln_attn",
    "ln_mlp", "ln_f", "wte", "loss_head", "optimizer",
)
ABS_COUNTER = "attn.sink_abs_max"

mean_keys = laguna_flops.mean_keys
kernel_seconds_by_scope = laguna_flops.kernel_seconds_by_scope
scopes_ms_per_step = sarvam_flops.scopes_ms_per_step


def layers_of(cfg: dict, kind: int):
    """``[(query heads, kv heads)]`` of the layers of ``kind``."""
    own = "swa_" if kind == WINDOW else ""
    return [
        (cfg[own + "num_attention_heads"], cfg[own + "num_key_value_heads"])
        for k in cfg["hybrid_layer_pattern"] if k == kind
    ]


def head_lanes(cfg: dict) -> float:
    """Lanes a head's four matmuls (QK^T and dP against ``head_dim``,
    PV and dV against ``v_head_dim``) average: ``(d_qk + d_v) / 2``."""
    return (cfg["head_dim"] + cfg["v_head_dim"]) / 2


def window_flops_per_token(cfg: dict, seq: int) -> float:
    """Windowed attention, forward + backward, per token, all window
    layers: ``12 x keys x lanes`` a head (``laguna_flops``)."""
    keys = mean_keys(seq, cfg["sliding_window"])
    return sum(
        12.0 * keys * heads * head_lanes(cfg)
        for heads, _ in layers_of(cfg, WINDOW)
    )


def full_flops_per_token(cfg: dict, seq: int) -> float:
    """Causal attention over the whole sequence, all full layers:
    ``12 x seq / 2 x lanes`` a head."""
    return sum(
        6.0 * seq * heads * head_lanes(cfg)
        for heads, _ in layers_of(cfg, 1 - WINDOW)
    )


def attention_bytes_per_token(cfg: dict, kind: int, itemsize: int = 2):
    """HBM traffic the attention of the layers of ``kind`` cannot
    avoid, a token: q is read forward and backward and dq written (3
    x ``head_dim`` a query head), o written, read back and do read (3
    x ``v_head_dim``); k and dk, v and dv likewise a kv head.  The
    per-row statistics and the sink are left out."""
    both = 3 * (cfg["head_dim"] + cfg["v_head_dim"])
    return float(sum(
        (heads + kv) * both for heads, kv in layers_of(cfg, kind)
    ) * itemsize)


def window_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    return window_flops_per_token(cfg, seq) * batch * seq


def window_bytes_per_step(cfg: dict, batch: int, seq: int) -> float:
    return attention_bytes_per_token(cfg, WINDOW) * batch * seq


def expert_layers(cfg: dict) -> int:
    return sum(cfg["moe_layer_freq"])


def held_expert_flops_per_step(
    cfg: dict, batch: int, seq: int, share: float
) -> float:
    """Required FLOPs of the held experts' grouped matmuls, all sparse
    layers, from the COUNTED share of the ``tokens x k`` assignments
    that reached a held expert: 6 per matmul parameter per row, three
    matrices an expert."""
    rows = share * batch * seq * cfg["num_experts_per_tok"]
    per_row = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return 6.0 * rows * per_row * expert_layers(cfg)


def matmul_params_per_token(cfg: dict, share: float) -> float:
    """Matmul parameters a token meets on this chip: the fused
    projection and ``W_o`` of every layer at the heads held, the dense
    SwiGLU, each sparse layer's router and ``share x k`` experts, the
    head at the vocabulary held."""
    h, d, dv = cfg["hidden_size"], cfg["head_dim"], cfg["v_head_dim"]
    attention = sum(
        h * ((heads + kv) * d + kv * dv) + heads * dv * h
        for kind in (0, WINDOW) for heads, kv in layers_of(cfg, kind)
    )
    sparse = expert_layers(cfg)
    dense = (cfg["num_hidden_layers"] - sparse) * 3 * h * cfg[
        "intermediate_size"
    ]
    routed = sparse * (
        h * cfg["router_outputs"] + share * cfg["num_experts_per_tok"]
        * 3 * h * cfg["moe_intermediate_size"]
    )
    return attention + dense + routed + cfg["vocab_size"] * h


def expected_share(cfg: dict) -> float:
    """What uniform routing would send here: held over outputs."""
    return cfg["n_routed_experts"] / cfg["router_outputs"]


def train_flops_per_token(cfg: dict, seq: int, share=None) -> float:
    """Required FLOPs per trained token, the whole step: 6 per matmul
    parameter plus both kinds' attention; ``share`` defaults to the
    expectation."""
    share = expected_share(cfg) if share is None else share
    return (
        6.0 * matmul_params_per_token(cfg, share)
        + window_flops_per_token(cfg, seq) + full_flops_per_token(cfg, seq)
    )


def step_by_scope(run):
    """``{part: ms a traced step}``: every device operation that is no
    container counted ONCE, a flash kernel under its layer kind
    (``swa kernels`` | ``full_attn kernels``), any other under the
    first of :data:`STEP_SCOPES` its name stack holds, what holds none
    under ``other named``, what has no name stack under ``unnamed``
    (a ``%while`` holds its body's operations, which the trace has
    too: ``ouro_flops.CONTAINER``).  None as ``moe_flops.seconds_per_step``."""
    trace = run.trace
    if not trace or not trace.get("steps"):
        return None
    stacks = moe_flops._stacks_of(scopes.op_names_file(run))
    if stacks is None:
        return None
    kinds = {scope: f"{scope} kernels" for scope in (SWA_SCOPE, FULL_SCOPE)}
    out = dict.fromkeys(
        (*kinds.values(), *STEP_SCOPES, "other named", "unnamed"), 0.0
    )
    flash = kernel_ops(trace, "flash")
    for instruction, op in trace["ops"].items():
        if ouro_flops.CONTAINER.match(instruction):
            continue
        stack = stacks.get(instruction)
        if not stack:
            part = "unnamed"
        elif instruction in flash:
            part = next(
                (name for scope, name in kinds.items()
                 if scopes.in_scope(stack, scope)), "other named",
            )
        else:
            part = next(
                (scope for scope in STEP_SCOPES
                 if scopes.in_scope(stack, scope)), "other named",
            )
        out[part] += op["seconds"] / trace["steps"] * 1e3
    return {part: ms for part, ms in out.items() if ms}


def ms_under_all(run, *names):
    """Device milliseconds per traced step of the operations whose
    name stack holds EVERY one of ``names`` (a scope inside a layer
    kind's); None as ``moe_flops.seconds_per_step``."""
    trace = run.trace
    if not trace or not trace.get("steps"):
        return None
    stacks = moe_flops._stacks_of(scopes.op_names_file(run))
    if stacks is None:
        return None
    seconds = sum(
        op["seconds"] for instruction, op in trace["ops"].items()
        if all(
            scopes.in_scope(stacks.get(instruction, ""), name)
            for name in names
        )
    )
    return seconds / trace["steps"] * 1e3 if seconds else None
