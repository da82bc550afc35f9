"""The benchmark's arithmetic for a gated-delta-rule linear-attention
layer, and how its readers find the layer's device operations.

Sizes come from a configuration file with the HF ``olmo_hybrid`` key
names (``layer_types``, ``linear_num_value_heads``,
``linear_key_head_dim``, ``linear_value_head_dim``) and the traffic's
``batch`` and ``seq``.  REQUIRED is the recurrence's own work, which no
chunking can undercut: per token, head and linear layer the decay of
the state, the read ``S^T k``, the rank-one write and the read-out
``S^T q``, and twice that backward; the chunk-wise form the program
runs computes MORE (the ``C x C`` inverse, the recompute under remat),
so a share of a peak built on these numbers cannot pass 100%.

The program names the layer's parts itself (``jax.named_scope``:
``gdn_conv``, ``gdn_gates``, ``gdn_rule``, ``gdn_norm``), and a reader
joins the reduced trace's operations with the step executable's
instruction -> name-stack map, as ``moe_flops.py`` does.  One thing
more: the rule holds a ``lax.scan``, which the device trace shows
TWICE, as the ``%while`` instruction (name stack ``../gdn_rule/while``,
one event that spans the loop) and as the operations of its body
(``../gdn_rule/while/body/..``).  :func:`seconds_per_step` counts the
``%while`` and leaves the bodies out (or, where the trace holds no
``%while`` of the scope, the bodies), never both.

Checked against hand-worked values in ``tests/test_gdn_flops.py``.
"""

import re

import moe_flops
import scopes

RULE_SCOPE = "gdn_rule"
MIX_SCOPES = ("gdn_conv", "gdn_gates", "gdn_norm")
LINEAR = "linear_attention"
WHILE = re.compile(r"^%?while(\.|$)")


def linear_layers(cfg: dict) -> int:
    return sum(kind == LINEAR for kind in cfg["layer_types"])


def rule_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """Required FLOPs of the rule, all linear layers: per token and
    head ``6 d_k d_v`` forward (decay ``d_k d_v``, ``S^T k`` ``2 d_k
    d_v``, the write ``d_k d_v``, ``S^T q`` ``2 d_k d_v``) and twice
    that backward."""
    per_head = 18.0 * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]
    return (
        per_head * cfg["linear_num_value_heads"] * batch * seq
        * linear_layers(cfg)
    )


def rule_bytes_per_step(
    cfg: dict, batch: int, seq: int, itemsize: int = 2
) -> float:
    """HBM traffic the rule cannot avoid, all linear layers: the
    forward reads q, k, v (``itemsize``) and g, beta (float32) and
    writes o; the backward reads those and do and writes the five
    gradients."""
    heads = cfg["linear_num_value_heads"]
    keys = heads * cfg["linear_key_head_dim"]
    values = heads * cfg["linear_value_head_dim"]
    gates = 2 * heads * 4
    forward = (2 * keys + 2 * values) * itemsize + gates
    backward = (
        (2 * keys + 2 * values) * itemsize + gates      # q, k, v, do
        + (2 * keys + values) * itemsize + gates        # dq, dk, dv
    )
    return float(forward + backward) * batch * seq * linear_layers(cfg)


def _bare(component):
    """A stack component without jax's transformation wrappers:
    ``transpose(jvp(gdn_rule))`` reads ``gdn_rule``."""
    while True:
        inner = scopes.WRAPPED.match(component)
        if not inner:
            return component
        component = inner.group(1)


def in_loop_of(stack, scope):
    """Whether the operation runs inside a loop that ``scope`` holds:
    a ``while`` component after ``scope`` with more behind it
    (``../gdn_rule/while/body/mul``).  The loop's own stack ends in
    ``while``; a loop the scope itself sits in comes before it."""
    parts = [_bare(part) for part in stack.split("/")]
    after = parts[parts.index(scope) + 1:]
    return "while" in after[:-1]


def seconds_per_step(run, scope):
    """Device seconds per traced step under ``scope``, a scan counted
    once; ``(seconds, operations, seconds of loop bodies left out)``
    or None: no trace, no map, or no such operation (a program
    without the layer: the parent of PR 32).

    Where the trace holds ``%while`` instructions of the scope, they
    are counted (one event spans the loop, the compiler's unnamed
    operations in it included) and every operation inside a loop is
    left out; the compiler may share one body between several loops
    and joins their name stacks, so a body cannot be told to its own
    ``%while`` by name.  Where it holds none, the bodies are what is
    counted."""
    trace = run.trace
    if not trace or not trace.get("steps"):
        return None
    stacks = moe_flops._stacks_of(scopes.op_names_file(run))
    if stacks is None:
        return None
    outside, inside, loops = [], [], 0
    for instruction, op in trace["ops"].items():
        stack = stacks.get(instruction, "")
        if not scopes.in_scope(stack, scope):
            continue
        if in_loop_of(stack, scope):
            inside.append(op)
        else:
            outside.append(op)
            loops += bool(WHILE.match(instruction))
    counted = outside if loops else outside + inside
    if not counted:
        return None
    steps = trace["steps"]
    return (
        sum(op["seconds"] for op in counted) / steps,
        sum(op["count"] for op in counted) / steps,
        sum(op["seconds"] for op in inside) / steps if loops else 0.0,
    )
