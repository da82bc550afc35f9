"""The benchmark's arithmetic for a looped model (a stack of blocks
that runs ``total_ut_steps`` times over the same weights, an exit
after every pass), and how its readers find the stack's device
operations.

Sizes come from a configuration file of the ``ouro`` family
(``num_hidden_layers`` = the blocks HELD, ``total_ut_steps``,
``hidden_size``, ``num_attention_heads``, ``head_dim``,
``intermediate_size``, ``vocab_size``) and the traffic's ``batch`` and
``seq``.  Required means what forward and backward need once, by
``flops.py``'s rules (6 a matmul parameter a token, causal attention
``6 x seq x width`` an application): the remat copy of a block's
forward is NOT counted, so a share of a peak built on these numbers
cannot pass 100%.  The count is by APPLICATION: a block's weights are
held once and multiplied ``total_ut_steps`` times.

The program names the parts itself (``jax.named_scope``): a pass of
the stack sits under ``ut`` (outside the block modules; the passes
are one scan, so the ``R`` passes are the same instructions run ``R``
times and a trace gives their sum), ``exit_gate`` holds the gate's projection, the sigmoids, the exit
distribution, its entropy and the counters; the four exits' head is
``loss_head`` as in every cell.  A reader joins the reduced trace's
operations with the step executable's instruction -> name-stack map
(``moe_flops._stacks_of``).

Checked against hand-worked values and ``flops.py``'s count on the
configuration's GPT-2 keys in ``tests/test_ouro_flops.py``.
"""

import re

import moe_flops
import scopes

PASS_SCOPE = "ut"
GATE_SCOPE = "exit_gate"
HEAD_SCOPE = "loss_head"
OPTIMIZER_SCOPE = "optimizer"
# an instruction that only holds others (the passes' scan, the chunked
# head's): the trace has it AND its body, so a sum over operations
# skips it
CONTAINER = re.compile(r"^%?while(\.|$)")

seconds_per_step = moe_flops.seconds_per_step


def applications(cfg: dict) -> int:
    """Block applications of one forward pass."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def block_matmul_params(cfg: dict) -> int:
    """Matmul parameters a token meets in ONE application of a block:
    q, k, v, o and the SwiGLU's three matrices (norms are no
    matmuls)."""
    h = cfg["hidden_size"]
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4 * h * width + 3 * h * cfg["intermediate_size"]


def block_flops_per_token(cfg: dict, seq: int) -> float:
    """One application, forward + backward: 6 a matmul parameter plus
    causal attention (``flops.attention_flops_per_token``'s rule)."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return 6.0 * block_matmul_params(cfg) + 6.0 * seq * width


def exit_flops_per_token(cfg: dict) -> float:
    """One exit's head, forward + backward."""
    return 6.0 * cfg["vocab_size"] * cfg["hidden_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Every application and every exit (the gate's 2048 parameters
    an exit are left out: a 50-thousandth of an exit's head)."""
    return (
        applications(cfg) * block_flops_per_token(cfg, seq)
        + cfg["total_ut_steps"] * exit_flops_per_token(cfg)
    )


def blocks_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    return (
        applications(cfg) * block_flops_per_token(cfg, seq) * batch * seq
    )


def blocks_bytes_per_step(
    cfg: dict, batch: int, seq: int, itemsize: int = 2
) -> float:
    """HBM traffic the applications cannot avoid: an application reads
    its block's weights forward and twice backward (the gradient to
    the rows, the gradient to the weights) and writes the weights'
    gradient once, and reads and writes its ``[rows, hidden]`` input
    and output forward and backward.  Far under the FLOPs' time at the
    cell's shape (28 ms against 358): the stack is bound by FLOPs."""
    h = cfg["hidden_size"]
    per_application = (
        4 * block_matmul_params(cfg) + 4 * batch * seq * h
    )
    return float(applications(cfg) * per_application * itemsize)


def by_scope(run):
    """Device seconds a traced step of every operation that is not a
    container, split by the program's scopes: ``{"blocks": {"forward",
    "remat", "backward"} (all passes together), "exit_gate",
    "loss_head", "optimizer", "other", "unnamed"}``, or None: no trace
    or no instruction -> name-stack map (the parent of this cell's
    PR)."""
    trace = run.trace
    if not trace or not trace.get("steps"):
        return None
    stacks = moe_flops._stacks_of(scopes.op_names_file(run))
    if stacks is None:
        return None
    steps = trace["steps"]
    blocks = {"forward": 0.0, "remat": 0.0, "backward": 0.0}
    out = {
        "blocks": blocks, GATE_SCOPE: 0.0, HEAD_SCOPE: 0.0,
        OPTIMIZER_SCOPE: 0.0, "other": 0.0, "unnamed": 0.0,
    }
    for instruction, op in trace["ops"].items():
        if CONTAINER.match(instruction):
            continue
        seconds = op["seconds"] / steps
        stack = stacks.get(instruction)
        if not stack:
            out["unnamed"] += seconds
            continue
        if scopes.in_scope(stack, PASS_SCOPE):
            if scopes.in_scope(stack, "rematted_computation"):
                blocks["remat"] += seconds
            elif "transpose(" in stack:
                blocks["backward"] += seconds
            else:
                blocks["forward"] += seconds
            continue
        for scope in (OPTIMIZER_SCOPE, HEAD_SCOPE, GATE_SCOPE):
            if scopes.in_scope(stack, scope):
                out[scope] += seconds
                break
        else:
            out["other"] += seconds
    return out


def blocks_seconds_per_step(run):
    """Device seconds a traced step under the scope ``ut``, or None
    where no operation carries it."""
    found = by_scope(run)
    if found is None:
        return None
    return sum(found["blocks"].values()) or None


def first_counter(run, name):
    """A ``loop.*`` counter at the run's FIRST step (the initial
    parameters on the fixed batch: the value the family's reference
    checks), or None where no ``train_step`` event carries it.  Not
    the window's: some sixty steps on one batch drive the gate onto
    exit 1 (1.0000 and 0.0000 in every run), which says that one batch
    was memorised and nothing of the layer."""
    found = [e for e in run.of("train_step") if name in e]
    if not found:
        return None
    return min(found, key=lambda e: e["step"])[name]
