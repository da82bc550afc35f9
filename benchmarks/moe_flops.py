"""The benchmark's arithmetic for a dropless mixture-of-experts layer,
and how its readers find the layer's device operations.

Sizes come from a configuration file with the HF ``olmoe`` key names
(``hidden_size``, ``intermediate_size`` = one expert's width,
``num_experts``, ``num_experts_per_tok``, ``num_hidden_layers``) and
the traffic's ``batch`` and ``seq``.  Required means what forward and
backward need once: the remat copy of the forward is NOT counted, so a
share of a peak built on these numbers cannot pass 100%.

The program names the layer's parts itself (``jax.named_scope``:
``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``),
and a reader joins the reduced trace's operations with the step
executable's instruction -> name-stack map (``scopes.op_names_file``).
``scopes.SCOPES`` is a fixed tuple that knows none of these, hence the
join here.  The grouped-matmul kernels (``%gmm_fwd.<n>``,
``%gmm_dlhs.<n>``, ``%gmm_drhs.<n>``) are Pallas calls of the repo's
own and keep their name stack, so the scope finds them.  (A program
whose expert matmuls were ``jax.lax.ragged_dot`` would NOT be found
this way: the TPU compiler replaces each with a Mosaic kernel of its
own, ``%ragged-dot-<n>`` with ``op_name="ragged-dot-none"``, and the
jax name stack is gone; my chip run, PR 28.)

Checked against hand-worked values in ``tests/test_moe_flops.py``.
"""

import functools
import json

import scopes

ROUTE_SCOPES = ("moe_router", "moe_dispatch", "moe_combine")
EXPERT_SCOPE = "moe_experts"


def routed_rows(cfg: dict, batch: int, seq: int) -> int:
    """Rows the experts of ONE layer compute: tokens x top-k."""
    return batch * seq * cfg["num_experts_per_tok"]


def expert_params_per_row(cfg: dict) -> int:
    """Matmul parameters one routed row meets in one layer: the gate,
    up and down matrices of its expert."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """Required FLOPs of the grouped matmuls, all layers: 6 per matmul
    parameter per routed row (2 forward, 4 backward)."""
    return (
        6.0 * routed_rows(cfg, batch, seq) * expert_params_per_row(cfg)
        * cfg["num_hidden_layers"]
    )


def expert_bytes_per_step(
    cfg: dict, batch: int, seq: int, itemsize: int = 2
) -> float:
    """HBM traffic the grouped matmuls cannot avoid, all layers.  Each
    of the three matrices takes three passes (forward, the gradient
    to the rows, the gradient to the weights); a pass reads its two
    operands and writes its result once: rows x in, rows x out and
    the ``[experts, in, out]`` weights, each met twice as an operand
    and once as a result over the three passes."""
    rows = routed_rows(cfg, batch, seq)
    h, w = cfg["hidden_size"], cfg["intermediate_size"]
    per_matrix = 3 * (rows * h + rows * w + cfg["num_experts"] * h * w)
    return 3.0 * per_matrix * itemsize * cfg["num_hidden_layers"]


@functools.lru_cache(maxsize=4)
def _stacks_of(path):
    try:
        with open(path) as f:
            return json.load(f).get("op_names") or None
    except (OSError, ValueError):
        return None


def seconds_per_step(run, scope):
    """Device seconds per traced step of the operations whose name
    stack holds ``scope``; ``(seconds, operations)`` or None: no
    trace, no map (the step executable's instruction -> name-stack
    file, read once), or no such operation (a program without the
    layer)."""
    trace = run.trace
    if not trace or not trace.get("steps"):
        return None
    stacks = _stacks_of(scopes.op_names_file(run))
    if stacks is None:
        return None
    seconds, count = 0.0, 0
    for instruction, op in trace["ops"].items():
        if scopes.in_scope(stacks.get(instruction, ""), scope):
            seconds += op["seconds"]
            count += op["count"]
    if not count:
        return None
    return seconds / trace["steps"], count / trace["steps"]
