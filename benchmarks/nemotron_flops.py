"""The benchmark's arithmetic for a Nemotron-H model (layers that are
ONE mixer each: a Mamba-2 state-space mixer, a layer of ungated sparse
experts of which the chip holds a range, or grouped-query attention),
and how its readers find the layers' device operations.

Sizes come from a configuration file of the ``nemotron_h`` family
(``hybrid_override_pattern``, one character a layer: ``M`` | ``E`` |
``*``; ``hidden_size``; ``mamba_num_heads``, ``mamba_head_dim``,
``n_groups``, ``ssm_state_size``; ``moe_intermediate_size``,
``moe_shared_expert_intermediate_size``, ``n_routed_experts`` = the
experts HELD on this chip, ``router_outputs`` = all of the layer's,
``num_experts_per_tok``; ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``; ``vocab_size``) and the
traffic's ``batch`` and ``seq``.

REQUIRED is what forward and backward need once, whatever implements
them: the remat copy of a block's forward is NOT counted, and of the
state-space scan only the RECURRENCE's own work (per token and head the
decay of the state, the rank-one write and the read-out, and twice
that backward), which no chunking can undercut; the chunk-wise form
the program runs computes MORE (the ``C x C`` scores and decays of a
chunk), so a share of a peak built on these numbers cannot pass 100%.
The held experts' work is reckoned from the rows the program COUNTED
(``moe.held_rows_share``), as ``sarvam_flops.py`` does.

The program names the parts itself (``jax.named_scope``): the
state-space mixer's ``ssm_in_proj``, ``ssm_conv``, ``ssm_gates``
(softplus, the decay's mean, the ``D`` skip), ``ssm_scan``,
``ssm_norm``, ``ssm_out_proj``; the expert layer's ``moe_router``,
``moe_dispatch``, ``moe_experts``, ``moe_combine``, ``moe_shared``;
``full_attn`` round an attention layer; ``loss_head`` and
``optimizer`` as in every cell.  A reader joins the reduced trace's
operations with the step executable's instruction -> name-stack map
(``moe_flops._stacks_of``); an instruction that only holds others (a
``%while``) is left out and its body counted, as ``ouro_flops.py``
does.

Checked against a count written out layer by layer, and against
``flops.py``'s count on the configuration's GPT-2 keys, in
``tests/test_nemotron_flops.py``.
"""

import moe_flops
import ouro_flops
import sarvam_flops
import scopes

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
SCAN_SCOPE = "ssm_scan"
MIX_SCOPES = ("ssm_conv", "ssm_gates", "ssm_norm")
PROJ_SCOPES = ("ssm_in_proj", "ssm_out_proj")
ROUTE_SCOPES = sarvam_flops.ROUTE_SCOPES
EXPERT_SCOPE = sarvam_flops.EXPERT_SCOPE
SHARED_SCOPE = sarvam_flops.SHARED_SCOPE
ATTENTION_SCOPE = "full_attn"
# every scope a step's device time is split by, the first that an
# operation's name stack holds
STEP_SCOPES = (
    (SCAN_SCOPE,) + MIX_SCOPES + PROJ_SCOPES + ROUTE_SCOPES
    + (EXPERT_SCOPE, SHARED_SCOPE, ATTENTION_SCOPE,
       ouro_flops.HEAD_SCOPE, ouro_flops.OPTIMIZER_SCOPE)
)

counted_share = sarvam_flops.counted_share


def layers(cfg: dict, kind: str) -> int:
    return cfg["hybrid_override_pattern"].count(kind)


# -- the recurrence -----------------------------------------------------------


def recurrence_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """Required FLOPs of the recurrence, all state-space layers: per
    token and head ``5 P N`` forward (the decay of the state ``P N``,
    the rank-one write ``2 P N``, the read-out ``S C`` ``2 P N``) and
    twice that backward."""
    per_head = 15.0 * cfg["mamba_head_dim"] * cfg["ssm_state_size"]
    return (
        per_head * cfg["mamba_num_heads"] * batch * seq
        * layers(cfg, MAMBA)
    )


def recurrence_bytes_per_step(
    cfg: dict, batch: int, seq: int, itemsize: int = 2
) -> float:
    """HBM traffic the recurrence cannot avoid, all state-space
    layers: the forward reads x, B, C (``itemsize``) and dt (float32)
    and writes y; the backward reads those and dy and writes the four
    gradients."""
    heads = cfg["mamba_num_heads"]
    x = heads * cfg["mamba_head_dim"] * itemsize
    bc = 2 * cfg["n_groups"] * cfg["ssm_state_size"] * itemsize
    dt = heads * 4
    forward = x + bc + dt + x
    backward = (x + bc + dt + x) + (x + bc + dt)
    return float(forward + backward) * batch * seq * layers(cfg, MAMBA)


# -- the ungated experts ------------------------------------------------------


def assignments(cfg: dict, batch: int, seq: int) -> int:
    """Assignments the router of ONE layer makes: tokens x top-k."""
    return batch * seq * cfg["num_experts_per_tok"]


def expected_share(cfg: dict) -> float:
    """What uniform routing would send here: held over outputs."""
    return cfg["n_routed_experts"] / cfg["router_outputs"]


def relu2_expert_flops_per_step(
    cfg: dict, batch: int, seq: int, share: float
) -> float:
    """Required FLOPs of the held experts' grouped matmuls, all
    expert layers: 6 per matmul parameter per counted row (2 forward,
    4 backward), TWO matrices an expert (no gate)."""
    per_row = 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return (
        6.0 * share * assignments(cfg, batch, seq) * per_row
        * layers(cfg, EXPERTS)
    )


def relu2_expert_bytes_per_step(
    cfg: dict, batch: int, seq: int, share: float, itemsize: int = 2
) -> float:
    """HBM traffic the held experts cannot avoid, all expert layers:
    each of the two matrices takes three passes (forward, the gradient
    to the rows, the gradient to the weights), a pass meets rows x
    in, rows x out and the held experts' ``[held, in, out]`` weights
    once each."""
    rows = share * assignments(cfg, batch, seq)
    h, w = cfg["hidden_size"], cfg["moe_intermediate_size"]
    per_matrix = 3 * (rows * h + rows * w + cfg["n_routed_experts"] * h * w)
    return 2.0 * per_matrix * itemsize * layers(cfg, EXPERTS)


# -- the whole step -----------------------------------------------------------


def mamba_matmul_params(cfg: dict) -> int:
    """The two projections of one state-space layer: ``hidden -> [z |
    xBC | dt]`` and ``inner -> hidden``."""
    h = cfg["hidden_size"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    bc = 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return h * (2 * inner + bc + cfg["mamba_num_heads"]) + inner * h


def attention_matmul_params(cfg: dict) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h


def expert_layer_matmul_params(cfg: dict, share: float) -> float:
    """What a token is multiplied by in one expert layer on this
    chip: the router, the shared expert, and ``top-k x share`` routed
    experts (``share`` = :func:`expected_share` for the expectation)."""
    h = cfg["hidden_size"]
    return (
        h * cfg["router_outputs"]
        + 2 * h * cfg["moe_shared_expert_intermediate_size"]
        + cfg["num_experts_per_tok"] * share
        * 2 * h * cfg["moe_intermediate_size"]
    )


def matmul_params_per_token(cfg: dict) -> float:
    """Matmul parameters a token REQUIRES on this chip, the routed
    experts at their expectation, the output head included."""
    return (
        layers(cfg, MAMBA) * mamba_matmul_params(cfg)
        + layers(cfg, ATTENTION) * attention_matmul_params(cfg)
        + layers(cfg, EXPERTS)
        * expert_layer_matmul_params(cfg, expected_share(cfg))
        + cfg["vocab_size"] * cfg["hidden_size"]
    )


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Causal attention, forward + backward, ``flops.py``'s rule at
    the query heads' width."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return 6.0 * layers(cfg, ATTENTION) * seq * width


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Required FLOPs a trained token: 6 a matmul parameter, causal
    attention, and the recurrence (the convolutions, norms and gates
    are left out, as ``flops.py`` leaves every elementwise pass
    out)."""
    return (
        6.0 * matmul_params_per_token(cfg)
        + attention_flops_per_token(cfg, seq)
        + recurrence_flops_per_step(cfg, 1, 1)
    )


# -- the readers' join --------------------------------------------------------


def by_scope(run):
    """Device seconds a traced step of every operation that is not a
    container, by the first of :data:`STEP_SCOPES` its name stack
    holds: ``{scope: {"forward", "remat", "backward"}, ..., "other":
    seconds, "unnamed": seconds}``, or None: no trace or no
    instruction -> name-stack map (a program that wrote none)."""
    trace = run.trace
    if not trace or not trace.get("steps"):
        return None
    stacks = moe_flops._stacks_of(scopes.op_names_file(run))
    if stacks is None:
        return None
    steps = trace["steps"]
    out = {
        scope: {"forward": 0.0, "remat": 0.0, "backward": 0.0}
        for scope in STEP_SCOPES
    }
    out.update(other=0.0, unnamed=0.0)
    for instruction, op in trace["ops"].items():
        if ouro_flops.CONTAINER.match(instruction):
            continue
        seconds = op["seconds"] / steps
        stack = stacks.get(instruction)
        if not stack:
            out["unnamed"] += seconds
            continue
        for scope in STEP_SCOPES:
            if scopes.in_scope(stack, scope):
                if scopes.in_scope(stack, "rematted_computation"):
                    out[scope]["remat"] += seconds
                elif "transpose(" in stack:
                    out[scope]["backward"] += seconds
                else:
                    out[scope]["forward"] += seconds
                break
        else:
            out["other"] += seconds
    return out


def scope_seconds(found, names):
    """Seconds a step under ``names`` of :func:`by_scope`'s result,
    or None where no operation carries any."""
    total = sum(sum(found[name].values()) for name in names)
    return total or None


def parts_note(found, names):
    """``name f | r | b`` in ms for each scope: forward | remat copy |
    backward."""
    return ", ".join(
        f"{name} " + " | ".join(
            f"{found[name][k] * 1e3:.3f}"
            for k in ("forward", "remat", "backward")
        ) for name in names
    )
