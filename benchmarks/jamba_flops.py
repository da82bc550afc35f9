"""The benchmark's arithmetic for a ``jamba`` configuration
(AI21-Jamba2-3B): layers that mix tokens by a Mamba-1 selective
state-space scan, causal attention where ``i % attn_layer_period ==
attn_layer_offset``, a dense SwiGLU in every layer and a head tied to
the embedding; and how its readers find the mixer's device operations.

Sizes come from a configuration file of the family (the HF key names)
and the traffic's ``batch`` and ``seq``.  REQUIRED means what forward
and backward need once; a remat copy and the backward's second pass
over a chunk's states are not counted, so a share of a peak built on
these numbers cannot pass 100%:

- the recurrence ``h = exp(dt A) h + dt B x``, ``y = C h + D x``: per
  token, channel and state lane the ``exp`` (one transcendental), the
  decay's product and the write (3 operations) and the read-out (2),
  once forward; the backward twice that (``dh``'s step and the five
  gradients each cost about what the forward's does): ``3 x (1 + 3 +
  2) = 18`` operations a token, channel and lane, an ``exp`` counted
  as one.  In HBM ``x``, ``y`` and their gradients at the
  activations' size and ``dt`` and its gradient in float32, once each
  way: the forward reads ``x``, ``dt`` and writes ``y`` (2 + 4 + 2
  bytes a channel and token in bf16), the backward reads ``x``,
  ``dt``, ``dy`` and writes ``dx``, ``d dt`` (2 + 4 + 2 + 2 + 4);
  ``B``, ``C`` and the start states are under 1% of that;
- the matmul parameters a token meets: the mixer's four matrices
  (``W_in``, ``W_x``, ``W_dt``, ``W_out``), the attention layers'
  four, every layer's SwiGLU, the head at the whole vocabulary;
  causal attention at ``heads x head`` lanes in the attention layers.

The chip's published peaks are the MXU's and HBM's; the recurrence is
bound by neither (the VPU's and the EUP's work), so its share of the
roofline reads LOW by design and cannot pass 100%.

The program names the parts itself (``telemetry/tracing.py::
device_scope``): ``s6_in_proj``, ``s6_conv``, ``s6_x_proj``,
``s6_params``, ``s6_scan``, ``s6_gate``, ``s6_out_proj``;
``full_attn`` round the attention layer; ``loss_head`` and
``optimizer`` as in every cell.  A reader joins the reduced trace's
operations with the step executable's instruction -> name-stack map
(``moe_flops._stacks_of``); an instruction that only holds others (a
``%while``) is left out and its body counted, as ``ouro_flops.py``
does.

Checked against a count written out layer by layer, and against
``flops.py``'s count on the configuration's GPT-2 keys, in
``benchmarks/tests/test_jamba_flops.py``.
"""

import ling_flops
import moe_flops
import ouro_flops
import scopes

MAMBA, ATTENTION = "mamba", "attention"
SCAN_SCOPE = "s6_scan"
MIX_SCOPES = ("s6_conv", "s6_params", "s6_gate")
PROJ_SCOPES = ("s6_in_proj", "s6_x_proj", "s6_out_proj")
ATTENTION_SCOPE = "full_attn"
# every scope a step's device time is split by, the first that an
# operation's name stack holds
STEP_SCOPES = (
    (SCAN_SCOPE,) + MIX_SCOPES + PROJ_SCOPES
    + (ATTENTION_SCOPE, "loss_head", "optimizer")
)
KERNELS = ("s6_fwd", "s6_bwd")
COUNTERS = ("s6.state_rms_max", "s6.decay_mean", "s6.dt_mean")
# operations a token, channel and state lane: (exp + 3 + 2) forward,
# twice that backward
SCAN_OPS = 18

counter_over_window = ling_flops.counter_over_window


def layer_types(cfg: dict):
    return [
        ATTENTION
        if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
        else MAMBA
        for i in range(cfg["num_hidden_layers"])
    ]


def layers(cfg: dict, kind: str) -> int:
    return layer_types(cfg).count(kind)


def inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


# -- the recurrence -----------------------------------------------------------


def scan_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """Required operations of the recurrence, all state-space layers,
    forward and backward."""
    return float(
        SCAN_OPS * inner(cfg) * cfg["mamba_d_state"] * batch * seq
        * layers(cfg, MAMBA)
    )


def scan_exps_per_step(cfg: dict, batch: int, seq: int) -> float:
    """The ``exp``s among them: one a token, channel and lane each
    way forward, two backward."""
    return scan_flops_per_step(cfg, batch, seq) * 3 / SCAN_OPS


def scan_bytes_per_step(
    cfg: dict, batch: int, seq: int, itemsize: int = 2
) -> float:
    """HBM traffic the recurrence cannot avoid, all state-space
    layers: forward ``x``, ``dt`` in and ``y`` out, backward ``x``,
    ``dt``, ``dy`` in and ``dx``, ``d dt`` out (``dt`` float32)."""
    a_token = inner(cfg) * (5 * itemsize + 3 * 4)
    return float(a_token * batch * seq * layers(cfg, MAMBA))


# -- the matmul parameters a token meets --------------------------------------


def mamba_params(cfg: dict) -> int:
    """``W_in`` (h x 2 E), ``W_x`` (E x (R + 2 N)), ``W_dt`` (R x E),
    ``W_out`` (E x h)."""
    h, e = cfg["hidden_size"], inner(cfg)
    rank, n = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    return 2 * h * e + e * (rank + 2 * n) + rank * e + e * h


def attention_params(cfg: dict) -> int:
    """``q_proj`` and ``o_proj`` (h x H d), ``k_proj`` and ``v_proj``
    (h x G d)."""
    h = cfg["hidden_size"]
    d = h // cfg["num_attention_heads"]
    return 2 * h * d * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    )


def matmul_params(cfg: dict) -> int:
    """Matmul parameters a token is multiplied by; the tied table
    counts once, as the head."""
    h = cfg["hidden_size"]
    return (
        layers(cfg, MAMBA) * mamba_params(cfg)
        + layers(cfg, ATTENTION) * attention_params(cfg)
        + cfg["num_hidden_layers"] * 3 * h * cfg["intermediate_size"]
        + cfg["vocab_size"] * h
    )


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Causal attention of the attention layers, forward + backward,
    a token: ``6 seq heads head_dim`` a layer (``flops.py``'s
    count)."""
    return 6.0 * layers(cfg, ATTENTION) * seq * cfg["hidden_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Required FLOPs a trained token: 6 a matmul parameter, the
    attention layers' causal scores and the recurrence."""
    return (
        6.0 * matmul_params(cfg) + attention_flops_per_token(cfg, seq)
        + scan_flops_per_step(cfg, 1, 1)
    )


def total_params(cfg: dict) -> int:
    """Every parameter the train state holds (the tied table once):
    what ``report["params"]`` reads."""
    h, e = cfg["hidden_size"], inner(cfg)
    rank, n = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    mixer = (
        mamba_params(cfg) + cfg["mamba_d_conv"] * e + e   # taps, bias
        + e                                               # b_dt
        + e * n + e                                       # A_log, D
        + rank + 2 * n                                    # inner norms
    )
    return (
        layers(cfg, MAMBA) * mixer
        + layers(cfg, ATTENTION) * attention_params(cfg)
        + cfg["num_hidden_layers"] * (
            3 * h * cfg["intermediate_size"] + 2 * h
        )
        + cfg["vocab_size"] * h + h
    )


# -- the readers' join --------------------------------------------------------


def by_scope(run):
    """Device seconds a traced step of every operation that is not a
    container, by the first of :data:`STEP_SCOPES` its name stack
    holds: ``{scope: {"forward", "remat", "backward"}, ..., "other":
    seconds, "unnamed": seconds}``, or None: no trace or no
    instruction -> name-stack map (a program that wrote none).
    (``nemotron_flops.by_scope`` over this family's scopes: that one
    reads its module's own list, and a file the benchmark has is not
    this PR's to edit.)"""
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


def scopes_ms(run, names, what):
    """Device milliseconds a traced step under ``names`` of
    :func:`by_scope`, with a note of each scope's forward | remat copy
    | backward; None where no operation carries any (a program of
    another family, or one without the scopes)."""
    if "mamba_d_state" not in run.config:
        return None
    found = by_scope(run)
    if found is None:
        return None
    total = sum(sum(found[name].values()) for name in names)
    if not total:
        return None
    run.note(f"{what}, ms a step, forward | remat copy | backward: " + (
        ", ".join(
            f"{name} " + " | ".join(
                f"{found[name][k] * 1e3:.3f}"
                for k in ("forward", "remat", "backward")
            ) for name in names
        )
    ))
    return total * 1e3
