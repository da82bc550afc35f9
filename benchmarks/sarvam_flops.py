"""The benchmark's arithmetic for a latent-attention model whose
expert layers hold a RANGE of their experts, and how its readers find
the layers' device operations.

Sizes come from a configuration file of the ``sarvam_mla`` family
(``hidden_size``, ``moe_intermediate_size`` = one expert's width,
``num_experts`` = the experts HELD on this chip, ``router_outputs`` =
all of the layer's, ``num_experts_per_tok``, ``num_hidden_layers``,
``first_k_dense_replace``) and the traffic's ``batch`` and ``seq``.

What the held experts compute depends on the routing, so their
required work is reckoned from the program's own counter
(``moe.held_rows_share``: the share of the step's ``tokens x k``
assignments that reached a held expert, mean over the expert layers),
its median over the window, not from the expectation ``held /
outputs``: a share of a peak built on it cannot pass 100% on a seed
that happens to send few rows here.  Required means what forward and
backward need once: the remat copy of the forward is NOT counted.

The program names the parts itself (``jax.named_scope``): latent
attention's ``mla_q``, ``mla_kv_down`` (projection + the latent's
norm), ``mla_kv_up``, ``mla_out`` and ``mla_rope`` (rope, the key's
assembly, layouts into the kernel); the expert layer's ``moe_router``,
``moe_dispatch``, ``moe_experts``, ``moe_combine`` (as a layer that
holds all its experts) and ``moe_shared`` (the shared expert).  A
reader joins the reduced trace's operations with the step
executable's instruction -> name-stack map, as ``moe_flops.py`` does
(its ``seconds_per_step``).  Where the compiler fuses operations of
two scopes, the fusion carries one of the two names.

Checked against hand-worked values in ``tests/test_sarvam_flops.py``.
"""

import statistics

import moe_flops

PROJ_SCOPES = ("mla_q", "mla_kv_down", "mla_kv_up", "mla_out")
ROPE_SCOPE = "mla_rope"
SHARED_SCOPE = "moe_shared"
ROUTE_SCOPES = moe_flops.ROUTE_SCOPES
EXPERT_SCOPE = moe_flops.EXPERT_SCOPE
COUNTER = "moe.held_rows_share"

seconds_per_step = moe_flops.seconds_per_step


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def assignments(cfg: dict, batch: int, seq: int) -> int:
    """Assignments the router of ONE layer makes: tokens x top-k."""
    return batch * seq * cfg["num_experts_per_tok"]


def held_rows(cfg: dict, batch: int, seq: int, share: float) -> float:
    """Rows the held experts of ONE layer compute, from the counted
    share of the layer's assignments."""
    return share * assignments(cfg, batch, seq)


def expected_share(cfg: dict) -> float:
    """What uniform routing would send here: held over outputs."""
    return cfg["num_experts"] / cfg["router_outputs"]


def held_expert_flops_per_step(
    cfg: dict, batch: int, seq: int, share: float
) -> float:
    """Required FLOPs of the held experts' grouped matmuls, all
    expert layers: 6 per matmul parameter per row (2 forward, 4
    backward), three matrices an expert."""
    per_row = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return (
        6.0 * held_rows(cfg, batch, seq, share) * per_row
        * expert_layers(cfg)
    )


def held_expert_bytes_per_step(
    cfg: dict, batch: int, seq: int, share: float, itemsize: int = 2
) -> float:
    """HBM traffic the held experts cannot avoid, all expert layers:
    each of the three matrices takes three passes (forward, the
    gradient to the rows, the gradient to the weights), a pass meets
    rows x in, rows x out and the held experts' ``[held, in, out]``
    weights once each."""
    rows = held_rows(cfg, batch, seq, share)
    h, w = cfg["hidden_size"], cfg["moe_intermediate_size"]
    per_matrix = 3 * (rows * h + rows * w + cfg["num_experts"] * h * w)
    return 3.0 * per_matrix * itemsize * expert_layers(cfg)


def counted_share(run):
    """Median over the window's ``train_step`` events of the
    program's counter, with how many events carried it; None where
    none did (a program without the layer)."""
    steps = {s["step"] for s in run.report["window"]["steps"]}
    values = [
        e[COUNTER] for e in run.of("train_step")
        if e.get("step") in steps and COUNTER in e
    ]
    if not values:
        return None
    return statistics.median(values), len(values)


def scopes_ms_per_step(run, names, what):
    """Device milliseconds per traced step under ``names``, summed,
    with a note of each part; None where no operation carries any."""
    parts = {name: seconds_per_step(run, name) for name in names}
    if not any(parts.values()):
        return None
    run.note(f"{what}: " + ", ".join(
        f"{name} {found[0] * 1e3:.3f} ms ({found[1]:.0f} operations)"
        for name, found in parts.items() if found
    ))
    return sum(found[0] for found in parts.values() if found) * 1e3
