"""The benchmark's arithmetic for a model of the ``motif`` family: a
token of ``n`` residual streams mixed round every sub-layer, grouped
differential attention on a latent key in window and full layers,
PolyNorm experts that hold a range of their layer, and one prediction
layer through the shared head; and which scopes its readers sum.

Sizes come from a configuration file of the family (``layer_kinds``: 0
full, 1 window; the head counts HELD here; ``num_experts`` = the
experts held, ``router_outputs``) and the traffic's ``batch`` and
``seq``.  Required means what forward and backward need once: the
remat copy of the forward is NOT counted, a window layer's query meets
``min(i + 1, window)`` keys whatever tiles the kernels walk, and the
streams' mixing is the read and the write of the ``n`` streams a
sub-layer and pass, the same whatever implements it.  So a share of a
peak built on these numbers cannot pass 100%.  The held experts' work
is reckoned from the program's own counter (``moe.held_rows_share``),
as ``sarvam_flops.py`` does.  The prediction layer is one more block
(full attention, sparse) and one more pass of the head.

Checked against a count written out part by part in
``benchmarks/tests/test_motif_flops.py``.
"""

import laguna_flops
import sarvam_flops

WINDOW = 1
MIX_SCOPES = ("mhc_coeff", "mhc_sinkhorn", "mhc_mix")
PROJ_SCOPES = ("gdla_q_latent", "gdla_kv", "gdla_out")
ROPE_SCOPE = "gdla_rope"
DIFF_SCOPES = ("gdla_diff", "gdla_gate")
POLYNORM_SCOPE = "polynorm"
MTP_SCOPE = "mtp"
EXPERT_SCOPE = sarvam_flops.EXPERT_SCOPE

mean_keys = laguna_flops.mean_keys
scopes_ms_per_step = sarvam_flops.scopes_ms_per_step
counted_share = sarvam_flops.counted_share


def blocks(cfg: dict) -> int:
    """The stack's blocks and the prediction layer's one."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def kinds(cfg: dict):
    """Every block's attention kind, the prediction layer's (full)
    last."""
    return list(cfg["layer_kinds"]) + [0] * cfg["num_nextn_predict_layers"]


def sparse_layers(cfg: dict) -> int:
    return blocks(cfg) - cfg["n_dense_first_layers"]


# -- the streams' mixing ------------------------------------------------------


def mix_bytes_per_step(
    cfg: dict, batch: int, seq: int, itemsize: int = 2
) -> float:
    """HBM traffic the mixing REQUIRES: round each of a block's two
    sub-layers the ``n`` streams are read and written once forward and
    once backward (their gradient), ``2 x 2 x n x C`` values a token
    and sub-layer.  The one-stream ``u`` and ``y`` beside them, the
    coefficients and a fused implementation's savings are left out."""
    values = cfg["mhc_expansion_rate"] * cfg["hidden_size"]
    return float(
        2 * blocks(cfg) * 2 * 2 * values * itemsize * batch * seq
    )


def mix_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """The mixing's arithmetic: ``phi``'s product (6 a parameter), and
    2 a multiply-add of ``H_pre``, ``H_res`` and ``H_post`` over ``C``
    lanes, three times for forward and the two gradients each."""
    n, c = cfg["mhc_expansion_rate"], cfg["hidden_size"]
    phi = 6.0 * n * c * (2 * n + n * n)
    mixes = 3 * 2.0 * c * (n + n * n + n)
    return 2 * blocks(cfg) * (phi + mixes) * batch * seq


# -- attention ----------------------------------------------------------------


def head_lanes(cfg: dict) -> float:
    return (cfg["head_dim"] + cfg["v_head_dim"]) / 2


def window_flops_per_token(cfg: dict, seq: int) -> float:
    """Windowed attention, forward + backward, all window layers: ``12
    x keys x lanes`` a query head, signal and noise alike."""
    keys = mean_keys(seq, cfg["sliding_window"])
    return sum(
        12.0 * keys * cfg["num_attention_heads"] * head_lanes(cfg)
        for kind in kinds(cfg) if kind == WINDOW
    )


def full_flops_per_token(cfg: dict, seq: int) -> float:
    """Causal attention over the whole sequence, all full layers, the
    prediction layer's among them."""
    return sum(
        6.0 * seq * cfg["num_attention_heads"] * head_lanes(cfg)
        for kind in kinds(cfg) if kind != WINDOW
    )


def attention_bytes_per_token(cfg: dict, kind: int, itemsize: int = 2):
    """HBM traffic the flash kernels of the layers of ``kind`` cannot
    avoid, a token (``mimo_flops.attention_bytes_per_token``'s
    rule)."""
    both = 3 * (cfg["head_dim"] + cfg["v_head_dim"])
    heads = cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    return float(
        sum(heads * both for k in kinds(cfg) if k == kind) * itemsize
    )


def attention_matmul_params(cfg: dict) -> int:
    """One layer's projections at the heads held: the query latent
    down and up, the kv latent down and up, ``W_lambda``, ``W_gate``,
    ``W_o``."""
    h, d, dv = cfg["hidden_size"], cfg["head_dim"], cfg["v_head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    signal = heads - cfg["num_noise_heads"]
    nope = d - cfg["qk_rope_head_dim"]
    return (
        h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * d
        + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
        + cfg["kv_lora_rank"] * kv * (nope + dv)
        + h * signal + 2 * h * signal * dv
    )


# -- the feed-forwards --------------------------------------------------------


def assignments(cfg: dict, batch: int, seq: int) -> int:
    return batch * seq * cfg["experts_top_k"]


def expected_share(cfg: dict) -> float:
    """What uniform routing would send here: held over outputs."""
    return cfg["num_experts"] / cfg["router_outputs"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def polynorm_expert_flops_per_step(
    cfg: dict, batch: int, seq: int, share: float
) -> float:
    """Required FLOPs of the held experts' grouped matmuls, all sparse
    layers, from the COUNTED share of the assignments: 6 per matmul
    parameter per row, three matrices an expert.  PolyNorm's own
    arithmetic (some 40 operations a hidden value beside 2 x 4096 x 3
    of the products) is left out."""
    rows = share * assignments(cfg, batch, seq)
    return 6.0 * rows * expert_params(cfg) * sparse_layers(cfg)


def polynorm_expert_bytes_per_step(
    cfg: dict, batch: int, seq: int, share: float, itemsize: int = 2
) -> float:
    """HBM traffic the held experts cannot avoid, all sparse layers:
    each of the three matrices takes three passes (forward, the
    gradient to the rows, the gradient to the weights), a pass meets
    rows x in, rows x out and the held ``[held, in, out]`` weights
    once each (``nemotron_flops.relu2_expert_bytes_per_step`` at
    three matrices)."""
    rows = share * assignments(cfg, batch, seq)
    h, w = cfg["hidden_size"], cfg["moe_intermediate_size"]
    per_matrix = 3 * (rows * h + rows * w + cfg["num_experts"] * h * w)
    return 3.0 * per_matrix * itemsize * sparse_layers(cfg)


def matmul_params_per_token(cfg: dict, share: float) -> float:
    """Matmul parameters a token meets on this chip: every block's
    attention projections and its two ``phi``, the dense feed-forward,
    each sparse layer's router, shared expert and ``share x k`` routed
    experts, ``W_eh``, and the head at the vocabulary held ONCE A
    PASS: twice with a prediction layer."""
    h = cfg["hidden_size"]
    n = cfg["mhc_expansion_rate"]
    predicted = cfg["num_nextn_predict_layers"]
    sparse = (
        h * cfg["router_outputs"]
        + cfg["num_shared_experts"] * expert_params(cfg)
        + share * cfg["experts_top_k"] * expert_params(cfg)
    )
    return (
        blocks(cfg) * (
            attention_matmul_params(cfg) + 2 * n * h * (2 * n + n * n)
        )
        + cfg["n_dense_first_layers"] * 3 * h * cfg["intermediate_size"]
        + sparse_layers(cfg) * sparse
        + predicted * 2 * h * h
        + (1 + predicted) * cfg["vocab_size"] * h
    )


def train_flops_per_token(cfg: dict, seq: int, share=None) -> float:
    """Required FLOPs per trained token, the whole step: 6 per matmul
    parameter plus both kinds' attention; ``share`` defaults to the
    expectation.  The mixing's multiply-adds, the norms and PolyNorm
    are left out, as ``flops.py`` leaves every elementwise pass out."""
    share = expected_share(cfg) if share is None else share
    return (
        6.0 * matmul_params_per_token(cfg, share)
        + window_flops_per_token(cfg, seq) + full_flops_per_token(cfg, seq)
    )


def counter_at_last_step(run, name):
    """``(step, value)`` of the program's counter ``name`` at the
    window's last step that carries it, or None."""
    steps = {s["step"] for s in run.report["window"]["steps"]}
    found = [
        (e["step"], e[name]) for e in run.of("train_step")
        if e.get("step") in steps and name in e
    ]
    return max(found) if found else None
