"""The benchmark's arithmetic for an ``lfm2_moe`` configuration
(LFM2-24B-A2B): layers that mix tokens by a doubly gated short
convolution, grouped-query attention in every fourth, sigmoid-routed
experts of which the chip holds a range and a head tied to the
embedding; and how its readers find the mixer's device operations.

Sizes come from a configuration file of the family (the HF key names;
``layer_types`` = the mixer of each layer BUILT, ``num_experts`` = the
experts HELD here, ``router_outputs`` = all of a layer's) and the
traffic's ``batch`` and ``seq``.  REQUIRED means what forward and
backward need once; a remat copy is not counted, so a share of a peak
built on these numbers cannot pass 100%:

- the mixer ``y = C * conv_K(B * u)`` between its two matmuls: the
  forward reads ``B``, ``C``, ``u`` and writes ``y`` once (4 c a
  token), the backward reads those and ``dy`` and writes the three
  gradients once (7 c): 11 c values a token and layer, whatever
  implements it (the plain form moves several times that); the taps
  and their gradient are ``2 K c`` numbers a layer, nothing beside it;
- the arithmetic between them: 7 operations a channel and token
  forward (the product, ``K`` multiplies and ``K - 1`` adds at ``K =
  3``, the second gate), 15 backward (``g``, ``dC``, ``dv``'s 5,
  ``dB``, ``du``, the taps' ``2 K``), on the vector unit: no roofline
  is bound by it, the bytes bound;
- the matmul parameters a token meets (the held experts at their
  share of the assignments), causal attention at ``heads x head`` lanes
  in the attention layers, the head at the whole vocabulary.

The program names the parts itself (``telemetry/tracing.py::
device_scope``): ``sconv_proj`` (the ``W_in`` and ``W_out`` matmuls),
``sconv_mix`` (the ``bcx_fwd`` / ``bcx_bwd`` kernels and the counter's
reduction).

Checked against hand-worked values in
``benchmarks/tests/test_lfm2_flops.py``.
"""

import ling_flops
import sarvam_flops

MIX_SCOPE = "sconv_mix"
PROJ_SCOPE = "sconv_proj"
KERNELS = ("bcx_fwd", "bcx_bwd")
COUNTER = "sconv.out_rms_max"
CONV, ATTENTION = "conv", "full_attention"

seconds_per_step = sarvam_flops.seconds_per_step
counter_over_window = ling_flops.counter_over_window


def conv_layers(cfg: dict) -> int:
    return cfg["layer_types"].count(CONV)


def attention_layers(cfg: dict) -> int:
    return cfg["layer_types"].count(ATTENTION)


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


# -- the mixer ----------------------------------------------------------------


def mix_bytes_per_step(
    cfg: dict, batch: int, seq: int, itemsize: int = 2
) -> float:
    """HBM traffic the mixer cannot avoid between its matmuls, all
    conv layers: forward ``B``, ``C``, ``u`` in and ``y`` out (4 c),
    backward those and ``dy`` in and the three gradients out (7 c)."""
    return float(
        11 * cfg["hidden_size"] * itemsize * batch * seq * conv_layers(cfg)
    )


def mix_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """Required operations of the mixer's arithmetic, all conv
    layers: ``2 K + 1`` a channel and token forward, ``4 K + 3``
    backward."""
    k = cfg["conv_L_cache"]
    return float(
        (6 * k + 4) * cfg["hidden_size"] * batch * seq * conv_layers(cfg)
    )


# -- the matmul parameters a token meets --------------------------------------


def conv_params(cfg: dict) -> int:
    """``W_in`` (h x 3 h) and ``W_out`` (h x h)."""
    return 4 * cfg["hidden_size"] ** 2


def attention_params(cfg: dict) -> int:
    """``q_proj`` and ``out_proj`` (h x H d), ``k_proj`` and
    ``v_proj`` (h x G d)."""
    h = cfg["hidden_size"]
    d = h // cfg["num_attention_heads"]
    return 2 * h * d * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    )


def expected_share(cfg: dict) -> float:
    """What uniform routing would send here: held over outputs."""
    return cfg["num_experts"] / cfg["router_outputs"]


def sparse_params(cfg: dict, share: float) -> float:
    """One sparse layer: the router over ALL its outputs and ``top-k
    x share`` routed experts a token; no shared expert."""
    h = cfg["hidden_size"]
    return (
        h * cfg["router_outputs"] + cfg["num_experts_per_tok"] * share
        * 3 * h * cfg["moe_intermediate_size"]
    )


def matmul_params(cfg: dict, share=None) -> float:
    """Matmul parameters a token is multiplied by on this chip
    (``share``: of the assignments held here; uniform routing's where
    none is counted); the tied table counts once, as the head."""
    share = expected_share(cfg) if share is None else share
    h = cfg["hidden_size"]
    return (
        conv_layers(cfg) * conv_params(cfg)
        + attention_layers(cfg) * attention_params(cfg)
        + cfg["num_dense_layers"] * 3 * h * cfg["intermediate_size"]
        + expert_layers(cfg) * sparse_params(cfg, share)
        + cfg["vocab_size"] * h
    )


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Causal attention of the attention layers, forward + backward,
    a token: ``6 seq heads head_dim`` a layer (``flops.py``'s
    count)."""
    return 6.0 * attention_layers(cfg) * seq * cfg["hidden_size"]


def train_flops_per_token(cfg: dict, seq: int, share=None) -> float:
    """Required FLOPs a trained token: 6 a matmul parameter, the
    attention layers' causal scores and the mixer's arithmetic."""
    return (
        6.0 * matmul_params(cfg, share)
        + attention_flops_per_token(cfg, seq)
        + mix_flops_per_step(cfg, 1, 1)
    )


def total_params(cfg: dict) -> int:
    """Every parameter the train state holds on this chip (the tied
    table once): what ``report["params"]`` reads."""
    h = cfg["hidden_size"]
    d = h // cfg["num_attention_heads"]
    sparse = (
        h * cfg["router_outputs"] + cfg["router_outputs"]
        + cfg["num_experts"] * 3 * h * cfg["moe_intermediate_size"]
    )
    return (
        conv_layers(cfg) * (conv_params(cfg) + cfg["conv_L_cache"] * h)
        + attention_layers(cfg) * (attention_params(cfg) + 2 * d)
        + cfg["num_dense_layers"] * 3 * h * cfg["intermediate_size"]
        + expert_layers(cfg) * sparse
        + cfg["vocab_size"] * h
        + (2 * cfg["num_hidden_layers"] + 1) * h
    )
