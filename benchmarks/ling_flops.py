"""The benchmark's arithmetic for a ``bailing_hybrid`` configuration
(Ling-3.0-flash): Kimi-Delta-Attention layers, one latent-attention
layer a group, grouped experts of which the chip holds a range, and how
its readers find the new parts' device operations.

Sizes come from a configuration file of the family (the HF key names;
``num_experts`` = the experts HELD here, ``router_outputs`` = all of a
layer's, ``layers_held`` = the published index of each layer built)
and the traffic's ``batch`` and ``seq``.  REQUIRED means what forward
and backward need once; neither a remat copy nor the chunk-wise
form's extra work is counted, so a share of a peak built on these
numbers cannot pass 100%:

- the rule: the RECURRENCE's work a token and head (the decay of the
  state ``d_k d_v``, the read ``S^T k`` ``2 d_k d_v``, the write ``d_k
  d_v``, the read-out ``2 d_k d_v``; twice that backward), which no
  chunking undercuts, and the bytes of ``q, k, v`` (2 B), ``g`` (a
  float32 a CHANNEL), ``beta`` and ``o`` forward, those and ``do`` and
  the five gradients backward, once each;
- the chunk-wise form the kernels run (:func:`chunk_flops_per_step`,
  for PERF.md's reckoning, in no roofline): ``KK`` and ``QK`` below
  the diagonal, the inverse, ``W``, ``U``, ``V'``, the outputs and the
  hand-over at chunk ``C``, a pair of tokens counted once whatever
  sub-block it lies in (the levels' masked products are MORE:
  ``ops/kda.py`` runs four full ``C x C`` tiles for each of the two,
  the ``SUB x SUB`` diagonal blocks' in three passes);
- the latent layer's causal attention at ``(qk + v) / 2`` lanes a
  head, the matmul parameters a token meets (the held experts at
  their share of the assignments), the head.

The program names the parts itself (``telemetry/tracing.py::
device_scope``): ``kda_proj``, ``kda_conv``, ``kda_gates``,
``kda_rule`` (layouts and the ``kda_fwd`` / ``kda_bwd`` kernels),
``kda_norm``, ``kda_out``, and ``moe_group_select`` beside
``moe_router``.

Checked against hand-worked values in ``benchmarks/tests/test_ling_flops.py``.
"""

import gdn_flops

RULE_SCOPE = "kda_rule"
MIX_SCOPES = ("kda_conv", "kda_gates", "kda_norm")
GROUP_ROUTE_SCOPES = ("moe_router", "moe_group_select")
KERNELS = ("kda_fwd", "kda_bwd")
KDA, LATENT = "kda", "latent"


def kinds(cfg: dict):
    ids = cfg.get("layers_held") or range(cfg["num_hidden_layers"])
    return [
        LATENT if (i + 1) % cfg["layer_group_size"] == 0 else KDA
        for i in ids
    ]


def kda_layers(cfg: dict) -> int:
    return kinds(cfg).count(KDA)


def latent_layers(cfg: dict) -> int:
    return kinds(cfg).count(LATENT)


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


# -- the rule -----------------------------------------------------------------


def rule_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """Required FLOPs of the recurrence, all KDA layers: ``6 d_k d_v``
    a token and head forward and twice that backward.  The decay a
    channel costs what the decay a head does: one multiply an entry
    of the state."""
    d = cfg["head_dim"]
    return (
        18.0 * d * d * cfg["num_attention_heads"] * batch * seq
        * kda_layers(cfg)
    )


def rule_bytes_per_step(
    cfg: dict, batch: int, seq: int, itemsize: int = 2
) -> float:
    """HBM traffic the rule cannot avoid, all KDA layers: the forward
    reads ``q, k, v`` (``itemsize``), ``g`` (float32, ``d_k`` a head)
    and ``beta`` (float32) and writes ``o``; the backward reads those
    and ``do`` and writes the five gradients."""
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    operands = 3 * heads * d * itemsize + heads * d * 4 + heads * 4
    out = heads * d * itemsize
    return float(
        (operands + out) + (operands + out + operands)
    ) * batch * seq * kda_layers(cfg)


def chunk_flops_per_step(
    cfg: dict, batch: int, seq: int, chunk: int = 128
) -> float:
    """FLOPs of the chunk-wise form, all KDA layers, forward and
    backward (twice the forward), recompute not counted.  Per chunk
    and head, ``C`` tokens, ``d`` = ``d_k`` = ``d_v``: ``KK`` strictly
    below and ``QK`` on and below the diagonal (``2 d`` a pair,
    whatever sub-block it lies in), the inverse by substitution
    (``C^3 / 3``), ``W`` and ``U`` (a triangle by ``[C, d]`` each),
    ``V' = U - W S``, the outputs ``Q S`` and ``QK V'``, and the
    hand-over ``K^T V'``; the exponentials and scalings apart."""
    d = cfg["head_dim"]
    c = chunk
    below, onto = c * (c - 1) // 2, c * (c + 1) // 2
    forward = (
        2 * d * (below + onto)          # KK, QK
        + c ** 3 / 3                    # (I + A)^-1
        + 2 * onto * 2 * d              # W, U
        + 2 * c * d * d                 # W S
        + 2 * c * d * d + 2 * onto * d  # Q S, QK V'
        + 2 * c * d * d                 # K^T V'
    )
    chunks = -(-seq // c)
    return (
        3.0 * forward * chunks * cfg["num_attention_heads"] * batch
        * kda_layers(cfg)
    )


# -- the latent layer ---------------------------------------------------------


def latent_lanes(cfg: dict) -> int:
    """The width at which ``flops.py`` counts attention: heads x (qk +
    v) / 2."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return cfg["num_attention_heads"] * (qk + cfg["v_head_dim"]) // 2


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Causal attention of the latent layers, forward + backward, a
    token: ``6 seq heads (qk + v) / 2`` a layer (``flops.py``'s
    count)."""
    return 6.0 * latent_layers(cfg) * seq * latent_lanes(cfg)


# -- the matmul parameters a token meets --------------------------------------


def kda_params(cfg: dict) -> int:
    """``W_q, W_k, W_v, W_f, W_g`` (h x H d each), ``W_b`` (h x H),
    ``W_o`` (H d x h)."""
    h = cfg["hidden_size"]
    wide = cfg["num_attention_heads"] * cfg["head_dim"]
    return 6 * h * wide + h * cfg["num_attention_heads"]


def latent_params(cfg: dict) -> int:
    """``W_q`` (h x H 192), ``W_dkv`` (h x (latent + rope)), ``W_ukv``
    (latent x H (nope + v)), the head-wise gate (h x H), ``W_o``."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
    )
    return (
        h * heads * (nope + rope) + h * (cfg["kv_lora_rank"] + rope)
        + cfg["kv_lora_rank"] * heads * (nope + v) + h * heads
        + heads * v * h
    )


def expected_share(cfg: dict) -> float:
    return cfg["num_experts"] / cfg["router_outputs"]


def sparse_params(cfg: dict, share: float) -> float:
    """One sparse layer: the router over ALL its outputs, the shared
    expert, and ``top-k x share`` routed experts a token (``share`` of
    the assignments reach an expert held here)."""
    h = cfg["hidden_size"]
    return (
        h * cfg["router_outputs"]
        + 3 * h * cfg["moe_shared_expert_intermediate_size"]
        * cfg["num_shared_experts"]
        + cfg["num_experts_per_tok"] * share
        * 3 * h * cfg["moe_intermediate_size"]
    )


def matmul_params(cfg: dict, share=None) -> float:
    """Matmul parameters a token is multiplied by on this chip
    (``share``: of the assignments held here; uniform routing's where
    none is counted)."""
    share = expected_share(cfg) if share is None else share
    h = cfg["hidden_size"]
    return (
        kda_layers(cfg) * kda_params(cfg)
        + latent_layers(cfg) * latent_params(cfg)
        + cfg["first_k_dense_replace"] * 3 * h * cfg["intermediate_size"]
        + expert_layers(cfg) * sparse_params(cfg, share)
        + cfg["vocab_size"] * h
    )


def train_flops_per_token(cfg: dict, seq: int, share=None) -> float:
    """Required FLOPs a trained token: 6 a matmul parameter, the
    latent layers' causal attention, and the rule's recurrence."""
    return (
        6.0 * matmul_params(cfg, share)
        + attention_flops_per_token(cfg, seq)
        + rule_flops_per_step(cfg, 1, 1)
    )


# -- the readers' shared parts ------------------------------------------------


def rule_seconds_per_step(run):
    """``gdn_flops.seconds_per_step`` under ``kda_rule`` (a loop, if
    the scope ever holds one, counted once)."""
    return gdn_flops.seconds_per_step(run, RULE_SCOPE)


def counter_over_window(run, name):
    """The ``train_step`` events of the window that carry the
    program's counter ``name``, in step order; ``[]`` where none
    does."""
    steps = {s["step"] for s in run.report["window"]["steps"]}
    return sorted(
        (e for e in run.of("train_step")
         if e.get("step") in steps and name in e),
        key=lambda e: e["step"],
    )
