"""Hybrid decoder of channel-gated linear attention and latent
attention over grouped experts (``model_type: "bailing_hybrid"``;
Ling-3.0-flash's layer equations): layer ``i`` of every
``layer_group_size`` is a latent-attention layer where ``(i + 1) %
layer_group_size == 0`` and a Kimi-Delta-Attention layer otherwise
(Ring-linear's rule); ``first_dense`` leading blocks have a dense
SwiGLU, the others experts.  Per block, pre-norm::

    h = x + Mixer(RMSNorm(x))
    y = h + FFN(RMSNorm(h))

final RMSNorm, untied head.

KDA mixer (Kimi Linear, arXiv:2510.26692), ``H`` heads of ``d`` keys
and ``d`` values, per token ``x``::

    q, k, v = SiLU(causal depthwise conv_4(x W_q | x W_k | x W_v))
              (:func:`dlrover_tpu.ops.causal_conv.causal_conv`)
    q_h <- q_h / |q_h| * d^-1/2         k_h <- k_h / |k_h|
    g   = lower x sigmoid(exp(A_log_h) (x W_f + dt_bias))   [H, d]
          (the safe gate: every channel's log-decay in [lower, 0],
          ``lower`` = ``kda_lower_bound`` = -5; ``W_f`` full rank,
          float32 out; these three one pass over the rows,
          :func:`dlrover_tpu.ops.kda_rows.kda_gates`)
    beta_h = sigmoid(x W_b)_h
    o = kda_rule(q, k, v, g, beta)      (:mod:`dlrover_tpu.ops.kda`)
    y = (RMSNorm_d(o_h) * sigmoid(x W_g)) W_o
          (norm and gate one pass,
          :func:`dlrover_tpu.ops.kda_rows.kda_norm`)

Between the projections every array of the mixer stays the ``[b, s, H
d]`` rows its matmul wrote: the convolution, the gates, the rule and
the norm are Pallas kernels that take a head as ``d`` lanes of a row,
and XLA is left the matmuls, ``beta`` and the per-channel sums.

Latent-attention mixer (DeepSeek-V2, arXiv:2405.04434; no query
latent; the head-wise output gate of arXiv:2505.06708)::

    q = x W_q             [H, nope + rope]
    [c | k_r] = x W_dkv   [latent | rope]    c <- RMSNorm(c)
    [k_h | v_h] = c W_ukv                    [H, nope | v]
    q_r, k_r <- rope on INTERLEAVED pairs (lanes 2i, 2i + 1), theta
    as published, no scaling; k_r is ONE head that every head uses
    A_h = softmax_causal([q_h | q_r] [k_h | k_r]^T (nope + rope)^-1/2) v_h
    y = [A_h x sigmoid(x w_h)] W_o           (one gate a head and token)

Expert layer (:class:`dlrover_tpu.parallel.moe.DroplessMoE`): sigmoid
scores over all ``num_experts``, group-limited selection (``n_group``
groups of consecutive experts, a group's score the sum of its two
largest ``score + bias``, the best ``topk_group`` groups kept, top-k
of what is left), weights the unbiased scores renormalised and
scaled, a shared expert beside them; the chip holds
``experts_held``.  The bias moves by the loss's ``state_updates``.

The prediction layer (``num_nextn_predict_layers``) at the published
loss weight 0 takes no gradient and is not built; any other weight,
and a non-zero SwiGLU clamp, raise.

Flax names: ``kda`` and ``attn`` (the benchmark finds flash kernels
by the second).  Device scopes: ``kda_proj``, ``kda_conv``,
``kda_gates`` (the kernels ``kda_gates_fwd`` / ``kda_gates_bwd``, and
``beta``), ``kda_rule``, ``kda_norm`` (``kda_norm_fwd`` /
``kda_norm_bwd``), ``kda_out``; ``mla_q``,
``mla_kv_down``, ``mla_kv_up``, ``mla_rope``, ``attn_gate``,
``mla_out``; the expert layer's ``moe_*``.
"""

from dataclasses import dataclass
from functools import partial
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models import layers
from dlrover_tpu.models.losses import chunked_cross_entropy
from dlrover_tpu.ops.causal_conv import causal_conv
from dlrover_tpu.ops.kda import kda_rule
from dlrover_tpu.ops.kda_rows import kda_gates, kda_norm
from dlrover_tpu.parallel.moe import DroplessMoE, bias_deltas
from dlrover_tpu.telemetry.tracing import device_scope

KDA, LATENT = "kda", "latent"


@dataclass(frozen=True)
class BailingHybridConfig:
    """Field names follow the repo's configs; the HF key each one
    carries is in the comment.  The defaults are Ling-3.0-flash's,
    whole; a chip's share sets ``experts_held`` and ``vocab_size``."""

    vocab_size: int = 157184
    max_seq_len: int = 262144         # max_position_embeddings
    num_layers: int = 42              # num_hidden_layers
    layer_group_size: int = 6
    # the published index of each layer built (a pipeline stage's own
    # layers), which sets its kind; empty: 0 .. num_layers - 1
    layer_ids: Tuple[int, ...] = ()
    first_dense: int = 2              # first_k_dense_replace
    num_heads: int = 32               # num_attention_heads, both kinds
    hidden_dim: int = 2560            # hidden_size
    head_dim: int = 128               # head_dim: KDA's keys and values
    conv_kernel: int = 4              # short_conv_kernel_size
    kda_lower_bound: float = -5.0
    qk_nope_dim: int = 128            # qk_nope_head_dim
    qk_rope_dim: int = 64             # qk_rope_head_dim
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: float = 6e6
    dense_dim: int = 6144             # intermediate_size
    expert_dim: int = 768             # moe_intermediate_size
    shared_dim: int = 768             # moe_shared_expert_intermediate_size
    shared_experts: int = 1           # num_shared_experts
    num_experts: int = 512            # the router's outputs
    experts_held: Tuple[int, int] = (0, 512)   # (first, count) held here
    top_k: int = 8                    # num_experts_per_tok
    n_group: int = 8
    topk_group: int = 4
    routed_scale: float = 2.5         # routed_scaling_factor
    bias_update_rate: float = 0.001   # u of the bias's rule
    # a clamp a layer (expert_swiglu_limit_list and its shared twin),
    # for the layers that are built: 0 is no clamp
    swiglu_limits: Tuple[float, ...] = ()
    nextn_layers: int = 0             # num_nextn_predict_layers
    nextn_loss_weight: float = 0.0    # mtp_loss_scaling_factor
    rms_eps: float = 1e-6             # rms_norm_eps
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "xla"

    def __post_init__(self):
        if any(self.swiglu_limits):
            raise NotImplementedError(
                f"a SwiGLU clamp {self.swiglu_limits}: the published "
                "keys give its limit and not its form"
            )
        if self.nextn_layers and self.nextn_loss_weight:
            raise NotImplementedError(
                "a prediction layer at loss weight "
                f"{self.nextn_loss_weight}: the family builds it at 0 "
                "alone, where it takes no gradient"
            )
        if self.layer_ids and len(self.layer_ids) != self.num_layers:
            raise ValueError(
                f"{len(self.layer_ids)} layer ids for {self.num_layers} "
                "layers"
            )

    def kind(self, i: int) -> str:
        """Layer ``i``'s mixer: latent attention closes every group of
        ``layer_group_size`` published layers."""
        published = self.layer_ids[i] if self.layer_ids else i
        return (
            LATENT if (published + 1) % self.layer_group_size == 0 else KDA
        )

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @classmethod
    def tiny(cls, **kw) -> "BailingHybridConfig":
        return cls(**{**dict(
            vocab_size=256, max_seq_len=256, num_layers=4,
            layer_group_size=3, first_dense=1, num_heads=2,
            hidden_dim=64, head_dim=32, qk_nope_dim=16, qk_rope_dim=8,
            v_head_dim=16, kv_lora_rank=32, rope_theta=1e4,
            dense_dim=96, expert_dim=32, shared_dim=32, num_experts=16,
            experts_held=(4, 4), top_k=4, n_group=4, topk_group=2,
        ), **kw})


def _a_log_init(key, shape, dtype):
    # the repo's convention for a delta rule's gate (olmo_hybrid)
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-6, 16.0))


def _dt_bias_init(key, shape, dtype):
    """``softplus^-1(dt)``, ``dt`` log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, jnp.log(1e-3), jnp.log(1e-1)
    ))
    return dt + jnp.log(-jnp.expm1(-dt))


def rotate_pairs(x, cos, sin):
    """``x [b, s, heads, rope]``: lanes ``2i`` and ``2i + 1`` rotate
    together (``rope_interleave``), float32 inside; ``cos, sin [1, s,
    1, rope / 2]``."""
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).reshape(x.shape).astype(x.dtype)


class KdaAttention(nn.Module):
    """The channel-gated linear-attention mixer; returns ``(y, stats)``
    with the least log-decay of the call and the rms of the final
    state."""

    config: BailingHybridConfig

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.config
        b, s, _ = x.shape
        heads, d = cfg.num_heads, cfg.head_dim
        proj = partial(
            layers.dense, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            init_std=cfg.init_std,
        )
        with device_scope("kda_proj"):
            q = proj(heads * d, "q_proj")(x)
            k = proj(heads * d, "k_proj")(x)
            v = proj(heads * d, "v_proj")(x)
            z = proj(heads * d, "g_proj")(x)
            bb = proj(heads, "b_proj")(x).astype(jnp.float32)
            # the decay's projection leaves its matmul in float32
            f = nn.Dense(
                heads * d, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.initializers.normal(cfg.init_std),
                dot_general=partial(
                    jax.lax.dot_general,
                    preferred_element_type=jnp.float32,
                ),
                name="f_proj",
            )(x)
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        dt_bias = self.param(
            "dt_bias", _dt_bias_init, (heads * d,), jnp.float32
        )
        with device_scope("kda_conv"):
            def conv(name, y, dtype):
                taps = self.param(
                    name, layers.conv_init,
                    (cfg.conv_kernel, y.shape[-1]), cfg.param_dtype,
                )
                return causal_conv(y, taps, dtype=dtype)

            # (float32 for the per-head norm that reads q and k next)
            q = conv("q_conv", q, jnp.float32)
            k = conv("k_conv", k, jnp.float32)
            v = conv("v_conv", v, cfg.dtype)
        with device_scope("kda_gates"):
            # (rows in, rows out: a head is d lanes of a row)
            q, k, g, least = kda_gates(
                q, k, f, a_log, dt_bias, lower=cfg.kda_lower_bound,
                dtype=cfg.dtype,
            )
            beta = jax.nn.sigmoid(bb)
        with device_scope("kda_rule"):
            # (the kernels' custom_vjp says what the backward keeps)
            o, state = kda_rule(*(
                y.reshape(b, s, heads, d) for y in (q, k, v, g)
            ), beta)
        with device_scope("kda_norm"):
            # per head, one learned scale of size d, gated by sigmoid(z)
            scale = self.param(
                "o_norm", nn.initializers.ones, (d,), jnp.float32
            )
            o = kda_norm(
                o.reshape(b, s, heads * d), z, scale, eps=cfg.rms_eps,
                dtype=cfg.dtype,
            )
            stats = {
                "log_decay_min": jax.lax.stop_gradient(least),
                "state_rms": jax.lax.stop_gradient(
                    jnp.sqrt(jnp.mean(state * state))
                ),
            }
        with device_scope("kda_out"):
            return proj(cfg.hidden_dim, "o_proj")(o), stats


class LatentAttention(nn.Module):
    config: BailingHybridConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        b, s, _ = x.shape
        heads, nope, rope, dv = (
            cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_head_dim,
        )
        proj = partial(
            layers.dense, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            init_std=cfg.init_std,
        )
        with device_scope("mla_q"):
            q = proj(heads * (nope + rope), "q_proj")(x)
        with device_scope("mla_kv_down"):
            down = proj(cfg.kv_lora_rank + rope, "kv_down")(x)
            latent = layers.RMSNorm(cfg.rms_eps, name="kv_norm")(
                down[..., :cfg.kv_lora_rank]
            )
        with device_scope("mla_kv_up"):
            up = proj(heads * (nope + dv), "kv_up")(latent)
        with device_scope("mla_rope"):
            angles = (
                jnp.arange(s, dtype=jnp.float32)[:, None]
                * cfg.rope_theta ** (
                    -jnp.arange(0, rope, 2, dtype=jnp.float32) / rope
                )[None, :]
            )
            cos = jnp.cos(angles)[None, :, None, :]
            sin = jnp.sin(angles)[None, :, None, :]
            q = q.reshape(b, s, heads, nope + rope)
            q = jnp.concatenate(
                [q[..., :nope], rotate_pairs(q[..., nope:], cos, sin)],
                axis=-1,
            )
            up = up.reshape(b, s, heads, nope + dv)
            # the one rope key, broadcast to every head's key
            k_pe = rotate_pairs(
                down[..., None, cfg.kv_lora_rank:], cos, sin
            )
            k = jnp.concatenate([
                up[..., :nope],
                jnp.broadcast_to(k_pe, (b, s, heads, rope)),
            ], axis=-1)
            v = up[..., nope:]
        out = layers.attention(
            cfg.attention_impl, q, k, v, scale=cfg.qk_head_dim ** -0.5,
            dtype=cfg.dtype,
        )
        with device_scope("attn_gate"):
            gate = jax.nn.sigmoid(
                proj(heads, "g_proj")(x).astype(jnp.float32)
            )
            out = (out * gate[..., None]).astype(cfg.dtype)
        with device_scope("mla_out"):
            return proj(cfg.hidden_dim, "o_proj")(
                out.reshape(b, s, heads * dv)
            )


class BailingHybridBlock(nn.Module):
    """``kind`` picks the mixer, ``dense`` the feed-forward.  Returns
    ``(y, the KDA mixer's stats or None, router stats or None)``."""

    config: BailingHybridConfig
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.config
        h = layers.RMSNorm(cfg.rms_eps, name="ln_attn")(x)
        if self.kind == KDA:
            mixed, rule = KdaAttention(cfg, name="kda")(h)
        elif self.kind == LATENT:
            mixed, rule = LatentAttention(cfg, name="attn")(h), None
        else:
            raise ValueError(f"unknown layer kind {self.kind!r}")
        x = x + mixed
        h = layers.RMSNorm(cfg.rms_eps, name="ln_mlp")(x)
        if self.dense:
            return x + layers.SwiGLU(
                cfg.dense_dim, cfg.hidden_dim, cfg.dtype,
                cfg.param_dtype, cfg.init_std, name="mlp",
            )(h), rule, None
        out, stats = DroplessMoE(
            num_experts=cfg.num_experts, mlp_dim=cfg.expert_dim,
            top_k=cfg.top_k, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(cfg.init_std),
            held=cfg.experts_held, score="sigmoid", select_bias=True,
            renormalise=True, scale=cfg.routed_scale,
            shared_dim=cfg.shared_experts * cfg.shared_dim,
            n_group=cfg.n_group, topk_group=cfg.topk_group, name="moe",
        )(h)
        return x + out, rule, stats


def _stacked(per_layer):
    return jax.tree.map(lambda *a: jnp.stack(a), *per_layer)


class BailingHybrid(nn.Module):
    config: BailingHybridConfig

    @nn.compact
    def __call__(
        self, tokens: jax.Array, return_hidden: bool = False,
        return_stats: bool = False,
    ):
        """Logits ``[b, s, vocab]`` in float32, or with
        ``return_hidden`` the final-norm output for a chunked head
        (``models/losses.py``); with ``return_stats`` also ``(the KDA
        layers' stats, the expert layers' router stats)``, each
        stacked over its layers."""
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.initializers.normal(cfg.init_std),
            name="wte",
        )(tokens)
        block = (
            layers.rematted(BailingHybridBlock, prevent_cse=True)
            if cfg.remat else BailingHybridBlock
        )
        rules, routers = [], []
        for i in range(cfg.num_layers):
            x, rule, stats = block(
                cfg, cfg.kind(i), i < cfg.first_dense, name=f"block_{i}"
            )(x)
            if rule is not None:
                rules.append(rule)
            if stats is not None:
                routers.append(stats)
        x = layers.RMSNorm(cfg.rms_eps, name="ln_f")(x)
        if not return_hidden:
            x = layers.dense(
                cfg.vocab_size, "lm_head", cfg.dtype, cfg.param_dtype,
                cfg.init_std,
            )(x).astype(jnp.float32)
        if not return_stats:
            return x
        return x, (_stacked(rules), _stacked(routers))

    init_params = layers.init_params


def make_bailing_hybrid_loss(model: BailingHybrid, num_chunks: int = 8):
    """Next-token cross entropy through the chunked head, alone (no
    balance term: the bias balances the load).  ``loss_fn(params,
    batch) -> (loss, aux)``; ``aux`` holds the step's ``kda.*`` and
    ``moe.*`` counters and, under ``"state_updates"`` (the key that
    ``make_train_step`` documents), each expert layer's bias delta
    for the step to add."""
    cfg = model.config
    expert_layers = range(cfg.first_dense, cfg.num_layers)

    def loss_fn(params, batch):
        hidden, (rules, stats) = model.apply(
            {"params": params}, batch["x"], return_hidden=True,
            return_stats=True,
        )
        loss = chunked_cross_entropy(
            hidden, params["lm_head"]["kernel"], batch["y"],
            num_chunks=num_chunks,
        )
        with device_scope("moe_router"):
            counts = jax.lax.stop_gradient(stats["counts"])
            deltas = bias_deltas(counts, cfg.bias_update_rate)
            biases = jnp.stack([
                params[f"block_{i}"]["moe"]["select_bias"]
                for i in expert_layers
            ])
        return loss, {
            "kda.log_decay_min": jnp.min(rules["log_decay_min"]),
            "kda.state_rms_max": jnp.max(rules["state_rms"]),
            "moe.groups_per_token_mean": jnp.mean(
                stats["groups_per_token"]
            ),
            "moe.held_rows_share": jnp.mean(
                stats["held_rows"] / counts.sum(axis=1)
            ),
            "moe.bias_abs_max": jnp.max(jnp.abs(biases)),
            "state_updates": {
                f"block_{i}": {"moe": {"select_bias": deltas[j]}}
                for j, i in enumerate(expert_layers)
            },
        }

    loss_fn.has_aux = True
    return loss_fn
