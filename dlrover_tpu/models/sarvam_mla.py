"""Latent-attention mixture-of-experts decoder (``model_type:
"sarvam_mla"``; the DeepSeek-V2-Lite family of layer equations): a
leading dense block, then expert blocks, each ``x += Attn(RMSNorm(x))``,
``x += FFN(RMSNorm(x))``; final RMSNorm, untied head.

Latent attention, ``H`` heads HELD on this chip, per token ``x``::

    q = x W_q             [H, nope + rope]   (no query latent)
    [c | k_pe] = x W_kva  [latent | rope]    c <- RMSNorm(c)
    [k_nope_h | v_h] = c W_kvb               [H, nope | v]
    q_pe, k_pe <- rope (yarn frequencies); k_pe is ONE head that
    every query head uses
    o_h = softmax_causal([q_nope_h | q_pe_h] [k_nope_h | k_pe]^T
                         x (nope + rope)^-1/2 x m^2) v_h
    out = [o_1 .. o_H] W_o

so q and k are ``nope + rope`` wide (192) and v ``v_head_dim`` (128):
``ops/flash_attention.py`` takes the two sizes; nothing is padded, and
the absorbed form (a decode optimisation) is not used in training.
The rope pairs lane ``i`` with lane ``i + rope / 2`` (half-split; the
published weights pair neighbours, which is a permutation of ``W_q``'s
and ``W_kva``'s rope columns).

Expert layer (:class:`dlrover_tpu.parallel.moe.DroplessMoE`): sigmoid
scores, the top-k of ``score + bias`` chosen and weighted by the score
alone, renormalised and scaled, a shared expert beside them.  The chip
holds experts ``[lo, lo + count)`` of ``num_experts``: it routes over
all and computes its own.  The bias takes no gradient: after each step
``b_e += u x sign(mean(n) - n_e)``, ``n`` the step's assignments to
each expert of that layer; the loss hands the train step those deltas
(``aux["state_updates"]``, which ``make_train_step`` adds).

The flax module of the attention is called ``attn`` (the benchmark
finds flash kernels by that name).  Device scopes: ``mla_q``,
``mla_kv_down``, ``mla_kv_up``, ``mla_rope``, ``mla_out``, and the
expert layer's ``moe_*``.
"""

from dataclasses import dataclass
from functools import partial
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models import layers
from dlrover_tpu.models.losses import chunked_cross_entropy
from dlrover_tpu.parallel.moe import DroplessMoE, bias_deltas
from dlrover_tpu.telemetry.tracing import device_scope


@dataclass(frozen=True)
class SarvamMlaConfig:
    """Field names follow the repo's configs; the HF key each one
    carries is in the comment.  The defaults are sarvam-105b's, whole;
    a chip's share sets ``num_heads_held``, ``experts_held`` and
    ``vocab_size``."""

    vocab_size: int = 262144
    max_seq_len: int = 131072         # max_position_embeddings
    num_layers: int = 32              # num_hidden_layers
    first_dense: int = 1              # first_k_dense_replace
    num_heads_held: int = 64          # num_attention_heads (held here)
    hidden_dim: int = 4096            # hidden_size
    qk_nope_dim: int = 128            # qk_nope_head_dim
    qk_rope_dim: int = 64             # qk_rope_head_dim
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    dense_dim: int = 16384            # intermediate_size
    expert_dim: int = 2048            # moe_intermediate_size
    shared_experts: int = 1           # num_shared_experts
    num_experts: int = 128            # the router's outputs
    experts_held: Tuple[int, int] = (0, 128)   # (first, count) held here
    top_k: int = 8                    # num_experts_per_tok
    routed_scale: float = 2.5         # routed_scaling_factor
    bias_update_rate: float = 0.001   # u of the bias's rule
    rope_theta: float = 10000.0
    rope_factor: float = 40.0         # rope_scaling.factor
    rope_original_len: int = 4096     # .original_max_position_embeddings
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_eps: float = 1e-6             # rms_norm_eps
    init_std: float = 0.02            # initializer_range
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "xla"

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @classmethod
    def tiny(cls, **kw) -> "SarvamMlaConfig":
        return cls(**{**dict(
            vocab_size=256, max_seq_len=256, num_layers=3,
            num_heads_held=2, hidden_dim=64, qk_nope_dim=16,
            qk_rope_dim=8, v_head_dim=16, kv_lora_rank=32,
            dense_dim=96, expert_dim=32, num_experts=16,
            experts_held=(4, 4), top_k=4, rope_original_len=64,
        ), **kw})


def softmax_scale(cfg: SarvamMlaConfig) -> float:
    """``d_qk^-1/2 x m^2``, ``m`` yarn's attention factor over all
    dims."""
    scale = cfg.qk_head_dim ** -0.5
    if cfg.rope_mscale_all_dim:
        scale *= layers.yarn_mscale(
            cfg.rope_factor, cfg.rope_mscale_all_dim
        ) ** 2
    return scale


class LatentAttention(nn.Module):
    config: SarvamMlaConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        b, s, _ = x.shape
        heads, nope, rope, dv = (
            cfg.num_heads_held, cfg.qk_nope_dim, cfg.qk_rope_dim,
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
                * jnp.asarray(layers.yarn_inv_freq(
                    rope, cfg.rope_theta, cfg.rope_factor,
                    cfg.rope_original_len, cfg.rope_beta_fast,
                    cfg.rope_beta_slow,
                ), jnp.float32)[None, :]
            )
            m = layers.yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / (
                layers.yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
            )
            cos = (jnp.cos(angles) * m)[None, :, None, :]
            sin = (jnp.sin(angles) * m)[None, :, None, :]
            q = q.reshape(b, s, heads, nope + rope)
            q = jnp.concatenate(
                [q[..., :nope], layers.rotate_half(q[..., nope:], cos, sin)],
                axis=-1,
            )
            up = up.reshape(b, s, heads, nope + dv)
            # the one rope key, broadcast to every head's key
            k_pe = layers.rotate_half(
                down[..., None, cfg.kv_lora_rank:], cos, sin
            )
            k = jnp.concatenate([
                up[..., :nope],
                jnp.broadcast_to(k_pe, (b, s, heads, rope)),
            ], axis=-1)
            v = up[..., nope:]
        out = layers.attention(
            cfg.attention_impl, q, k, v, scale=softmax_scale(cfg),
            dtype=cfg.dtype,
        )
        with device_scope("mla_out"):
            return proj(cfg.hidden_dim, "o_proj")(
                out.reshape(b, s, heads * dv)
            )


class SarvamMlaBlock(nn.Module):
    """``dense`` picks the feed-forward: the leading blocks' SwiGLU or
    the expert layer; nothing else differs.  Returns ``(y, router
    stats)``, ``None`` for a dense block."""

    config: SarvamMlaConfig
    dense: bool

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.config
        x = x + LatentAttention(cfg, name="attn")(
            layers.RMSNorm(cfg.rms_eps, name="ln_attn")(x)
        )
        h = layers.RMSNorm(cfg.rms_eps, name="ln_mlp")(x)
        if self.dense:
            return x + layers.SwiGLU(
                cfg.dense_dim, cfg.hidden_dim, cfg.dtype,
                cfg.param_dtype, cfg.init_std, name="mlp",
            )(h), None
        out, stats = DroplessMoE(
            num_experts=cfg.num_experts, mlp_dim=cfg.expert_dim,
            top_k=cfg.top_k, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(cfg.init_std),
            held=cfg.experts_held, score="sigmoid", select_bias=True,
            renormalise=True, scale=cfg.routed_scale,
            shared_dim=cfg.shared_experts * cfg.expert_dim, name="moe",
        )(h)
        return x + out, stats


class SarvamMla(nn.Module):
    config: SarvamMlaConfig

    @nn.compact
    def __call__(
        self, tokens: jax.Array, return_hidden: bool = False,
        return_router_stats: bool = False,
    ):
        """Logits ``[b, s, vocab]`` in float32, or with
        ``return_hidden`` the final-norm output for a chunked head
        (``models/losses.py``); with ``return_router_stats`` also
        :func:`dropless_moe`'s ``stats``, stacked over the expert
        layers."""
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.initializers.normal(cfg.init_std),
            name="wte",
        )(tokens)
        block = (
            layers.rematted(SarvamMlaBlock, prevent_cse=True) if cfg.remat
            else SarvamMlaBlock
        )
        per_layer = []
        for i in range(cfg.num_layers):
            x, stats = block(
                cfg, i < cfg.first_dense, name=f"block_{i}"
            )(x)
            if stats is not None:
                per_layer.append(stats)
        x = layers.RMSNorm(cfg.rms_eps, name="ln_f")(x)
        if not return_hidden:
            x = layers.dense(
                cfg.vocab_size, "lm_head", cfg.dtype, cfg.param_dtype,
                cfg.init_std,
            )(x).astype(jnp.float32)
        if not return_router_stats:
            return x
        return x, jax.tree.map(lambda *a: jnp.stack(a), *per_layer)

    init_params = layers.init_params


def make_sarvam_mla_loss(model: SarvamMla, num_chunks: int = 8):
    """Next-token cross entropy through the chunked head, alone (no
    auxiliary loss: the bias balances the load).  ``loss_fn(params,
    batch) -> (loss, aux)``; ``aux`` holds the step's ``moe.*``
    counters and, under ``"state_updates"`` (the key that
    ``make_train_step`` documents), each expert layer's bias delta
    for the step to add."""
    cfg = model.config
    expert_layers = range(cfg.first_dense, cfg.num_layers)

    def loss_fn(params, batch):
        hidden, stats = model.apply(
            {"params": params}, batch["x"], return_hidden=True,
            return_router_stats=True,
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
            "moe.held_rows_share": jnp.mean(
                stats["held_rows"] / counts.sum(axis=1)
            ),
            "moe.held_tiles_share": jnp.mean(
                stats["tiles_used"] / stats["tiles"]
            ),
            "moe.bias_abs_max": jnp.max(jnp.abs(biases)),
            "state_updates": {
                f"block_{i}": {"moe": {"select_bias": deltas[j]}}
                for j, i in enumerate(expert_layers)
            },
        }

    loss_fn.has_aux = True
    return loss_fn
