"""Llama-family decoder (RMSNorm + RoPE + SwiGLU + GQA).

The reference accelerates HF Llama via module swaps
(``atorch/modules/transformer/layers.py:1353 LlamaAttentionFA``,
auto_accelerate FSDP strategies); the BASELINE north star trains
Llama-2-7B.  This is a native flax implementation sharing the GPT
conventions: bf16 compute / fp32 norms, fused projections, pluggable
attention (Pallas flash), param names matched by the TP partition
rules (q_proj/k_proj/v_proj/o_proj, gate/up/down).
"""

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.models.gpt import PipelinedDecoder
from dlrover_tpu.models import layers
from dlrover_tpu.ops.attention import cached_decode_attention
from dlrover_tpu.parallel.moe import MoEMLP


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32      # < num_heads -> grouped-query attn
    hidden_dim: int = 4096
    intermediate_dim: int = 11008
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "full"  # "full" | "offload" (see gpt.py)
    attention_impl: str = "xla"
    # KV-cache decoding (same contract as GPTConfig.decode): RoPE uses
    # absolute positions continued across chunks; the cache stores
    # post-RoPE keys at kv-head granularity (GQA-aware)
    decode: bool = False
    # Mixtral-style sparse MoE: >0 replaces the SwiGLU MLP of every
    # ``moe_every``-th block with gated (SwiGLU) experts dispatched
    # over the ``expert`` mesh axis
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 1
    moe_capacity_factor: float = 1.25

    def __post_init__(self):
        if self.remat_policy not in layers.REMAT_POLICIES:
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r} "
                "(full | offload)"
            )
        if self.remat_policy != "full" and not self.remat:
            raise ValueError(
                "remat_policy='offload' requires remat=True"
            )

    @property
    def head_dim(self) -> int:
        return self.hidden_dim // self.num_heads

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        return cls(
            vocab_size=256, max_seq_len=128, num_layers=2,
            num_heads=4, num_kv_heads=2, hidden_dim=64,
            intermediate_dim=128, **kw,
        )

    @classmethod
    def llama2_7b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        return cls(
            vocab_size=128256, max_seq_len=8192, num_layers=32,
            num_heads=32, num_kv_heads=8, hidden_dim=4096,
            intermediate_dim=14336, rope_theta=500000.0, **kw,
        )

    @classmethod
    def mixtral_8x7b(cls, **kw) -> "LlamaConfig":
        """Mixtral-class sparse MoE (8 experts, top-2, GQA)."""
        return cls(
            vocab_size=32000, max_seq_len=4096, num_layers=32,
            num_heads=32, num_kv_heads=8, hidden_dim=4096,
            intermediate_dim=14336, rope_theta=1e6,
            moe_experts=8, moe_top_k=2, **kw,
        )


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        b, s, _ = x.shape
        hd = cfg.head_dim
        q = nn.Dense(
            cfg.num_heads * hd, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="q_proj",
        )(x).reshape(b, s, cfg.num_heads, hd)
        k = nn.Dense(
            cfg.num_kv_heads * hd, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="k_proj",
        )(x).reshape(b, s, cfg.num_kv_heads, hd)
        v = nn.Dense(
            cfg.num_kv_heads * hd, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="v_proj",
        )(x).reshape(b, s, cfg.num_kv_heads, hd)

        if cfg.decode:
            cache_shape = (
                b, cfg.max_seq_len, cfg.num_kv_heads, hd
            )
            ck = self.variable(
                "cache", "cached_key",
                lambda: jnp.zeros(cache_shape, k.dtype),
            )
            cv = self.variable(
                "cache", "cached_value",
                lambda: jnp.zeros(cache_shape, v.dtype),
            )
            idx = self.variable(
                "cache", "cache_index",
                lambda: jnp.zeros((), jnp.int32),
            )
            pos = idx.value
            positions = pos + jnp.arange(s)
            q = layers.rope(q, positions, cfg.rope_theta)
            k = layers.rope(k, positions, cfg.rope_theta)
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k, (0, pos, 0, 0)
            )
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v, (0, pos, 0, 0)
            )
            idx.value = pos + s
            # GQA-aware shared helper: the cache stays at kv-head
            # granularity; q folds into (kv_head, group) instead of
            # expanding the whole cache every decode step
            out = cached_decode_attention(
                q, ck.value, cv.value, positions, dtype=cfg.dtype
            )
        else:
            positions = jnp.arange(s)
            q = layers.rope(q, positions, cfg.rope_theta)
            k = layers.rope(k, positions, cfg.rope_theta)
            out = layers.attention(
                cfg.attention_impl, q, k, v, dtype=cfg.dtype
            )
        out = out.reshape(b, s, cfg.num_heads * hd)
        return nn.Dense(
            cfg.hidden_dim, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="o_proj",
        )(out)


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        gate = nn.Dense(
            cfg.intermediate_dim, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="gate",
        )(x)
        up = nn.Dense(
            cfg.intermediate_dim, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="up",
        )(x)
        return nn.Dense(
            cfg.hidden_dim, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="down",
        )(nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    config: LlamaConfig
    use_moe: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        # named for the offload remat policy (no-op otherwise)
        x = checkpoint_name(x, "block_in")
        h = layers.RMSNorm(cfg.rms_eps, name="ln_attn")(x)
        x = x + LlamaAttention(cfg, name="attn")(h)
        h = layers.RMSNorm(cfg.rms_eps, name="ln_mlp")(x)
        if self.use_moe:
            mlp_out = MoEMLP(
                num_experts=cfg.moe_experts,
                hidden_dim=cfg.hidden_dim,
                mlp_dim=cfg.intermediate_dim,
                top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                no_drop=cfg.decode,
                gated=True,  # SwiGLU experts (Mixtral)
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name="moe",
            )(h)
        else:
            mlp_out = LlamaMLP(cfg, name="mlp")(h)
        x = x + mlp_out
        return x


class Llama(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(
        self, tokens: jax.Array, return_hidden: bool = False
    ) -> jax.Array:
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="wte",
        )(tokens)
        block = LlamaBlock
        if cfg.remat:
            block = layers.rematted(
                LlamaBlock, prevent_cse=False, policy=cfg.remat_policy
            )
        for i in range(cfg.num_layers):
            # shared convention with GPT: every moe_every-th block,
            # counting from the end of the first stride (moe_every=1
            # -> all blocks, =2 -> blocks 1,3,5...)
            use_moe = (
                cfg.moe_experts > 0
                and (i + 1) % cfg.moe_every == 0
            )
            x = block(cfg, use_moe=use_moe, name=f"block_{i}")(x)
        x = layers.RMSNorm(cfg.rms_eps, name="ln_f")(x)
        if return_hidden:
            # for chunked/fused losses (models/losses.py)
            return x
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="lm_head",
        )(x)
        return logits.astype(jnp.float32)

    init_params = layers.init_params


class PipelinedLlama(PipelinedDecoder):
    """Llama family over the pipeline axis: RoPE blocks need no
    position embedding at the boundary (positions are absolute inside
    each block's attention), RMSNorm + untied lm head."""

    def _embed(self, embed_pp, tokens):
        cfg = self.config
        wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
        )
        return wte.apply({"params": embed_pp["wte"]}, tokens)

    def _block(self):
        return LlamaBlock(self.config)

    def _apply_head(self, head_pp, wte_params, h):
        cfg = self.config
        h = layers.RMSNorm(cfg.rms_eps).apply(
            {"params": head_pp["ln_f"]}, h
        )
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
        ).apply({"params": head_pp["lm_head"]}, h)
        return logits.astype(jnp.float32)


def to_pipelined(
    model: "Llama", num_stages: int, num_microbatches: int,
    batch_axis=("data", "fsdp"),
) -> PipelinedLlama:
    """auto_accelerate protocol hook (build_from_plan calls this when
    the plan's mesh has pipeline > 1)."""
    return PipelinedLlama(
        model, num_stages, num_microbatches, batch_axis
    )


Llama.to_pipelined = to_pipelined
