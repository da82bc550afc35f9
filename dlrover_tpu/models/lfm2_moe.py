"""Gated-short-convolution and grouped-query-attention decoder over
sigmoid-routed experts (``model_type: "lfm2_moe"``; LFM2-24B-A2B's
layer equations).  ``layer_types[i]`` picks block ``i``'s mixer, the
first ``num_dense_layers`` blocks have a dense SwiGLU and the others
experts.  Per block, pre-norm::

    h = x + Mixer(RMSNorm_operator(x))
    y = h + FFN(RMSNorm_ffn(h))

then the final ``embedding_norm`` and a head TIED to the embedding
(logits ``= h E^T``; the loss goes through ``losses.py``'s chunked
head on the table itself).

``"conv"`` mixer (``Lfm2ShortConv``): a doubly gated short
convolution, NO activation and no state beyond ``K - 1`` rows::

    [B | C | u] = x W_in         (hidden x 3 hidden, chunk(3) of the lanes)
    y = C * conv_K(B * u)        (depthwise, causal, K = conv_kernel
                                  taps, no bias:
                                  :func:`dlrover_tpu.ops.short_conv.short_conv`)
    out = y W_out

``"full_attention"`` mixer (``Lfm2Attention``): ``q``, ``k``, ``v``
by three matrices, ``num_heads`` | ``num_kv_heads`` heads of
``head_dim``; an RMSNorm over each head's lanes on ``q`` and on ``k``
(one learned scale of ``head_dim`` each, shared by the heads) BEFORE
rope; rope on all lanes, half-split pairs (``rotate_half``); causal
softmax at ``head_dim ** -0.5``; no bias, no gate, no window.

Expert layer (:class:`dlrover_tpu.parallel.moe.DroplessMoE`): router
logits in float32, sigmoid scores, the top-k of ``score +
expert_bias`` chosen and weighted by the score alone, divided by their
sum + 1e-6 and scaled, NO shared expert; the chip holds experts
``[lo, lo + count)``, routes over all and computes its own.  The bias
takes no gradient: the loss hands the train step its deltas
(``aux["state_updates"]``, ``parallel/moe.py::bias_deltas``).  No
auxiliary loss.

Flax names: ``short_conv`` and ``attn`` (the benchmark finds flash
kernels by the second; a full layer's sits under the device scope
``full_attn``, OUTSIDE the module, as ``mimo_v2.py``'s does).  Device
scopes: ``sconv_proj`` (the ``W_in`` and ``W_out`` matmuls),
``sconv_mix`` (everything between them: the ``bcx_fwd`` / ``bcx_bwd``
kernels and the counter's reduction); ``attn_qkv`` (the three
projections and the per-head norms), ``attn_rope``, ``attn_out``; the
expert layer's ``moe_*``; the head's ``loss_head``.
"""

from dataclasses import dataclass
from functools import partial
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models import layers
from dlrover_tpu.models.losses import chunked_cross_entropy
from dlrover_tpu.ops.short_conv import short_conv
from dlrover_tpu.parallel.moe import DroplessMoE, bias_deltas
from dlrover_tpu.telemetry.tracing import device_scope

CONV, ATTENTION = "conv", "full_attention"


@dataclass(frozen=True)
class Lfm2MoeConfig:
    """Field names follow the repo's configs; the HF key each one
    carries is in the comment.  The defaults are LFM2-24B-A2B's, whole;
    a chip's share sets ``experts_held`` and a stage's layers
    ``layer_types`` and ``num_dense_layers``."""

    vocab_size: int = 65536
    max_seq_len: int = 128000         # max_position_embeddings
    hidden_dim: int = 2048            # hidden_size
    # one mixer kind a layer built: attention every fourth, from 2
    layer_types: Tuple[str, ...] = tuple(
        ATTENTION if i % 4 == 2 else CONV for i in range(40)
    )
    num_dense_layers: int = 2
    num_heads: int = 32               # num_attention_heads
    num_kv_heads: int = 8             # num_key_value_heads
    head_dim: int = 64                # hidden_size / num_attention_heads
    conv_kernel: int = 3              # conv_L_cache
    rope_theta: float = 1e6           # rope_parameters.rope_theta
    dense_dim: int = 11776            # intermediate_size
    expert_dim: int = 1536            # moe_intermediate_size
    num_experts: int = 64             # the router's outputs
    experts_held: Tuple[int, int] = (0, 64)   # (first, count) held here
    top_k: int = 4                    # num_experts_per_tok
    routed_scale: float = 1.0         # routed_scaling_factor
    bias_update_rate: float = 0.001   # u of the bias's rule
    rms_eps: float = 1e-5             # norm_eps
    init_std: float = 0.02            # initializer_range
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "xla"

    def __post_init__(self):
        unknown = set(self.layer_types) - {CONV, ATTENTION}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads over {self.num_kv_heads} "
                "kv heads"
            )

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(range(self.num_dense_layers, self.num_layers))

    @classmethod
    def tiny(cls, **kw) -> "Lfm2MoeConfig":
        return cls(**{**dict(
            vocab_size=256, max_seq_len=256, hidden_dim=64,
            layer_types=(CONV, ATTENTION, CONV, CONV),
            num_dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=16,
            rope_theta=1e4, dense_dim=96, expert_dim=32, num_experts=16,
            experts_held=(4, 4), top_k=4,
        ), **kw})


class ShortConv(nn.Module):
    """The doubly gated short convolution; returns ``(out, the rms of
    y)``, the second with no gradient."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.config
        h = cfg.hidden_dim
        proj = partial(
            layers.dense, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            init_std=cfg.init_std,
        )
        with device_scope("sconv_proj"):
            bcu = proj(3 * h, "in_proj")(x)
        taps = self.param(
            "taps", layers.conv_init, (cfg.conv_kernel, h), cfg.param_dtype
        )
        with device_scope("sconv_mix"):
            # (the projection's own array in, rows out: the kernels'
            # custom_vjp says what the backward keeps)
            y = short_conv(bcu, taps, dtype=cfg.dtype)
            y32 = jax.lax.stop_gradient(y).astype(jnp.float32)
            rms = jnp.sqrt(jnp.mean(y32 * y32))
        with device_scope("sconv_proj"):
            return proj(h, "out_proj")(y), rms


class Lfm2Attention(nn.Module):
    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        b, s, _ = x.shape
        heads, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        proj = partial(
            layers.dense, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            init_std=cfg.init_std,
        )
        with device_scope("attn_qkv"):
            q = proj(heads * d, "q_proj")(x).reshape(b, s, heads, d)
            k = proj(kv * d, "k_proj")(x).reshape(b, s, kv, d)
            v = proj(kv * d, "v_proj")(x).reshape(b, s, kv, d)
            # over a head's lanes, one scale of d shared by the heads
            q = layers.RMSNorm(cfg.rms_eps, name="q_layernorm")(q)
            k = layers.RMSNorm(cfg.rms_eps, name="k_layernorm")(k)
        with device_scope("attn_rope"):
            cos, sin = layers.RopeRule(theta=cfg.rope_theta).tables(s, d)
            q = layers.rotate_half(q, cos, sin)
            k = layers.rotate_half(k, cos, sin)
        out = layers.attention(
            cfg.attention_impl, q, k, v, dtype=cfg.dtype
        )
        with device_scope("attn_out"):
            return proj(cfg.hidden_dim, "out_proj")(
                out.reshape(b, s, heads * d)
            )


class Lfm2MoeBlock(nn.Module):
    """``kind`` picks the mixer, ``dense`` the feed-forward.  Returns
    ``(y, the conv mixer's output rms or None, router stats or
    None)``."""

    config: Lfm2MoeConfig
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.config
        h = layers.RMSNorm(cfg.rms_eps, name="operator_norm")(x)
        if self.kind == CONV:
            mixed, rms = ShortConv(cfg, name="short_conv")(h)
        else:
            with device_scope("full_attn"):
                mixed, rms = Lfm2Attention(cfg, name="attn")(h), None
        x = x + mixed
        h = layers.RMSNorm(cfg.rms_eps, name="ffn_norm")(x)
        if self.dense:
            return x + layers.SwiGLU(
                cfg.dense_dim, cfg.hidden_dim, cfg.dtype,
                cfg.param_dtype, cfg.init_std, name="mlp",
            )(h), rms, None
        out, stats = DroplessMoE(
            num_experts=cfg.num_experts, mlp_dim=cfg.expert_dim,
            top_k=cfg.top_k, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(cfg.init_std),
            held=cfg.experts_held, score="sigmoid", select_bias=True,
            renormalise=True, renormalise_eps=1e-6,
            scale=cfg.routed_scale, shared_dim=0, name="moe",
        )(h)
        return x + out, rms, stats


class Lfm2Moe(nn.Module):
    config: Lfm2MoeConfig

    @nn.compact
    def __call__(
        self, tokens: jax.Array, return_hidden: bool = False,
        return_stats: bool = False,
    ):
        """Logits ``[b, s, vocab]`` in float32 (the hidden states
        against the embedding table), or with ``return_hidden`` the
        final-norm output for a chunked head (``models/losses.py``);
        with ``return_stats`` also ``(the conv mixers' output rms
        [conv layers], the expert layers' router stats)``, the second
        stacked over its layers."""
        cfg = self.config
        wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.initializers.normal(cfg.init_std),
            name="wte",
        )
        x = wte(tokens)
        block = (
            layers.rematted(Lfm2MoeBlock, prevent_cse=True) if cfg.remat
            else Lfm2MoeBlock
        )
        mixers, routers = [], []
        for i, kind in enumerate(cfg.layer_types):
            x, rms, stats = block(
                cfg, kind, i < cfg.num_dense_layers, name=f"block_{i}"
            )(x)
            if rms is not None:
                mixers.append(rms)
            if stats is not None:
                routers.append(stats)
        x = layers.RMSNorm(cfg.rms_eps, name="embedding_norm")(x)
        if not return_hidden:
            x = wte.attend(x).astype(jnp.float32)
        if not return_stats:
            return x
        return x, (
            jnp.stack(mixers),
            jax.tree.map(lambda *a: jnp.stack(a), *routers),
        )

    init_params = layers.init_params


def make_lfm2_moe_loss(model: Lfm2Moe, num_chunks: int = 8):
    """Next-token cross entropy through the chunked head on the TIED
    table, alone (no auxiliary loss: the bias balances the load).
    ``loss_fn(params, batch) -> (loss, aux)``; ``aux`` holds the
    step's ``sconv.*`` and ``moe.*`` counters and, under
    ``"state_updates"`` (the key that ``make_train_step`` documents),
    each expert layer's bias delta for the step to add."""
    cfg = model.config

    def loss_fn(params, batch):
        hidden, (mixers, stats) = model.apply(
            {"params": params}, batch["x"], return_hidden=True,
            return_stats=True,
        )
        loss = chunked_cross_entropy(
            hidden, params["wte"]["embedding"], batch["y"],
            num_chunks=num_chunks, transpose=True,
        )
        with device_scope("moe_router"):
            counts = jax.lax.stop_gradient(stats["counts"])
            deltas = bias_deltas(counts, cfg.bias_update_rate)
            biases = jnp.stack([
                params[f"block_{i}"]["moe"]["select_bias"]
                for i in cfg.expert_layers
            ])
        return loss, {
            "sconv.out_rms_max": jnp.max(mixers),
            "moe.held_rows_share": jnp.mean(
                stats["held_rows"] / counts.sum(axis=1)
            ),
            "moe.held_tiles_share": jnp.mean(
                stats["tiles_used"] / stats["tiles"]
            ),
            "moe.bias_abs_max": jnp.max(jnp.abs(biases)),
            "state_updates": {
                f"block_{i}": {"moe": {"select_bias": deltas[j]}}
                for j, i in enumerate(cfg.expert_layers)
            },
        }

    loss_fn.has_aux = True
    return loss_fn
