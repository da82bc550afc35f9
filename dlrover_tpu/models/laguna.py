"""Window-and-full-attention mixture-of-experts decoder (``model_type:
"laguna"``): pre-norm blocks ``x += Attn(RMSNorm(x))``, ``x +=
FFN(RMSNorm(x))``; final RMSNorm, untied head.

Attention is grouped-query (``num_kv_heads`` kv heads, heads of
``head_dim``) and takes three things from the layer's KIND
(``layer_types[l]``): its number of query heads
(``heads_per_layer[l]``: 48 in a full layer, 72 in a sliding one), its
mask (causal; a sliding layer also hides keys ``sliding_window`` or
more behind the query: ``ops/flash_attention.py``'s ``window``) and
its rope rule (:class:`layers.RopeRule`: a full layer rotates the first half
of each head with yarn frequencies and scales cos and sin, a sliding
layer rotates the whole head with the default rule).  Rope pairs lane
``i`` with lane ``i + rotated / 2`` (half-split).  Each head's output
is scaled by a gate of its own, ``sigmoid(x W_g)`` of the block's
normed input, before the output projection.

``mlp_layer_types[l]`` picks the feed-forward: a dense SwiGLU or the
expert layer (:class:`dlrover_tpu.parallel.moe.DroplessMoE`: softmax
scores over all the router's outputs, the top-k renormalised and
scaled, a shared expert beside them; the chip holds experts ``[lo, lo
+ count)``, routes over all and computes its own).  No auxiliary loss.

The flax module of the attention is called ``attn`` (the benchmark
finds flash kernels by that name); a sliding layer's sits under the
device scope ``swa``, a full layer's under ``full_attn``, both OUTSIDE
the module, so the op-name map tells their kernel calls apart.  Device
scopes inside: ``attn_rope`` (both tables, the rotation, the layouts
into the kernels), ``attn_gate`` (the gate's matmul, sigmoid and
scaling), and the expert layer's ``moe_*``.
"""

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models import layers
from dlrover_tpu.models.losses import chunked_cross_entropy
from dlrover_tpu.parallel.moe import DroplessMoE
from dlrover_tpu.telemetry.tracing import device_scope

FULL, SLIDING = "full_attention", "sliding_attention"
SCOPE_OF = {FULL: "full_attn", SLIDING: "swa"}


RopeRule = layers.RopeRule

# Laguna-S-2.1's two rules
FULL_ROPE = RopeRule(
    theta=500000.0, rotated=0.5, factor=128.0, original_len=8192,
    attention_factor=1.4852030263919618,
)
SLIDING_ROPE = RopeRule(theta=10000.0)


@dataclass(frozen=True)
class LagunaConfig:
    """Field names follow the repo's configs; the HF key each one
    carries is in the comment.  The defaults are Laguna-S-2.1's widths
    at one period of its layers; a chip's share sets ``experts_held``
    and ``vocab_size``."""

    vocab_size: int = 100352
    max_seq_len: int = 1048576        # max_position_embeddings
    hidden_dim: int = 3072            # hidden_size
    head_dim: int = 128
    num_kv_heads: int = 8             # num_key_value_heads
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING)
    heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72)
    mlp_layer_types: Tuple[str, ...] = (
        "dense", "sparse", "sparse", "sparse",
    )
    sliding_window: int = 512
    full_rope: RopeRule = FULL_ROPE         # rope_parameters[...]
    sliding_rope: RopeRule = SLIDING_ROPE
    dense_dim: int = 12288            # intermediate_size
    expert_dim: int = 1024            # moe_intermediate_size
    shared_dim: int = 1024            # shared_expert_intermediate_size
    num_experts: int = 256            # the router's outputs
    experts_held: Tuple[int, int] = (0, 256)   # (first, count) held here
    top_k: int = 10                   # num_experts_per_tok
    routed_scale: float = 2.5         # moe_routed_scaling_factor
    rms_eps: float = 1e-6             # rms_norm_eps
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "xla"

    def __post_init__(self):
        layers = len(self.layer_types)
        if not (
            len(self.heads_per_layer) == len(self.mlp_layer_types) == layers
        ):
            raise ValueError("one kind, head count and mlp kind a layer")
        for heads in self.heads_per_layer:
            if heads % self.num_kv_heads:
                raise ValueError(
                    f"{heads} query heads over {self.num_kv_heads} kv heads"
                )

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @classmethod
    def tiny(cls, **kw) -> "LagunaConfig":
        return cls(**{**dict(
            vocab_size=256, max_seq_len=256, hidden_dim=64, head_dim=16,
            num_kv_heads=2, layer_types=(FULL, SLIDING, SLIDING),
            heads_per_layer=(4, 6, 6),
            mlp_layer_types=("dense", "sparse", "sparse"),
            sliding_window=24, dense_dim=96, expert_dim=32, shared_dim=32,
            num_experts=16, experts_held=(4, 4), top_k=4,
            full_rope=RopeRule(
                theta=500000.0, rotated=0.5, factor=128.0,
                original_len=64, attention_factor=1.4852030263919618,
            ),
        ), **kw})


class LagunaAttention(nn.Module):
    """``heads``, ``window`` (None: full) and ``rope`` come from the
    layer's kind."""

    config: LagunaConfig
    heads: int
    window: Optional[int]
    rope: RopeRule

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        b, s, _ = x.shape
        heads, kv, d = self.heads, cfg.num_kv_heads, cfg.head_dim
        proj = partial(
            layers.dense, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            init_std=cfg.init_std,
        )
        q = proj(heads * d, "q_proj")(x)
        k = proj(kv * d, "k_proj")(x)
        v = proj(kv * d, "v_proj")(x)
        with device_scope("attn_rope"):
            cos, sin = self.rope.tables(s, d)
            q = layers.rotate_partial(q.reshape(b, s, heads, d), cos, sin)
            k = layers.rotate_partial(k.reshape(b, s, kv, d), cos, sin)
            v = v.reshape(b, s, kv, d)
        out = layers.attention(
            cfg.attention_impl, q, k, v, window=self.window,
            dtype=cfg.dtype,
        )
        with device_scope("attn_gate"):
            gate = jax.nn.sigmoid(
                proj(heads, "g_proj")(x).astype(jnp.float32)
            )
            out = (out * gate[..., None]).astype(cfg.dtype)
        return proj(cfg.hidden_dim, "o_proj")(
            out.reshape(b, s, heads * d)
        )


class LagunaBlock(nn.Module):
    """``kind`` and ``heads`` set the attention, ``dense`` the
    feed-forward.  Returns ``(y, router stats)``, ``None`` for a dense
    block."""

    config: LagunaConfig
    kind: str
    heads: int
    dense: bool

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.config
        sliding = self.kind == SLIDING
        with device_scope(SCOPE_OF[self.kind]):
            x = x + LagunaAttention(
                cfg, self.heads,
                cfg.sliding_window if sliding else None,
                cfg.sliding_rope if sliding else cfg.full_rope,
                name="attn",
            )(layers.RMSNorm(cfg.rms_eps, name="ln_attn")(x))
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
            held=cfg.experts_held, score="softmax", renormalise=True,
            scale=cfg.routed_scale, shared_dim=cfg.shared_dim,
            name="moe",
        )(h)
        return x + out, stats


class Laguna(nn.Module):
    config: LagunaConfig

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
            layers.rematted(LagunaBlock, prevent_cse=True) if cfg.remat
            else LagunaBlock
        )
        per_layer = []
        for i, (kind, heads, mlp) in enumerate(zip(
            cfg.layer_types, cfg.heads_per_layer, cfg.mlp_layer_types
        )):
            x, stats = block(
                cfg, kind, heads, mlp == "dense", name=f"block_{i}"
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


def window_tiles_share(cfg: LagunaConfig, seq: int, itemsize: int = 2):
    """Sub-blocks the sliding layers' kernels walk over those a causal
    walk of the same tiles would (``block_schedule``): what the window
    saves of the walk, a constant of the shapes.  None where no
    sliding layer goes through the kernels."""
    if cfg.attention_impl != "flash" or SLIDING not in cfg.layer_types:
        return None
    return layers.window_tiles_share(seq, cfg.sliding_window, itemsize)


def make_laguna_loss(model: Laguna, num_chunks: int = 8):
    """Next-token cross entropy through the chunked head, alone.
    ``loss_fn(params, batch) -> (loss, aux)``; ``aux`` holds the
    step's ``moe.*`` counters and ``attn.window_tiles_share``."""
    cfg = model.config

    def loss_fn(params, batch):
        hidden, stats = model.apply(
            {"params": params}, batch["x"], return_hidden=True,
            return_router_stats=True,
        )
        loss = chunked_cross_entropy(
            hidden, params["lm_head"]["kernel"], batch["y"],
            num_chunks=num_chunks,
        )
        counts = jax.lax.stop_gradient(stats["counts"])
        aux = {
            "moe.held_rows_share": jnp.mean(
                stats["held_rows"] / counts.sum(axis=1)
            ),
            "moe.held_tiles_share": jnp.mean(
                stats["tiles_used"] / stats["tiles"]
            ),
        }
        share = window_tiles_share(
            cfg, batch["x"].shape[1], jnp.dtype(cfg.dtype).itemsize
        )
        if share is not None:
            aux["attn.window_tiles_share"] = jnp.float32(share)
        return loss, aux

    loss_fn.has_aux = True
    return loss_fn
