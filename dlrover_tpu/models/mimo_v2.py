"""Window-with-a-sink and full attention mixture-of-experts decoder
(``model_type: "mimo_v2"``): pre-norm blocks ``x += Attn(RMSNorm(x))``,
``x += FFN(RMSNorm(x))``; final RMSNorm, untied head.

Attention is grouped-query with heads of two sizes (q and k
``head_dim``, v ``v_head_dim``: 192 | 128) and takes five things from
the layer's KIND (``layer_pattern[l]``: 0 full, 1 window): its query
and kv head counts, its mask (causal; a window layer also hides keys
``sliding_window`` or more behind the query), its rope rule
(:class:`layers.RopeRule`: both kinds rotate the first ``rotated x
head_dim`` lanes of every q and k head, half-split pairs, each with its
own theta) and whether its softmax has a SINK: a learned scalar a head
(``sink`` ``[heads]`` float32, a leaf of the attention module) that
joins every row's denominator and nothing else
(``ops/flash_attention.py``).  q, k and v come from ONE matrix
(``qkv_proj``: ``[q heads | k heads | v heads]`` columns); v is scaled
by ``value_scale`` before the product.  No gate, no biases.

``moe_layers[l]`` picks the feed-forward: 0 a dense SwiGLU, 1 the
expert layer (:class:`dlrover_tpu.parallel.moe.DroplessMoE`: sigmoid
scores, the top-k of ``score + bias`` chosen and weighted by the score
alone, renormalised and scaled, NO shared expert; the chip holds
experts ``[lo, lo + count)``, routes over all and computes its own, so
a token none of whose choices is held gets nothing from the layer).
The bias takes no gradient: the loss hands the train step its deltas
(``aux["state_updates"]``, ``parallel/moe.py::bias_deltas``).  No
auxiliary loss.

The flax module of the attention is called ``attn`` (the benchmark
finds flash kernels by that name); a window layer's sits under the
device scope ``swa``, a full layer's under ``full_attn``, both OUTSIDE
the module.  Device scopes inside: ``attn_qkv`` (the fused projection,
the split, the value scale), ``attn_rope`` (the tables, the partial
rotation, the layouts into the kernels), ``attn_sink`` (what the sink
costs outside the kernels: its gradient's reduction and the counter's),
``attn_out``, and the expert layer's ``moe_*``.
"""

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models import layers
from dlrover_tpu.models.losses import chunked_cross_entropy
from dlrover_tpu.parallel.moe import DroplessMoE, bias_deltas
from dlrover_tpu.telemetry.tracing import device_scope

FULL, WINDOW = 0, 1
SCOPE_OF = {FULL: "full_attn", WINDOW: "swa"}


@dataclass(frozen=True)
class MiMoV2Config:
    """Field names follow the repo's configs; the HF key each one
    carries is in the comment.  The defaults are MiMo-V2.5's widths at
    layer 0 and one period of its layers; a chip's share sets the four
    head counts, ``experts_held`` and ``vocab_size``."""

    vocab_size: int = 152576
    max_seq_len: int = 1048576        # max_position_embeddings
    hidden_dim: int = 4096            # hidden_size
    head_dim: int = 192               # head_dim = swa_head_dim (q, k)
    v_head_dim: int = 128             # v_head_dim = swa_v_head_dim
    num_heads: int = 64               # num_attention_heads (full)
    num_kv_heads: int = 4             # num_key_value_heads (full)
    swa_num_heads: int = 64           # swa_num_attention_heads
    swa_num_kv_heads: int = 8         # swa_num_key_value_heads
    layer_pattern: Tuple[int, ...] = (0, 1, 1, 1, 1, 1, 0)
    moe_layers: Tuple[int, ...] = (0, 1, 1, 1, 1, 1, 1)  # moe_layer_freq
    sliding_window: int = 128
    full_rope: layers.RopeRule = layers.RopeRule(    # rope_theta,
        theta=1e7, rotated=0.334                     # partial_rotary_factor
    )
    swa_rope: layers.RopeRule = layers.RopeRule(     # swa_rope_theta
        theta=1e4, rotated=0.334
    )
    value_scale: float = 0.707        # attention_value_scale
    full_sink: bool = False           # add_full_attention_sink_bias
    swa_sink: bool = True             # add_swa_attention_sink_bias
    sink_init_std: float = 0.0        # a sink starts as a normal of this
    dense_dim: int = 16384            # intermediate_size
    expert_dim: int = 2048            # moe_intermediate_size
    num_experts: int = 256            # the router's outputs
    experts_held: Tuple[int, int] = (0, 256)   # (first, count) held here
    top_k: int = 8                    # num_experts_per_tok
    routed_scale: float = 1.0         # routed_scaling_factor (null)
    bias_update_rate: float = 0.001   # u of the bias's rule
    rms_eps: float = 1e-5             # layernorm_epsilon
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "xla"

    def __post_init__(self):
        if len(self.layer_pattern) != len(self.moe_layers):
            raise ValueError("one attention kind and one mlp kind a layer")
        for heads, kv in (
            (self.num_heads, self.num_kv_heads),
            (self.swa_num_heads, self.swa_num_kv_heads),
        ):
            if heads % kv:
                raise ValueError(f"{heads} query heads over {kv} kv heads")

    @property
    def num_layers(self) -> int:
        return len(self.layer_pattern)

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, m in enumerate(self.moe_layers) if m)

    @property
    def sink_layers(self) -> Tuple[int, ...]:
        sinked = {FULL: self.full_sink, WINDOW: self.swa_sink}
        return tuple(
            i for i, kind in enumerate(self.layer_pattern) if sinked[kind]
        )

    @classmethod
    def tiny(cls, **kw) -> "MiMoV2Config":
        return cls(**{**dict(
            vocab_size=256, max_seq_len=256, hidden_dim=64, head_dim=24,
            v_head_dim=16, num_heads=4, num_kv_heads=1, swa_num_heads=4,
            swa_num_kv_heads=2, layer_pattern=(0, 1, 1, 0),
            moe_layers=(0, 1, 1, 1), sliding_window=24, dense_dim=96,
            expert_dim=32, num_experts=16, experts_held=(4, 4), top_k=4,
            sink_init_std=1.0,
        ), **kw})


class MiMoV2Attention(nn.Module):
    """``heads``, ``kv``, ``window`` (None: full), ``rope`` and
    ``sinked`` come from the layer's kind.  Returns ``(out, the sink's
    mean share of the softmax a head)``, None without a sink."""

    config: MiMoV2Config
    heads: int
    kv: int
    window: Optional[int]
    rope: layers.RopeRule
    sinked: bool

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.config
        b, s, _ = x.shape
        heads, kv, d, dv = self.heads, self.kv, cfg.head_dim, cfg.v_head_dim
        proj = partial(
            layers.dense, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            init_std=cfg.init_std,
        )
        with device_scope("attn_qkv"):
            qkv = proj((heads + kv) * d + kv * dv, "qkv_proj")(x)
            q, k, v = jnp.split(
                qkv, (heads * d, (heads + kv) * d), axis=-1
            )
            v = (v * cfg.value_scale).reshape(b, s, kv, dv)
        with device_scope("attn_rope"):
            cos, sin = self.rope.tables(s, d)
            q = layers.rotate_partial(q.reshape(b, s, heads, d), cos, sin)
            k = layers.rotate_partial(k.reshape(b, s, kv, d), cos, sin)
        sink = mass = None
        if self.sinked:
            sink = self.param(
                "sink", nn.initializers.normal(cfg.sink_init_std),
                (heads,), jnp.float32,
            )
        out = layers.attention(
            cfg.attention_impl, q, k, v, window=self.window,
            dtype=cfg.dtype, sink=sink,
        )
        if self.sinked:
            out, mass = out
        with device_scope("attn_out"):
            return proj(cfg.hidden_dim, "o_proj")(
                out.reshape(b, s, heads * dv)
            ), mass


class MiMoV2Block(nn.Module):
    """``kind`` sets the attention, ``dense`` the feed-forward.
    Returns ``(y, router stats, sink mass)``, each ``None`` where the
    block has no such part."""

    config: MiMoV2Config
    kind: int
    dense: bool

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.config
        window = self.kind == WINDOW
        with device_scope(SCOPE_OF[self.kind]):
            out, mass = MiMoV2Attention(
                cfg,
                cfg.swa_num_heads if window else cfg.num_heads,
                cfg.swa_num_kv_heads if window else cfg.num_kv_heads,
                cfg.sliding_window if window else None,
                cfg.swa_rope if window else cfg.full_rope,
                cfg.swa_sink if window else cfg.full_sink,
                name="attn",
            )(layers.RMSNorm(cfg.rms_eps, name="ln_attn")(x))
            x = x + out
        h = layers.RMSNorm(cfg.rms_eps, name="ln_mlp")(x)
        if self.dense:
            return x + layers.SwiGLU(
                cfg.dense_dim, cfg.hidden_dim, cfg.dtype,
                cfg.param_dtype, cfg.init_std, name="mlp",
            )(h), None, mass
        out, stats = DroplessMoE(
            num_experts=cfg.num_experts, mlp_dim=cfg.expert_dim,
            top_k=cfg.top_k, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(cfg.init_std),
            held=cfg.experts_held, score="sigmoid", select_bias=True,
            renormalise=True, scale=cfg.routed_scale, shared_dim=0,
            name="moe",
        )(h)
        return x + out, stats, mass


class MiMoV2(nn.Module):
    config: MiMoV2Config

    @nn.compact
    def __call__(
        self, tokens: jax.Array, return_hidden: bool = False,
        return_stats: bool = False,
    ):
        """Logits ``[b, s, vocab]`` in float32, or with
        ``return_hidden`` the final-norm output for a chunked head
        (``models/losses.py``); with ``return_stats`` also
        :func:`dropless_moe`'s ``stats`` stacked over the expert
        layers and the sinks' mass ``[sink layers, heads]``."""
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.initializers.normal(cfg.init_std),
            name="wte",
        )(tokens)
        block = (
            layers.rematted(MiMoV2Block, prevent_cse=True) if cfg.remat
            else MiMoV2Block
        )
        routers, sinks = [], []
        for i, (kind, sparse) in enumerate(
            zip(cfg.layer_pattern, cfg.moe_layers)
        ):
            x, stats, mass = block(
                cfg, kind, not sparse, name=f"block_{i}"
            )(x)
            if stats is not None:
                routers.append(stats)
            if mass is not None:
                sinks.append(mass)
        x = layers.RMSNorm(cfg.rms_eps, name="ln_f")(x)
        if not return_hidden:
            x = layers.dense(
                cfg.vocab_size, "lm_head", cfg.dtype, cfg.param_dtype,
                cfg.init_std,
            )(x).astype(jnp.float32)
        if not return_stats:
            return x
        return (
            x, jax.tree.map(lambda *a: jnp.stack(a), *routers),
            jnp.stack(sinks) if sinks else None,
        )

    init_params = layers.init_params


def window_tiles_share(cfg: MiMoV2Config, seq: int, itemsize: int = 2):
    """``layers.window_tiles_share`` of the window layers; None where
    none goes through the kernels."""
    if cfg.attention_impl != "flash" or WINDOW not in cfg.layer_pattern:
        return None
    return layers.window_tiles_share(seq, cfg.sliding_window, itemsize)


def make_mimo_v2_loss(model: MiMoV2, num_chunks: int = 8):
    """Next-token cross entropy through the chunked head, alone (no
    auxiliary loss: the bias balances the load).  ``loss_fn(params,
    batch) -> (loss, aux)``; ``aux`` holds the step's ``moe.*`` and
    ``attn.*`` counters and, under ``"state_updates"`` (the key that
    ``make_train_step`` documents), each expert layer's bias delta
    for the step to add."""
    cfg = model.config

    def loss_fn(params, batch):
        hidden, stats, sink_mass = model.apply(
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
                for i in cfg.expert_layers
            ])
        aux = {
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
        if sink_mass is not None:
            with device_scope(layers.SINK_SCOPE):
                aux["attn.sink_mass_mean"] = jnp.mean(sink_mass)
                aux["attn.sink_abs_max"] = jnp.max(jnp.abs(jnp.stack([
                    params[f"block_{i}"]["attn"]["sink"]
                    for i in cfg.sink_layers
                ])))
        share = window_tiles_share(
            cfg, batch["x"].shape[1], jnp.dtype(cfg.dtype).itemsize
        )
        if share is not None:
            aux["attn.window_tiles_share"] = jnp.float32(share)
        return loss, aux

    loss_fn.has_aux = True
    return loss_fn
