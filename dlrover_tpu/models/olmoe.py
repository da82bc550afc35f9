"""OLMoE decoder (Muennighoff et al. 2024, arXiv:2409.02060; HF
``modeling_olmoe``): pre-norm blocks of multi-head attention with
QK-norm and RoPE, and a dropless top-k mixture of SwiGLU experts in
place of every MLP; untied output head.

Per block, as published::

    h = x + Attn(RMSNorm(x))        q = RMSNorm_q(W_q u), k = RMSNorm_k(W_k u)
    y = h + MoE(RMSNorm(h))         over the WHOLE projection, before the
                                    split into heads; then RoPE

No biases, no ``clip_qkv``, no shared expert, top-k weights not
renormalised.  The shared pieces (``RMSNorm``, ``rope``, the attention
call, remat) are ``models/layers.py``'s; the
expert layer is :class:`dlrover_tpu.parallel.moe.DroplessMoE`: one
chip holds every expert of its layers.
"""

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models import layers
from dlrover_tpu.models.losses import chunked_cross_entropy
from dlrover_tpu.parallel.moe import DroplessMoE
from dlrover_tpu.telemetry.tracing import device_scope


@dataclass(frozen=True)
class OlmoeConfig:
    """Field names follow the repo's configs; the HF key each one
    carries is in the comment.  The defaults are OLMoE-1B-7B's."""

    vocab_size: int = 50304
    max_seq_len: int = 4096           # max_position_embeddings
    num_layers: int = 16              # num_hidden_layers
    num_heads: int = 16               # num_attention_heads (= kv heads)
    hidden_dim: int = 2048            # hidden_size
    expert_dim: int = 1024            # intermediate_size (one expert)
    num_experts: int = 64
    top_k: int = 8                    # num_experts_per_tok
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5             # rms_norm_eps
    init_std: float = 0.02            # initializer_range
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "xla"

    @property
    def head_dim(self) -> int:
        return self.hidden_dim // self.num_heads

    @classmethod
    def tiny(cls, **kw) -> "OlmoeConfig":
        return cls(**{**dict(
            vocab_size=256, max_seq_len=128, num_layers=2,
            num_heads=4, hidden_dim=64, expert_dim=32, num_experts=8,
            top_k=2,
        ), **kw})


class OlmoeAttention(nn.Module):
    config: OlmoeConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        b, s, _ = x.shape
        heads, hd = cfg.num_heads, cfg.head_dim

        def proj(name):
            return layers.dense(
                cfg.hidden_dim, name, cfg.dtype, cfg.param_dtype,
                cfg.init_std,
            )

        # QK-norm over all heads together, before the split
        q = layers.RMSNorm(cfg.rms_eps, name="q_norm")(proj("q_proj")(x))
        k = layers.RMSNorm(cfg.rms_eps, name="k_norm")(proj("k_proj")(x))
        v = proj("v_proj")(x)
        positions = jnp.arange(s)
        q = layers.rope(q.reshape(b, s, heads, hd), positions, cfg.rope_theta)
        k = layers.rope(k.reshape(b, s, heads, hd), positions, cfg.rope_theta)
        out = layers.attention(
            cfg.attention_impl, q, k, v.reshape(b, s, heads, hd),
            dtype=cfg.dtype,
        )
        return proj("o_proj")(out.reshape(b, s, cfg.hidden_dim))


class OlmoeBlock(nn.Module):
    config: OlmoeConfig

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.config
        h = layers.RMSNorm(cfg.rms_eps, name="ln_attn")(x)
        x = x + OlmoeAttention(cfg, name="attn")(h)
        h = layers.RMSNorm(cfg.rms_eps, name="ln_mlp")(x)
        out, stats = DroplessMoE(
            num_experts=cfg.num_experts, mlp_dim=cfg.expert_dim,
            top_k=cfg.top_k, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(cfg.init_std),
            name="moe",
        )(h)
        return x + out, stats


class Olmoe(nn.Module):
    config: OlmoeConfig

    @nn.compact
    def __call__(
        self, tokens: jax.Array, return_hidden: bool = False,
        return_router_stats: bool = False,
    ):
        """Logits ``[b, s, vocab]`` in float32, or with
        ``return_hidden`` the final-norm output for a chunked head
        (``models/losses.py``); with ``return_router_stats`` also
        :func:`dropless_moe`'s ``stats``, stacked over the layers."""
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.initializers.normal(cfg.init_std),
            name="wte",
        )(tokens)
        block = (
            layers.rematted(OlmoeBlock, prevent_cse=False) if cfg.remat
            else OlmoeBlock
        )
        per_layer = []
        for i in range(cfg.num_layers):
            x, stats = block(cfg, name=f"block_{i}")(x)
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


def router_losses(stats, top_k: int):
    """``(load-balancing loss, z-loss, load max over mean)`` from the
    layers' stacked router stats.

    Load balancing as HF ``load_balancing_loss_func`` computes it, over
    all layers' assignments together: ``E * sum_e f_e P_e`` with
    ``f_e`` the assignments to expert e over the ``layers x tokens``
    routed rows (it sums to k) and ``P_e`` the mean router probability.
    Z-loss (paper, section 3): ``mean_t logsumexp(logits_t) ** 2``,
    summed over layers.  The counter: in the worst layer, the busiest
    expert's rows over the mean ``tokens * k / E``."""
    counts, prob_sum = stats["counts"], stats["prob_sum"]
    experts = counts.shape[1]
    rows = counts.sum() / top_k  # layers x tokens
    lb = experts * jnp.sum(
        (counts.sum(0) / rows) * (prob_sum.sum(0) / rows)
    )
    load = jnp.max(counts.max(axis=1) / counts.mean(axis=1))
    return lb, stats["z_loss"].sum(), load


def make_olmoe_loss(
    model: Olmoe, lb_weight: float = 0.01, z_weight: float = 0.001,
    num_chunks: int = 8,
):
    """The training loss: next-token cross entropy through the chunked
    head + ``lb_weight`` x load balancing + ``z_weight`` x router
    z-loss (OLMoE paper, section 3: 0.01 and 0.001).  ``loss_fn(params,
    batch) -> (loss, aux)``; ``aux`` holds the step's ``moe.*``
    counters, which ``make_train_step`` adds to the metrics
    (``loss_fn.has_aux``)."""

    def loss_fn(params, batch):
        hidden, stats = model.apply(
            {"params": params}, batch["x"], return_hidden=True,
            return_router_stats=True,
        )
        ce = chunked_cross_entropy(
            hidden, params["lm_head"]["kernel"], batch["y"],
            num_chunks=num_chunks,
        )
        with device_scope("moe_router"):
            lb, z, load = router_losses(stats, model.config.top_k)
        loss = ce + lb_weight * lb + z_weight * z
        return loss, {
            "moe.lb_loss": lb, "moe.z_loss": z,
            "moe.load_max_over_mean": load,
        }

    loss_fn.has_aux = True
    return loss_fn
