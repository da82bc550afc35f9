"""Olmo-Hybrid decoder (``model_type: "olmo_hybrid"``): blocks of two
kinds in a published pattern (``layer_types``), three with a Gated
DeltaNet linear-attention mixer (Yang, Kautz, Hatamizadeh 2024,
arXiv:2412.06464, with write strengths up to 2: Grazzi et al. 2024,
arXiv:2411.12537) for every one with full causal attention; SwiGLU
MLPs; the OLMo 2 reordered norm (arXiv:2501.00656); untied head.

Per block, whatever its kind::

    h = x + RMSNorm(Mixer(x))
    y = h + RMSNorm(MLP(h))

Linear-attention mixer (``H`` heads of ``d_k`` keys and ``d_v``
values; :func:`dlrover_tpu.ops.gated_delta_rule.gated_delta_rule`)::

    q, k = W_q x, W_k x  [H d_k]       v, z = W_v x, W_z x  [H d_v]
    q, k, v <- SiLU(causal depthwise conv1d over the sequence)
               (:func:`dlrover_tpu.ops.causal_conv.causal_conv`: a
               ``conv_fwd`` kernel each under ``gdn_conv``, a
               ``conv_bwd`` each under its transpose)
    q_h <- q_h / |q_h| * d_k^-1/2      k_h <- k_h / |k_h|
    beta_h = 2 sigmoid(W_b x)_h        (1 sigmoid without neg. eigenvalues)
    g_h = -exp(A_log_h) softplus((W_a x)_h + dt_bias_h)
    o = gated_delta_rule(q, k, v, g, beta)
    y = W_o (RMSNorm_{d_v}(o) * SiLU(z))

Full-attention mixer: ``q = RMSNorm(W_q x)``, ``k = RMSNorm(W_k x)``
over the whole projection (the family's QK-norm, as
``models/olmoe.py``), NO positional embedding (the published
``rope_theta`` is null), causal, through ``layers.attention``.  Its
flax module is called ``attn`` (the benchmark finds flash kernels by
that name); the linear mixer's is ``gdn``.
"""

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models import layers
from dlrover_tpu.models.losses import chunked_cross_entropy
from dlrover_tpu.ops.causal_conv import causal_conv
from dlrover_tpu.ops.gated_delta_rule import gated_delta_rule
from dlrover_tpu.telemetry.tracing import device_scope

LINEAR, FULL = "linear_attention", "full_attention"
PERIOD = (LINEAR, LINEAR, LINEAR, FULL)


@dataclass(frozen=True)
class OlmoHybridConfig:
    """Field names follow the repo's configs; the HF key each one
    carries is in the comment.  The defaults are Olmo-Hybrid-7B's."""

    vocab_size: int = 100352
    max_seq_len: int = 65536          # max_position_embeddings
    layer_types: Tuple[str, ...] = PERIOD * 8   # num_hidden_layers = 32
    num_heads: int = 30               # num_attention_heads (= kv heads)
    hidden_dim: int = 3840            # hidden_size
    mlp_dim: int = 11008              # intermediate_size
    linear_heads: int = 30            # linear_num_key_heads (= value heads)
    linear_key_dim: int = 96          # linear_key_head_dim
    linear_value_dim: int = 192       # linear_value_head_dim
    conv_kernel: int = 4              # linear_conv_kernel_dim
    allow_neg_eigval: bool = True     # linear_allow_neg_eigval
    rms_eps: float = 1e-6             # rms_norm_eps
    init_std: float = 0.02            # initializer_range
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "xla"

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_dim // self.num_heads

    @classmethod
    def tiny(cls, **kw) -> "OlmoHybridConfig":
        return cls(**{**dict(
            vocab_size=256, max_seq_len=256, layer_types=PERIOD,
            num_heads=4, hidden_dim=64, mlp_dim=96, linear_heads=4,
            linear_key_dim=8, linear_value_dim=16,
        ), **kw})


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-6, 16.0))


def _dt_bias_init(key, shape, dtype):
    """``softplus^-1(dt)``, ``dt`` log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1)
    ))
    return dt + jnp.log(-jnp.expm1(-dt))


def _indicator(heads, d):
    """``[heads x d, heads]``: 1 where the channel is the head's."""
    return jnp.repeat(jnp.eye(heads, dtype=jnp.float32), d, axis=0)


def _head_sums(x, heads):
    """``[.., heads x d] -> [.., heads]``: each head's sum, float32."""
    return jnp.einsum(
        "...c,ch->...h", x, _indicator(heads, x.shape[-1] // heads),
        precision=jax.lax.Precision.HIGHEST,
    )


def _spread(y, d):
    """``[.., heads] -> [.., heads x d]``: each head's value on its
    ``d`` channels."""
    return jnp.einsum(
        "...h,ch->...c", y, _indicator(y.shape[-1], d),
        precision=jax.lax.Precision.HIGHEST,
    )


def _head_rsqrt(x, heads, eps, mean=False):
    """``x [.., heads x d]`` over the root of each head's sum (or
    mean) of squares ``+ eps``, in that layout."""
    d = x.shape[-1] // heads
    squares = _head_sums(x * x, heads)
    if mean:
        squares = squares / d
    return x * _spread(jax.lax.rsqrt(squares + eps), d)


class GatedDeltaNet(nn.Module):
    """The linear-attention mixer; returns ``(y, rms of the final
    state)``."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.config
        b, s, _ = x.shape
        heads, dk, dv = (
            cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
        )
        proj = partial(
            layers.dense, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            init_std=cfg.init_std,
        )
        q = proj(heads * dk, "q_proj")(x)
        k = proj(heads * dk, "k_proj")(x)
        v = proj(heads * dv, "v_proj")(x)
        z = proj(heads * dv, "g_proj")(x)
        a = proj(heads, "a_proj")(x).astype(jnp.float32)
        bb = proj(heads, "b_proj")(x).astype(jnp.float32)
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        dt_bias = self.param(
            "dt_bias", _dt_bias_init, (heads,), jnp.float32
        )

        # convolutions, norms and gates stay in the projections' own
        # [b, s, heads x d] layout (a head's sum is a matmul with the
        # heads' indicator): a [.., heads, d] view of 96 or 192 lanes
        # costs a relayout each way
        with device_scope("gdn_conv"):
            def conv(name, y, dtype):
                taps = self.param(
                    name, layers.conv_init,
                    (cfg.conv_kernel, y.shape[-1]),
                    cfg.param_dtype,
                )
                return causal_conv(y, taps, dtype=dtype)

            # (float32 for the per-head norm that reads q and k next)
            q = conv("q_conv", q, jnp.float32)
            k = conv("k_conv", k, jnp.float32)
            v = conv("v_conv", v, cfg.dtype)
        with device_scope("gdn_gates"):
            q = _head_rsqrt(q, heads, 1e-6) * dk ** -0.5
            k = _head_rsqrt(k, heads, 1e-6)
            q, k = q.astype(cfg.dtype), k.astype(cfg.dtype)
            beta = jax.nn.sigmoid(bb)
            if cfg.allow_neg_eigval:
                beta = 2.0 * beta
            g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
        with device_scope("gdn_rule"):
            # (the kernels' custom_vjp says what the backward keeps)
            o, state = gated_delta_rule(
                q.reshape(b, s, heads, dk), k.reshape(b, s, heads, dk),
                v.reshape(b, s, heads, dv), g, beta,
            )
        with device_scope("gdn_norm"):
            # per head, one learned scale of size d_v, gated by z
            scale = self.param(
                "o_norm", nn.initializers.ones, (dv,), jnp.float32
            )
            o32 = _head_rsqrt(
                o.reshape(b, s, heads * dv).astype(jnp.float32), heads,
                cfg.rms_eps, mean=True,
            ) * jnp.tile(scale, heads)
            o = (o32 * nn.silu(z.astype(jnp.float32))).astype(cfg.dtype)
            state_rms = jnp.sqrt(jnp.mean(state * state))
        return proj(cfg.hidden_dim, "o_proj")(o), state_rms


class FullAttention(nn.Module):
    config: OlmoHybridConfig

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

        # QK-norm over all heads together, before the split; no rope
        q = layers.RMSNorm(cfg.rms_eps, name="q_norm")(proj("q_proj")(x))
        k = layers.RMSNorm(cfg.rms_eps, name="k_norm")(proj("k_proj")(x))
        v = proj("v_proj")(x)
        out = layers.attention(
            cfg.attention_impl, q.reshape(b, s, heads, hd),
            k.reshape(b, s, heads, hd), v.reshape(b, s, heads, hd),
            dtype=cfg.dtype,
        )
        return proj("o_proj")(out.reshape(b, s, cfg.hidden_dim))


class OlmoHybridBlock(nn.Module):
    """``kind`` (an entry of ``layer_types``) picks the mixer; nothing
    else differs.  Returns ``(y, rms of the linear mixer's final
    state)``, 0 for a full-attention block."""

    config: OlmoHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.config
        if self.kind == LINEAR:
            mixed, state_rms = GatedDeltaNet(cfg, name="gdn")(x)
        elif self.kind == FULL:
            mixed = FullAttention(cfg, name="attn")(x)
            state_rms = jnp.zeros((), jnp.float32)
        else:
            raise ValueError(f"unknown layer type {self.kind!r}")
        x = x + layers.RMSNorm(cfg.rms_eps, name="ln_mixer")(mixed)
        x = x + layers.RMSNorm(cfg.rms_eps, name="ln_mlp")(layers.SwiGLU(
            cfg.mlp_dim, cfg.hidden_dim, cfg.dtype, cfg.param_dtype,
            cfg.init_std, name="mlp",
        )(x))
        return x, state_rms


class OlmoHybrid(nn.Module):
    config: OlmoHybridConfig

    @nn.compact
    def __call__(
        self, tokens: jax.Array, return_hidden: bool = False,
        return_state_rms: bool = False,
    ):
        """Logits ``[b, s, vocab]`` in float32, or with
        ``return_hidden`` the final-norm output for a chunked head
        (``models/losses.py``); with ``return_state_rms`` also the
        largest root mean square of a linear layer's final state."""
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.initializers.normal(cfg.init_std),
            name="wte",
        )(tokens)
        block = (
            layers.rematted(OlmoHybridBlock, prevent_cse=True) if cfg.remat
            else OlmoHybridBlock
        )
        state_rms = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(cfg.layer_types):
            x, rms = block(cfg, kind, name=f"block_{i}")(x)
            state_rms = jnp.maximum(state_rms, rms)
        x = layers.RMSNorm(cfg.rms_eps, name="ln_f")(x)
        if not return_hidden:
            x = layers.dense(
                cfg.vocab_size, "lm_head", cfg.dtype, cfg.param_dtype,
                cfg.init_std,
            )(x).astype(jnp.float32)
        if not return_state_rms:
            return x
        return x, state_rms

    init_params = layers.init_params


def make_olmo_hybrid_loss(model: OlmoHybrid, num_chunks: int = 8):
    """Next-token cross entropy through the chunked head.
    ``loss_fn(params, batch) -> (loss, aux)``; ``aux`` holds the
    step's ``gdn.state_rms_max``, which ``make_train_step`` adds to
    the metrics (``loss_fn.has_aux``)."""

    def loss_fn(params, batch):
        hidden, state_rms = model.apply(
            {"params": params}, batch["x"], return_hidden=True,
            return_state_rms=True,
        )
        loss = chunked_cross_entropy(
            hidden, params["lm_head"]["kernel"], batch["y"],
            num_chunks=num_chunks,
        )
        return loss, {
            "gdn.state_rms_max": jax.lax.stop_gradient(state_rms)
        }

    loss_fn.has_aux = True
    return loss_fn
