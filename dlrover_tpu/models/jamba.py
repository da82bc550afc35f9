"""Jamba decoder (``model_type: "jamba"``; Lieber et al. 2024,
arXiv:2403.19887; the layer equations of ``transformers``'
``modeling_jamba.py``): layer ``i`` mixes tokens by causal attention
where ``i % attn_layer_period == attn_layer_offset`` and by a Mamba-1
selective state-space mixer everywhere else; every layer's
feed-forward is a dense SwiGLU.  Per block, pre-norm::

    h = x + Mixer(RMSNorm_input(x))
    y = h + SwiGLU(RMSNorm_pre_ff(h))

then ``final_layernorm`` and a head TIED to the embedding (logits ``=
h E^T``; the loss goes through ``losses.py``'s chunked head on the
table itself).  No rope and no position table anywhere: the recurrent
layers carry position.

``"mamba"`` mixer (``JambaMambaMixer``), ``E = expand x hidden``
channels, ``N`` state lanes a channel, ``R`` the step's rank::

    [x | z] = u W_in                              (hidden x 2 E)
    x  = SiLU(causal depthwise conv_K(x) + b)     (ops/causal_conv.py)
    [dt_r | B | C] = x W_x                        (E x (R + 2 N))
    dt_r, B, C = RMSNorm(dt_r), RMSNorm(B), RMSNorm(C)
    dt = softplus(dt_r W_dt + b_dt)               (float32 from the sum on)
    A  = -exp(A_log)                              ([E, N] float32)
    h_t[e, n] = exp(dt_t[e] A[e, n]) h_{t-1}[e, n] + dt_t[e] B_t[n] x_t[e]
    y_t[e] = sum_n C_t[n] h_t[e, n] + D[e] x_t[e]
                              (``ops/selective_scan.py::selective_scan``)
    out = (y * SiLU(z)) W_out

``"attention"`` mixer: ``num_heads`` query heads over ``num_kv_heads``
key and value heads of ``head_dim``, no bias, NO positional term,
through ``layers.attention``.

This family has no experts (``num_experts`` 1: ``JambaSparseMoeBlock``
is never built), no projection bias, no window and no untied head:
:meth:`JambaConfig.from_hf` refuses those keys with the reason.

Flax names: ``mamba`` and ``attn`` (the benchmark finds flash kernels
by the second; its scope is ``full_attn``, OUTSIDE the module, as
``nemotron_h.py``'s).  Device scopes of the mixer: ``s6_in_proj``,
``s6_conv``, ``s6_x_proj``, ``s6_params`` (the three norms,
``dt_proj``'s sum, softplus, ``A``, the decay's mean), ``s6_scan``
(the ``s6_fwd`` / ``s6_bwd`` kernels, the skip inside them, and the
final state's rms), ``s6_gate``, ``s6_out_proj``; the head's
``loss_head``.
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
from dlrover_tpu.ops.selective_scan import selective_scan
from dlrover_tpu.telemetry.tracing import device_scope

MAMBA, ATTENTION = "mamba", "attention"
# rows between two of those the decay's mean is taken over (the whole
# [s, E, N] mean is as many exponentials as the scan's own forward)
DECAY_STRIDE = 64


@dataclass(frozen=True)
class JambaConfig:
    """Field names follow the repo's configs; the HF key each one
    carries is in the comment.  The defaults are AI21-Jamba2-3B's,
    whole; a pipeline stage's layers are ``layer_types``."""

    vocab_size: int = 65536
    max_seq_len: int = 262144         # max_position_embeddings
    hidden_dim: int = 2560            # hidden_size
    # attention where i % attn_layer_period == attn_layer_offset
    layer_types: Tuple[str, ...] = tuple(
        ATTENTION if i % 14 == 7 else MAMBA for i in range(28)
    )
    mlp_dim: int = 8192               # intermediate_size
    ssm_inner: int = 5120             # mamba_expand x hidden_size
    ssm_state: int = 16               # mamba_d_state
    conv_kernel: int = 4              # mamba_d_conv
    dt_rank: int = 160                # mamba_dt_rank
    chunk_size: int = 128             # rows of a scan kernel's grid step
    num_heads: int = 20               # num_attention_heads
    num_kv_heads: int = 1             # num_key_value_heads
    head_dim: int = 128               # hidden_size / num_attention_heads
    rms_eps: float = 1e-6             # rms_norm_eps
    init_std: float = 0.02            # initializer_range
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "xla"

    def __post_init__(self):
        unknown = set(self.layer_types) - {MAMBA, ATTENTION}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads over {self.num_kv_heads} "
                "kv heads"
            )

    @staticmethod
    def layers_block_type(
        num_layers: int, period: int, offset: int
    ) -> Tuple[str, ...]:
        """``JambaConfig.layers_block_type`` of ``transformers``."""
        return tuple(
            ATTENTION if i % period == offset else MAMBA
            for i in range(num_layers)
        )

    @classmethod
    def from_hf(cls, hf: dict, **kw) -> "JambaConfig":
        """From the published keys; what this family does not build is
        refused with the reason."""
        for key, value, why in (
            ("num_experts", 1, "no expert layer (JambaSparseMoeBlock)"),
            ("mamba_proj_bias", False, "no bias on the mixer's matrices"),
            ("mamba_conv_bias", True, "the convolution has its bias"),
            ("sliding_window", None, "no attention window"),
            ("tie_word_embeddings", True, "the head is the table"),
            ("hidden_act", "silu", "SiLU in the mixer and the SwiGLU"),
        ):
            if hf.get(key, value) != value:
                raise ValueError(
                    f"the jamba family has no {key} = {hf[key]!r}: {why}"
                )
        hidden = hf["hidden_size"]
        return cls(**{**dict(
            vocab_size=hf["vocab_size"],
            max_seq_len=hf["max_position_embeddings"],
            hidden_dim=hidden,
            layer_types=cls.layers_block_type(
                hf["num_hidden_layers"], hf["attn_layer_period"],
                hf["attn_layer_offset"],
            ),
            mlp_dim=hf["intermediate_size"],
            ssm_inner=hf["mamba_expand"] * hidden,
            ssm_state=hf["mamba_d_state"],
            conv_kernel=hf["mamba_d_conv"],
            dt_rank=hf["mamba_dt_rank"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hidden // hf["num_attention_heads"],
            rms_eps=hf["rms_norm_eps"],
        ), **kw})

    @classmethod
    def tiny(cls, **kw) -> "JambaConfig":
        return cls(**{**dict(
            vocab_size=256, max_seq_len=256, hidden_dim=64,
            layer_types=(MAMBA, ATTENTION, MAMBA), mlp_dim=96,
            ssm_inner=128, ssm_state=16, dt_rank=8, chunk_size=16,
            num_heads=4, num_kv_heads=1, head_dim=16,
        ), **kw})


def _a_log_init(key, shape, dtype):
    """``A = 1 .. N`` a channel (S4D-real)."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape
    )


def _dt_bias_init(key, shape, dtype):
    """``softplus^-1(dt)``, ``dt`` log-uniform in ``[1e-3, 1e-1]`` and
    at least ``1e-4`` (Mamba's own rule: ``dt_min``, ``dt_max``,
    ``dt_init_floor``; ``transformers``' ``_init_weights`` zeroes the
    bias, a placeholder for a loaded checkpoint)."""
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1)
    )), 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


class MambaMixer(nn.Module):
    """The selective state-space mixer; returns ``(out, {"state_rms":
    root mean square of the final state, "decay_mean": mean of exp(dt
    A) over every ``DECAY_STRIDE``-th row, "dt_mean": mean step})``,
    the second with no gradient."""

    config: JambaConfig

    @nn.compact
    def __call__(self, u: jax.Array):
        cfg = self.config
        inner, n, rank = cfg.ssm_inner, cfg.ssm_state, cfg.dt_rank
        proj = partial(
            layers.dense, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            init_std=cfg.init_std,
        )
        norm = partial(layers.RMSNorm, cfg.rms_eps)
        with device_scope("s6_in_proj"):
            xz = proj(2 * inner, "in_proj")(u)
        with device_scope("s6_conv"):
            taps = self.param(
                "conv", nn.initializers.normal(cfg.init_std),
                (cfg.conv_kernel, inner), cfg.param_dtype,
            )
            bias = self.param(
                "conv_bias", nn.initializers.zeros, (inner,), jnp.float32
            )
            # x straight out of the projection's first lanes: no slice
            x = causal_conv(xz, taps, bias, first=0)
        with device_scope("s6_x_proj"):
            p = proj(rank + 2 * n, "x_proj")(x)
        a_log = self.param("A_log", _a_log_init, (inner, n), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (inner,), jnp.float32)
        w_dt = self.param(
            "dt_proj", nn.initializers.normal(cfg.init_std), (rank, inner),
            cfg.param_dtype,
        )
        dt_bias = self.param(
            "dt_bias", _dt_bias_init, (inner,), jnp.float32
        )
        with device_scope("s6_params"):
            dt_r = norm(name="dt_layernorm")(p[..., :rank])
            B = norm(name="b_layernorm")(p[..., rank:rank + n])
            C = norm(name="c_layernorm")(p[..., rank + n:])
            dt = jax.nn.softplus(jnp.dot(
                dt_r, w_dt.astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            ) + dt_bias)
            A = -jnp.exp(a_log)
            some = jax.lax.stop_gradient(dt[:, ::DECAY_STRIDE])
            decay_mean = jnp.mean(jnp.exp(some[..., None] * A))
            dt_mean = jnp.mean(some)
        with device_scope("s6_scan"):
            # (the block's remat keeps what the forward kernel wrote)
            y, state = selective_scan(
                x, dt, A, B, C, skip, chunk=cfg.chunk_size
            )
            state = jax.lax.stop_gradient(state)
            state_rms = jnp.sqrt(jnp.mean(state * state))
        with device_scope("s6_gate"):
            y = (
                y.astype(jnp.float32)
                * nn.silu(xz[..., inner:].astype(jnp.float32))
            ).astype(cfg.dtype)
        with device_scope("s6_out_proj"):
            out = proj(cfg.hidden_dim, "out_proj")(y)
        return out, {
            "state_rms": state_rms, "decay_mean": decay_mean,
            "dt_mean": dt_mean,
        }


class JambaAttention(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        b, s, _ = x.shape
        heads, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        proj = partial(
            layers.dense, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            init_std=cfg.init_std,
        )
        out = layers.attention(
            cfg.attention_impl,
            proj(heads * d, "q_proj")(x).reshape(b, s, heads, d),
            proj(kv * d, "k_proj")(x).reshape(b, s, kv, d),
            proj(kv * d, "v_proj")(x).reshape(b, s, kv, d),
            dtype=cfg.dtype,
        )
        return proj(cfg.hidden_dim, "o_proj")(out.reshape(b, s, heads * d))


class JambaBlock(nn.Module):
    """``kind`` picks the mixer.  Returns ``(y, the state-space
    mixer's counters or None)``."""

    config: JambaConfig
    kind: str

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.config
        h = layers.RMSNorm(cfg.rms_eps, name="input_layernorm")(x)
        if self.kind == MAMBA:
            mixed, stats = MambaMixer(cfg, name="mamba")(h)
        else:
            with device_scope("full_attn"):
                mixed, stats = JambaAttention(cfg, name="attn")(h), None
        x = x + mixed
        h = layers.RMSNorm(cfg.rms_eps, name="pre_ff_layernorm")(x)
        return x + layers.SwiGLU(
            cfg.mlp_dim, cfg.hidden_dim, cfg.dtype, cfg.param_dtype,
            cfg.init_std, name="mlp",
        )(h), stats


class Jamba(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(
        self, tokens: jax.Array, return_hidden: bool = False,
        return_stats: bool = False,
    ):
        """Logits ``[b, s, vocab]`` in float32 (the hidden states
        against the embedding table), or with ``return_hidden`` the
        final-norm output for a chunked head (``models/losses.py``);
        with ``return_stats`` also the state-space layers' counters,
        stacked over those layers."""
        cfg = self.config
        wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.initializers.normal(cfg.init_std),
            name="wte",
        )
        x = wte(tokens)
        block = (
            layers.rematted(JambaBlock, prevent_cse=True) if cfg.remat
            else JambaBlock
        )
        mixers = []
        for i, kind in enumerate(cfg.layer_types):
            x, stats = block(cfg, kind, name=f"block_{i}")(x)
            if stats is not None:
                mixers.append(stats)
        x = layers.RMSNorm(cfg.rms_eps, name="final_layernorm")(x)
        if not return_hidden:
            x = wte.attend(x).astype(jnp.float32)
        if not return_stats:
            return x
        return x, jax.tree.map(lambda *a: jnp.stack(a), *mixers)

    init_params = layers.init_params


def make_jamba_loss(model: Jamba, num_chunks: int = 8):
    """Next-token cross entropy through the chunked head on the TIED
    table.  ``loss_fn(params, batch) -> (loss, aux)``; ``aux`` holds
    the step's ``s6.*`` counters."""

    def loss_fn(params, batch):
        hidden, mixers = model.apply(
            {"params": params}, batch["x"], return_hidden=True,
            return_stats=True,
        )
        loss = chunked_cross_entropy(
            hidden, params["wte"]["embedding"], batch["y"],
            num_chunks=num_chunks, transpose=True,
        )
        mixers = jax.lax.stop_gradient(mixers)
        return loss, {
            "s6.state_rms_max": jnp.max(mixers["state_rms"]),
            "s6.decay_mean": jnp.mean(mixers["decay_mean"]),
            "s6.dt_mean": jnp.mean(mixers["dt_mean"]),
        }

    loss_fn.has_aux = True
    return loss_fn
