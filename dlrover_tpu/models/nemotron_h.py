"""Nemotron-H decoder (``model_type: "nemotron_h"``; the Nemotron-H
report, arXiv:2504.03624): a stack of layers that are ONE mixer each,
``x <- x + mixer_l(RMSNorm(x))``, with no feed-forward inside a mixer
layer and no second norm.  ``hybrid_override_pattern`` names the mixer
of every layer: ``M`` a Mamba-2 state-space mixer (Dao and Gu 2024,
arXiv:2405.21060), ``E`` a layer of sparse experts, ``*`` causal
attention.  Final RMSNorm, untied head.

``M``, ``H`` heads of ``P`` channels, ``G`` groups of state size ``N``
(:func:`dlrover_tpu.ops.ssd.ssd_scan`)::

    [z | xBC | dt] = W_in u            [H P | H P + 2 G N | H]
    xBC <- SiLU(causal depthwise conv(xBC) + b_conv)     (ops/causal_conv.py)
    [x | B | C] = xBC                  [H, P | G, N | G, N]
    dt_h = softplus(dt_h + dt_bias_h)  A_h = -exp(A_log_h)
    S_h,t = exp(dt_h,t A_h) S_h,t-1 + (dt_h,t x_h,t) B_g,t^T
    y_h,t = S_h,t C_g,t + D_h x_h,t    (g = h // (H / G), S float32 from 0)
    out = W_out (groupRMSNorm(y * SiLU(z)) * w_norm)

the gate BEFORE the norm, whose groups are the ``G`` runs of ``H P /
G`` channels.  ``dt`` is not clamped.

``E`` (:class:`dlrover_tpu.parallel.moe.DroplessMoE`,
``expert_form="relu2"``): sigmoid scores in float32, the top-k of
``score + bias`` chosen and weighted by the score alone, renormalised
and scaled; an expert is
``W_down relu(W_up x) ** 2``, no gate matrix, and a shared expert of
the same form beside them.  The chip holds experts ``[lo, lo +
count)`` of ``num_experts``: it routes over all and computes its own.
The bias takes no gradient: after each step ``b_e += u x sign(mean(n)
- n_e)``; the loss hands the train step those deltas
(``aux["state_updates"]``), as ``models/sarvam_mla.py`` does.

``*``: ``num_heads`` query heads over ``num_kv_heads`` key and value
heads of ``head_dim``, no bias, NO positional term (the recurrent
layers carry position), through ``layers.attention``.

Flax module names: ``ssm``, ``moe``, ``attn`` (the benchmark finds
flash kernels by that name; its scope is ``full_attn``).  Device
scopes: ``ssm_in_proj``, ``ssm_conv`` (three ``conv_fwd`` kernels of
``ops/causal_conv.py``, one each for ``x``, ``B`` and ``C``; three
``conv_bwd`` under its transpose), ``ssm_gates`` (softplus, the
decay's mean, the ``D`` skip), ``ssm_scan``, ``ssm_norm``,
``ssm_out_proj``, and the expert layer's ``moe_*``.
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
from dlrover_tpu.ops.ssd import ssd_scan
from dlrover_tpu.parallel.moe import DroplessMoE, bias_deltas
from dlrover_tpu.telemetry.tracing import device_scope

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass(frozen=True)
class NemotronHConfig:
    """Field names follow the repo's configs; the HF key each one
    carries is in the comment.  The defaults are
    NVIDIA-Nemotron-3-Nano-30B-A3B's, whole; a chip's share sets
    ``experts_held`` and ``vocab_size``."""

    vocab_size: int = 131072
    max_seq_len: int = 262144         # max_position_embeddings
    pattern: str = PUBLISHED_PATTERN  # hybrid_override_pattern
    hidden_dim: int = 2688            # hidden_size
    ssm_heads: int = 64               # mamba_num_heads
    ssm_head_dim: int = 64            # mamba_head_dim
    ssm_groups: int = 8               # n_groups
    ssm_state: int = 128              # ssm_state_size
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    num_heads: int = 32               # num_attention_heads
    num_kv_heads: int = 2             # num_key_value_heads
    head_dim: int = 128
    expert_dim: int = 1856            # moe_intermediate_size
    shared_dim: int = 3712            # moe_shared_expert_intermediate_size
    num_experts: int = 128            # the router's outputs
    experts_held: Tuple[int, int] = (0, 128)   # (first, count) held here
    top_k: int = 6                    # num_experts_per_tok
    routed_scale: float = 2.5         # routed_scaling_factor
    bias_update_rate: float = 0.001   # u of the bias's rule
    rms_eps: float = 1e-5             # layer_norm_epsilon
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "xla"

    @property
    def ssm_inner(self) -> int:
        """``d_inner = H P``, NOT ``expand x hidden``."""
        return self.ssm_heads * self.ssm_head_dim

    @classmethod
    def tiny(cls, **kw) -> "NemotronHConfig":
        return cls(**{**dict(
            vocab_size=256, max_seq_len=256, pattern="ME*EM",
            hidden_dim=64, ssm_heads=4, ssm_head_dim=8, ssm_groups=2,
            ssm_state=16, chunk_size=16, num_heads=4, num_kv_heads=2,
            head_dim=16, expert_dim=24, shared_dim=48, num_experts=16,
            experts_held=(4, 4), top_k=3,
        ), **kw})


def _a_log_init(key, shape, dtype):
    """``A = 1 .. H`` (Mamba-2's ``A_init_range``)."""
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


def _dt_bias_init(low: float, high: float, floor: float):
    """``softplus^-1(dt)``, ``dt`` log-uniform in ``[low, high]`` and
    at least ``floor``."""

    def init(key, shape, dtype):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, dtype, math.log(low), math.log(high)
        )), floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


class Mamba2Mixer(nn.Module):
    """The state-space mixer; returns ``(y, {"state_rms": root mean
    square of the final state, "decay_mean": mean of exp(dt A)})``."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, u: jax.Array):
        cfg = self.config
        b, s, _ = u.shape
        heads, p = cfg.ssm_heads, cfg.ssm_head_dim
        groups, n = cfg.ssm_groups, cfg.ssm_state
        inner, bc = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
        proj = partial(
            layers.dense, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            init_std=cfg.init_std,
        )
        with device_scope("ssm_in_proj"):
            zxbcdt = proj(2 * inner + 2 * bc + heads, "in_proj")(u)
            z = zxbcdt[..., :inner]
            dt = zxbcdt[..., 2 * inner + 2 * bc:]
        with device_scope("ssm_conv"):
            taps = self.param(
                "conv", layers.conv_init, (cfg.conv_kernel, inner + 2 * bc),
                cfg.param_dtype,
            )
            bias = self.param(
                "conv_bias", nn.initializers.zeros, (inner + 2 * bc,),
                cfg.param_dtype,
            )
            # x, B and C straight out of the projection's lanes, each
            # its own array: no slice copy before the kernel or after
            x, B, C = (
                causal_conv(
                    zxbcdt, taps[:, lo:hi], bias[lo:hi], first=inner + lo
                )
                for lo, hi in (
                    (0, inner), (inner, inner + bc),
                    (inner + bc, inner + 2 * bc),
                )
            )
            x = x.reshape(b, s, heads, p)
            B, C = B.reshape(b, s, groups, n), C.reshape(b, s, groups, n)
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        dt_bias = self.param(
            "dt_bias", _dt_bias_init(
                cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor
            ), (heads,), jnp.float32,
        )
        with device_scope("ssm_gates"):
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            A = -jnp.exp(a_log)
            decay_mean = jnp.mean(jnp.exp(dt * A))
        with device_scope("ssm_scan"):
            # (the block's remat keeps what the forward kernel wrote)
            y, state = ssd_scan(x, dt, A, B, C, chunk=cfg.chunk_size)
        with device_scope("ssm_gates"):
            y = y.astype(jnp.float32) + skip[:, None] * x.astype(
                jnp.float32
            )
        with device_scope("ssm_norm"):
            # the gate first, then one RMS a group of channels
            scale = self.param(
                "norm", nn.initializers.ones, (inner,), jnp.float32
            )
            y = y.reshape(b, s, inner) * nn.silu(z.astype(jnp.float32))
            y = y.reshape(b, s, groups, inner // groups)
            y = y * jax.lax.rsqrt(
                jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_eps
            )
            y = (y.reshape(b, s, inner) * scale).astype(cfg.dtype)
            state_rms = jnp.sqrt(jnp.mean(state * state))
        with device_scope("ssm_out_proj"):
            out = proj(cfg.hidden_dim, "out_proj")(y)
        return out, {"state_rms": state_rms, "decay_mean": decay_mean}


class Attention(nn.Module):
    config: NemotronHConfig

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


class NemotronHBlock(nn.Module):
    """One norm and the mixer ``kind`` names (a character of the
    pattern).  Returns ``(y, stats)``: the state-space mixer's pair of
    counters, the expert layer's router stats, ``None`` for
    attention."""

    config: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.config
        h = layers.RMSNorm(cfg.rms_eps, name="norm")(x)
        if self.kind == MAMBA:
            out, stats = Mamba2Mixer(cfg, name="ssm")(h)
        elif self.kind == EXPERTS:
            out, stats = DroplessMoE(
                num_experts=cfg.num_experts, mlp_dim=cfg.expert_dim,
                top_k=cfg.top_k, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.initializers.normal(cfg.init_std),
                held=cfg.experts_held, score="sigmoid", select_bias=True,
                renormalise=True, scale=cfg.routed_scale,
                shared_dim=cfg.shared_dim, expert_form="relu2",
                name="moe",
            )(h)
        elif self.kind == ATTENTION:
            with device_scope("full_attn"):
                out, stats = Attention(cfg, name="attn")(h), None
        else:
            raise ValueError(f"unknown layer kind {self.kind!r}")
        return x + out, stats


def _stacked(per_layer):
    return jax.tree.map(lambda *a: jnp.stack(a), *per_layer)


class NemotronH(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(
        self, tokens: jax.Array, return_hidden: bool = False,
        return_stats: bool = False,
    ):
        """Logits ``[b, s, vocab]`` in float32, or with
        ``return_hidden`` the final-norm output for a chunked head
        (``models/losses.py``); with ``return_stats`` also ``(the
        state-space layers' counters, :func:`dropless_moe`'s
        stats)``, each stacked over its kind's layers."""
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.initializers.normal(cfg.init_std),
            name="wte",
        )(tokens)
        block = (
            layers.rematted(NemotronHBlock, prevent_cse=True)
            if cfg.remat else NemotronHBlock
        )
        of_kind = {MAMBA: [], EXPERTS: []}
        for i, kind in enumerate(cfg.pattern):
            x, stats = block(cfg, kind, name=f"block_{i}")(x)
            if stats is not None:
                of_kind[kind].append(stats)
        x = layers.RMSNorm(cfg.rms_eps, name="norm_f")(x)
        if not return_hidden:
            x = layers.dense(
                cfg.vocab_size, "lm_head", cfg.dtype, cfg.param_dtype,
                cfg.init_std,
            )(x).astype(jnp.float32)
        if not return_stats:
            return x
        return x, (_stacked(of_kind[MAMBA]), _stacked(of_kind[EXPERTS]))

    init_params = layers.init_params


def make_nemotron_h_loss(model: NemotronH, num_chunks: int = 8):
    """Next-token cross entropy through the chunked head, alone (no
    auxiliary loss: the bias balances the load).  ``loss_fn(params,
    batch) -> (loss, aux)``; ``aux`` holds the step's ``ssm.*`` and
    ``moe.*`` counters and, under ``"state_updates"`` (the key that
    ``make_train_step`` documents), each expert layer's bias delta
    for the step to add."""
    cfg = model.config
    expert_layers = [
        i for i, kind in enumerate(cfg.pattern) if kind == EXPERTS
    ]

    def loss_fn(params, batch):
        hidden, (ssm, moe) = model.apply(
            {"params": params}, batch["x"], return_hidden=True,
            return_stats=True,
        )
        loss = chunked_cross_entropy(
            hidden, params["lm_head"]["kernel"], batch["y"],
            num_chunks=num_chunks,
        )
        with device_scope("moe_router"):
            counts = jax.lax.stop_gradient(moe["counts"])
            deltas = bias_deltas(counts, cfg.bias_update_rate)
            biases = jnp.stack([
                params[f"block_{i}"]["moe"]["select_bias"]
                for i in expert_layers
            ])
        return loss, {
            "ssm.state_rms_max": jax.lax.stop_gradient(
                jnp.max(ssm["state_rms"])
            ),
            "ssm.decay_mean": jax.lax.stop_gradient(
                jnp.mean(ssm["decay_mean"])
            ),
            "moe.held_rows_share": jnp.mean(
                moe["held_rows"] / counts.sum(axis=1)
            ),
            "moe.held_tiles_share": jnp.mean(
                moe["tiles_used"] / moe["tiles"]
            ),
            "moe.bias_abs_max": jnp.max(jnp.abs(biases)),
            "state_updates": {
                f"block_{i}": {"moe": {"select_bias": deltas[j]}}
                for j, i in enumerate(expert_layers)
            },
        }

    loss_fn.has_aux = True
    return loss_fn
