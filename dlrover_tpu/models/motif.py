"""Hyper-connected differential-latent-attention mixture-of-experts
decoder (``model_type: "motif"``).  A token carries ``n`` residual
streams of ``C`` (``[b, s, n * C]``, ``models/layers.py``): the
embedding copied into each; round EVERY sub-layer ``F`` (attention,
then the feed-forward; each with its own coefficients)::

    H_pre, H_post, H_res = StreamCoefficients(X)   (H_res by Sinkhorn)
    u  = H_pre X                                   (one stream of C)
    X' = H_res X + H_post^T F(RMSNorm_F(u))

and the stack's output is the SUM of the streams, RMSNorm, the untied
head.

Attention (grouped differential attention on a latent key): per token
``u``::

    c_q = RMSNorm(u W_dq)             q = c_q W_uq   [H, nope + rope]
    [c | k_r] = u W_dkv               [k_g | v_g] = RMSNorm(c) W_ukv
    key of kv head g = [k_g, rope(k_r)]: ONE rope key for all
    A_h = softmax(q_h K_g^T (nope + rope)^-1/2 + mask) V_g

The ``H`` query heads are ``G`` groups over one kv head each, a
group's first heads SIGNAL and its last ``noise / G`` heads NOISE::

    lambda = sigmoid(u W_lambda)      one number a token and signal head
    o_gj = A_signal(g, j) - lambda_gj A_noise(g)
    out  = (o * sigmoid(u W_gate)) W_o

(the subtract-after-attention form: two softmaxes' outputs, each
through the flash kernels as an ordinary head of a group of ``H /
G``).  ``layer_pattern[l]`` is the layer's kind: 0 full (causal; its
rope rule may be yarn's), 1 window (a key ``sliding_window`` or more
behind the query is hidden; the default rule).  No sink, no biases.

Feed-forward: PolyNorm in silu's place everywhere
(``ops/grouped_matmul.py::poly_norm``): the leading ``first_dense``
layers :class:`layers.PolyNormGLU`, the others
:class:`dlrover_tpu.parallel.moe.DroplessMoE` with
``expert_form="polynorm"``: sigmoid scores, top-k by the score alone
(no selection bias), renormalised and scaled, a shared expert, the
chip holding experts ``[lo, lo + count)`` of the router's outputs; the
loss adds ``balance_coeff x`` the load-balancing term over ALL of the
router's outputs.

One multi-token-prediction layer (DeepSeek-V3's form; ``mtp``): ``h' =
[RMSNorm(h) ; RMSNorm(embed(token_{t+1}))] W_eh`` of the summed
streams ``h`` before the final norm, copied into ``n`` streams, one
more sparse full-attention block, the streams' sum, a final norm of
its own and the SHARED head, predicting token ``t + 2``.

The flax module of the attention is called ``attn`` (the benchmark
finds flash kernels by that name); a window layer's sits under the
device scope ``swa``, a full layer's under ``full_attn``.  Device
scopes inside: ``gdla_q_latent``, ``gdla_kv``, ``gdla_rope``,
``gdla_diff``, ``gdla_gate``, ``gdla_out``; the streams' ``mhc_*``;
``polynorm``; the expert layer's ``moe_*``; ``mtp`` round the
prediction layer and its pass of the head.
"""

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models import layers
from dlrover_tpu.models.losses import (
    chunked_cross_entropy,
    weighted_chunked_cross_entropy,
)
from dlrover_tpu.parallel.moe import DroplessMoE
from dlrover_tpu.telemetry.tracing import device_scope

FULL, WINDOW = 0, 1
SCOPE_OF = {FULL: "full_attn", WINDOW: "swa"}


@dataclass(frozen=True)
class MotifConfig:
    """Field names follow the repo's configs; the HF key each one
    carries is in the comment.  The defaults are Motif-3-Beta's widths
    and one period of its layers behind one dense layer; a chip's
    share sets the three head counts, ``experts_held`` and
    ``vocab_size``."""

    vocab_size: int = 220160
    max_seq_len: int = 262144         # max_position_embeddings
    hidden_dim: int = 4096            # hidden_size
    streams: int = 4                  # mhc_expansion_rate
    sinkhorn_iters: int = 20          # mhc_sinkhorn_iters
    num_heads: int = 80               # num_attention_heads (held here)
    num_kv_heads: int = 16            # num_key_value_heads (held here)
    num_noise_heads: int = 16         # num_noise_heads (held here)
    qk_nope_dim: int = 128            # head_dim - qk_rope_head_dim
    qk_rope_dim: int = 64             # qk_rope_head_dim
    v_head_dim: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    layer_pattern: Tuple[int, ...] = (1, 1, 1, 1, 0)
    first_dense: int = 1              # n_dense_first_layers
    sliding_window: int = 128
    full_rope: layers.RopeRule = layers.RopeRule(    # rope_scaling
        theta=1e4, factor=64.0, original_len=4096
    )
    swa_rope: layers.RopeRule = layers.RopeRule(theta=1e4)
    dense_dim: int = 12288            # intermediate_size
    expert_dim: int = 1280            # moe_intermediate_size
    shared_experts: int = 1           # num_shared_experts
    num_experts: int = 384            # the router's outputs
    experts_held: Tuple[int, int] = (0, 384)   # (first, count) held here
    top_k: int = 8                    # experts_top_k
    routed_scale: float = 2.0         # route_scale
    balance_coeff: float = 1e-4       # load_balance_coeff
    polynorm_scale: float = 0.5       # polynorm_output_scale
    polynorm_clamp: float = 0.5       # polynorm_bias_clamp
    mtp_layers: int = 1               # num_nextn_predict_layers
    mtp_weight: float = 0.3
    rms_eps: float = 1e-5             # rms_norm_eps
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "xla"

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads or (
            self.num_noise_heads % self.num_kv_heads
        ):
            raise ValueError(
                f"{self.num_heads} query heads, {self.num_noise_heads} "
                f"of them noise, over {self.num_kv_heads} kv heads"
            )
        if not 0 < self.noise_per_group < self.group:
            raise ValueError("a group needs signal heads and noise heads")
        if self.mtp_layers not in (0, 1):
            raise ValueError("one prediction layer or none")

    @property
    def num_layers(self) -> int:
        return len(self.layer_pattern)

    @property
    def group(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def noise_per_group(self) -> int:
        return self.num_noise_heads // self.num_kv_heads

    @property
    def signal_heads(self) -> int:
        return self.num_heads - self.num_noise_heads

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @classmethod
    def tiny(cls, **kw) -> "MotifConfig":
        return cls(**{**dict(
            vocab_size=256, max_seq_len=256, hidden_dim=64, streams=4,
            sinkhorn_iters=20, num_heads=6, num_kv_heads=2,
            num_noise_heads=2, qk_nope_dim=16, qk_rope_dim=8,
            v_head_dim=16, q_lora_rank=48, kv_lora_rank=32,
            layer_pattern=(1, 1, 0), sliding_window=24,
            full_rope=layers.RopeRule(
                theta=1e4, factor=4.0, original_len=32
            ),
            dense_dim=96, expert_dim=32, num_experts=16,
            experts_held=(4, 4), top_k=4,
        ), **kw})


def differential(out, lam, signal: int):
    """``o_gj = A_signal(g, j) - lambda_gj A_noise(g)``: ``out [b, s,
    G, group, dv]`` (a group's first ``signal`` heads signal, the rest
    noise; several noise heads are averaged), ``lam [b, s, G, signal,
    1]``; float32 ``(o [b, s, G, signal, dv], what was taken away)``.
    A noise head's gradient is ``- sum_j lambda_gj d o_gj``."""
    out = out.astype(jnp.float32)
    wanted, noise = out[..., :signal, :], out[..., signal:, :]
    if noise.shape[-2] > 1:
        noise = noise.mean(axis=-2, keepdims=True)
    removed = lam * noise
    return wanted - removed, removed


class GdlaAttention(nn.Module):
    """``window`` (None: full) and ``rope`` come from the layer's
    kind.  Returns ``(out, {"lambda_mean", "noise_share"})``, the
    counters float32 scalars with no gradient."""

    config: MotifConfig
    window: Optional[int]
    rope: layers.RopeRule

    @nn.compact
    def __call__(self, u: jax.Array):
        cfg = self.config
        b, s, _ = u.shape
        heads, kv, group = cfg.num_heads, cfg.num_kv_heads, cfg.group
        nope, rope, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        signal = group - cfg.noise_per_group
        proj = partial(
            layers.dense, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            init_std=cfg.init_std,
        )
        with device_scope("gdla_q_latent"):
            q = proj(heads * (nope + rope), "q_up")(
                layers.RMSNorm(cfg.rms_eps, name="q_norm")(
                    proj(cfg.q_lora_rank, "q_down")(u)
                )
            )
        with device_scope("gdla_kv"):
            down = proj(cfg.kv_lora_rank + rope, "kv_down")(u)
            up = proj(kv * (nope + dv), "kv_up")(
                layers.RMSNorm(cfg.rms_eps, name="kv_norm")(
                    down[..., :cfg.kv_lora_rank]
                )
            )
        with device_scope("gdla_rope"):
            cos, sin = self.rope.tables(s, rope)
            q = q.reshape(b, s, heads, nope + rope)
            q = jnp.concatenate(
                [q[..., :nope], layers.rotate_half(q[..., nope:], cos, sin)],
                axis=-1,
            )
            up = up.reshape(b, s, kv, nope + dv)
            # the one rope key, broadcast to every kv head's key
            k_pe = layers.rotate_half(
                down[..., None, cfg.kv_lora_rank:], cos, sin
            )
            k = jnp.concatenate([
                up[..., :nope], jnp.broadcast_to(k_pe, (b, s, kv, rope)),
            ], axis=-1)
            v = up[..., nope:]
        out = layers.attention(
            cfg.attention_impl, q, k, v, window=self.window,
            dtype=cfg.dtype,
        )
        with device_scope("gdla_diff"):
            w_lambda = self.param(
                "lambda_proj", nn.initializers.normal(cfg.init_std),
                (cfg.hidden_dim, kv * signal), cfg.param_dtype,
            )
            lam = jax.nn.sigmoid(jnp.einsum(
                "bsh,hj->bsj", u.astype(cfg.dtype),
                w_lambda.astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            )).reshape(b, s, kv, signal, 1)
            diff, removed = differential(
                out.reshape(b, s, kv, group, dv), lam, signal
            )
            stats = jax.lax.stop_gradient({
                "lambda_mean": jnp.mean(lam),
                "noise_share": jnp.mean(jnp.abs(removed))
                / jnp.mean(jnp.abs(diff + removed)),
            })
        with device_scope("gdla_gate"):
            gate = jax.nn.sigmoid(
                proj(kv * signal * dv, "gate_proj")(u).astype(jnp.float32)
            )
            gated = (diff.reshape(b, s, kv * signal * dv) * gate).astype(
                cfg.dtype
            )
        with device_scope("gdla_out"):
            return proj(cfg.hidden_dim, "o_proj")(gated), stats


class MotifBlock(nn.Module):
    """``kind`` sets the attention, ``dense`` the feed-forward; ``x
    [b, s, n * C]`` in and out.  Returns ``(x, router stats, counters)``,
    the router stats ``None`` in a dense block."""

    config: MotifConfig
    kind: int
    dense: bool

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.config
        window = self.kind == WINDOW

        def coefficients(name):
            return layers.StreamCoefficients(
                cfg.streams, cfg.sinkhorn_iters, cfg.rms_eps, cfg.dtype,
                cfg.param_dtype, cfg.init_std, name=name,
            )

        h_pre, h_post, h_res, err_attn = coefficients("mhc_attn")(x)
        u = layers.read_streams(x, h_pre)
        with device_scope(SCOPE_OF[self.kind]):
            out, counters = GdlaAttention(
                cfg, cfg.sliding_window if window else None,
                cfg.swa_rope if window else cfg.full_rope, name="attn",
            )(layers.RMSNorm(cfg.rms_eps, name="ln_attn")(u))
        x = layers.write_streams(x, out, h_post, h_res)

        h_pre, h_post, h_res, err_mlp = coefficients("mhc_mlp")(x)
        h = layers.RMSNorm(cfg.rms_eps, name="ln_mlp")(
            layers.read_streams(x, h_pre)
        )
        stats = None
        if self.dense:
            out = layers.PolyNormGLU(
                cfg.dense_dim, cfg.hidden_dim, cfg.dtype, cfg.param_dtype,
                cfg.init_std, cfg.polynorm_scale, cfg.polynorm_clamp,
                name="mlp",
            )(h)
        else:
            out, stats = DroplessMoE(
                num_experts=cfg.num_experts, mlp_dim=cfg.expert_dim,
                top_k=cfg.top_k, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.initializers.normal(cfg.init_std),
                held=cfg.experts_held, score="sigmoid", renormalise=True,
                scale=cfg.routed_scale,
                shared_dim=cfg.shared_experts * cfg.expert_dim,
                expert_form="polynorm",
                polynorm_scale=cfg.polynorm_scale,
                polynorm_clamp=cfg.polynorm_clamp, name="moe",
            )(h)
        x = layers.write_streams(x, out, h_post, h_res)
        counters["res_sum_err"] = jnp.maximum(err_attn, err_mlp)
        return x, stats, counters


class MotifPrediction(nn.Module):
    """The multi-token-prediction layer: ``(its final-norm output [b,
    s, C], router stats, counters)`` from the summed streams ``h`` and
    the NEXT tokens' embeddings."""

    config: MotifConfig
    block: Any

    @nn.compact
    def __call__(self, h: jax.Array, next_embedded: jax.Array):
        cfg = self.config
        joined = jnp.concatenate([
            layers.RMSNorm(cfg.rms_eps, name="ln_h")(h),
            layers.RMSNorm(cfg.rms_eps, name="ln_e")(next_embedded),
        ], axis=-1)
        x = layers.dense(
            cfg.hidden_dim, "eh_proj", cfg.dtype, cfg.param_dtype,
            cfg.init_std,
        )(joined)
        x, stats, counters = self.block(cfg, FULL, False, name="block")(
            jnp.tile(x, (1, 1, cfg.streams))
        )
        return layers.RMSNorm(cfg.rms_eps, name="ln_f")(
            layers.sum_streams(x, cfg.streams)
        ), stats, counters


class Motif(nn.Module):
    config: MotifConfig

    @nn.compact
    def __call__(
        self, tokens: jax.Array, next_tokens: Optional[jax.Array] = None,
        return_hidden: bool = False, return_stats: bool = False,
    ):
        """Logits ``[b, s, vocab]`` in float32, or with
        ``return_hidden`` the final-norm output for a chunked head
        (``models/losses.py``).  With ``next_tokens`` (``token_{t+1}``
        at ``t``) and a prediction layer the first result is a pair,
        ``(main, prediction layer's)``.  With ``return_stats`` also
        :func:`dropless_moe`'s ``stats`` stacked over the sparse
        layers (the prediction layer's last) and the blocks' counters
        stacked likewise."""
        cfg = self.config
        wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.initializers.normal(cfg.init_std),
            name="wte",
        )
        block = (
            layers.rematted(MotifBlock, prevent_cse=True) if cfg.remat
            else MotifBlock
        )
        x = jnp.tile(wte(tokens), (1, 1, cfg.streams))
        routers, counters = [], []
        for i, kind in enumerate(cfg.layer_pattern):
            x, stats, found = block(
                cfg, kind, i < cfg.first_dense, name=f"block_{i}"
            )(x)
            counters.append(found)
            if stats is not None:
                routers.append(stats)
        h = layers.sum_streams(x, cfg.streams)
        hidden = layers.RMSNorm(cfg.rms_eps, name="ln_f")(h)
        if next_tokens is not None and cfg.mtp_layers:
            with device_scope("mtp"):
                predicted, stats, found = MotifPrediction(
                    cfg, block, name="mtp"
                )(h, wte(next_tokens))
            routers.append(stats)
            counters.append(found)
            hidden = (hidden, predicted)
        if not return_hidden:
            head = layers.dense(
                cfg.vocab_size, "lm_head", cfg.dtype, cfg.param_dtype,
                cfg.init_std,
            )
            hidden = jax.tree.map(
                lambda a: head(a).astype(jnp.float32), hidden
            )
        if not return_stats:
            return hidden
        stack = lambda *a: jnp.stack(a)  # noqa: E731
        return (
            hidden, jax.tree.map(stack, *routers),
            jax.tree.map(stack, *counters),
        )

    def init_params(self, rng, batch_size: int = 2, seq_len: int = 0):
        """The ``params`` tree, the prediction layer's leaves among
        them, initialised on a batch of zeros."""
        seq_len = seq_len or min(self.config.max_seq_len, 128)
        tokens = jnp.zeros((batch_size, seq_len), dtype=jnp.int32)
        return self.init(rng, tokens, next_tokens=tokens)["params"]


def balance_loss(stats, top_k: int):
    """``E sum_e f_e P_e`` over the stacked router stats, all layers'
    assignments together (``models/olmoe.py::router_losses``' form):
    ``f_e`` the share of the routed rows' assignments at expert ``e``
    (it sums to k), ``P_e`` the mean score; over ALL of the router's
    outputs, held here or not."""
    counts, prob_sum = stats["counts"], stats["prob_sum"]
    rows = counts.sum() / top_k  # layers x tokens
    return counts.shape[1] * jnp.sum(
        (counts.sum(0) / rows) * (prob_sum.sum(0) / rows)
    )


def make_motif_loss(model: Motif, num_chunks: int = 8):
    """Next-token cross entropy through the chunked head + ``mtp_weight
    x`` the prediction layer's (token ``t + 2`` through the SAME head,
    the last position at weight 0) + ``balance_coeff x`` the
    load-balancing term.  ``loss_fn(params, batch) -> (loss, aux)``;
    ``aux`` holds the step's ``moe.*``, ``mhc.*``, ``gdla.*`` and
    ``mtp.*`` counters."""
    cfg = model.config

    def loss_fn(params, batch):
        x, y = batch["x"], batch["y"]
        hidden, stats, counters = model.apply(
            {"params": params}, x, next_tokens=y, return_hidden=True,
            return_stats=True,
        )
        head = params["lm_head"]["kernel"]
        aux = {}
        if cfg.mtp_layers:
            hidden, predicted = hidden
        loss = chunked_cross_entropy(hidden, head, y, num_chunks=num_chunks)
        if cfg.mtp_layers:
            with device_scope("mtp"):
                b, s = y.shape
                # token t + 2 is y's next; the last position has none
                weights = jnp.broadcast_to(
                    (jnp.arange(s) < s - 1) / (b * (s - 1)), (b, s)
                )
                mtp, _ = weighted_chunked_cross_entropy(
                    predicted, head, jnp.roll(y, -1, axis=1), weights,
                    num_chunks=num_chunks,
                )
            loss = loss + cfg.mtp_weight * mtp
            aux["mtp.loss"] = mtp
        with device_scope("moe_router"):
            counts = jax.lax.stop_gradient(stats["counts"])
            balance = balance_loss(stats, cfg.top_k)
        loss = loss + cfg.balance_coeff * balance
        aux.update({
            "moe.lb_loss": balance,
            "moe.load_max_over_mean": jnp.max(
                counts.max(axis=1) / counts.mean(axis=1)
            ),
            "moe.held_rows_share": jnp.mean(
                stats["held_rows"] / counts.sum(axis=1)
            ),
            "moe.held_tiles_share": jnp.mean(
                stats["tiles_used"] / stats["tiles"]
            ),
            "mhc.res_sum_err_max": jnp.max(counters["res_sum_err"]),
            "gdla.lambda_mean": jnp.mean(counters["lambda_mean"]),
            "gdla.noise_share": jnp.mean(counters["noise_share"]),
        })
        return loss, aux

    loss_fn.has_aux = True
    return loss_fn
