"""Looped decoder (``model_type: "ouro"``; "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741): a stack of ``L`` dense
blocks that runs ``R = total_ut_steps`` times over the SAME weights, an
exit after every pass, and a training loss that is the expectation of
the exits' cross entropies under a learned exit distribution.

::

    h = E[tokens]
    for t in 1..R:                                  # the same parameters
        for l in 1..L:
            a = h + N2_l(Attn_l(N1_l(h)))           # sandwich: a norm before
            h = a + N4_l(SwiGLU_l(N3_l(a)))         #   AND after each sublayer
        h = N_f(h)                                  # closes EVERY pass:
        x_t = h                                     #   exit t and pass t + 1's input
        lam_t = sigmoid(x_t . w_g + b_g)            # one scalar a token, float32
    p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j);  p_R = prod_{j<R}(1 - lam_j)
    loss = mean over tokens of [sum_t p_t nll_t - beta H(p)]

Attention is multi-head (no grouping) with half-split rope on every
lane, the same positions in every pass; no biases but the gate's.

**The tie.**  The block modules are constructed once and CALLED ``R``
times, so the parameter tree holds ``L`` blocks (``block_<l>`` at the
top, as in every model here) and every block weight takes ``R``
gradient contributions, a pass apart.  The passes are ONE scan
(``nn.scan`` of :meth:`Ouro.one_pass` with the parameters broadcast):
the step holds ``L`` applications' instructions, run ``R`` times, a
quarter of the unrolled form's compile time and 1.4 GB fewer
temporaries at no cost in step time (PERF.md section 6, PR 43).  A
pass's operations carry the device scope ``ut`` (OUTSIDE the block
modules, as ``swa`` sits outside ``attn`` in ``models/laguna.py``),
forward, remat copy and backward alike; being one set of instructions
they are told from the rest of the step, not from each other.
``exit_gate`` holds the gate's projection and sigmoids, the exit
distribution, its entropy and the counters; the mixing of the exits is
the weighted head's (``loss_head``, ``models/losses.py``).

``lam_R`` enters nothing (the last exit takes what is left): the scan
computes the last pass's gate logit like the others' and drops it.
"""

from dataclasses import dataclass
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models import layers
from dlrover_tpu.models.losses import weighted_chunked_cross_entropy
from dlrover_tpu.telemetry.tracing import device_scope


@dataclass(frozen=True)
class OuroConfig:
    """Field names follow the repo's configs; the HF key each one
    carries is in the comment.  The defaults are Ouro-2.6B's."""

    vocab_size: int = 49152
    max_seq_len: int = 65536          # max_position_embeddings
    num_layers: int = 48              # num_hidden_layers
    ut_steps: int = 4                 # total_ut_steps
    num_heads: int = 16               # num_attention_heads (= kv heads)
    head_dim: int = 128
    hidden_dim: int = 2048            # hidden_size
    dense_dim: int = 5632             # intermediate_size
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6             # rms_norm_eps
    entropy_weight: float = 0.05      # beta (the paper's later stages)
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "xla"

    @classmethod
    def tiny(cls, **kw) -> "OuroConfig":
        return cls(**{**dict(
            vocab_size=256, max_seq_len=128, num_layers=2, ut_steps=3,
            num_heads=4, head_dim=16, hidden_dim=64, dense_dim=96,
        ), **kw})


class OuroAttention(nn.Module):
    config: OuroConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        b, s, _ = x.shape
        heads, d = cfg.num_heads, cfg.head_dim
        positions = jnp.arange(s)

        proj = partial(
            layers.dense, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            init_std=cfg.init_std,
        )

        def heads_of(name, rotate):
            t = proj(heads * d, name)(x).reshape(b, s, heads, d)
            return layers.rope(t, positions, cfg.rope_theta) if rotate else t

        out = layers.attention(
            cfg.attention_impl, heads_of("q_proj", True),
            heads_of("k_proj", True), heads_of("v_proj", False),
            dtype=cfg.dtype,
        )
        return proj(cfg.hidden_dim, "o_proj")(
            out.reshape(b, s, heads * d)
        )


class OuroBlock(nn.Module):
    """The sandwich: each sublayer between two RMSNorms of its own."""

    config: OuroConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config

        def norm(name):
            return layers.RMSNorm(cfg.rms_eps, name=name)

        x = x + norm("ln_attn_out")(
            OuroAttention(cfg, name="attn")(norm("ln_attn")(x))
        )
        return x + norm("ln_mlp_out")(layers.SwiGLU(
            cfg.dense_dim, cfg.hidden_dim, cfg.dtype, cfg.param_dtype,
            cfg.init_std, name="mlp",
        )(norm("ln_mlp")(x)))


class ExitGate(nn.Module):
    """``x . w_g + b_g``: one float32 logit a token."""

    config: OuroConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        kernel = self.param(
            "kernel", nn.initializers.normal(cfg.init_std),
            (x.shape[-1], 1), cfg.param_dtype,
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (1,), cfg.param_dtype
        )
        return jnp.einsum(
            "bsh,ho->bso", x, kernel.astype(x.dtype),
            preferred_element_type=jnp.float32,
        )[..., 0] + bias.astype(jnp.float32)


class Ouro(nn.Module):
    config: OuroConfig

    def setup(self):
        cfg = self.config
        self.wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.initializers.normal(cfg.init_std),
        )
        block = (
            layers.rematted(OuroBlock, prevent_cse=True) if cfg.remat
            else OuroBlock
        )
        # built once, called in every pass: L blocks in the tree
        for i in range(cfg.num_layers):
            setattr(self, f"block_{i}", block(cfg))
        self.ln_f = layers.RMSNorm(cfg.rms_eps)
        if cfg.ut_steps > 1:
            self.exit_gate = ExitGate(cfg)
        self.lm_head = layers.dense(
            cfg.vocab_size, "lm_head", cfg.dtype, cfg.param_dtype,
            cfg.init_std,
        )

    def one_pass(self, x: jax.Array):
        """``(x_t, its gate logit [b, s] float32 or None)``: the stack
        and the final norm that closes the pass."""
        cfg = self.config
        with device_scope("ut"):
            for i in range(cfg.num_layers):
                x = getattr(self, f"block_{i}")(x)
            x = self.ln_f(x)
        if cfg.ut_steps == 1:
            # one pass: no gate, and none in the tree
            return x, None
        with device_scope("exit_gate"):
            return x, self.exit_gate(x)

    def __call__(self, tokens: jax.Array, return_hidden: bool = False):
        """``(exits, gate logits [R - 1, b, s] float32)``: every pass's
        final-norm output ``[R, b, s, hidden]`` with ``return_hidden``
        (for the weighted chunked head), else every exit's logits
        ``[R, b, s, vocab]`` in float32."""
        cfg = self.config

        def body(model, x, _):
            x, logit = model.one_pass(x)
            return x, (x, logit)

        _, (x, logits) = nn.scan(
            body, variable_broadcast="params",
            split_rngs={"params": False}, length=cfg.ut_steps,
        )(self, self.wte(tokens), None)
        if not return_hidden:
            x = self.lm_head(x).astype(jnp.float32)
        if logits is None:
            return x, jnp.zeros((0,) + tokens.shape, jnp.float32)
        return x, logits[:-1]

    init_params = layers.init_params


def exit_distribution(gate_logits: jax.Array):
    """``(p, log p)``, both ``[R, ...]`` float32, from the first ``R -
    1`` passes' gate logits ``[R - 1, ...]``: a token leaves at exit
    ``t`` with ``lam_t`` of what the earlier gates let through, and at
    the last exit with what is left.  In logs (``log sigmoid``), so a
    saturated gate gives 0 x finite in the entropy and never ``log
    0``."""
    z = gate_logits.astype(jnp.float32)
    zero = jnp.zeros((1,) + z.shape[1:], jnp.float32)
    stay = jax.nn.log_sigmoid(-z)                   # log(1 - lam_t)
    # log prod_{j<t}(1 - lam_j), then + log lam_t but at the last exit
    passed = jnp.concatenate([zero, jnp.cumsum(stay, axis=0)])
    log_p = passed + jnp.concatenate([jax.nn.log_sigmoid(z), zero])
    return jnp.exp(log_p), log_p


def make_ouro_loss(model: Ouro, num_chunks: int = 16):
    """The expected-exit loss (the paper's entropy-regularised stage I):
    ``mean over tokens of [sum_t p_t nll_t - beta H(p)]``, the ``R``
    exits through ONE weighted chunked head over ``R x`` the rows.
    ``loss_fn(params, batch) -> (loss, aux)``; ``aux`` holds the step's
    ``loop.*`` counters."""
    cfg = model.config

    def loss_fn(params, batch):
        exits, gate_logits = model.apply(
            {"params": params}, batch["x"], return_hidden=True
        )
        steps, b, s, h = exits.shape
        with device_scope("exit_gate"):
            p, log_p = exit_distribution(gate_logits)
            entropy = -jnp.sum(p * log_p, axis=0)           # [b, s]
        mixed, nll = weighted_chunked_cross_entropy(
            exits.reshape(steps * b, s, h), params["lm_head"]["kernel"],
            jnp.tile(batch["y"], (steps, 1)),
            (p / (b * s)).reshape(steps * b, s), num_chunks=num_chunks,
        )
        with device_scope("exit_gate"):
            loss = mixed - cfg.entropy_weight * entropy.mean()
            nll = nll.reshape(steps, b, s)
            exit_at = jnp.arange(1, steps + 1, dtype=jnp.float32)
            aux = {
                "loop.expected_exit": jnp.mean(
                    jnp.sum(exit_at[:, None, None] * p, axis=0)
                ),
                "loop.exit_entropy": entropy.mean(),
                "loop.nll_first": nll[0].mean(),
                "loop.nll_last": nll[-1].mean(),
            }
        return loss, aux

    loss_fn.has_aux = True
    return loss_fn
