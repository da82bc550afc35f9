"""Plain reference for the ``olmo_hybrid`` configurations: the forward
pass and training loss of a decoder that mixes Gated DeltaNet
linear-attention blocks with full-attention blocks, in straightforward
``jax.numpy`` and float32.

No kernels, no chunks, no remat, no flax: the gated delta rule is the
RECURRENCE, one token at a time (``lax.scan`` over the sequence), the
convolution is four shifted multiply-adds, attention is a masked
softmax.  Written against the parameter tree the system under test
trains (``wte``, ``block_<i>/{gdn/{q_proj, k_proj, v_proj, g_proj,
a_proj, b_proj, o_proj, q_conv, k_conv, v_conv, A_log, dt_bias,
o_norm} | attn/{q_proj, k_proj, v_proj, o_proj, q_norm, k_norm},
ln_mixer, mlp/{gate_proj, up_proj, down_proj}, ln_mlp}``, ``ln_f``,
``lm_head``), by key.  It shares no code with ``dlrover_tpu``.

The equations (Yang, Kautz, Hatamizadeh 2024, arXiv:2412.06464, eq. 10,
with ``linear_allow_neg_eigval``: Grazzi et al. 2024,
arXiv:2411.12537), per token ``x_t`` and head ``h``::

    q, k, v, z = W_q x, W_k x, W_v x, W_z x
    q, k, v <- SiLU(causal depthwise conv, taps c_0..c_3: sum_j c_j x_{t-3+j})
    q <- q / sqrt(|q|^2 + 1e-6) * d_k^-1/2     k <- k / sqrt(|k|^2 + 1e-6)
    beta = 2 sigmoid(W_b x)                    g = -exp(A_log) softplus(W_a x + dt_bias)
    S_t = exp(g) S_{t-1} + beta k (v - exp(g) S_{t-1}^T k)^T        S_0 = 0
    o = S_t^T q
    y = W_o (o / sqrt(mean(o^2) + eps) * o_norm * SiLU(z))

Block: ``h = x + RMSNorm(Mixer(x))``, ``y = h + RMSNorm(MLP(h))``
(OLMo 2, arXiv:2501.00656), SwiGLU MLP, final RMSNorm, untied head.
Full attention: ``q = RMSNorm(W_q x)``, ``k = RMSNorm(W_k x)`` over the
whole projection, no positional embedding, causal.

Departures from the published descriptions this file's author knows:
none in the equations.  What the published ``config.json`` does not
state (the norm placement, the QK-norm, that a null ``rope_theta``
means no positional embedding on the full-attention layers, the 1e-6
inside the L2 norm: fla's ``l2norm``) is the OLMo family's and the
Gated DeltaNet code's convention and stands in the configuration
file under ``assumed``.

Memory: the parameters arrive in the type they are served in (bf16)
and are up-cast to float32 INSIDE each jitted piece, one block at a
time; one sequence is run at a time; attention scores and the logits
are computed in row blocks, so that the whole fits beside the train
state at 8192 tokens and a 100352-word vocabulary.

On a TPU a float32 matmul runs in lower precision unless
``default_matmul_precision("highest")`` is set; every piece sets it.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"
ROWS = 512  # rows of attention scores / logits alive at a time


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _row_blocks(x):
    """``[seq, ...] -> [blocks, rows, ...]``, ``rows`` the largest
    divisor of ``seq`` up to ``ROWS``."""
    seq = x.shape[0]
    rows = max(r for r in range(1, min(seq, ROWS) + 1) if seq % r == 0)
    return x.reshape((seq // rows, rows) + x.shape[1:])


def _kernel(p, name):
    return p[name]["kernel"].astype(F32)


def _conv_silu(x, taps):
    """``x [seq, c]``, ``taps [K, c]``: four shifted multiply-adds."""
    taps = taps.astype(F32)
    k, seq = taps.shape[0], x.shape[0]
    out = jnp.zeros_like(x)
    for j in range(k):
        shift = k - 1 - j  # taps[j] meets x_{t - shift}
        shifted = jnp.concatenate(
            [jnp.zeros((shift, x.shape[1]), F32), x[:seq - shift]]
        )
        out = out + shifted * taps[j]
    return jax.nn.silu(out)


def _delta_rule(q, k, v, g, beta):
    """The recurrence, token by token.  ``q, k [seq, H, d_k]``, ``v
    [seq, H, d_v]``, ``g, beta [seq, H]`` -> ``o [seq, H, d_v]``."""
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = jnp.exp(g_t)[:, None, None] * state
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + (beta_t[:, None] * k_t)[:, :, None] * (
            v_t - read
        )[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, out = jax.lax.scan(
        token, jnp.zeros((heads, dk, dv), F32), (q, k, v, g, beta)
    )
    return out


def _linear_attention(x, p, *, heads, dk, dv, neg_eigval, eps):
    seq = x.shape[0]
    q = _conv_silu(x @ _kernel(p, "q_proj"), p["q_conv"])
    k = _conv_silu(x @ _kernel(p, "k_proj"), p["k_conv"])
    v = _conv_silu(x @ _kernel(p, "v_proj"), p["v_conv"])
    z = x @ _kernel(p, "g_proj")
    q, k = q.reshape(seq, heads, dk), k.reshape(seq, heads, dk)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = q / math.sqrt(dk)
    beta = jax.nn.sigmoid(x @ _kernel(p, "b_proj"))
    if neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(p["A_log"].astype(F32)) * jax.nn.softplus(
        x @ _kernel(p, "a_proj") + p["dt_bias"].astype(F32)
    )
    o = _delta_rule(q, k, v.reshape(seq, heads, dv), g, beta)
    o = _rms_norm(o, p["o_norm"], eps)
    o = o * jax.nn.silu(z).reshape(seq, heads, dv)
    return o.reshape(seq, heads * dv) @ _kernel(p, "o_proj")


def _full_attention(x, p, *, n_head, eps):
    seq, h = x.shape
    d = h // n_head
    q = _rms_norm(x @ _kernel(p, "q_proj"), p["q_norm"]["scale"], eps)
    k = _rms_norm(x @ _kernel(p, "k_proj"), p["k_norm"]["scale"], eps)
    v = x @ _kernel(p, "v_proj")
    heads = lambda a: a.reshape(seq, n_head, d).transpose(1, 0, 2)  # noqa: E731
    k, v = heads(k), heads(v)
    position = jnp.arange(seq)

    def rows(block):
        q_rows, at = block                      # [rows, h], [rows]
        q_rows = q_rows.reshape(-1, n_head, d).transpose(1, 0, 2)
        scores = q_rows @ k.transpose(0, 2, 1) / math.sqrt(d)
        causal = at[:, None] >= position[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        out = jax.nn.softmax(scores, axis=-1) @ v
        return out.transpose(1, 0, 2).reshape(-1, h)

    out = jax.lax.map(rows, (_row_blocks(q), _row_blocks(position)))
    return out.reshape(seq, h) @ _kernel(p, "o_proj")


def _mlp(x, p):
    return (
        jax.nn.silu(x @ _kernel(p, "gate_proj")) * (x @ _kernel(p, "up_proj"))
    ) @ _kernel(p, "down_proj")


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "kind", "n_head", "heads", "dk", "dv", "neg_eigval", "eps",
))
def _block(x, p, *, kind, n_head, heads, dk, dv, neg_eigval, eps):
    """One block on one sequence ``[seq, h]``."""
    with jax.default_matmul_precision("highest"):
        if kind == LINEAR:
            mixed = _linear_attention(
                x, p["gdn"], heads=heads, dk=dk, dv=dv,
                neg_eigval=neg_eigval, eps=eps,
            )
        else:
            mixed = _full_attention(x, p["attn"], n_head=n_head, eps=eps)
        x = x + _rms_norm(mixed, p["ln_mixer"]["scale"], eps)
        return x + _rms_norm(_mlp(x, p["mlp"]), p["ln_mlp"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_f, lm_head, *, eps):
    """Final norm and the untied output head: ``[seq, vocab]``."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, ln_f["scale"], eps)
        return x @ lm_head["kernel"].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _nll_sum(x, ln_f, lm_head, targets, *, eps):
    """Sum of the next-token negative log likelihoods of one
    sequence, the logits alive one row block at a time."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, ln_f["scale"], eps)
        kernel = lm_head["kernel"].astype(F32)

        def rows(block):
            x_rows, t_rows = block
            logp = jax.nn.log_softmax(x_rows @ kernel, axis=-1)
            return -jnp.take_along_axis(
                logp, t_rows[:, None], axis=-1
            ).sum()

        return jax.lax.map(
            rows, (_row_blocks(x), _row_blocks(targets))
        ).sum()


def _kw(cfg):
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("key and value heads of the rule differ")
    return dict(
        n_head=cfg["num_attention_heads"],
        heads=cfg["linear_num_key_heads"],
        dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
        neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
        eps=cfg["rms_norm_eps"],
    )


def hidden(params, row_tokens, cfg: dict):
    """The last block's output for one sequence ``[seq]``."""
    kw = _kw(cfg)
    x = _embed(params["wte"]["embedding"], row_tokens)
    for i, kind in enumerate(cfg["layer_types"]):
        if kind not in (LINEAR, FULL):
            raise ValueError(f"unknown layer type {kind!r}")
        x = _block(x, params[f"block_{i}"], kind=kind, **kw)
    return x


def forward(params, tokens, cfg: dict):
    """Per sequence, the float32 logits ``[seq, vocab]``."""
    return [
        _head(
            hidden(params, tokens[row], cfg), params["ln_f"],
            params["lm_head"], eps=cfg["rms_norm_eps"],
        )
        for row in range(tokens.shape[0])
    ]


def loss_of(params, tokens, targets, cfg: dict):
    """The training loss, mean next-token cross entropy,
    differentiable (a test takes its gradients)."""
    nll = sum(
        _nll_sum(
            hidden(params, tokens[row], cfg), params["ln_f"],
            params["lm_head"], targets[row], eps=cfg["rms_norm_eps"],
        )
        for row in range(tokens.shape[0])
    )
    return nll / (targets.shape[0] * targets.shape[1])


def loss(params, tokens, targets, cfg: dict) -> float:
    return float(np.asarray(loss_of(params, tokens, targets, cfg)))
