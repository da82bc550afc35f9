"""Plain reference for the ``olmoe`` configurations: OLMoE's forward
pass and training loss in straightforward ``jax.numpy`` and float32.

No kernels, no sort, no grouped matmul, no remat, no chunking of the
loss, no flax: the equations of Muennighoff et al. 2024
(arXiv:2409.02060) as HF ``modeling_olmoe`` states them, written
against the parameter tree the system under test trains (``wte``,
``block_<i>/{ln_attn, attn/{q_proj, k_proj, v_proj, o_proj, q_norm,
k_norm}, ln_mlp, moe/{router, experts_w_gate, experts_w_in,
experts_w_out}}``, ``ln_f``, ``lm_head``).  It shares no code with
``dlrover_tpu``.

Per block: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``.
Attention: ``q = RMSNorm(W_q u)``, ``k = RMSNorm(W_k u)`` over the
whole projection before the split into heads, rotary embedding
(``rotate_half``), full ``[heads, seq, seq]`` causal softmax.  Experts:
a loop over ALL experts, each computed on EVERY row and kept where
the top-k mask says, weighted by the router's softmax probability as
it is (``norm_topk_prob: false``).  Loss: mean next-token cross
entropy + ``lb_weight`` x load balancing + ``z_weight`` x router
z-loss.

Memory: the parameters arrive in the type they are served in (bf16)
and are up-cast to float32 INSIDE each jitted piece, one block (and
one expert) at a time; one sequence is run at a time.

On a TPU a float32 matmul runs in lower precision unless
``default_matmul_precision("highest")`` is set; every piece sets it.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rotary(x, theta):
    """``x [heads, seq, d]``: HF's ``apply_rotary_pos_emb``."""
    _, seq, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    freqs = jnp.arange(seq, dtype=F32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


def _attention(x, p, *, n_head, eps, theta):
    seq, h = x.shape
    d = h // n_head
    kernel = lambda name: p[name]["kernel"].astype(F32)  # noqa: E731
    q = _rms_norm(x @ kernel("q_proj"), p["q_norm"]["scale"], eps)
    k = _rms_norm(x @ kernel("k_proj"), p["k_norm"]["scale"], eps)
    v = x @ kernel("v_proj")
    heads = lambda a: a.reshape(seq, n_head, d).transpose(1, 0, 2)  # noqa: E731
    q, k, v = _rotary(heads(q), theta), _rotary(heads(k), theta), heads(v)
    scores = q @ k.transpose(0, 2, 1) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ v
    return out.transpose(1, 0, 2).reshape(seq, h) @ kernel("o_proj")


def _experts(x, p, *, top_k):
    """``(out, counts [e], prob_sum [e], sum_t logsumexp ** 2)``."""
    logits = x @ p["router"].astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    # departure from HF in form only: ``lax.top_k`` for torch.topk
    gate, ids = jax.lax.top_k(probs, top_k)
    experts = probs.shape[-1]
    # [seq, e]: the probability where the expert is among the top k
    weight = jnp.sum(
        gate[:, :, None] * (ids[:, :, None] == jnp.arange(experts)),
        axis=1,
    )

    def one(out, xs):
        # every expert on every row; kept where the mask says
        w_gate, w_up, w_down, w = xs
        y = (
            jax.nn.silu(x @ w_gate.astype(F32)) * (x @ w_up.astype(F32))
        ) @ w_down.astype(F32)
        return out + y * w[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_w_gate"], p["experts_w_in"], p["experts_w_out"],
        weight.T,
    ))
    counts = jnp.sum(weight > 0, axis=0).astype(F32)
    z = jnp.sum(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return out, counts, probs.sum(axis=0), z


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(F32)


@functools.partial(
    jax.jit, static_argnames=("n_head", "eps", "theta", "top_k")
)
def _block(x, p, *, n_head, eps, theta, top_k):
    """One block on one sequence ``[seq, h]``; the router's stats."""
    with jax.default_matmul_precision("highest"):
        a = _rms_norm(x, p["ln_attn"]["scale"], eps)
        x = x + _attention(
            a, p["attn"], n_head=n_head, eps=eps, theta=theta
        )
        m = _rms_norm(x, p["ln_mlp"]["scale"], eps)
        out, counts, prob_sum, z = _experts(m, p["moe"], top_k=top_k)
        return x + out, (counts, prob_sum, z)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_f, lm_head, *, eps):
    """Final norm and the untied output head: ``[seq, vocab]``."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, ln_f["scale"], eps)
        return x @ lm_head["kernel"].astype(F32)


@jax.jit
def _nll_sum(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum()


def forward(params, tokens, cfg: dict, keep=lambda row, logits: logits):
    """``(per sequence: keep(row, logits [seq, vocab]), stats)``, one
    sequence at a time; ``stats`` = per layer ``(counts [e], prob_sum
    [e], sum of logsumexp ** 2)`` summed over the batch's sequences."""
    kw = dict(
        n_head=cfg["num_attention_heads"], eps=cfg["rms_norm_eps"],
        theta=float(cfg["rope_theta"]),
        top_k=cfg["num_experts_per_tok"],
    )
    layers = cfg["num_hidden_layers"]
    logits, stats = [], [None] * layers
    for row in range(tokens.shape[0]):
        x = _embed(params["wte"]["embedding"], tokens[row])
        for i in range(layers):
            x, s = _block(x, params[f"block_{i}"], **kw)
            stats[i] = s if stats[i] is None else jax.tree.map(
                jnp.add, stats[i], s
            )
        logits.append(keep(row, _head(
            x, params["ln_f"], params["lm_head"], eps=kw["eps"]
        )))
    return logits, stats


def loss_of(params, tokens, targets, cfg: dict):
    """The training loss, differentiable (a test takes its
    gradients); the float32 logits live one sequence at a time."""
    nll, stats = forward(
        params, tokens, cfg,
        keep=lambda row, logits: _nll_sum(logits, targets[row]),
    )
    tokens = targets.shape[0] * targets.shape[1]
    ce = sum(nll) / tokens
    experts = cfg["num_experts"]
    counts = sum(s[0] for s in stats)
    prob_sum = sum(s[1] for s in stats)
    rows = len(stats) * tokens
    # HF load_balancing_loss_func on the layers' logits concatenated:
    # E * sum_e (assignments to e / rows) * (mean probability of e);
    # the first factor sums to k
    lb = experts * jnp.sum((counts / rows) * (prob_sum / rows))
    # paper section 3: mean_t logsumexp(logits_t) ** 2, summed over
    # layers (HF's config carries no z-loss; OLMo's trainer does)
    z = sum(s[2] for s in stats) / tokens
    weights = cfg["recipe"]
    return (
        ce + weights["load_balancing_loss_weight"] * lb
        + weights["router_z_loss_weight"] * z
    )


def loss(params, tokens, targets, cfg: dict) -> float:
    return float(np.asarray(loss_of(params, tokens, targets, cfg)))
