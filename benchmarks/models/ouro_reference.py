"""Plain reference for the ``ouro`` configurations: the forward pass,
the expected-exit training loss and (by ``jax.grad`` of that plain
function) the gradients of a looped decoder, in straightforward
``jax.numpy`` and float32.

No kernels, no flax, no chunked or weighted head: the equations that
``model_type: "ouro"`` names (the configuration's ``assumed`` says
what the published config leaves open and how it is set), written
against the parameter tree the system under test trains (``wte``,
``block_<l>/{ln_attn, attn/{q_proj, k_proj, v_proj, o_proj},
ln_attn_out, ln_mlp, mlp/{gate_proj, up_proj, down_proj},
ln_mlp_out}``, ``ln_f``, ``exit_gate/{kernel, bias}``, ``lm_head``).
It shares no code with ``dlrover_tpu``; the one thing it borrows is the
``sarvam_mla`` reference's ``gradients_of`` (which leaves of a tree a
gradient is taken for: no model's arithmetic).

With ``L`` layers and ``R = total_ut_steps`` passes::

    h = E[tokens]
    for t in 1..R:
        for l in 1..L:
            a = h + N2(Attn(N1(h)));  h = a + N4(SwiGLU(N3(a)))
        h = N_f(h);  x_t = h;  lam_t = sigmoid(x_t . w_g + b_g)
    p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j);  p_R = prod_{j<R}(1 - lam_j)
    nll_t = -log softmax(x_t W_head)[target]
    loss = mean over tokens of [sum_t p_t nll_t + beta sum_t p_t log p_t]

The ``R`` passes are a ``lax.scan`` over the ``L`` blocks written out:
the same arithmetic as two loops, a quarter of the program to trace,
lower and load (48 applications written out took the harness 100 s a
run).  Which weights pass ``t`` uses at layer ``l`` is an ARGUMENT: the
model gives every pass the same blocks (the scan closes over them, and
only the leaves a gradient is asked for get an accumulator); the tie's
test gives each of the ``R x L`` applications a copy of its own
(:func:`copies_of`: ``[R, ...]`` a leaf, the scan's ``xs``) and adds
the copies' gradients up.

Attention has a MATERIALISED causal mask and takes ``ATTN_ROWS`` query
rows at a time (16 heads x 256 x 4096 float32 scores are 67 MB), the
feed-forward and each exit's logits ``ROWS`` rows at a time; each block
and each such pass is a ``jax.checkpoint``, as in the other families'
references: the values are the same, and the float32 temporaries of
the gradient fit beside the train state (without it one block keeps 1
GB of attention probabilities, 48 applications 48).  Everything is
traced under ``default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import xlogy

import loader

gradients_of = loader.load_module(
    "models", "sarvam_mla_reference"
).gradients_of

F32 = jnp.float32
ATTN_ROWS = 256
ROWS = 1024


def _by_rows(fn, rows, x, *more):
    """``fn(rows of x, rows of each of more)`` over blocks of ``rows``
    rows, stacked by block; each pass a checkpoint."""
    rows = min(rows, x.shape[0])
    if x.shape[0] % rows:
        raise ValueError(f"{x.shape[0]} rows do not divide into {rows}")

    def blocks(a):
        return a.reshape((a.shape[0] // rows, rows) + a.shape[1:])

    return jax.lax.map(
        lambda xs: jax.checkpoint(fn)(*xs),
        (blocks(x),) + tuple(blocks(a) for a in more),
    )


def _rms_norm(x, p, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rope(x, theta):
    """``x [heads, seq, d]``: ``x cos + rotate_half(x) sin`` on every
    lane, positions ``0 .. seq - 1``."""
    seq, d = x.shape[-2:]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angles = jnp.arange(seq, dtype=F32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([angles, angles], axis=-1)
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


def _attention(x, p, *, heads, d, theta):
    seq = x.shape[0]

    def heads_of(name):
        t = x @ p[name]["kernel"].astype(F32)
        return t.reshape(seq, heads, d).transpose(1, 0, 2)   # [H, seq, d]

    q = _rope(heads_of("q_proj"), theta)
    k = _rope(heads_of("k_proj"), theta)
    v = heads_of("v_proj")

    def some_rows(mine, position):
        # mine [rows, H, d], position [rows]
        scores = jnp.einsum("rhd,hsd->hrs", mine, k) * d ** -0.5
        seen = jnp.arange(seq)[None, :] <= position[:, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum(
            "hrs,hsd->rhd", jax.nn.softmax(scores, axis=-1), v
        )

    out = _by_rows(
        some_rows, ATTN_ROWS, q.transpose(1, 0, 2), jnp.arange(seq)
    )
    return out.reshape(seq, heads * d) @ p["o_proj"]["kernel"].astype(F32)


def _swiglu(x, p):
    gate = x @ p["gate_proj"]["kernel"].astype(F32)
    up = x @ p["up_proj"]["kernel"].astype(F32)
    return (jax.nn.silu(gate) * up) @ p["down_proj"]["kernel"].astype(F32)


def _block(x, p, cfg):
    """One application of one block on one sequence ``[seq, h]``."""
    eps = cfg["rms_norm_eps"]
    a = x + _rms_norm(_attention(
        _rms_norm(x, p["ln_attn"], eps), p["attn"],
        heads=cfg["num_attention_heads"], d=cfg["head_dim"],
        theta=float(cfg["rope_theta"]),
    ), p["ln_attn_out"], eps)
    out = _by_rows(
        lambda rows: _swiglu(rows, p["mlp"]), ROWS,
        _rms_norm(a, p["ln_mlp"], eps),
    ).reshape(a.shape)
    return a + _rms_norm(out, p["ln_mlp_out"], eps)


def _exit_nll(x, lm_head, targets):
    """One exit's cross entropy, a token: ``[seq]``."""

    def some_rows(rows, wanted):
        logp = jax.nn.log_softmax(
            rows @ lm_head["kernel"].astype(F32), axis=-1
        )
        return -jnp.take_along_axis(logp, wanted[:, None], axis=-1)[:, 0]

    return _by_rows(some_rows, ROWS, x, targets).reshape(-1)


def exit_distribution(lams):
    """``p [R, seq]`` from ``lam_1 .. lam_{R-1}`` (``R`` > 1)."""
    p, left = [], 1.0
    for lam in lams:
        p.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(p + [left])


def blocks_of(params, cfg):
    """The model's ``L`` blocks, in order."""
    return [params[f"block_{i}"] for i in range(cfg["num_hidden_layers"])]


def copies_of(params, cfg):
    """``R`` independent, equal copies of the model's blocks: a list of
    ``L`` blocks whose leaves are ``[R, ...]``, pass ``t``'s copy at
    ``[t]``."""
    return jax.tree.map(
        lambda leaf: jnp.broadcast_to(
            leaf, (cfg["total_ut_steps"],) + leaf.shape
        ), blocks_of(params, cfg),
    )


def exits_of(params, tokens, cfg, copies=None):
    """Of one sequence: ``(x_1 .. x_R [R, seq, h], p [R, seq])``; every
    pass over the model's own blocks, or pass ``t`` over
    ``copies[...][t]``."""
    eps, passes = cfg["rms_norm_eps"], cfg["total_ut_steps"]

    def one_pass(x, blocks):
        for p in blocks:
            x = jax.checkpoint(lambda x, p: _block(x, p, cfg))(x, p)
        x = _rms_norm(x, params["ln_f"], eps)
        if passes == 1:
            return x, (x, None)
        gate = params["exit_gate"]
        z = x @ gate["kernel"].astype(F32)[:, 0] + gate["bias"].astype(F32)
        return x, (x, jax.nn.sigmoid(z))

    x = params["wte"]["embedding"][tokens].astype(F32)
    if copies is None:
        tied = blocks_of(params, cfg)
        _, (exits, lams) = jax.lax.scan(
            lambda x, _: one_pass(x, tied), x, None, length=passes
        )
    else:
        _, (exits, lams) = jax.lax.scan(one_pass, x, copies)
    if passes == 1:
        return exits, jnp.ones((1,) + tokens.shape, F32)
    # lam_R enters nothing: the last exit takes what is left
    return exits, exit_distribution(list(lams[:-1]))


def loss_and_aux(params, tokens, targets, cfg, copies=None):
    """``(the training loss, aux)``, differentiable; ``aux`` has the
    system's four counters under their names."""
    beta = cfg["recipe"]["entropy_weight"]

    def sequence(row):
        exits, p = exits_of(params, row[0], cfg, copies)
        return p, jax.lax.map(
            lambda x: _exit_nll(x, params["lm_head"], row[1]), exits
        )

    with jax.default_matmul_precision("highest"):
        p, nll = jax.lax.map(sequence, (tokens, targets))  # [b, R, seq]
        entropy = -jnp.sum(xlogy(p, p), axis=1)
        loss = jnp.mean(jnp.sum(p * nll, axis=1) - beta * entropy)
        exit_at = jnp.arange(1, p.shape[1] + 1, dtype=F32)
        return loss, {
            "loop.expected_exit": jnp.mean(
                jnp.sum(exit_at[None, :, None] * p, axis=1)
            ),
            "loop.exit_entropy": jnp.mean(entropy),
            "loop.nll_first": jnp.mean(nll[:, 0]),
            "loop.nll_last": jnp.mean(nll[:, -1]),
        }


def loss(params, tokens, targets, cfg) -> float:
    return float(np.asarray(loss_and_aux(params, tokens, targets, cfg)[0]))


def exit_logits(params, tokens, cfg):
    """Every exit's logits ``[R, b, seq, vocab]`` and the exit
    distribution ``[R, b, seq]``, for the tests."""
    with jax.default_matmul_precision("highest"):
        exits, p = jax.lax.map(
            lambda row: exits_of(params, row, cfg), tokens
        )
        logits = exits @ params["lm_head"]["kernel"].astype(F32)
    return jnp.moveaxis(logits, 0, 1), jnp.moveaxis(p, 0, 1)


def gradients(params, tokens, targets, cfg, pick):
    """``(loss, aux, {path: gradient})`` of the reference for the
    leaves ``pick`` names."""
    return gradients_of(
        lambda p, x, y: loss_and_aux(p, x, y, cfg), pick, params,
        tokens, targets,
    )
