"""Plain reference for the ``nemotron_h`` configurations: the forward
pass and training loss of a Nemotron-H decoder in straightforward
``jax.numpy`` and float32.

No kernels, no chunked scan, no sort, no grouped matmul, no flax: the
layer equations of the family (the Nemotron-H report, arXiv:2504.03624;
Mamba-2: Dao and Gu 2024, arXiv:2405.21060), written against the
parameter tree the system under test trains (``wte``, ``block_<i>/norm``
and one of ``ssm/{in_proj, conv, conv_bias, A_log, D, dt_bias, norm,
out_proj}``, ``moe/{router, select_bias, experts_w_in, experts_w_out,
shared_up, shared_down}``, ``attn/{q_proj, k_proj, v_proj, o_proj}``,
then ``norm_f``, ``lm_head``).  It shares no code with ``dlrover_tpu``;
the norm, the embedding, the head, the loss and the gradient helper are
``sarvam_mla_reference.py``'s (``base``), as ``laguna_reference.py``
takes them.

Every layer is ``x + mixer(RMSNorm(x))``; the pattern's character
picks the mixer.

``M``: the recurrence runs TOKEN BY TOKEN, a ``lax.scan`` of ``seq``
steps over a float32 state ``[H, P, N]`` (held as ``[G, H / G, P, N]``:
a group's heads read one ``B`` and ``C``) that starts at zero: ``S <-
exp(dt A) S + (dt x) B^T``, ``y = S C + D x``.  (The scan is cut into
segments of ``SEGMENT`` steps, each a ``jax.checkpoint``, so that a
gradient keeps a state a segment and not a state a token; the values
are the same.)  The gate comes BEFORE the grouped RMS norm.

``E``: sigmoid scores in float32; the top-k of ``score + bias`` are
chosen and weighted by ``scale x score / (sum of the chosen scores +
1e-20)``.  This chip holds experts ``[first, first + held)`` of the
router's outputs: EVERY held expert is computed on EVERY row and kept
under the weight, which is zero where the expert was not chosen; what
the other experts would add is left out, as in the program, and the
shared expert is added whole.  An expert is ``relu(x W_up) ** 2
W_down``: no gate matrix.

``*``: grouped-query attention with a materialised causal mask and no
positional term.

Everything that is a function of a row alone (the scores of a query
row, the experts, the head and its loss) is taken ``ROWS`` rows at a
time, and each block and each such pass is a ``jax.checkpoint``: the
float32 temporaries of the forward pass and of its GRADIENT
(``gradients``: what ``correct`` compares the program's first gradient
with) fit beside the train state.  The parameters arrive in the type
they are served in (bf16) and are up-cast to float32 INSIDE each
jitted piece; one sequence is run at a time.  On a TPU a float32
matmul runs in lower precision unless
``default_matmul_precision("highest")`` is set; every piece sets it.
Loss: mean next-token cross entropy over the vocabulary slice, alone.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

import loader

base = loader.load_module("models", "sarvam_mla_reference")
F32 = jnp.float32
# rows a pass takes: 32 heads x 256 x 8192 float32 scores are 0.27 GB
ROWS = 256
# steps of the recurrence between two kept states
SEGMENT = 128


def _by_rows(fn, x, *more):
    """``fn(rows of x, rows of each of more)`` over blocks of ``ROWS``
    rows, the results stacked by block; each pass a checkpoint, so a
    gradient keeps no pass's temporaries."""
    rows = min(ROWS, x.shape[0])
    if x.shape[0] % rows:
        raise ValueError(f"{x.shape[0]} rows do not divide into {rows}")

    def blocks(a):
        return a.reshape((a.shape[0] // rows, rows) + a.shape[1:])

    return jax.lax.map(
        lambda xs: jax.checkpoint(fn)(*xs), (blocks(x),) + tuple(
            blocks(a) for a in more
        ),
    )


def _causal_conv(x, taps, bias):
    """Depthwise, ``x [seq, c]``, ``taps [K, c]``: output ``t`` sees
    inputs ``t - K + 1 .. t``."""
    k, seq = taps.shape[0], x.shape[0]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(
        padded[j:j + seq] * taps[j].astype(F32) for j in range(k)
    ) + bias.astype(F32)


def _recurrence(x, dt, A, B, C):
    """``y [seq, G, R, P]`` and the final state of ``S_t = exp(dt_t A)
    S_t-1 + (dt_t x_t) B_t^T``, ``y_t = S_t C_t``, one token a step;
    ``x [seq, G, R, P]`` and ``dt [seq, G, R]`` by group and head of
    the group, ``A [G, R]``, ``B``, ``C`` ``[seq, G, N]`` (a group's
    heads read the same)."""
    seq = x.shape[0]

    def token(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (
            jnp.exp(dt_t * A)[..., None, None] * state
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        )
        return state, jnp.sum(state * c_t[:, None, None, :], axis=-1)

    def segment(state, ats):
        return jax.lax.scan(token, state, ats)

    # a tail that does not fill a segment: dt = 0 leaves the state be
    pad = -seq % SEGMENT
    ats = tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (-1, SEGMENT) + a.shape[1:]
        ) for a in (x, dt, B, C)
    )
    state, y = jax.lax.scan(
        jax.checkpoint(segment),
        jnp.zeros(x.shape[1:] + B.shape[-1:], F32), ats,
    )
    return y.reshape((seq + pad,) + x.shape[1:])[:seq], state


def _mamba(u, p, *, dims, eps):
    """``dims = (heads, head size, groups, state size)``; returns the
    mixer's output and its final state."""
    heads, hp, groups, n = dims
    seq = u.shape[0]
    inner, bc = heads * hp, groups * n
    zxbcdt = u @ p["in_proj"]["kernel"].astype(F32)
    z = zxbcdt[:, :inner]
    xbc = jax.nn.silu(_causal_conv(
        zxbcdt[:, inner:2 * inner + 2 * bc], p["conv"], p["conv_bias"]
    ))
    dt = jax.nn.softplus(
        zxbcdt[:, 2 * inner + 2 * bc:] + p["dt_bias"].astype(F32)
    )
    # head h is head h % (heads / groups) of group h // (heads / groups)
    by_group = (seq, groups, heads // groups)
    x = xbc[:, :inner].reshape(by_group + (hp,))
    y, state = _recurrence(
        x, dt.reshape(by_group),
        -jnp.exp(p["A_log"].astype(F32)).reshape(by_group[1:]),
        xbc[:, inner:inner + bc].reshape(seq, groups, n),
        xbc[:, inner + bc:].reshape(seq, groups, n),
    )
    y = y + p["D"].astype(F32).reshape(by_group[1:])[..., None] * x
    # the gate, then one RMS a group of inner / groups channels
    y = (y.reshape(seq, inner) * jax.nn.silu(z)).reshape(
        seq, groups, inner // groups
    )
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(seq, inner) * p["norm"].astype(F32)
    return y @ p["out_proj"]["kernel"].astype(F32), state


def _attention(x, p, *, dims):
    """``dims = (heads, kv heads, head size)``: no positional term."""
    heads, kv, d = dims
    seq = x.shape[0]
    kernel = lambda name: p[name]["kernel"].astype(F32)  # noqa: E731
    q = (x @ kernel("q_proj")).reshape(seq, kv, heads // kv, d)
    k = (x @ kernel("k_proj")).reshape(seq, kv, d)
    v = (x @ kernel("v_proj")).reshape(seq, kv, d)

    def some_rows(mine, position):
        # mine [rows, kv, group, d]: query head (j, g) reads kv head j
        scores = jnp.einsum("rjgd,sjd->jgrs", mine, k) * d ** -0.5
        causal = position[:, None] >= jnp.arange(seq)[None, :]
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum(
            "jgrs,sjd->rjgd", jax.nn.softmax(scores, axis=-1), v
        )

    out = _by_rows(some_rows, q, jnp.arange(seq))
    return out.reshape(seq, heads * d) @ kernel("o_proj")


def _relu2_mlp(x, up, down):
    return jnp.square(jax.nn.relu(x @ up.astype(F32))) @ down.astype(F32)


def _experts(x, p, *, top_k, first, scale):
    """``(out, counts [router outputs])``: the shared expert and the
    held experts' part of the routed sum."""
    scores = jax.nn.sigmoid(x @ p["router"].astype(F32))
    _, ids = jax.lax.top_k(scores + p["select_bias"].astype(F32), top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = scale * chosen / (
        chosen.sum(axis=-1, keepdims=True) + 1e-20
    )
    outputs = scores.shape[-1]
    # [rows, router outputs]: the weight where the expert is chosen
    weight = jnp.sum(
        weights[:, :, None] * (ids[:, :, None] == jnp.arange(outputs)),
        axis=1,
    )
    mask = jnp.sum(ids[:, :, None] == jnp.arange(outputs), axis=1)
    held = p["experts_w_in"].shape[0]

    def one(out, xs):
        # every held expert on every row; zero where it was not chosen
        w_up, w_down, w = xs
        return out + _relu2_mlp(x, w_up, w_down) * w[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_w_in"], p["experts_w_out"],
        weight.T[first:first + held],
    ))
    out = out + _relu2_mlp(
        x, p["shared_up"]["kernel"], p["shared_down"]["kernel"]
    )
    return out, mask.sum(axis=0).astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "ssm_dims", "attn_dims", "eps", "top_k", "first", "scale",
))
def _block(x, p, *, ssm_dims, attn_dims, eps, top_k, first, scale):
    """One layer on one sequence ``[seq, h]``: ``(y, what the mixer
    says of itself)``: a state-space mixer its final state's root mean
    square, an expert layer its assignment counts, attention None."""

    def block(x, p):
        h = base._rms_norm(x, p["norm"]["scale"], eps)
        if "ssm" in p:
            out, state = _mamba(h, p["ssm"], dims=ssm_dims, eps=eps)
            said = jnp.sqrt(jnp.mean(state * state))
        elif "moe" in p:
            out, counts = _by_rows(functools.partial(
                _experts, p=p["moe"], top_k=top_k, first=first,
                scale=scale,
            ), h)
            out, said = out.reshape(x.shape), counts.sum(axis=0)
        else:
            out, said = _attention(h, p["attn"], dims=attn_dims), None
        return x + out, said

    with jax.default_matmul_precision("highest"):
        return jax.checkpoint(block)(x, p)


def block_kwargs(cfg: dict) -> dict:
    return dict(
        ssm_dims=(
            cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["n_groups"], cfg["ssm_state_size"],
        ),
        attn_dims=(
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"],
        ),
        eps=cfg["layer_norm_epsilon"],
        top_k=cfg["num_experts_per_tok"],
        first=cfg["first_expert_held"],
        scale=cfg["routed_scaling_factor"],
    )


def _hidden(params, tokens, cfg: dict):
    """``(the last layer's output [seq, h], the state-space layers'
    final-state rms, per expert layer the assignments to each of the
    router's outputs)`` of one sequence."""
    rms, counts = [], []
    x = base._embed(params["wte"]["embedding"], tokens)
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        x, said = _block(x, params[f"block_{i}"], **block_kwargs(cfg))
        if kind == "M":
            rms.append(said)
        elif kind == "E":
            counts.append(said)
    return x, rms, counts


def forward(params, tokens, cfg: dict):
    """Per sequence the logits ``[seq, vocab]``, one sequence at a
    time."""
    return [
        base._head(
            _hidden(params, row, cfg)[0], params["norm_f"],
            params["lm_head"], eps=cfg["layer_norm_epsilon"],
        ) for row in tokens
    ]


def loss_and_said(params, tokens, targets, cfg: dict):
    """``(the training loss, {"counts" [expert layers, router
    outputs], "state_rms" [sequences, state-space layers]})``,
    differentiable; the float32 logits live ``ROWS`` rows at a
    time."""
    nll, rms, counts = [], [], []
    for row, wanted in zip(tokens, targets):
        x, r, n = _hidden(params, row, cfg)
        rms.append(jnp.stack(r))
        counts.append(n)
        nll.append(_by_rows(
            lambda rows, t: base._nll_sum(base._head(
                rows, params["norm_f"], params["lm_head"],
                eps=cfg["layer_norm_epsilon"],
            ), t), x, wanted,
        ).sum())
    return sum(nll) / targets.size, {
        "counts": jnp.stack([sum(n) for n in zip(*counts)]),
        "state_rms": jnp.stack(rms),
    }


def loss(params, tokens, targets, cfg: dict) -> float:
    return float(np.asarray(
        loss_and_said(params, tokens, targets, cfg)[0]
    ))


def gradients(params, tokens, targets, cfg: dict, pick):
    """``(loss, said, {path: gradient})`` of the reference for the
    leaves ``pick`` names (``base.gradients_of``)."""
    return base.gradients_of(
        lambda p, x, y: loss_and_said(p, x, y, cfg), pick, params,
        tokens, targets,
    )
