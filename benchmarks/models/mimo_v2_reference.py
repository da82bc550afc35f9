"""Plain reference for the ``mimo_v2`` configurations: the forward pass
and training loss of a decoder whose window layers' softmax has a
learned sink, with heads of 192 | 128 over kv heads that differ by
layer kind, one fused q | k | v projection and sigmoid-routed experts
with no shared one, in straightforward ``jax.numpy`` and float32.

No kernels, no sort, no grouped matmul, no flax: the layer equations
that ``model_type: "mimo_v2"`` names (the configuration's ``assumed``
says what the published config leaves open and how it is set), written
against the parameter tree the system under test trains (``wte``,
``block_<i>/{ln_attn, attn/{qkv_proj, o_proj, sink}, ln_mlp}``, then
``mlp/{gate_proj, up_proj, down_proj}`` in a dense block and
``moe/{router, select_bias, experts_w_gate, experts_w_in,
experts_w_out}`` in a sparse one, ``ln_f``, ``lm_head``).  It shares
no code with ``dlrover_tpu``; what is not this family's own (the norm,
SwiGLU, ``rotate_half`` rope, the head, the bias's rule and the
picked-leaf gradients) is the ``sarvam_mla`` reference's, beside this
file.

Attention, layer ``l`` of kind ``hybrid_layer_pattern[l]`` (0 full, 1
window): ``[q | k | v] = x W_qkv`` with ``H`` query heads of
``head_dim``, ``G`` kv heads of ``head_dim`` and ``G`` of
``v_head_dim`` (``H``, ``G`` from the kind's keys); the kv heads are
REPEATED ``H / G`` times (query head ``h`` reads kv head ``h // (H /
G)``); ``v`` is multiplied by ``attention_value_scale``; the first
``int(partial_rotary_factor x head_dim)`` lanes of every q and k head
rotate (``rotate_half`` pairing, the kind's theta), the rest pass
through.  A MATERIALISED mask ``[rows, seq]``: key ``t`` is visible to
query ``i`` iff ``t <= i`` and, in a window layer, ``t > i -
sliding_window``.  Where the kind's ``add_*_attention_sink_bias`` is
true the head's learned ``sink`` is appended to every row's scores as
ONE MORE COLUMN, the softmax is taken over ``seq + 1`` columns, and the
column is dropped before the product with ``v``.

Experts: sigmoid scores in float32; the top-k of ``score + bias`` are
chosen and weighted by ``scale x score / (sum of the chosen scores +
1e-20)``.  This chip holds experts ``[first, first + held)`` of the
router's outputs: EVERY held expert is computed on EVERY row and kept
under the weight, which is zero where the expert was not chosen; what
the other experts would add is left out, as in the program, and there
is no shared expert: a row none of whose choices is held gets exactly
zero from the layer.  Loss: mean next-token cross entropy over the
vocabulary slice, alone.

Scores are taken ``ATTN_ROWS`` query rows at a time (16 heads x 512 x
8193 float32 scores are 0.27 GB), everything else that is a function
of a row alone ``ROWS`` at a time; each block and each such pass is a
``jax.checkpoint``.  Every jitted piece sets
``default_matmul_precision("highest")``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

import loader

base = loader.load_module("models", "sarvam_mla_reference")

F32 = jnp.float32
ROWS = base.ROWS
ATTN_ROWS = 512
WINDOW = 1


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


def _attention(
    x, p, *, heads, kv, d, dv, window, theta, rotated, value_scale,
    sinked,
):
    """One sequence ``[seq, h]``; ``window`` None in a full layer,
    ``rotated`` the lanes of a head that rotate."""
    seq, _ = x.shape
    qkv = x @ p["qkv_proj"]["kernel"].astype(F32)
    q, k, v = jnp.split(qkv, (heads * d, (heads + kv) * d), axis=-1)
    freq = jnp.asarray(
        theta ** (-np.arange(0, rotated, 2, dtype=np.float64) / rotated),
        F32,
    )

    def heads_of(t, n, width):
        return t.reshape(seq, n, width).transpose(1, 0, 2)  # [n, seq, w]

    def rotate(t):
        return jnp.concatenate([
            base._rotary(t[..., :rotated], freq, 1.0), t[..., rotated:],
        ], axis=-1)

    q = rotate(heads_of(q, heads, d))
    # the kv heads repeated: query head h reads kv head h // group
    k = jnp.repeat(rotate(heads_of(k, kv, d)), heads // kv, axis=0)
    v = jnp.repeat(
        value_scale * heads_of(v, kv, dv), heads // kv, axis=0
    )

    def some_rows(mine, position):
        # mine [rows, H, d], position [rows]
        scores = jnp.einsum("rhd,hsd->hrs", mine, k) * d ** -0.5
        key = jnp.arange(seq)[None, :]
        seen = key <= position[:, None]
        if window is not None:
            seen = seen & (key > position[:, None] - window)
        scores = jnp.where(seen[None], scores, -jnp.inf)
        if sinked:
            # the sink: one more column, dropped after the softmax
            column = jnp.broadcast_to(
                p["sink"].astype(F32)[:, None, None],
                scores.shape[:2] + (1,),
            )
            scores = jnp.concatenate([scores, column], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1)[..., :seq]
        return jnp.einsum("hrs,hsd->rhd", probs, v)

    out = _by_rows(
        some_rows, ATTN_ROWS, q.transpose(1, 0, 2), jnp.arange(seq)
    ).reshape(seq, heads * dv)
    return out @ p["o_proj"]["kernel"].astype(F32)


def _experts(x, p, *, top_k, first, scale):
    """``(out, counts [router outputs])``: the held experts' part of
    the routed sum, and nothing beside it."""
    scores = jax.nn.sigmoid(x @ p["router"].astype(F32))
    # departure from HF in form only: ``lax.top_k`` for torch.topk
    _, ids = jax.lax.top_k(scores + p["select_bias"].astype(F32), top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = scale * chosen / (
        chosen.sum(axis=-1, keepdims=True) + 1e-20
    )
    outputs = scores.shape[-1]
    picked = ids[:, :, None] == jnp.arange(outputs)  # [rows, k, outputs]
    # [rows, router outputs]: the weight where chosen, else zero
    weight = jnp.sum(weights[:, :, None] * picked, axis=1)
    held = p["experts_w_gate"].shape[0]

    def one(out, xs):
        # every held expert on every row, under its weight
        w_gate, w_up, w_down, w = xs
        return out + base._swiglu(x, w_gate, w_up, w_down) * w[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_w_gate"], p["experts_w_in"], p["experts_w_out"],
        weight.T[first:first + held],
    ))
    return out, picked.sum(axis=(0, 1)).astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv", "d", "dv", "window", "theta", "rotated",
    "value_scale", "sinked", "eps", "top_k", "first", "scale",
))
def _block(
    x, p, *, heads, kv, d, dv, window, theta, rotated, value_scale,
    sinked, eps, top_k, first, scale,
):
    """One block on one sequence ``[seq, h]``; a sparse block's
    assignment counts, a dense block's None."""

    def feed_forward(m):
        if "mlp" in p:
            mlp = p["mlp"]
            return base._swiglu(
                m, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"],
                mlp["down_proj"]["kernel"],
            ), None
        return _experts(
            m, p["moe"], top_k=top_k, first=first, scale=scale
        )

    def block(x, p):
        a = base._rms_norm(x, p["ln_attn"]["scale"], eps)
        x = x + _attention(
            a, p["attn"], heads=heads, kv=kv, d=d, dv=dv, window=window,
            theta=theta, rotated=rotated, value_scale=value_scale,
            sinked=sinked,
        )
        out, counts = _by_rows(
            feed_forward, ROWS,
            base._rms_norm(x, p["ln_mlp"]["scale"], eps),
        )
        if counts is not None:
            counts = counts.sum(axis=0)
        return x + out.reshape(x.shape), counts

    with jax.default_matmul_precision("highest"):
        return jax.checkpoint(block)(x, p)


def routed_scale(cfg: dict) -> float:
    """``routed_scaling_factor``; null reads 1.0 (``assumed``)."""
    scale = cfg["routed_scaling_factor"]
    return 1.0 if scale is None else float(scale)


def block_kwargs(cfg: dict, layer: int) -> dict:
    window = cfg["hybrid_layer_pattern"][layer] == WINDOW
    own = "swa_" if window else ""
    d = cfg[own + "head_dim"]
    return dict(
        heads=cfg[own + "num_attention_heads"],
        kv=cfg[own + "num_key_value_heads"], d=d,
        dv=cfg[own + "v_head_dim"],
        window=cfg["sliding_window"] if window else None,
        theta=float(cfg[own + "rope_theta"]),
        rotated=int(cfg["partial_rotary_factor"] * d),
        value_scale=float(cfg["attention_value_scale"]),
        sinked=bool(cfg[
            "add_swa_attention_sink_bias" if window
            else "add_full_attention_sink_bias"
        ]),
        eps=cfg["layernorm_epsilon"], top_k=cfg["num_experts_per_tok"],
        first=cfg["first_expert_held"], scale=routed_scale(cfg),
    )


def _hidden(params, tokens, cfg: dict):
    """``(the last block's output [seq, h], per sparse layer the
    assignments to each of the router's outputs)`` of one sequence."""
    counts = []
    x = base._embed(params["wte"]["embedding"], tokens)
    for i in range(cfg["num_hidden_layers"]):
        x, n = _block(x, params[f"block_{i}"], **block_kwargs(cfg, i))
        if n is not None:
            counts.append(n)
    return x, counts


def forward(params, tokens, cfg: dict):
    """``(per sequence the logits [seq, vocab], counts)``, one
    sequence at a time."""
    logits, counts = [], []
    for row in tokens:
        x, n = _hidden(params, row, cfg)
        counts.append(n)
        logits.append(base._head(
            x, params["ln_f"], params["lm_head"],
            eps=cfg["layernorm_epsilon"],
        ))
    return logits, [sum(n) for n in zip(*counts)]


def loss_and_counts(params, tokens, targets, cfg: dict):
    """``(the training loss, counts [sparse layers, router
    outputs])``, differentiable; the float32 logits live ``ROWS`` rows
    at a time."""
    nll, counts = [], []
    for row, wanted in zip(tokens, targets):
        x, n = _hidden(params, row, cfg)
        counts.append(n)
        nll.append(_by_rows(
            lambda rows, t: base._nll_sum(base._head(
                rows, params["ln_f"], params["lm_head"],
                eps=cfg["layernorm_epsilon"],
            ), t), ROWS, x, wanted,
        ).sum())
    return sum(nll) / targets.size, jnp.stack(
        [sum(n) for n in zip(*counts)]
    )


def loss(params, tokens, targets, cfg: dict) -> float:
    return float(np.asarray(
        loss_and_counts(params, tokens, targets, cfg)[0]
    ))


def gradients(params, tokens, targets, cfg: dict, pick):
    """``(loss, counts, {path: gradient})`` of the reference for the
    leaves ``pick`` names (``gradients_of`` of the ``sarvam_mla``
    reference)."""
    return base.gradients_of(
        lambda p, x, y: loss_and_counts(p, x, y, cfg), pick, params,
        tokens, targets,
    )
