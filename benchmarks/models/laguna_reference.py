"""Plain reference for the ``laguna`` configurations: the forward pass
and training loss of a decoder with window and full attention layers
mixed, a per-head output gate and softmax-routed experts, in
straightforward ``jax.numpy`` and float32.

No kernels, no sort, no grouped matmul, no flax: the layer equations
that ``model_type: "laguna"`` names (the configuration's ``assumed``
says what the published config leaves open and how it is set), written
against the parameter tree the system under test trains (``wte``,
``block_<i>/{ln_attn, attn/{q_proj, k_proj, v_proj, g_proj, o_proj},
ln_mlp}``, then ``mlp/{gate_proj, up_proj, down_proj}`` in a dense
block and ``moe/{router, experts_w_gate, experts_w_in, experts_w_out,
shared_gate, shared_up, shared_down}`` in a sparse one, ``ln_f``,
``lm_head``).  It shares no code with ``dlrover_tpu``; what is not
this family's own (the norm, SwiGLU, ``rotate_half`` rope, yarn's
frequencies, the head, the row-block passes' checkpoints and the
picked-leaf gradients) is the ``sarvam_mla`` reference's, beside this
file.

Attention, layer ``l``: ``H = num_attention_heads_per_layer[l]`` query
heads over ``num_key_value_heads`` kv heads (query head ``j`` reads kv
head ``j // (H / kv)``), a MATERIALISED mask ``[rows, seq]``: key ``t``
is visible to query ``i`` iff ``t <= i`` and, in a sliding layer, ``t >
i - sliding_window``.  Rope by the layer's kind
(``rope_parameters[kind]``): the first ``partial_rotary_factor x
head_dim`` lanes of every q and k head rotate (``rotate_half``
pairing), with yarn's frequencies and cos and sin times
``attention_factor`` where the kind says yarn, the rest pass through.
Each head's output is multiplied by ``sigmoid(x W_g)`` of the block's
normed input before the output projection.

Experts: softmax over ALL the router's outputs in float32; the top-k
are weighted by ``scale x p / (sum of the chosen p)``.  This chip holds
experts ``[first, first + held)``: EVERY held expert is computed on
EVERY row and kept under the weight where it was chosen; what the
other experts would add is left out, as in the program, and the shared
expert is added whole.  Loss: mean next-token cross entropy over the
vocabulary slice, alone.

Scores are taken ``ATTN_ROWS`` query rows at a time (72 heads x 256 x
8192 float32 scores are 0.6 GB), everything else that is a function of
a row alone ``ROWS`` at a time; each block and each such pass is a
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
ATTN_ROWS = 256
SLIDING = "sliding_attention"


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


def inv_freq(head_dim: int, rule: dict) -> np.ndarray:
    """The rotated lanes' frequencies of one layer kind: ``dim =
    partial_rotary_factor x head_dim`` lanes, ``dim / 2`` pairs."""
    dim = int(head_dim * rule["partial_rotary_factor"])
    theta = float(rule["rope_theta"])
    if rule["rope_type"] == "default":
        return theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rule["rope_type"] != "yarn":
        raise ValueError(f"no rope rule {rule['rope_type']!r}")
    return base.yarn_inv_freq(dim, theta, rule)


def _attention(x, p, *, heads, kv, d, window, rule):
    """``rule``: the layer kind's ``rope_parameters`` entry, hashable
    (sorted items); ``window`` None in a full layer."""
    rule = dict(rule)
    seq, _ = x.shape
    kernel = lambda name: p[name]["kernel"].astype(F32)  # noqa: E731
    freq = jnp.asarray(inv_freq(d, rule), F32)
    factor = rule.get("attention_factor", 1.0)
    rotated = 2 * freq.shape[0]

    def heads_of(name, n):
        t = (x @ kernel(name)).reshape(seq, n, d).transpose(1, 0, 2)
        return t                                     # [n, seq, d]

    def rotate(t):
        return jnp.concatenate([
            base._rotary(t[..., :rotated], freq, factor),
            t[..., rotated:],
        ], axis=-1)

    q = rotate(heads_of("q_proj", heads))
    k = rotate(heads_of("k_proj", kv))
    v = heads_of("v_proj", kv)
    group = heads // kv

    def some_rows(mine, position):
        # mine [rows, H, d], position [rows]
        mine = mine.reshape(mine.shape[0], kv, group, d)
        scores = jnp.einsum("rkgd,ksd->kgrs", mine, k) * d ** -0.5
        key = jnp.arange(seq)[None, :]
        seen = key <= position[:, None]
        if window is not None:
            seen = seen & (key > position[:, None] - window)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum(
            "kgrs,ksd->rkgd", jax.nn.softmax(scores, axis=-1), v
        ).reshape(mine.shape[0], heads, d)

    out = _by_rows(
        some_rows, ATTN_ROWS, q.transpose(1, 0, 2), jnp.arange(seq)
    ).reshape(seq, heads, d)
    gate = jax.nn.sigmoid(x @ kernel("g_proj"))      # [seq, H]
    out = out * gate[:, :, None]
    return out.reshape(seq, heads * d) @ kernel("o_proj")


def _experts(x, p, *, top_k, first, scale):
    """``(out, counts [router outputs])``: the shared expert and the
    held experts' part of the routed sum."""
    probs = jax.nn.softmax(x @ p["router"].astype(F32), axis=-1)
    # departure from HF in form only: ``lax.top_k`` for torch.topk
    chosen, ids = jax.lax.top_k(probs, top_k)
    weights = scale * chosen / chosen.sum(axis=-1, keepdims=True)
    outputs = probs.shape[-1]
    picked = ids[:, :, None] == jnp.arange(outputs)  # [rows, k, outputs]
    weight = jnp.sum(weights[:, :, None] * picked, axis=1)
    held = p["experts_w_gate"].shape[0]

    def one(out, xs):
        # every held expert on every row; kept where it was chosen
        w_gate, w_up, w_down, w = xs
        return out + base._swiglu(x, w_gate, w_up, w_down) * w[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_w_gate"], p["experts_w_in"], p["experts_w_out"],
        weight.T[first:first + held],
    ))
    out = out + base._swiglu(
        x, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
        p["shared_down"]["kernel"],
    )
    return out, picked.sum(axis=(0, 1)).astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv", "d", "window", "rule", "eps", "top_k", "first",
    "scale",
))
def _block(x, p, *, heads, kv, d, window, rule, eps, top_k, first, scale):
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
            a, p["attn"], heads=heads, kv=kv, d=d, window=window,
            rule=rule,
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


def block_kwargs(cfg: dict, layer: int) -> dict:
    kind = cfg["layer_types"][layer]
    return dict(
        heads=cfg["num_attention_heads_per_layer"][layer],
        kv=cfg["num_key_value_heads"], d=cfg["head_dim"],
        window=cfg["sliding_window"] if kind == SLIDING else None,
        rule=tuple(sorted(cfg["rope_parameters"][kind].items())),
        eps=cfg["rms_norm_eps"], top_k=cfg["num_experts_per_tok"],
        first=cfg["first_expert_held"],
        scale=cfg["moe_routed_scaling_factor"],
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
            eps=cfg["rms_norm_eps"],
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
                eps=cfg["rms_norm_eps"],
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
