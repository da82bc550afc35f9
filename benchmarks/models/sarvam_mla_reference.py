"""Plain reference for the ``sarvam_mla`` configurations: the forward
pass and training loss of a latent-attention mixture-of-experts
decoder in straightforward ``jax.numpy`` and float32.

No kernels, no sort, no grouped matmul, no flax: the layer equations of the DeepSeek-V2-Lite family that
``model_type: "sarvam_mla"`` names (HF ``modeling_deepseek``: the
query is one projection, the keys and values come up from a normed
latent of 512, one rotary key head serves every query head), written
against the parameter tree the system under test trains (``wte``,
``block_<i>/{ln_attn, attn/{q_proj, kv_down, kv_norm, kv_up, o_proj},
ln_mlp}``, then ``mlp/{gate_proj, up_proj, down_proj}`` in the leading
dense blocks and ``moe/{router, select_bias, experts_w_gate,
experts_w_in, experts_w_out, shared_gate, shared_up, shared_down}`` in
the others, ``ln_f``, ``lm_head``).  It shares no code with
``dlrover_tpu``.

Attention is the UNABSORBED form with a materialised causal mask.
Everything that is a function of a row alone (the scores of a query
row, the feed-forward, the head and its loss) is taken ``ROWS`` rows
at a time, and each block and each such pass is a ``jax.checkpoint``:
the values are the same, and the float32 temporaries of the forward
pass and of its GRADIENT (``gradients``: what ``correct`` compares
the program's first gradient with) fit beside the train state.  Rotary
pairs are half-split
(``rotate_half``); the frequencies are ``deepseek_yarn``'s.

Experts: sigmoid scores in float32; the top-k of ``score + bias`` are
chosen and weighted by ``scale x score / (sum of the chosen scores +
1e-20)``.  This chip holds experts ``[first, first + held)`` of the
router's outputs: EVERY held expert is computed on EVERY row and kept
under a 0/1 mask times the weight; what the other experts would add is
left out, as in the program, and the shared expert is added whole.
Loss: mean next-token cross entropy over the vocabulary slice, alone.

The parameters arrive in the type they are served in (bf16) and are
up-cast to float32 INSIDE each jitted piece, one block (and one
expert) at a time; one sequence is run at a time.  On a TPU a float32
matmul runs in lower precision unless
``default_matmul_precision("highest")`` is set; every piece sets it.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# rows a pass takes: 16 heads x 1024 x 8192 float32 scores are 0.5 GB,
# 1024 x 16384 of the dense feed-forward and 1024 x 32768 logits less
ROWS = 1024


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


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_range(dim, theta, scaling):
    """``(low, high)`` of HF's ``yarn_find_correction_range``."""
    original = scaling["original_max_position_embeddings"]

    def pair_of(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = math.floor(pair_of(scaling["beta_fast"]))
    high = math.ceil(pair_of(scaling["beta_slow"]))
    return max(low, 0), min(high, dim - 1)


def yarn_inv_freq(dim, theta, scaling):
    freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    low, high = yarn_range(dim, theta, scaling)
    ramp = np.clip(
        (np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0
    )
    return freq / scaling["factor"] * ramp + freq * (1.0 - ramp)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rotary(x, inv_freq, mscale):
    """``x [.., seq, d]``: ``x cos + rotate_half(x) sin``."""
    seq = x.shape[-2]
    freqs = jnp.arange(seq, dtype=F32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return (
        x * jnp.cos(emb) * mscale + _rotate_half(x) * jnp.sin(emb) * mscale
    )


def _attention(x, p, *, dims, eps, theta, scaling):
    """``dims = (heads, nope, rope, v, latent)``."""
    heads, nope, rope, dv, latent = dims
    seq, _ = x.shape
    kernel = lambda name: p[name]["kernel"].astype(F32)  # noqa: E731
    inv_freq = jnp.asarray(yarn_inv_freq(rope, theta, scaling), F32)
    mscale = yarn_mscale(scaling["factor"], scaling["mscale"]) / (
        yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
    )
    q = (x @ kernel("q_proj")).reshape(seq, heads, nope + rope)
    q = q.transpose(1, 0, 2)                        # [H, seq, 192]
    q = jnp.concatenate(
        [q[..., :nope], _rotary(q[..., nope:], inv_freq, mscale)], -1
    )
    down = x @ kernel("kv_down")                    # [seq, 512 + 64]
    c = _rms_norm(down[:, :latent], p["kv_norm"]["scale"], eps)
    k_pe = _rotary(down[:, latent:], inv_freq, mscale)   # ONE head
    up = (c @ kernel("kv_up")).reshape(seq, heads, nope + dv)
    up = up.transpose(1, 0, 2)
    k = jnp.concatenate([
        up[..., :nope], jnp.broadcast_to(k_pe, (heads, seq, rope)),
    ], -1)
    v = up[..., nope:]
    scale = (nope + rope) ** -0.5
    if scaling["mscale_all_dim"]:
        scale *= yarn_mscale(
            scaling["factor"], scaling["mscale_all_dim"]
        ) ** 2

    def some_rows(mine, position):
        # mine [rows, H, 192], position [rows]
        scores = jnp.einsum("rhd,hsd->hrs", mine, k) * scale
        causal = position[:, None] >= jnp.arange(seq)[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum(
            "hrs,hsd->rhd", jax.nn.softmax(scores, axis=-1), v
        )

    out = _by_rows(some_rows, q.transpose(1, 0, 2), jnp.arange(seq))
    return out.reshape(seq, heads * dv) @ kernel("o_proj")


def _swiglu(x, gate, up, down):
    return (
        jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))
    ) @ down.astype(F32)


def _experts(x, p, *, top_k, first, scale):
    """``(out, counts [router outputs])``: the shared expert and the
    held experts' part of the routed sum."""
    scores = jax.nn.sigmoid(x @ p["router"].astype(F32))
    # departure from HF in form only: ``lax.top_k`` for torch.topk
    _, ids = jax.lax.top_k(scores + p["select_bias"].astype(F32), top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = scale * chosen / (
        chosen.sum(axis=-1, keepdims=True) + 1e-20
    )
    outputs = scores.shape[-1]
    # [seq, router outputs]: the weight where the expert is chosen
    weight = jnp.sum(
        weights[:, :, None] * (ids[:, :, None] == jnp.arange(outputs)),
        axis=1,
    )
    mask = jnp.sum(ids[:, :, None] == jnp.arange(outputs), axis=1)
    held = p["experts_w_gate"].shape[0]

    def one(out, xs):
        # every held expert on every row; kept where the mask says
        w_gate, w_up, w_down, w = xs
        return out + _swiglu(x, w_gate, w_up, w_down) * w[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_w_gate"], p["experts_w_in"], p["experts_w_out"],
        weight.T[first:first + held],
    ))
    out = out + _swiglu(
        x, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
        p["shared_down"]["kernel"],
    )
    return out, mask.sum(axis=0).astype(F32)


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(F32)


def _hashable(scaling):
    return tuple(sorted(scaling.items()))


@functools.partial(jax.jit, static_argnames=(
    "dims", "eps", "theta", "scaling", "top_k", "first", "scale",
))
def _block(x, p, *, dims, eps, theta, scaling, top_k, first, scale):
    """One block on one sequence ``[seq, h]``; an expert block's
    assignment counts, a dense block's None."""

    def feed_forward(m):
        if "mlp" in p:
            mlp = p["mlp"]
            return _swiglu(
                m, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"],
                mlp["down_proj"]["kernel"],
            ), None
        return _experts(
            m, p["moe"], top_k=top_k, first=first, scale=scale
        )

    def block(x, p):
        a = _rms_norm(x, p["ln_attn"]["scale"], eps)
        x = x + _attention(
            a, p["attn"], dims=dims, eps=eps, theta=theta,
            scaling=dict(scaling),
        )
        out, counts = _by_rows(
            feed_forward, _rms_norm(x, p["ln_mlp"]["scale"], eps)
        )
        if counts is not None:
            counts = counts.sum(axis=0)
        return x + out.reshape(x.shape), counts

    with jax.default_matmul_precision("highest"):
        return jax.checkpoint(block)(x, p)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_f, lm_head, *, eps):
    """Final norm and the untied output head: ``[seq, vocab]``."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, ln_f["scale"], eps)
        return x @ lm_head["kernel"].astype(F32)


def _nll_sum(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum()


def block_kwargs(cfg: dict) -> dict:
    return dict(
        dims=(
            cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["kv_lora_rank"],
        ),
        eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        scaling=_hashable(cfg["rope_scaling"]),
        top_k=cfg["num_experts_per_tok"],
        first=cfg["first_expert_held"],
        scale=cfg["routed_scaling_factor"],
    )


def _hidden(params, tokens, cfg: dict):
    """``(the last block's output [seq, h], per expert layer the
    assignments to each of the router's outputs)`` of one sequence."""
    counts = []
    x = _embed(params["wte"]["embedding"], tokens)
    for i in range(cfg["num_hidden_layers"]):
        x, n = _block(x, params[f"block_{i}"], **block_kwargs(cfg))
        if n is not None:
            counts.append(n)
    return x, counts


def forward(params, tokens, cfg: dict):
    """``(per sequence the logits [seq, vocab], counts)``, one
    sequence at a time; ``counts`` = per expert layer the assignments
    to each of the router's outputs, summed over the sequences."""
    logits, counts = [], []
    for row in tokens:
        x, n = _hidden(params, row, cfg)
        counts.append(n)
        logits.append(_head(
            x, params["ln_f"], params["lm_head"],
            eps=cfg["rms_norm_eps"],
        ))
    return logits, [sum(n) for n in zip(*counts)]


def loss_and_counts(params, tokens, targets, cfg: dict):
    """``(the training loss, counts [expert layers, router
    outputs])``, differentiable; the float32 logits live ``ROWS`` rows
    at a time."""
    nll, counts = [], []
    for row, wanted in zip(tokens, targets):
        x, n = _hidden(params, row, cfg)
        counts.append(n)
        nll.append(_by_rows(
            lambda rows, t: _nll_sum(_head(
                rows, params["ln_f"], params["lm_head"],
                eps=cfg["rms_norm_eps"],
            ), t), x, wanted,
        ).sum())
    return sum(nll) / targets.size, jnp.stack(
        [sum(n) for n in zip(*counts)]
    )


def loss_of(params, tokens, targets, cfg: dict):
    return loss_and_counts(params, tokens, targets, cfg)[0]


def loss(params, tokens, targets, cfg: dict) -> float:
    return float(np.asarray(loss_of(params, tokens, targets, cfg)))


def bias_deltas(counts, rate: float):
    """The router bias's rule, a layer a row: each expert moves by
    ``rate`` towards the mean load."""
    counts = np.asarray(counts)
    return rate * np.sign(counts.mean(axis=1, keepdims=True) - counts)


def gradients_of(fn, pick, params, *inputs):
    """``(value, aux, {path: d value / d leaf})`` of ``fn(params,
    *inputs) -> (value, aux)`` for the leaves whose path
    ``pick(jax.tree_util.keystr(path))`` names, in ONE program; a
    gradient comes back in its leaf's own type.  ``inputs`` are the
    program's arguments (closed over, a batch would be a constant of
    the program and every seed a new compilation)."""
    paths, tree = jax.tree_util.tree_flatten_with_path(params)
    names = [jax.tree_util.keystr(path) for path, _ in paths]
    picked = [pick(name) for name in names]

    def of(some, rest, *inputs):
        some, rest = iter(some), iter(rest)
        return fn(jax.tree_util.tree_unflatten(tree, [
            next(some) if mine else next(rest) for mine in picked
        ]), *inputs)

    leaves = [leaf for _, leaf in paths]
    (value, aux), grads = jax.jit(jax.value_and_grad(of, has_aux=True))(
        [leaf for leaf, mine in zip(leaves, picked) if mine],
        [leaf for leaf, mine in zip(leaves, picked) if not mine],
        *inputs,
    )
    return value, aux, dict(zip(
        [name for name, mine in zip(names, picked) if mine], grads
    ))


def gradients(params, tokens, targets, cfg: dict, pick):
    """``(loss, counts, gradients)`` of the reference: see
    :func:`gradients_of`."""
    return gradients_of(
        lambda p, x, y: loss_and_counts(p, x, y, cfg), pick, params,
        tokens, targets,
    )
