"""Plain reference for the ``motif`` configurations: the forward pass
and training loss of a decoder whose token carries ``n`` residual
streams mixed by a Sinkhorn-normalised matrix round every sub-layer,
with grouped differential attention on a latent key, PolyNorm in every
feed-forward and one multi-token-prediction layer through the shared
head, in straightforward ``jax.numpy`` and float32.

No kernels, no sort, no grouped matmul, no flax: the layer equations
that ``model_type: "motif"`` names (the configuration's ``assumed``
says what the published config leaves open and how it is set), written
against the parameter tree the system under test trains (``wte``,
``block_<i>/{mhc_attn, mhc_mlp}/{phi, alpha, bias}``, ``ln_attn``,
``attn/{q_down, q_norm, q_up, kv_down, kv_norm, kv_up, lambda_proj,
gate_proj, o_proj}``, ``ln_mlp``, then ``mlp/{gate_proj, up_proj,
down_proj, polynorm_w, polynorm_b}`` in a dense block and ``moe/{router,
experts_w_gate, experts_w_in, experts_w_out, experts_polynorm_w,
experts_polynorm_b, shared_gate, shared_up, shared_down,
shared_polynorm_w, shared_polynorm_b}`` in a sparse one, ``ln_f``,
``mtp/{ln_h, ln_e, eh_proj, block, ln_f}``, ``lm_head``).  It shares
no code with ``dlrover_tpu``; what is not this family's own (the norm,
``rotate_half`` rope and yarn's frequencies, the head and the
picked-leaf gradients) is the ``sarvam_mla`` reference's, beside this
file.

**Streams.**  A token's state is ``X [n, C]`` (the embedding copied
``n`` times; a sequence's is kept as ``vec(X) [seq, n C]`` and taken
apart a row block at a time).  Round each sub-layer ``F``: ``x~ =
vec(X) / rms(vec(X))`` over all ``n C`` numbers (no learned scale);
``[p | q | r] = x~ Phi``;
``H_pre = sigmoid(alpha_0 p + b)``, ``H_post = 2 sigmoid(alpha_1 q +
b)``, ``M = exp(alpha_2 mat(r) + b)`` and then, ``mhc_sinkhorn_iters``
times WRITTEN OUT, every row divided by its sum, then every column by
its sum; ``u = H_pre X``; ``X' = H_res X + outer(H_post, F(norm(u)))``.

**Attention.**  ``q = RMSNorm(u W_dq) W_uq``, ``H`` heads of ``nope +
rope``; ``[c | k_r] = u W_dkv``; ``[k_g | v_g] = RMSNorm(c) W_ukv``,
``G`` kv heads, each REPEATED ``H / G`` times; the key is ``[k_g,
rope(k_r)]`` with ONE rope key; a MATERIALISED mask (key ``t`` visible
to query ``i`` iff ``t <= i`` and, in a window layer, ``t > i -
sliding_window``); ``A = softmax(q K^T / sqrt(nope + rope) + mask) V``.
Of each group of ``H / G`` heads the last ``noise / G`` are noise
heads: ``o_gj = A_gj - sigmoid(u W_lambda)_gj mean(A_noise(g))``, then
``out = (o * sigmoid(u W_gate)) W_o``.  A full layer's rope is yarn's
(frequencies only: no scale on cos, sin or the softmax), a window
layer's the default rule.

**Feed-forward.**  ``down(P(gate(x)) * up(x))``, ``P(z) = scale (w_0
N(z^3) + w_1 N(z^2) + w_2 N(z)) + clip(b, +-clamp)``, ``N(a) = a /
sqrt(mean over the width of a^2 + eps)``.  Experts: sigmoid scores,
the top-k chosen and weighted by ``route_scale x score / (sum of the
chosen + 1e-20)``; this chip holds experts ``[first, first + held)``:
EVERY held expert is computed on EVERY row and kept under its weight,
zero where it was not chosen; the shared expert is added whole.

**Loss.**  Mean next-token cross entropy + ``mtp_weight x`` the
prediction layer's (``[RMSNorm(h_t) ; RMSNorm(embed(token_{t+1}))]
W_eh`` copied into ``n`` streams, one sparse full-attention block, the
streams' sum, its own final norm, the SHARED head against token ``t +
2``, the mean over the first ``seq - 1`` positions) +
``load_balance_coeff x E sum_e f_e P_e`` over every sparse layer's
(the prediction layer's too) assignments and scores together.

Scores are taken ``ATTN_ROWS`` query rows at a time, everything else
that is a function of a row alone (the streams' mixes among it)
``ROWS`` at a time; each block and each such pass is a
``jax.checkpoint``, and so is each HALF of the stack (a block's input
is 0.5 GB in float32 at 8192 x 4 x 4096: the gradient keeps the
second half's input and makes the blocks' own again).  Every jitted piece sets
``default_matmul_precision("highest")``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

import loader

base = loader.load_module("models", "sarvam_mla_reference")
mimo = loader.load_module("models", "mimo_v2_reference")

F32 = jnp.float32
ROWS = base.ROWS
ATTN_ROWS = 256
WINDOW = 1
_by_rows = mimo._by_rows


def _kernel(p, name):
    return p[name]["kernel"].astype(F32)


def _coefficients(flat, p, *, n, iters, eps):
    """``(H_pre [rows, n], H_post [rows, n], H_res [rows, n, n])`` of
    the rows' state ``vec(X) [rows, n C]``."""
    seq = flat.shape[0]
    normed = flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + eps
    )
    raw = normed @ p["phi"].astype(F32)
    alpha, bias = p["alpha"].astype(F32), p["bias"].astype(F32)
    h_pre = jax.nn.sigmoid(alpha[0] * raw[:, :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(
        alpha[1] * raw[:, n:2 * n] + bias[n:2 * n]
    )
    m = jnp.exp(
        alpha[2] * raw[:, 2 * n:] + bias[2 * n:]
    ).reshape(seq, n, n)
    for _ in range(iters):
        m = m / m.sum(axis=2, keepdims=True)    # every row by its sum
        m = m / m.sum(axis=1, keepdims=True)    # every column by its sum
    return h_pre, h_post, m


def _unblocked(a):
    """``_by_rows``' stacked blocks back as rows."""
    return a.reshape((-1,) + a.shape[2:])


def _mat(flat, n):
    """A row block's ``X [rows, n, C]`` from ``vec(X) [rows, n C]``.
    The whole sequence's state stays in the flat form: on a TPU an
    axis of 4 before the lanes is padded to 8, twice the bytes."""
    return flat.reshape(flat.shape[0], n, -1)


def _mixed(flat, y, h_post, h_res):
    """``vec(H_res X + outer(H_post, y))`` of a row block."""
    out = (
        jnp.einsum("snm,smc->snc", h_res, _mat(flat, h_post.shape[1]))
        + h_post[:, :, None] * y[:, None, :]
    )
    return out.reshape(flat.shape)


def _read(flat, p, *, n, iters, eps):
    """``(u, H_post, H_res)`` of the state, ``ROWS`` rows a pass: the
    mixes are functions of a row alone."""
    def some_rows(flat):
        h_pre, h_post, h_res = _coefficients(
            flat, p, n=n, iters=iters, eps=eps
        )
        return jnp.einsum("sn,snc->sc", h_pre, _mat(flat, n)), h_post, h_res

    return jax.tree.map(_unblocked, _by_rows(some_rows, ROWS, flat))


def _write(flat, y, h_post, h_res):
    """``X' = H_res X + outer(H_post, y)``, ``ROWS`` rows a pass."""
    return _unblocked(_by_rows(_mixed, ROWS, flat, y, h_post, h_res))


def _inv_freq(rope, theta, scaling):
    if scaling is None:
        return theta ** (-np.arange(0, rope, 2, dtype=np.float64) / rope)
    return base.yarn_inv_freq(rope, theta, dict(scaling))


def _attention(x, p, *, dims, window, theta, scaling, eps):
    """``dims = (heads, kv, noise, nope, rope, v, kv latent)``; one
    sequence ``[seq, C]``."""
    heads, kv, noise, nope, rope, dv, latent = dims
    seq, _ = x.shape
    group = heads // kv
    signal = group - noise // kv
    freq = jnp.asarray(_inv_freq(rope, theta, scaling), F32)
    c_q = base._rms_norm(x @ _kernel(p, "q_down"), p["q_norm"]["scale"], eps)
    q = (c_q @ _kernel(p, "q_up")).reshape(seq, heads, nope + rope)
    q = q.transpose(1, 0, 2)                         # [H, seq, 192]
    q = jnp.concatenate(
        [q[..., :nope], base._rotary(q[..., nope:], freq, 1.0)], -1
    )
    down = x @ _kernel(p, "kv_down")
    c = base._rms_norm(down[:, :latent], p["kv_norm"]["scale"], eps)
    k_r = base._rotary(down[:, latent:], freq, 1.0)  # ONE rope key
    up = (c @ _kernel(p, "kv_up")).reshape(seq, kv, nope + dv)
    up = up.transpose(1, 0, 2)
    k = jnp.concatenate([
        up[..., :nope], jnp.broadcast_to(k_r, (kv, seq, rope)),
    ], -1)
    # the kv heads repeated: query head h reads kv head h // group
    k = jnp.repeat(k, group, axis=0)
    v = jnp.repeat(up[..., nope:], group, axis=0)

    def some_rows(mine, position):
        scores = jnp.einsum("rhd,hsd->hrs", mine, k) * (nope + rope) ** -0.5
        key = jnp.arange(seq)[None, :]
        seen = key <= position[:, None]
        if window is not None:
            seen = seen & (key > position[:, None] - window)
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum(
            "hrs,hsd->rhd", jax.nn.softmax(scores, axis=-1), v
        )

    a = _by_rows(
        some_rows, ATTN_ROWS, q.transpose(1, 0, 2), jnp.arange(seq)
    ).reshape(seq, kv, group, dv)
    lam = jax.nn.sigmoid(x @ p["lambda_proj"].astype(F32))
    o = a[:, :, :signal] - lam.reshape(seq, kv, signal, 1) * jnp.mean(
        a[:, :, signal:], axis=2, keepdims=True
    )
    gate = jax.nn.sigmoid(x @ _kernel(p, "gate_proj"))
    return (o.reshape(seq, -1) * gate) @ _kernel(p, "o_proj")


def _poly_norm(z, w, b, *, scale, clamp, poly_eps):
    def normed(a):
        return a / jnp.sqrt(
            jnp.mean(a * a, axis=-1, keepdims=True) + poly_eps
        )

    w = w.astype(F32)
    return scale * (
        w[0] * normed(z ** 3) + w[1] * normed(z ** 2) + w[2] * normed(z)
    ) + jnp.clip(b.astype(F32), -clamp, clamp)


def _glu(x, gate, up, down, w, b, poly):
    return (
        _poly_norm(x @ gate.astype(F32), w, b, **dict(poly))
        * (x @ up.astype(F32))
    ) @ down.astype(F32)


def _experts(x, p, *, top_k, first, scale, poly):
    """``(out, (counts, score sums))``, both ``[router outputs]``."""
    scores = jax.nn.sigmoid(x @ p["router"].astype(F32))
    # departure from torch in form only: ``lax.top_k`` for torch.topk
    chosen, ids = jax.lax.top_k(scores, top_k)
    weights = scale * chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20)
    outputs = scores.shape[-1]
    picked = ids[:, :, None] == jnp.arange(outputs)  # [rows, k, outputs]
    weight = jnp.sum(weights[:, :, None] * picked, axis=1)
    held = p["experts_w_gate"].shape[0]

    def one(out, xs):
        # every held expert on every row, under its weight
        w_gate, w_up, w_down, w = xs
        return out + _glu(
            x, w_gate, w_up, w_down, p["experts_polynorm_w"],
            p["experts_polynorm_b"], poly,
        ) * w[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_w_gate"], p["experts_w_in"], p["experts_w_out"],
        weight.T[first:first + held],
    ))
    out = out + _glu(
        x, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
        p["shared_down"]["kernel"], p["shared_polynorm_w"],
        p["shared_polynorm_b"], poly,
    )
    return out, (picked.sum(axis=(0, 1)).astype(F32), scores.sum(axis=0))


@functools.partial(jax.jit, static_argnames=(
    "dims", "window", "theta", "scaling", "eps", "n", "iters", "top_k",
    "first", "scale", "poly",
))
def _block(
    X, p, *, dims, window, theta, scaling, eps, n, iters, top_k, first,
    scale, poly,
):
    """One block on one sequence's state ``vec(X) [seq, n C]``; a
    sparse block's ``(counts, score sums)``, a dense block's None."""

    def feed_forward(m):
        if "mlp" in p:
            mlp = p["mlp"]
            return _glu(
                m, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"],
                mlp["down_proj"]["kernel"], mlp["polynorm_w"],
                mlp["polynorm_b"], poly,
            ), None
        return _experts(
            m, p["moe"], top_k=top_k, first=first, scale=scale, poly=poly
        )

    def mlp_rows(flat):
        # everything round the feed-forward is a function of a row
        h_pre, h_post, h_res = _coefficients(
            flat, p["mhc_mlp"], n=n, iters=iters, eps=eps
        )
        u = jnp.einsum("sn,snc->sc", h_pre, _mat(flat, n))
        y, routed = feed_forward(
            base._rms_norm(u, p["ln_mlp"]["scale"], eps)
        )
        return _mixed(flat, y, h_post, h_res), routed

    def block(X, p):
        u, h_post, h_res = _read(
            X, p["mhc_attn"], n=n, iters=iters, eps=eps
        )
        X = _write(X, _attention(
            base._rms_norm(u, p["ln_attn"]["scale"], eps), p["attn"],
            dims=dims, window=window, theta=theta, scaling=scaling,
            eps=eps,
        ), h_post, h_res)
        X, routed = _by_rows(mlp_rows, ROWS, X)
        if routed is not None:
            routed = jax.tree.map(lambda a: a.sum(axis=0), routed)
        return _unblocked(X), routed

    with jax.default_matmul_precision("highest"):
        return jax.checkpoint(block)(X, p)


def block_kwargs(cfg: dict, kind: int) -> dict:
    window = kind == WINDOW
    scaling = cfg["rope_scaling"]
    return dict(
        dims=(
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["num_noise_heads"],
            cfg["head_dim"] - cfg["qk_rope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["kv_lora_rank"],
        ),
        window=cfg["sliding_window"] if window else None,
        theta=float(
            cfg["swa_rope_theta"] if window else scaling["rope_theta"]
        ),
        scaling=None if window else tuple(sorted(
            (k, v) for k, v in scaling.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
        )),
        eps=cfg["rms_norm_eps"], n=cfg["mhc_expansion_rate"],
        iters=cfg["mhc_sinkhorn_iters"],
        top_k=cfg["experts_top_k"], first=cfg["first_expert_held"],
        scale=float(cfg["route_scale"]),
        poly=(
            ("scale", cfg["polynorm_output_scale"]),
            ("clamp", cfg["polynorm_bias_clamp"]),
            ("poly_eps", cfg["polynorm_eps"]),
        ),
    )


@functools.partial(jax.jit, static_argnames=("n",))
def _copies(x, *, n):
    """``vec(X)`` of ``x`` copied into ``n`` streams."""
    return jnp.tile(x, (1, n))


def _summed(flat, n):
    return sum(jnp.split(flat, n, axis=-1))


@functools.partial(jax.jit, static_argnames=("eps",))
def _joined(h, e, p, *, eps):
    """The prediction layer's input: ``[norm(h) ; norm(e)] W_eh``."""
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate([
            base._rms_norm(h, p["ln_h"]["scale"], eps),
            base._rms_norm(e, p["ln_e"]["scale"], eps),
        ], axis=-1) @ _kernel(p, "eh_proj")


def _hidden(params, tokens, next_tokens, cfg: dict):
    """``(the summed streams [seq, C], the prediction layer's summed
    streams or None, per sparse layer (counts, score sums))`` of one
    sequence."""
    n, routed = cfg["mhc_expansion_rate"], []
    wte = params["wte"]["embedding"]
    kinds = list(enumerate(cfg["layer_kinds"]))

    def some_blocks(X, blocks, which):
        found = []
        for (_, kind), p in zip(which, blocks):
            X, one = _block(X, p, **block_kwargs(cfg, kind))
            found.append(one)
        return X, found

    X = _copies(base._embed(wte, tokens), n=n)
    middle = (len(kinds) + 1) // 2
    for which in (kinds[:middle], kinds[middle:]):
        X, found = jax.checkpoint(
            lambda X, blocks, which=which: some_blocks(X, blocks, which)
        )(X, [params[f"block_{i}"] for i, _ in which])
        routed += [one for one in found if one is not None]
    h = _summed(X, n)
    if not cfg["num_nextn_predict_layers"]:
        return h, None, routed
    mtp = params["mtp"]
    x = _joined(
        h, base._embed(wte, next_tokens), mtp, eps=cfg["rms_norm_eps"]
    )
    X, found = _block(
        _copies(x, n=n), mtp["block"], **block_kwargs(cfg, 0)
    )
    routed.append(found)
    return h, _summed(X, n), routed


def loss_parts(params, tokens, targets, cfg: dict):
    """``(main, prediction, balance, counts [sparse layers, router
    outputs])``, differentiable; the float32 logits live ``ROWS`` rows
    at a time."""
    eps = cfg["rms_norm_eps"]
    main, predicted, routed = [], [], []

    def nll(x, ln_f, wanted, weights):
        return _by_rows(
            lambda rows, t, w: _weighted_nll(base._head(
                rows, ln_f, params["lm_head"], eps=eps
            ), t, w), ROWS, x, wanted, weights,
        ).sum()

    for row, wanted in zip(tokens, targets):
        h, h_mtp, found = _hidden(params, row, wanted, cfg)
        routed.append(found)
        seq = wanted.shape[0]
        main.append(nll(h, params["ln_f"], wanted, jnp.ones(seq, F32)))
        if h_mtp is not None:
            # token t + 2 is the target's next; the last has none
            predicted.append(nll(
                h_mtp, params["mtp"]["ln_f"], jnp.roll(wanted, -1),
                (jnp.arange(seq) < seq - 1).astype(F32),
            ))
    counts, scores = (
        jnp.stack([sum(layer[i] for layer in per_layer)
                   for per_layer in zip(*routed)])
        for i in (0, 1)
    )
    batch, seq = targets.shape
    rows = counts.sum() / cfg["experts_top_k"]
    balance = counts.shape[1] * jnp.sum(
        (counts.sum(0) / rows) * (scores.sum(0) / rows)
    )
    return (
        sum(main) / targets.size,
        sum(predicted) / (batch * (seq - 1)) if predicted else 0.0,
        balance, counts,
    )


def _weighted_nll(logits, targets, weights):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -(
        jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0] * weights
    ).sum()


def loss_and_counts(params, tokens, targets, cfg: dict):
    main, predicted, balance, counts = loss_parts(
        params, tokens, targets, cfg
    )
    return (
        main + cfg["recipe"]["mtp_weight"] * predicted
        + cfg["load_balance_coeff"] * balance
    ), counts


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
