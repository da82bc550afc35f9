"""Plain reference for the ``bailing_hybrid`` configurations
(Ling-3.0-flash): the forward pass and training loss of a decoder
whose layers mix tokens by Kimi Delta Attention (a delta rule with a
log-decay a key channel; Kimi Linear, arXiv:2510.26692) or, the last
of every ``layer_group_size`` published layers, by latent attention
with a head-wise output gate (DeepSeek-V2, arXiv:2405.04434;
arXiv:2505.06708), over experts chosen inside the best groups
(DeepSeek-V3's ``noaux_tc``, arXiv:2412.19437), in straightforward
``jax.numpy`` and float32.

No kernels, no chunks, no sort, no grouped matmul, no flax, no code of
``dlrover_tpu``: written against the parameter tree the system under
test trains (``wte``, ``block_<i>/{ln_attn, kda/{q_proj, k_proj,
v_proj, f_proj, g_proj, b_proj, q_conv, k_conv, v_conv, A_log,
dt_bias, o_norm, o_proj} | attn/{q_proj, kv_down, kv_norm, kv_up,
g_proj, o_proj}, ln_mlp, mlp/.. | moe/{router, select_bias,
experts_w_*, shared_*}}``, ``ln_f``, ``lm_head``).  What it shares
with the other held-expert references (row blocks, the norm, SwiGLU,
the head, the way gradients are taken) is ``sarvam_mla_reference.py``'s
(``base``).

Per block ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``.

KDA mixer: the three convolutions are four shifted multiply-adds and
SiLU; ``q`` and ``k`` are divided by their head's L2 norm (``q`` also
by ``sqrt(d)``); the log-decay is ``lower x sigmoid(exp(A_log_h) (x
W_f + dt_bias))``, a number a channel in ``[lower, 0]``; the rule is
the RECURRENCE, token by token::

    S <- Diag(exp(g_t)) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T
    o_t = S^T q_t

(a scan over blocks of ``SCAN`` tokens, each a checkpoint, so that
its gradient keeps a state a block and not a token); the output is
``(RMSNorm_head(o) * sigmoid(x W_g)) W_o``.

Latent attention: the UNABSORBED form with a materialised causal
mask, ``ROWS`` query rows at a time; the rotary lanes rotate in
NEIGHBOURING pairs (``rope_interleave``) at ``theta^(-2i/rope)``, no
scaling; a head's output is multiplied by ``sigmoid(x w_h)``, one
number a head and token, before ``W_o``.

Experts: sigmoid scores; ``s' = s + bias``; the router's outputs in
``n_group`` groups of consecutive experts; a group's score the sum of
its two largest ``s'``; the groups ranked, the best ``topk_group``
kept, every other group's ``s'`` at ``-inf``; the top-k of what is
left, weighted by ``scale x s / (sum of the chosen s + 1e-20)``.  This
chip holds experts ``[first, first + held)``: EVERY held expert is
computed on EVERY row and kept under its weight (0 where it was not
chosen); what the other experts would add is left out, as in the
program, and the shared expert is added whole.  Loss: mean next-token
cross entropy over the vocabulary slice, alone.

The parameters arrive in the type they are served in and are up-cast
to float32 INSIDE each jitted piece; one sequence is run at a time;
every piece sets ``default_matmul_precision("highest")``.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import loader

base = loader.load_module("models", "sarvam_mla_reference")
F32 = base.F32
# rows a pass takes: 32 heads x 512 x 8192 float32 scores are 0.5 GB
ROWS = 512
# tokens a checkpointed block of the recurrence holds
SCAN = 64
KDA, LATENT = "kda", "latent"


def _by_rows(fn, x, *more):
    """``base._by_rows`` at this file's ``ROWS``: ``fn`` over blocks of
    rows, each pass a checkpoint, the results stacked by block."""
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


def kinds(cfg: dict):
    """A layer's mixer from its PUBLISHED index (``layers_held``; the
    layers' own positions where the key is absent): latent attention
    closes every group of ``layer_group_size``."""
    ids = cfg.get("layers_held") or range(cfg["num_hidden_layers"])
    return tuple(
        LATENT if (i + 1) % cfg["layer_group_size"] == 0 else KDA
        for i in ids
    )


def _kernel(p, name):
    return p[name]["kernel"].astype(F32)


def _conv_silu(x, taps):
    """``x [seq, c]``, ``taps [K, c]``: ``K`` shifted multiply-adds."""
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


def delta_rule(q, k, v, g, beta, mean_decay=False):
    """The recurrence, token by token.  ``q, k, g [seq, H, d_k]``, ``v
    [seq, H, d_v]``, ``beta [seq, H]`` -> ``(o [seq, H, d_v], the
    final state [H, d_k, d_v])``.  ``mean_decay``: the CONTROL in
    which a head's channels share the mean of their log-decays (the
    scalar rule)."""
    seq, heads, dk = q.shape
    dv = v.shape[2]
    if mean_decay:
        g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = jnp.exp(g_t)[:, :, None] * state
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + (beta_t[:, None] * k_t)[:, :, None] * (
            v_t - read
        )[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    block = max(b for b in range(1, min(seq, SCAN) + 1) if seq % b == 0)

    def blocks(a):
        return a.reshape((seq // block, block) + a.shape[1:])

    state, out = jax.lax.scan(
        jax.checkpoint(lambda s, xs: jax.lax.scan(token, s, xs)),
        jnp.zeros((heads, dk, dv), F32),
        tuple(blocks(a) for a in (q, k, v, g, beta)),
    )
    return out.reshape(seq, heads, dv), state


def _kda(x, p, *, heads, d, lower, eps, mean_decay):
    """``(out [seq, h], [rms of the final state, least log-decay])``."""
    seq = x.shape[0]
    q = _conv_silu(x @ _kernel(p, "q_proj"), p["q_conv"])
    k = _conv_silu(x @ _kernel(p, "k_proj"), p["k_conv"])
    v = _conv_silu(x @ _kernel(p, "v_proj"), p["v_conv"])
    q, k = q.reshape(seq, heads, d), k.reshape(seq, heads, d)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = q / math.sqrt(d)
    f = x @ _kernel(p, "f_proj") + p["dt_bias"].astype(F32)
    g = lower * jax.nn.sigmoid(
        jnp.exp(p["A_log"].astype(F32))[:, None] * f.reshape(seq, heads, d)
    )
    beta = jax.nn.sigmoid(x @ _kernel(p, "b_proj"))
    o, state = delta_rule(
        q, k, v.reshape(seq, heads, d), g, beta, mean_decay
    )
    o = base._rms_norm(o, p["o_norm"], eps).reshape(seq, heads * d)
    o = o * jax.nn.sigmoid(x @ _kernel(p, "g_proj"))
    return o @ _kernel(p, "o_proj"), jnp.stack(
        [jnp.sqrt(jnp.mean(state * state)), jnp.min(g)]
    )


def _rotate_pairs(x, theta):
    """``x [.., seq, d]``: lanes ``2i`` and ``2i + 1`` rotate by
    ``position x theta^(-2i/d)``."""
    seq, d = x.shape[-2:]
    angles = jnp.arange(seq, dtype=F32)[:, None] * jnp.asarray(
        theta ** (-np.arange(0, d, 2, dtype=np.float64) / d), F32
    )[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [even * cos - odd * sin, even * sin + odd * cos], axis=-1
    ).reshape(x.shape)


def _latent_attention(x, p, *, dims, eps, theta, head_gate):
    """``dims = (heads, nope, rope, v, latent)``."""
    heads, nope, rope, dv, latent = dims
    seq, _ = x.shape
    q = (x @ _kernel(p, "q_proj")).reshape(seq, heads, nope + rope)
    q = q.transpose(1, 0, 2)                        # [H, seq, 192]
    q = jnp.concatenate(
        [q[..., :nope], _rotate_pairs(q[..., nope:], theta)], -1
    )
    down = x @ _kernel(p, "kv_down")                # [seq, 512 + 64]
    c = base._rms_norm(down[:, :latent], p["kv_norm"]["scale"], eps)
    k_pe = _rotate_pairs(down[:, latent:], theta)   # ONE head
    up = (c @ _kernel(p, "kv_up")).reshape(seq, heads, nope + dv)
    up = up.transpose(1, 0, 2)
    k = jnp.concatenate([
        up[..., :nope], jnp.broadcast_to(k_pe, (heads, seq, rope)),
    ], -1)
    v = up[..., nope:]
    scale = (nope + rope) ** -0.5

    def some_rows(mine, position):
        # mine [rows, H, 192], position [rows]
        scores = jnp.einsum("rhd,hsd->hrs", mine, k) * scale
        causal = position[:, None] >= jnp.arange(seq)[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum(
            "hrs,hsd->rhd", jax.nn.softmax(scores, axis=-1), v
        )

    out = _by_rows(some_rows, q.transpose(1, 0, 2), jnp.arange(seq))
    out = out.reshape(seq, heads, dv)
    if head_gate:
        out = out * jax.nn.sigmoid(x @ _kernel(p, "g_proj"))[:, :, None]
    return out.reshape(seq, heads * dv) @ _kernel(p, "o_proj")


def choose(scores, bias, *, top_k, n_group, topk_group):
    """The two-stage top-k, written out: ``ids [rows, top_k]`` of
    ``scores [rows, outputs]``.  ``n_group = 0`` is the CONTROL without
    the group mask: the plain top-k of ``scores + bias``."""
    standing = scores + bias.astype(F32)
    if n_group:
        rows, outputs = standing.shape
        grouped = standing.reshape(rows, n_group, outputs // n_group)
        two = jnp.sort(grouped, axis=-1)[..., -2:].sum(axis=-1)
        # a group's rank among its token's groups, the best 0
        rank = jnp.argsort(jnp.argsort(-two, axis=-1), axis=-1)
        standing = jnp.where(
            (rank < topk_group)[:, :, None], grouped, -jnp.inf
        ).reshape(rows, outputs)
    # departure from HF in form only: ``lax.top_k`` for torch.topk
    return jax.lax.top_k(standing, top_k)[1]


def _experts(x, p, *, top_k, first, scale, n_group, topk_group):
    """``(out, [counts [router outputs] | distinct groups a token,
    summed])``: the shared expert and the held experts' part of the
    routed sum."""
    scores = jax.nn.sigmoid(x @ p["router"].astype(F32))
    outputs = scores.shape[-1]
    ids = choose(
        scores, p["select_bias"], top_k=top_k, n_group=n_group,
        topk_group=topk_group,
    )
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = scale * chosen / (
        chosen.sum(axis=-1, keepdims=True) + 1e-20
    )
    is_expert = ids[:, :, None] == jnp.arange(outputs)
    # [rows, router outputs]: the weight where the expert is chosen
    weight = jnp.sum(weights[:, :, None] * is_expert, axis=1)
    mask = jnp.sum(is_expert, axis=1)
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
    groups = max(n_group, 1)
    distinct = jnp.sum(jnp.any(
        (ids // (outputs // groups))[:, :, None] == jnp.arange(groups),
        axis=1,
    ))
    return out, jnp.concatenate([
        mask.sum(axis=0).astype(F32), distinct.astype(F32)[None],
    ])


@functools.partial(jax.jit, static_argnames=(
    "kind", "heads", "d", "lower", "dims", "eps", "theta", "top_k",
    "first", "scale", "n_group", "topk_group", "mean_decay", "head_gate",
))
def _block(
    x, p, *, kind, heads, d, lower, dims, eps, theta, top_k, first,
    scale, n_group, topk_group, mean_decay, head_gate,
):
    """One block on one sequence ``[seq, h]``: ``(y, the KDA mixer's
    [state rms, least log-decay] or None, an expert layer's [counts |
    distinct groups] or None)``."""

    def feed_forward(m):
        if "mlp" in p:
            mlp = p["mlp"]
            return base._swiglu(
                m, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"],
                mlp["down_proj"]["kernel"],
            ), None
        return _experts(
            m, p["moe"], top_k=top_k, first=first, scale=scale,
            n_group=n_group, topk_group=topk_group,
        )

    def block(x, p):
        a = base._rms_norm(x, p["ln_attn"]["scale"], eps)
        if kind == KDA:
            mixed, rule = _kda(
                a, p["kda"], heads=heads, d=d, lower=lower, eps=eps,
                mean_decay=mean_decay,
            )
        else:
            mixed, rule = _latent_attention(
                a, p["attn"], dims=dims, eps=eps, theta=theta,
                head_gate=head_gate,
            ), None
        x = x + mixed
        out, said = _by_rows(
            feed_forward, base._rms_norm(x, p["ln_mlp"]["scale"], eps)
        )
        if said is not None:
            said = said.sum(axis=0)
        return x + out.reshape(x.shape), rule, said

    with jax.default_matmul_precision("highest"):
        return jax.checkpoint(block)(x, p)


def block_kwargs(cfg: dict, control=None) -> dict:
    """``control``: None, or the mechanism a CONTROL leaves out:
    ``"channel_decay"`` (a head's channels share their mean
    log-decay), ``"group_mask"`` (plain top-k over all the outputs),
    ``"head_gate"`` (no gate on the softmax layers' heads)."""
    if control not in (None, "channel_decay", "group_mask", "head_gate"):
        raise ValueError(f"no control {control!r}")
    return dict(
        heads=cfg["num_attention_heads"], d=cfg["head_dim"],
        lower=float(cfg["kda_lower_bound"]),
        dims=(
            cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["kv_lora_rank"],
        ),
        eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        top_k=cfg["num_experts_per_tok"],
        first=cfg["first_expert_held"],
        scale=cfg["routed_scaling_factor"],
        n_group=0 if control == "group_mask" else cfg["n_group"],
        topk_group=cfg["topk_group"],
        mean_decay=control == "channel_decay",
        head_gate=control != "head_gate",
    )


def _hidden(params, tokens, cfg: dict, control=None):
    """``(the last block's output [seq, h], per KDA layer [state rms,
    least log-decay], per expert layer [counts | distinct groups])``
    of one sequence."""
    rules, routed = [], []
    x = base._embed(params["wte"]["embedding"], tokens)
    for i, kind in enumerate(kinds(cfg)):
        x, rule, said = _block(
            x, params[f"block_{i}"], kind=kind,
            **block_kwargs(cfg, control),
        )
        if rule is not None:
            rules.append(rule)
        if said is not None:
            routed.append(said)
    return x, rules, routed


def forward(params, tokens, cfg: dict):
    """Per sequence the logits ``[seq, vocab]``, one sequence at a
    time."""
    return [
        base._head(
            _hidden(params, row, cfg)[0], params["ln_f"],
            params["lm_head"], eps=cfg["rms_norm_eps"],
        ) for row in tokens
    ]


def loss_and_said(params, tokens, targets, cfg: dict, control=None):
    """``(the training loss, {"counts" [expert layers, router
    outputs], "groups_per_token", "state_rms" [sequences, KDA layers],
    "log_decay_min"})``, differentiable; the float32 logits live
    ``ROWS`` rows at a time."""
    nll, rules, routed = [], [], []
    for row, wanted in zip(tokens, targets):
        x, r, n = _hidden(params, row, cfg, control)
        rules.append(jnp.stack(r))
        routed.append(jnp.stack(n))
        nll.append(_by_rows(
            lambda rows, t: base._nll_sum(base._head(
                rows, params["ln_f"], params["lm_head"],
                eps=cfg["rms_norm_eps"],
            ), t), x, wanted,
        ).sum())
    rules, routed = jnp.stack(rules), sum(routed)
    return sum(nll) / targets.size, {
        "counts": routed[:, :-1],
        "groups_per_token": jnp.mean(routed[:, -1]) / targets.size,
        "state_rms": rules[:, :, 0],
        "log_decay_min": jnp.min(rules[:, :, 1]),
    }


def loss(params, tokens, targets, cfg: dict) -> float:
    return float(np.asarray(
        loss_and_said(params, tokens, targets, cfg)[0]
    ))


def gradients(params, tokens, targets, cfg: dict, pick, control=None):
    """``(loss, said, {path: gradient})`` of the reference for the
    leaves ``pick`` names (``base.gradients_of``)."""
    return base.gradients_of(
        lambda p, x, y: loss_and_said(p, x, y, cfg, control), pick,
        params, tokens, targets,
    )
