"""Plain reference for the ``lfm2_moe`` configurations (LFM2-24B-A2B):
the forward pass and training loss of a decoder whose layers mix
tokens by a doubly gated short convolution or, every fourth, by
grouped-query attention with an RMSNorm a head on q and k, over
sigmoid-routed experts with no shared one and a head TIED to the
embedding, in straightforward ``jax.numpy`` and float32.

No kernels, no sort, no grouped matmul, no chunked head, no remat of
the program's, no flax, no code of ``dlrover_tpu``: the layer
equations of ``transformers``' ``modeling_lfm2.py`` (``Lfm2ShortConv``,
``Lfm2Attention``, ``Lfm2DecoderLayer``, ``Lfm2RMSNorm``;
``tests/test_lfm2_moe.py`` holds this file's two mixers against that
code) and, for what ``lfm2_moe`` adds, the configuration's
``assumed``, written against the parameter tree the system under test
trains (``wte``, ``block_<i>/{operator_norm, short_conv/{in_proj,
taps, out_proj} | attn/{q_proj, k_proj, v_proj, q_layernorm,
k_layernorm, out_proj}, ffn_norm, mlp/{gate_proj, up_proj, down_proj}
| moe/{router, select_bias, experts_w_gate, experts_w_in,
experts_w_out}}``, ``embedding_norm``).  What is not this family's own
(the norm, SwiGLU, ``rotate_half`` rope, the bias's rule and the
picked-leaf gradients) is the ``sarvam_mla`` reference's and (the row
blocks) the ``mimo_v2`` reference's, beside this file.

Per block ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``;
after the last block ``embedding_norm``; logits ``= h E^T`` with ``E``
the embedding.

Conv mixer: ``[B | C | u] = x W_in`` (three equal lane ranges, in
that order: ``chunk(3)``); ``v = B * u``; the depthwise causal
convolution as ``K`` SHIFTED ADDS, ``c_t = sum_j w_j v_{t-K+1+j}``
with zeros before row 0, no bias and no activation; ``y = C * c``;
``out = y W_out``.  The mixer also says the rms of ``y`` (the
program's ``sconv.out_rms_max`` is the largest over the layers).

Attention: ``q``, ``k``, ``v`` by three matrices; ``H`` query heads
and ``G`` kv heads of ``d``; ``RMSNorm`` over each head's ``d`` lanes
of ``q`` and of ``k`` (one scale of ``d`` each) BEFORE rope; rope on
all ``d`` lanes (``rotate_half`` pairing, ``theta^(-2i/d)``); the kv
heads REPEATED ``H / G`` times (query head ``h`` reads kv head ``h //
(H / G)``); a MATERIALISED causal mask; softmax at ``d^-1/2``.

Experts: sigmoid scores in float32; the top-k of ``score + bias``
chosen and weighted by ``scale x score / (sum of the chosen scores +
1e-6)``.  This chip holds experts ``[first, first + held)`` of the
router's outputs: the router scores ALL its outputs, a LOOP over the
held experts computes each on EVERY row and keeps it under its weight
(0 where it was not chosen); what the other experts would add is left
out, as in the program, and there is no shared expert.  Loss: mean
next-token cross entropy over the whole vocabulary, alone.

Scores are taken ``ATTN_ROWS`` query rows at a time (32 heads x 512 x
8192 float32 scores are 0.5 GB), everything else that is a function of
a row alone ``ROWS`` at a time; each block and each such pass is a
``jax.checkpoint`` so that the GRADIENT fits beside the train state.
Every jitted piece sets ``default_matmul_precision("highest")``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

import loader

base = loader.load_module("models", "sarvam_mla_reference")
# ``fn`` over blocks of rows, each pass a checkpoint (the ``mimo_v2``
# reference's, which takes the rows a pass as an argument)
_by_rows = loader.load_module("models", "mimo_v2_reference")._by_rows

F32 = jnp.float32
ROWS = base.ROWS
ATTN_ROWS = 512
CONV, ATTENTION = "conv", "full_attention"


def _kernel(p, name):
    return p[name]["kernel"].astype(F32)


def mix(bcu, taps):
    """``y = C * conv_K(B * u)`` of ``bcu [seq, 3 h]`` (``B | C | u``:
    three equal lane ranges in that order) under ``taps [K, h]``: the
    convolution as ``K`` shifted adds, zeros before row 0."""
    seq, h = bcu.shape[0], taps.shape[1]
    gate_b, gate_c, u = bcu[:, :h], bcu[:, h:2 * h], bcu[:, 2 * h:]
    v = gate_b * u
    k = taps.shape[0]
    conv = jnp.zeros_like(v)
    for j in range(k):
        shift = k - 1 - j  # taps[j] meets v_{t - shift}
        conv = conv + jnp.concatenate(
            [jnp.zeros((shift, h), F32), v[:seq - shift]]
        ) * taps[j]
    return gate_c * conv


def short_conv(x, p):
    """One sequence ``[seq, h]`` -> ``(out [seq, h], the rms of y)``."""
    y = mix(x @ _kernel(p, "in_proj"), p["taps"].astype(F32))
    return y @ _kernel(p, "out_proj"), jnp.sqrt(jnp.mean(y * y))


def attention(x, p, *, heads, kv, d, theta, eps):
    """One sequence ``[seq, h]`` -> ``[seq, h]``."""
    seq, _ = x.shape
    freq = jnp.asarray(
        theta ** (-np.arange(0, d, 2, dtype=np.float64) / d), F32
    )

    def heads_of(t, n):
        return t.reshape(seq, n, d).transpose(1, 0, 2)   # [n, seq, d]

    def normed(t, n, norm):
        return base._rotary(base._rms_norm(
            heads_of(t, n), p[norm]["scale"], eps
        ), freq, 1.0)

    q = normed(x @ _kernel(p, "q_proj"), heads, "q_layernorm")
    # the kv heads repeated: query head h reads kv head h // group
    k = jnp.repeat(
        normed(x @ _kernel(p, "k_proj"), kv, "k_layernorm"),
        heads // kv, axis=0,
    )
    v = jnp.repeat(
        heads_of(x @ _kernel(p, "v_proj"), kv), heads // kv, axis=0
    )

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
    ).reshape(seq, heads * d)
    return out @ _kernel(p, "out_proj")


def _experts(x, p, *, top_k, first, scale):
    """``(out, counts [router outputs])``: the held experts' part of
    the routed sum, and nothing beside it."""
    scores = jax.nn.sigmoid(x @ p["router"].astype(F32))
    # departure from HF in form only: ``lax.top_k`` for torch.topk
    _, ids = jax.lax.top_k(scores + p["select_bias"].astype(F32), top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = scale * chosen / (
        chosen.sum(axis=-1, keepdims=True) + 1e-6
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
    "kind", "heads", "kv", "d", "theta", "eps", "top_k", "first", "scale",
))
def _block(x, p, *, kind, heads, kv, d, theta, eps, top_k, first, scale):
    """One block on one sequence ``[seq, h]``: ``(y, the conv mixer's
    output rms or None, a sparse block's assignment counts or
    None)``."""

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
        a = base._rms_norm(x, p["operator_norm"]["scale"], eps)
        if kind == CONV:
            mixed, rms = short_conv(a, p["short_conv"])
        else:
            mixed, rms = attention(
                a, p["attn"], heads=heads, kv=kv, d=d, theta=theta,
                eps=eps,
            ), None
        x = x + mixed
        out, counts = _by_rows(
            feed_forward, ROWS,
            base._rms_norm(x, p["ffn_norm"]["scale"], eps),
        )
        if counts is not None:
            counts = counts.sum(axis=0)
        return x + out.reshape(x.shape), rms, counts

    with jax.default_matmul_precision("highest"):
        return jax.checkpoint(block)(x, p)


def block_kwargs(cfg: dict) -> dict:
    return dict(
        heads=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
        d=cfg["hidden_size"] // cfg["num_attention_heads"],
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        eps=cfg["norm_eps"], top_k=cfg["num_experts_per_tok"],
        first=cfg["first_expert_held"],
        scale=float(cfg["routed_scaling_factor"]),
    )


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, wte, *, eps):
    """The final norm and the TIED head: ``[rows, vocab]``."""
    with jax.default_matmul_precision("highest"):
        x = base._rms_norm(x, norm["scale"], eps)
        return x @ wte["embedding"].astype(F32).T


def _hidden(params, tokens, cfg: dict):
    """``(the last block's output [seq, h], the conv layers' output
    rms, per sparse layer the assignments to each of the router's
    outputs)`` of one sequence."""
    mixers, counts = [], []
    x = base._embed(params["wte"]["embedding"], tokens)
    for i, kind in enumerate(cfg["layer_types"]):
        x, rms, n = _block(
            x, params[f"block_{i}"], kind=kind, **block_kwargs(cfg)
        )
        if rms is not None:
            mixers.append(rms)
        if n is not None:
            counts.append(n)
    return x, mixers, counts


def forward(params, tokens, cfg: dict):
    """Per sequence the logits ``[seq, vocab]``, one sequence at a
    time."""
    return [
        _head(
            _hidden(params, row, cfg)[0], params["embedding_norm"],
            params["wte"], eps=cfg["norm_eps"],
        ) for row in tokens
    ]


def loss_and_said(params, tokens, targets, cfg: dict):
    """``(the training loss, {"counts" [sparse layers, router
    outputs], "out_rms" [sequences, conv layers]})``, differentiable;
    the float32 logits live ``ROWS`` rows at a time."""
    nll, mixers, counts = [], [], []
    for row, wanted in zip(tokens, targets):
        x, rms, n = _hidden(params, row, cfg)
        mixers.append(jnp.stack(rms))
        counts.append(jnp.stack(n))
        nll.append(_by_rows(
            lambda rows, t: base._nll_sum(_head(
                rows, params["embedding_norm"], params["wte"],
                eps=cfg["norm_eps"],
            ), t), ROWS, x, wanted,
        ).sum())
    return sum(nll) / targets.size, {
        "counts": sum(counts), "out_rms": jnp.stack(mixers),
    }


def loss(params, tokens, targets, cfg: dict) -> float:
    return float(np.asarray(
        loss_and_said(params, tokens, targets, cfg)[0]
    ))


def gradients(params, tokens, targets, cfg: dict, pick):
    """``(loss, said, {path: gradient})`` of the reference for the
    leaves ``pick`` names (``gradients_of`` of the ``sarvam_mla``
    reference)."""
    return base.gradients_of(
        lambda p, x, y: loss_and_said(p, x, y, cfg), pick, params,
        tokens, targets,
    )
