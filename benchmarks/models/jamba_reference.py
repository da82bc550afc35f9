"""Plain reference for the ``jamba`` configurations (AI21-Jamba2-3B):
the forward pass and training loss of a decoder whose layers mix
tokens by a Mamba-1 selective state-space scan or, where ``i %
attn_layer_period == attn_layer_offset``, by causal attention with no
positional term, each followed by a dense SwiGLU, with a head TIED to
the embedding, in straightforward ``jax.numpy`` and float32.

No kernels, no chunked head, no remat of the program's, no flax, no
code of ``dlrover_tpu``: the layer equations of ``transformers``'
``modeling_jamba.py`` (``JambaMambaMixer.slow_forward``,
``JambaAttention``, ``JambaMLP``, ``JambaRMSNorm``, the two decoder
layers; ``tests/test_jamba.py`` holds this file against that code at a
toy size with copied weights), written against the parameter tree the
system under test trains (``wte``, ``block_<i>/{input_layernorm,
mamba/{in_proj, conv, conv_bias, x_proj, dt_layernorm, b_layernorm,
c_layernorm, dt_proj, dt_bias, A_log, D, out_proj} | attn/{q_proj,
k_proj, v_proj, o_proj}, pre_ff_layernorm, mlp/{gate_proj, up_proj,
down_proj}}``, ``final_layernorm``).  What is not this family's own
(the norm, SwiGLU, the embedding, the loss of a block of rows and the
picked-leaf gradients) is the ``sarvam_mla`` reference's and (the row
blocks) the ``mimo_v2`` reference's, beside this file.

Per block ``h = x + Mixer(RMSNorm(x))``, ``y = h + SwiGLU(RMSNorm(h))``;
after the last block ``final_layernorm``; logits ``= h E^T`` with ``E``
the embedding.

Mamba mixer: ``[x | z] = u W_in``; the depthwise causal convolution as
``K`` SHIFTED ADDS (``c_t = sum_j w_j x_{t-K+1+j}``, zeros before row
0) plus its bias, then SiLU; ``[dt_r | B | C] = x W_x``; an RMSNorm
each; ``dt = softplus(dt_r W_dt + b_dt)``; ``A = -exp(A_log)``; the
recurrence TOKEN BY TOKEN, a ``lax.scan`` of ``seq`` steps over a
float32 state ``[E, N]`` that starts at zero, ``h <- exp(dt A) h + (dt
x) B^T``, ``y = h C + D x`` (cut into segments of ``SEGMENT`` steps,
each a ``jax.checkpoint``, so that a gradient keeps one segment's
``[SEGMENT, E, N]`` states and not a state a token; the values are the
same); ``out = (y * SiLU(z)) W_out``.  The mixer also says its final
state's root mean square.

Departures from ``modeling_jamba.py``, none of which float32 can see
(``tests/test_jamba.py``): the read-out ``h C`` is float32 (the
family's CUDA path; the slow path rounds the state to the activations'
type first); a norm multiplies by its scale BEFORE the cast back to
the activations' type (``JambaRMSNorm`` after it).

Attention: ``q``, ``k``, ``v`` by three matrices; ``H`` query heads
and ``G`` kv heads of ``d``, the kv heads REPEATED ``H / G`` times; a
MATERIALISED causal mask; softmax at ``d^-1/2``; no positional term.

Scores are taken ``ATTN_ROWS`` query rows at a time (20 heads x 512 x
8192 float32 scores are 0.34 GB), the SwiGLU, the head and its loss
``ROWS`` at a time; each block and each such pass is a
``jax.checkpoint`` so that the GRADIENT fits beside the train state.
Every jitted piece sets ``default_matmul_precision("highest")``.
Loss: mean next-token cross entropy over the whole vocabulary.
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
# steps of the recurrence between two kept states
SEGMENT = 128
MAMBA, ATTENTION = "mamba", "attention"


def layer_types(cfg: dict):
    """``JambaConfig.layers_block_type`` of ``transformers``."""
    return [
        ATTENTION
        if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
        else MAMBA
        for i in range(cfg["num_hidden_layers"])
    ]


def _kernel(p, name):
    return p[name]["kernel"].astype(F32)


def causal_conv(x, taps, bias):
    """Depthwise, ``x [seq, E]``, ``taps [K, E]``: ``K`` shifted adds,
    zeros before row 0, then the bias."""
    seq, lanes = x.shape
    k = taps.shape[0]
    out = jnp.zeros_like(x)
    for j in range(k):
        shift = k - 1 - j  # taps[j] meets x_{t - shift}
        out = out + jnp.concatenate(
            [jnp.zeros((shift, lanes), F32), x[:seq - shift]]
        ) * taps[j]
    return out + bias


def recurrence(x, dt, A, B, C):
    """``(y [seq, E], the final state [E, N])`` of ``h_t = exp(dt_t A)
    h_{t-1} + (dt_t x_t) B_t^T``, ``y_t = h_t C_t``, one token a step
    from a zero state; ``x, dt [seq, E]``, ``A [E, N]``, ``B, C [seq,
    N]``, all float32."""
    seq = x.shape[0]

    def token(h, at):
        x_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * x_t)[:, None] * b_t
        return h, jnp.sum(h * c_t, axis=1)

    def segment(h, ats):
        return jax.lax.scan(token, h, ats)

    # a tail that fills no segment: dt = 0 leaves the state be
    pad = -seq % SEGMENT
    ats = tuple(
        jnp.pad(a, ((0, pad), (0, 0))).reshape(
            (-1, SEGMENT) + a.shape[1:]
        ) for a in (x, dt, B, C)
    )
    h, y = jax.lax.scan(
        jax.checkpoint(segment), jnp.zeros(A.shape, F32), ats
    )
    return y.reshape(seq + pad, -1)[:seq], h


def mamba(u, p, *, eps):
    """One sequence ``[seq, h]`` -> ``(out [seq, h], the final state
    [E, N])``."""
    n = p["A_log"].shape[1]
    rank = p["dt_proj"].shape[0]
    xz = u @ _kernel(p, "in_proj")
    inner = xz.shape[1] // 2
    x = jax.nn.silu(causal_conv(
        xz[:, :inner], p["conv"].astype(F32), p["conv_bias"].astype(F32)
    ))
    params = x @ _kernel(p, "x_proj")
    dt_r = base._rms_norm(
        params[:, :rank], p["dt_layernorm"]["scale"], eps
    )
    B = base._rms_norm(
        params[:, rank:rank + n], p["b_layernorm"]["scale"], eps
    )
    C = base._rms_norm(
        params[:, rank + n:], p["c_layernorm"]["scale"], eps
    )
    dt = jax.nn.softplus(
        dt_r @ p["dt_proj"].astype(F32) + p["dt_bias"].astype(F32)
    )
    y, state = recurrence(
        x, dt, -jnp.exp(p["A_log"].astype(F32)), B, C
    )
    y = y + p["D"].astype(F32) * x
    return (y * jax.nn.silu(xz[:, inner:])) @ _kernel(p, "out_proj"), state


def attention(x, p, *, heads, kv, d):
    """One sequence ``[seq, h]`` -> ``[seq, h]``."""
    seq, _ = x.shape

    def heads_of(t, n):
        return t.reshape(seq, n, d).transpose(1, 0, 2)   # [n, seq, d]

    q = heads_of(x @ _kernel(p, "q_proj"), heads)
    # the kv heads repeated: query head h reads kv head h // group
    k = jnp.repeat(heads_of(x @ _kernel(p, "k_proj"), kv), heads // kv, 0)
    v = jnp.repeat(heads_of(x @ _kernel(p, "v_proj"), kv), heads // kv, 0)

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
    return out @ _kernel(p, "o_proj")


@functools.partial(jax.jit, static_argnames=("heads", "kv", "d", "eps"))
def _block(x, p, *, heads, kv, d, eps):
    """One block on one sequence ``[seq, h]``: ``(y, the state-space
    mixer's final-state rms or None)``."""

    def feed_forward(m):
        mlp = p["mlp"]
        return base._swiglu(
            m, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"],
            mlp["down_proj"]["kernel"],
        )

    def block(x, p):
        a = base._rms_norm(x, p["input_layernorm"]["scale"], eps)
        if "mamba" in p:
            mixed, state = mamba(a, p["mamba"], eps=eps)
            rms = jnp.sqrt(jnp.mean(state * state))
        else:
            mixed, rms = attention(
                a, p["attn"], heads=heads, kv=kv, d=d
            ), None
        x = x + mixed
        out = _by_rows(
            feed_forward, ROWS,
            base._rms_norm(x, p["pre_ff_layernorm"]["scale"], eps),
        )
        return x + out.reshape(x.shape), rms

    with jax.default_matmul_precision("highest"):
        return jax.checkpoint(block)(x, p)


def block_kwargs(cfg: dict) -> dict:
    return dict(
        heads=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
        d=cfg["hidden_size"] // cfg["num_attention_heads"],
        eps=cfg["rms_norm_eps"],
    )


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, wte, *, eps):
    """The final norm and the TIED head: ``[rows, vocab]``."""
    with jax.default_matmul_precision("highest"):
        x = base._rms_norm(x, norm["scale"], eps)
        return x @ wte["embedding"].astype(F32).T


def _hidden(params, tokens, cfg: dict):
    """``(the last block's output [seq, h], the state-space layers'
    final-state rms)`` of one sequence."""
    rms = []
    x = base._embed(params["wte"]["embedding"], tokens)
    for i in range(cfg["num_hidden_layers"]):
        x, said = _block(x, params[f"block_{i}"], **block_kwargs(cfg))
        if said is not None:
            rms.append(said)
    return x, rms


def forward(params, tokens, cfg: dict):
    """Per sequence the logits ``[seq, vocab]``, one sequence at a
    time."""
    return [
        _head(
            _hidden(params, row, cfg)[0], params["final_layernorm"],
            params["wte"], eps=cfg["rms_norm_eps"],
        ) for row in tokens
    ]


def loss_and_said(params, tokens, targets, cfg: dict):
    """``(the training loss, {"state_rms" [sequences, state-space
    layers]})``, differentiable; the float32 logits live ``ROWS`` rows
    at a time."""
    nll, rms = [], []
    for row, wanted in zip(tokens, targets):
        x, r = _hidden(params, row, cfg)
        rms.append(jnp.stack(r))
        nll.append(_by_rows(
            lambda rows, t: base._nll_sum(_head(
                rows, params["final_layernorm"], params["wte"],
                eps=cfg["rms_norm_eps"],
            ), t), ROWS, x, wanted,
        ).sum())
    return sum(nll) / targets.size, {"state_rms": jnp.stack(rms)}


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
