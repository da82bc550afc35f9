"""Plain reference for the ``gpt2`` configurations: GPT-2's forward pass
and next-token loss in straightforward ``jax.numpy`` and float32.

No kernels, no remat, no donation, no flax: the equations of Radford
et al. 2019 as the HF ``gpt2`` modelling code states them (pre-norm
blocks, fused qkv, ``gelu_new``, learned positions, output head tied
to the token embedding), written against the parameter tree the system
under test trains (``wte``, ``wpe``, ``block_<i>/{ln_attn, attn/{qkv,
o_proj}, ln_mlp, mlp/{fc_in, fc_out}}``, ``ln_f``).  It shares no code
with ``dlrover_tpu.models``.

Memory: the parameters arrive in the type they are served in (bf16)
and are up-cast to float32 INSIDE each jitted piece, one block at a
time, so no second float32 copy of the model ever lives on the chip;
one sequence is run at a time, so the float32 logits are
``seq x vocab`` (206 MB at 1024 x 50304).

On a TPU a float32 matmul runs in lower precision unless
``default_matmul_precision("highest")`` is set; every piece sets it.
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (
        1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3))
    )


@jax.jit
def _embed(wte, wpe, tokens):
    with jax.default_matmul_precision("highest"):
        seq = tokens.shape[0]
        return wte[tokens].astype(F32) + wpe[:seq].astype(F32)


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def _block(x, p, *, n_head, eps):
    """One pre-norm transformer block on one sequence ``[seq, h]``."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        seq, h = x.shape
        d = h // n_head
        a = _layer_norm(x, p["ln_attn"], eps)
        q, k, v = jnp.split(_dense(a, p["attn"]["qkv"]), 3, axis=-1)
        q = q.reshape(seq, n_head, d).transpose(1, 0, 2)
        k = k.reshape(seq, n_head, d).transpose(1, 0, 2)
        v = v.reshape(seq, n_head, d).transpose(1, 0, 2)
        scores = q @ k.transpose(0, 2, 1) / math.sqrt(d)
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = (probs @ v).transpose(1, 0, 2).reshape(seq, h)
        x = x + _dense(out, p["attn"]["o_proj"])
        m = _layer_norm(x, p["ln_mlp"], eps)
        m = _dense(_gelu_new(_dense(m, p["mlp"]["fc_in"])),
                   p["mlp"]["fc_out"])
        return x + m


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_loss(x, ln_f, wte, targets, *, eps):
    """Final norm, tied output head, mean next-token cross entropy."""
    with jax.default_matmul_precision("highest"):
        x = _layer_norm(x, _f32(ln_f), eps)
        logits = x @ wte.astype(F32).T
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[:, None], axis=-1)
        return nll.mean()


def loss(params, tokens, targets, cfg: dict) -> float:
    """Mean next-token loss over a batch ``[batch, seq]``, one
    sequence at a time (equal lengths, so the mean of the
    per-sequence means is the batch mean)."""
    n_head, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    wte = params["wte"]["embedding"]
    wpe = params["wpe"]["embedding"]
    total = 0.0
    for row in range(tokens.shape[0]):
        x = _embed(wte, wpe, tokens[row])
        for i in range(cfg["n_layer"]):
            x = _block(x, params[f"block_{i}"], n_head=n_head, eps=eps)
        total += float(
            _head_loss(x, params["ln_f"], wte, targets[row], eps=eps)
        )
    return total / tokens.shape[0]
