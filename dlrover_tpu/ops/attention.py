"""Attention written out in plain jax: what the kernels and the
sequence-parallel schemes fall back to, and what tests compare them
with.  All take ``[batch, seq, heads, head_dim]``."""

from typing import Optional

import jax
import jax.numpy as jnp


def xla_causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, dtype=jnp.bfloat16,
    scale: Optional[float] = None,
) -> jax.Array:
    """Plain causal attention; XLA fuses softmax chains well on TPU.

    q,k,v: [batch, seq, heads, head_dim] -> v's shape out (v's head
    size may differ from q's and k's); ``scale`` defaults to q's
    ``head_dim ** -0.5``.
    """
    seq = q.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    mask = jnp.tril(jnp.ones((seq, seq), dtype=bool))
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def xla_window_attention(
    q, k, v, window: Optional[int], dtype, sink=None,
    return_lse: bool = False,
):
    """Plain grouped-query attention with the mask written out:
    ``[b, s, heads, d]`` queries over ``[b, s, kv heads, d]`` keys and
    values (``v``'s head size may differ), query head ``j`` reading kv
    head ``j // group``.  ``sink`` (``[heads]`` float32) is one more
    column of every row's scores, dropped after the softmax;
    ``return_lse`` also gives the rows' log-sum-exp ``[b, heads, s]``,
    the sink in it."""
    b, s, heads, d = q.shape
    kv = k.shape[2]
    q = q.reshape(b, s, kv, heads // kv, d)
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32
    ) * d ** -0.5
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = ahead >= 0
    if window is not None:
        seen = seen & (ahead < window)
    logits = jnp.where(seen, logits, -1e30)
    if sink is not None:
        column = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, kv, heads // kv, 1, 1),
            logits.shape[:-1] + (1,),
        )
        logits = jnp.concatenate([logits, column], axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    if sink is not None:
        probs = probs[..., :s]
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", probs.astype(dtype), v
    ).reshape(b, s, heads, v.shape[-1])
    if not return_lse:
        return out
    lse = jax.nn.logsumexp(jax.lax.stop_gradient(logits), axis=-1)
    return out, lse.reshape(b, heads, s)


def cached_decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    q_pos: jax.Array, dtype=jnp.bfloat16,
) -> jax.Array:
    """Chunked decode attention against a KV cache.

    ``q``: [b, s_new, h, d] (prompt prefill or a 1-token step);
    ``k_cache``/``v_cache``: [b, max_len, kv_heads, d] with this
    chunk already written (``kv_heads`` may divide ``h`` — GQA);
    ``q_pos``: [s_new] absolute positions.  Masks both causality
    inside the chunk and the unfilled cache tail.
    """
    b, s, h, d = q.shape
    kvh = k_cache.shape[2]
    group = h // kvh
    qg = q.reshape(b, s, kvh, group, d)
    scale = d**-0.5
    logits = jnp.einsum(
        "bqkgd,bmkd->bkgqm", qg, k_cache,
        preferred_element_type=jnp.float32,
    ) * scale
    k_pos = jnp.arange(k_cache.shape[1])
    mask = k_pos[None, :] <= q_pos[:, None]  # [s_new, max_len]
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    out = jnp.einsum("bkgqm,bmkd->bqkgd", probs, v_cache)
    return out.reshape(b, s, h, d)
