"""Sequence/context parallelism over the ``sequence`` mesh axis.

Two schemes:

1. **Ulysses-style SP** (reference parity: ``_SeqAllToAll`` +
   ``create_sequence_parallel_group``,
   ``atorch/distributed/distributed.py:435-501``): activations are
   sequence-sharded; an all-to-all swaps sequence-sharding for
   head-sharding so each device runs full-sequence attention on a head
   subset, then swaps back.  Constraints: ``num_heads % sp == 0`` and
   ``seq % sp == 0`` (same as the reference).  On TPU the all-to-all
   is a single XLA collective riding ICI.

2. **Ring/blockwise attention** (context parallelism — not present in
   the reference, flagged in SURVEY.md §2.8 as the idiomatic TPU
   extension): K/V shards rotate around the ring via
   ``lax.ppermute`` while each device accumulates online-softmax
   partials for its local queries, so sequence length scales with the
   number of devices without ever materializing full K/V on one chip.

Also :func:`shard_local_attention`, the map that data/tensor
parallelism needs around an attention kernel GSPMD cannot split.
"""

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from dlrover_tpu.ops.attention import xla_causal_attention
from dlrover_tpu.parallel.mesh import batch_axes


def _check_divisible(name, value, by):
    if value % by:
        raise ValueError(f"{name}={value} must be divisible by {by}")


# ---------------------------------------------------------------------------
# Ulysses SP
# ---------------------------------------------------------------------------


def ulysses_attention(
    attn_fn: Callable,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    axis: str = "sequence",
    **attn_kwargs,
):
    """Run ``attn_fn`` under sequence parallelism.

    Inputs are [batch, seq, heads, head_dim] sharded on ``seq`` over
    ``axis``; ``attn_fn(q, k, v, **kw)`` sees full-sequence,
    head-sharded tensors.
    """
    sp = mesh.shape[axis]
    if sp == 1:
        return attn_fn(q, k, v, **attn_kwargs)
    b, s, h, d = q.shape
    _check_divisible("num_heads", h, sp)
    _check_divisible("seq", s, sp)

    def local(q, k, v):
        # [b, s/sp, h, d] -> [b, s, h/sp, d]
        def fwd_a2a(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=2, concat_axis=1, tiled=True
            )

        def rev_a2a(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=1, concat_axis=2, tiled=True
            )

        out = attn_fn(fwd_a2a(q), fwd_a2a(k), fwd_a2a(v), **attn_kwargs)
        return rev_a2a(out)

    spec = P(("data", "fsdp"), axis, None, None)
    return shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Per-shard attention (data / tensor parallel)
# ---------------------------------------------------------------------------


def shard_local_attention(
    attn_fn: Callable,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    **attn_kwargs,
):
    """Run ``attn_fn`` once per shard of ``mesh``: batch over the data
    axes, heads over ``tensor``.

    For an ``attn_fn`` GSPMD cannot split by itself — a Mosaic kernel
    ("cannot be automatically partitioned").  Every shard is a whole
    attention problem, so no collective is needed.  A dimension its
    axes do not divide stays replicated (every device then computes
    all of it).  ``k``/``v`` may carry fewer
    heads than ``q`` (GQA, kv-head-major): contiguous head blocks keep
    each q head with its kv head.
    """
    over_batch = tuple(a for a in batch_axes() if mesh.shape[a] > 1)
    if q.shape[0] % math.prod(mesh.shape[a] for a in over_batch):
        over_batch = ()
    tensor = mesh.shape["tensor"]
    over_heads = (
        "tensor" if tensor > 1 and q.shape[2] % tensor == 0
        and k.shape[2] % tensor == 0 else None
    )
    spec = P(over_batch or None, None, over_heads, None)
    return shard_map(
        functools.partial(attn_fn, **attn_kwargs), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Ring / blockwise attention (context parallel)
# ---------------------------------------------------------------------------


def _block_partials(q, k, v, q_off, k_off, scale, causal):
    """Online-softmax partials of one (q_block, kv_block) pair.

    Shapes: q [b, sq, h, d]; k/v [b, sk, h, d].  Returns
    (unnormalized acc [b, sq, h, d] f32, m [b, sq, h], l [b, sq, h]).
    """
    logits = (
        jnp.einsum(
            "bqhd,bkhd->bhqk",
            q.astype(jnp.float32),
            k.astype(jnp.float32),
        )
        * scale
    )
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        q_pos = q_off + jnp.arange(sq)[:, None]
        k_pos = k_off + jnp.arange(sk)[None, :]
        logits = jnp.where(
            (q_pos >= k_pos)[None, None], logits, -jnp.inf
        )
    m = jnp.max(logits, axis=-1)  # [b, h, sq]
    # fully-masked rows: keep exp() finite
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    l = jnp.sum(p, axis=-1)  # [b, h, sq]
    acc = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    to_bqh = lambda x: x.transpose(0, 2, 1)  # [b,h,sq] -> [b,sq,h]
    return acc, to_bqh(jnp.where(jnp.isfinite(m), m, -jnp.inf)), to_bqh(l)


def _merge(acc, m, l, acc2, m2, l2):
    """Combine two online-softmax partial sets."""
    m_new = jnp.maximum(m, m2)
    m_new_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    c1 = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new_safe), 0.0)
    c2 = jnp.where(jnp.isfinite(m2), jnp.exp(m2 - m_new_safe), 0.0)
    acc_new = acc * c1[..., None] + acc2 * c2[..., None]
    l_new = l * c1 + l2 * c2
    return acc_new, m_new, l_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    axis: str = "sequence",
    causal: bool = True,
    scale: Optional[float] = None,
):
    """Context-parallel attention: K/V rotate around the ring.

    Inputs [batch, seq, heads, head_dim] with seq sharded over
    ``axis``; output sharded the same way.  Peak memory per device is
    one [s/sp, s/sp] logits block — long sequences scale with ring
    size.  Differentiable end-to-end (autodiff through the scan +
    ppermute; each block uses the online-softmax partials above).
    """
    sp = mesh.shape[axis]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if sp == 1:
        # no ring to rotate (running the ring machinery on one device
        # would only add a no-op scan + self-permute)
        if causal:
            return xla_causal_attention(q, k, v, dtype=q.dtype)
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k,
            preferred_element_type=jnp.float32,
        ) * scale
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    b, s, h, d = q.shape
    _check_divisible("seq", s, sp)
    s_loc = s // sp

    def local(q, k, v):
        idx = jax.lax.axis_index(axis)
        q_off = idx * s_loc
        perm = [(j, (j + 1) % sp) for j in range(sp)]

        def step(carry, step_idx):
            acc, m, l, k_cur, v_cur = carry
            src = (idx - step_idx) % sp  # whose shard we now hold

            def block(q, k_cur, v_cur, acc, m, l):
                acc2, m2, l2 = _block_partials(
                    q, k_cur, v_cur, q_off, src * s_loc, scale,
                    causal,
                )
                return _merge(acc, m, l, acc2, m2, l2)

            # remat per ring step: without it autodiff stores every
            # step's [s_loc, s_loc] logits (sp blocks alive at once in
            # the backward), capping the reachable context length;
            # recomputing one block at a time keeps peak memory at a
            # single block
            acc, m, l = jax.checkpoint(block)(
                q, k_cur, v_cur, acc, m, l
            )
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return (acc, m, l, k_nxt, v_nxt), None

        bl = q.shape[0]  # local batch (global / dp shards)
        acc0 = jnp.zeros((bl, s_loc, h, d), jnp.float32)
        m0 = jnp.full((bl, s_loc, h), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((bl, s_loc, h), jnp.float32)
        (acc, m, l, _, _), _ = jax.lax.scan(
            step, (acc0, m0, l0, k, v), jnp.arange(sp)
        )
        safe_l = jnp.where(l == 0.0, 1.0, l)
        return (acc / safe_l[..., None]).astype(q.dtype)

    spec = P(("data", "fsdp"), axis, None, None)
    return shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False,
    )(q, k, v)
