"""The state-space scan of a Mamba-2 layer, computed over chunks (the
"state space duality" form: Dao and Gu 2024, arXiv:2405.21060).

Per head ``h`` of ``H``, with a state ``S`` in ``R^{P x N}`` that starts
at 0, a scalar step ``dt_t > 0`` and a scalar ``A_h < 0``::

    S_t = exp(dt_t A_h) S_{t-1} + (dt_t x_t) B_t^T
    y_t = S_t C_t

``x_t`` in ``R^P`` is the head's own; ``B_t`` and ``C_t`` in ``R^N``
belong to the head's GROUP (``G`` groups of ``H / G`` heads share
them).  There is no delta correction and no inverse: the decay is a
scalar a head and token.  Token by token this is a scan of ``seq``
rank-one updates: no training path.  Over a chunk of ``chunk``
tokens, with ``cum`` the running sum of ``dt A`` inside the chunk and
``S`` the state the chunk starts from::

    L_ij = exp(cum_i - cum_j), i >= j
    Y    = ((C B^T) * L) (dt x) + (C * exp(cum)) S^T
    S   <- exp(cum_last) S + ((dt x) * exp(cum_last - cum))^T B

and the chunks' start states follow from their own contributions by
the decays between chunks, which for ``seq / chunk`` chunks is one
small matmul over the chunk axis (no loop: every part is an einsum
that XLA lowers to the MXU, batched over chunks, groups and heads).

What the backward keeps is the caller's business: plain autodiff of
this form keeps the ``[chunks, H, chunk, chunk]`` float32 decay
matrices, the masked scores and the chunks' states of every call, so
a model of many such layers calls it inside a rematted block
(``models/layers.py::rematted``, as ``models/nemotron_h.py`` does):
the block's backward makes the form again and a step holds one
layer's at a time.  No ``custom_vjp`` of its own: under that remat
one that keeps the five operands alone compiles to the same step,
instruction for instruction (PERF.md section 6, PR 47).

Precision: ``dt``, ``A``, the running sums, every decay and the state
are float32.  The matmuls take their operands in the type ``x``
arrives in (bf16 on the training path: the masked scores, ``dt x``
and, for the read-out alone, the chunk-start state are rounded to it)
and accumulate in float32; float32 operands run at ``HIGHEST``
throughout.  The hand-over between chunks is float32 at ``HIGHEST``
either way.
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _chunked(x, dt, A, B, C, chunk):
    b, s, heads, p = x.shape
    groups, n = B.shape[2:]
    per_group = heads // groups
    dtype = x.dtype
    precision = HIGHEST if dtype == F32 else None
    einsum = functools.partial(
        jnp.einsum, precision=precision, preferred_element_type=F32
    )
    # a tail that does not fill a chunk: dt = 0 neither decays nor
    # writes, so the final state is the last real token's
    pad = -s % chunk
    if pad:
        x, dt, B, C = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, B, C)
        )
    z = (s + pad) // chunk
    dt = dt.astype(F32)
    x_dt = (x.astype(F32) * dt[..., None]).reshape(
        b, z, chunk, groups, per_group, p
    )
    B = B.reshape(b, z, chunk, groups, n)
    C = C.reshape(b, z, chunk, groups, n)
    # [b, z, g, r, c]: the running sum of the log decay inside a chunk
    cum = jnp.cumsum(
        (dt * A.astype(F32)).reshape(
            b, z, chunk, groups, per_group
        ).transpose(0, 1, 3, 4, 2),
        axis=-1,
    )

    # inside a chunk: token i reads what tokens j <= i wrote
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        causal, cum[..., :, None] - cum[..., None, :], -jnp.inf
    ))
    scores = einsum("bzign,bzjgn->bzgij", C, B)
    y = einsum(
        "bzgrij,bzjgrp->bzigrp",
        (scores[:, :, :, None] * decay).astype(dtype), x_dt.astype(dtype),
    )

    # what each chunk adds to the state by its end
    to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 1, 4, 2, 3)
    own = einsum(
        "bzjgrp,bzjgn->bzgrpn",
        (x_dt * to_end[..., None]).astype(dtype), B,
    )

    # between chunks: the state chunk k starts from is every earlier
    # chunk's own part under the decays of the chunks between; row z
    # is the state after the last chunk
    total = jnp.pad(
        jnp.cumsum(cum[..., -1].transpose(0, 2, 3, 1), axis=-1),
        ((0, 0),) * 3 + ((1, 0),),
    )
    earlier = jnp.tril(jnp.ones((z + 1, z), bool), -1)
    between = jnp.exp(jnp.where(
        earlier, total[..., :, None] - total[..., None, 1:], -jnp.inf
    ))
    starts = jnp.einsum(
        "bgrkm,bmgrpn->bkgrpn", between, own, precision=HIGHEST
    )

    # the start state's part of a chunk's output
    from_start = jnp.exp(cum).transpose(0, 1, 4, 2, 3)
    y = y + from_start[..., None] * einsum(
        "bzign,bzgrpn->bzigrp", C, starts[:, :-1].astype(dtype)
    )
    y = y.reshape(b, s + pad, heads, p)[:, :s]
    return y.astype(dtype), starts[:, -1].reshape(b, heads, p, n)


def ssd_scan(
    x: jax.Array,    # [b, s, H, P]
    dt: jax.Array,   # [b, s, H]  float32, > 0 (after the softplus)
    A: jax.Array,    # [H]        float32, < 0
    B: jax.Array,    # [b, s, G, N]
    C: jax.Array,    # [b, s, G, N]
    chunk: int = 128,
):
    """``(y [b, s, H, P] in x's type, final state [b, H, P, N]
    float32)`` of the recurrence above from a zero state; head ``h``
    reads group ``h // (H / G)``.  Differentiable in all five
    operands.  The skip ``D x`` is the caller's (one multiply-add, no
    part of the recurrence)."""
    heads, groups = x.shape[2], B.shape[2]
    if heads % groups or B.shape != C.shape:
        raise ValueError(
            f"{heads} heads over B {B.shape} and C {C.shape}"
        )
    return _chunked(x, dt, A, B, C, chunk)
