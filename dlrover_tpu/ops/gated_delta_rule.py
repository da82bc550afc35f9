"""The gated delta rule of a linear-attention layer, computed over
chunks (Gated DeltaNet: Yang, Kautz, Hatamizadeh 2024,
arXiv:2412.06464; the chunk-wise form: Yang et al. 2024,
arXiv:2406.06484).

Per head, with a state ``S`` in ``R^{d_k x d_v}`` that starts at 0::

    S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

``g_t <= 0`` is the log of the decay and ``beta_t`` the write strength
(up to 2 where the layer allows negative eigenvalues).  Token by token
this is a scan of ``seq`` steps of rank-one updates: no training path.
Over a chunk of ``CHUNK`` tokens, with ``gamma`` the running sum of
``g`` inside the chunk and ``S`` the state the chunk starts from::

    A      = strictLower(diag(beta) K K^T * exp(gamma_i - gamma_j))
    (W, U) = (I + A)^-1 (diag(beta exp(gamma)) K,  diag(beta) V)
    V'     = U - W S
    O      = (Q * exp(gamma)) S + (Q K^T * exp(gamma_i - gamma_j), i >= j) V'
    S     <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

Everything but ``V'`` and the state's hand-over is batched matmuls
over all chunks at once; the hand-over is a ``lax.scan`` of ``seq /
CHUNK`` steps of two small matmuls, and ``O`` is computed after it from
the states the scan emits.  The backward is autodiff through all of
it (the model's per-block remat decides what is kept).

Precision: decays, running sums, the state and the inverse of ``I +
A`` are float32; the other matmuls take their operands in the type
``q`` arrives in (bf16 on the training path, float32 accumulation).
Float32 operands run at ``HIGHEST`` throughout; with bf16 operands
the inverse, which is rounded to bf16 for the next matmul, runs at
``HIGH`` (three bf16 passes).
"""

import functools

import jax
import jax.numpy as jnp

# Tokens a chunk holds.  128 against 32 and 64 on the chip: PERF.md,
# PR 32 (a float32 minor dimension under 128 is padded to the lanes,
# and the hand-over is seq / C steps).
CHUNK = 128
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _inverse_unit_lower(a, precision=HIGHEST):
    """``(I + A)^-1`` for strictly lower-triangular ``A [..., C, C]``.

    Block recursion, in matmuls: with ``X`` the inverse of the diagonal
    blocks of size ``b`` (block diagonal), the inverse at ``2b`` is ``X
    - X L21 X``, ``L21`` the lower-left ``b x b`` corner of each ``2b``
    block of ``A``.  As stable as forward substitution; the Neumann
    product ``(I - A)(I + A^2)(I + A^4)..`` is not (powers of ``A``
    grow with write strengths near 2 before they cancel).

    Its gradient needs the result alone (``dA = -T^T dT T^T``), so
    none of the recursion's levels is kept for the backward."""
    c = a.shape[-1]
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    # blocks of 2: [[1, 0], [a, 1]]^-1 = [[1, 0], [-a, 1]]
    x = jnp.where(
        (row // 2 == col // 2) & (row > col), -a,
        (row == col).astype(F32),
    )
    b = 2
    while b < c:
        corner = (
            (row // (2 * b) == col // (2 * b)) & (row // b != col // b)
            & (row > col)
        )
        l21 = jnp.where(corner, a, 0.0)
        x = x - jnp.matmul(
            jnp.matmul(x, l21, precision=precision), x,
            precision=precision,
        )
        b *= 2
    return x


def _inverse_fwd(a, precision):
    t = _inverse_unit_lower(a, precision)
    return t, t


def _inverse_bwd(precision, t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    da = -jnp.matmul(
        jnp.matmul(tt, dt, precision=precision), tt, precision=precision
    )
    c = t.shape[-1]
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    return (jnp.where(strict, da, 0.0),)


_inverse_unit_lower.defvjp(_inverse_fwd, _inverse_bwd)


def _chunked(x, n):
    """``[b, s, h, ...] -> [n, b, h, C, ...]``: chunks lead (the scan's
    axis), heads batch the matmuls."""
    b, _, h = x.shape[:3]
    x = x.reshape((b, n, CHUNK, h) + x.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)


def gated_delta_rule(q, k, v, g, beta):
    """``q, k [b, s, h, d_k]``, ``v [b, s, h, d_v]``, ``g, beta [b, s,
    h]`` -> ``(o [b, s, h, d_v] in q's type, S_T [b, h, d_k, d_v]
    float32)``: the rule's outputs and the state after the last token.

    ``q`` and ``k`` arrive normalised and scaled by the caller.  Any
    ``s``: the tail of the last chunk is padded with tokens that
    neither decay nor write (``g = 0``, ``beta = 0``)."""
    b, s, h, _ = q.shape
    dtype = q.dtype
    exact = dtype == jnp.float32
    precision = HIGHEST if exact else None
    mm = functools.partial(
        jnp.einsum, preferred_element_type=F32, precision=precision
    )
    pad = -s % CHUNK
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta)
        )
    n = (s + pad) // CHUNK
    q, k, v = _chunked(q, n), _chunked(k, n), _chunked(v, n)
    g, beta = _chunked(g.astype(F32), n), _chunked(beta.astype(F32), n)

    gamma = jnp.cumsum(g, axis=-1)                      # [n, b, h, C]
    row = jnp.arange(CHUNK)[:, None]
    col = jnp.arange(CHUNK)[None, :]
    # exp(gamma_i - gamma_j) where i >= j, 0 above the diagonal (the
    # exponent is masked, not the result: above it is positive)
    decay = jnp.exp(jnp.where(
        row >= col, gamma[..., :, None] - gamma[..., None, :], -jnp.inf
    ))
    kk = mm("nbhid,nbhjd->nbhij", k, k)
    a = jnp.where(row > col, beta[..., None] * kk * decay, 0.0)
    # (rounded to bf16 right after, three bf16 passes lose nothing)
    t = _inverse_unit_lower(
        a, HIGHEST if exact else jax.lax.Precision.HIGH
    ).astype(dtype)
    grow = jnp.exp(gamma)
    u = mm("nbhij,nbhjd->nbhid", t, (beta[..., None] * v).astype(dtype))
    w = mm(
        "nbhij,nbhjd->nbhid", t,
        ((beta * grow)[..., None] * k).astype(dtype),
    )
    # K * exp(gamma_C - gamma): what each write is worth at the
    # chunk's end
    k_end = (jnp.exp(gamma[..., -1:] - gamma)[..., None] * k).astype(dtype)
    chunk_decay = grow[..., -1]                          # [n, b, h]

    def hand_over(state, xs):
        u_n, w_n, k_end_n, decay_n = xs
        v_new = u_n - mm("bhid,bhde->bhie", w_n, state.astype(dtype))
        after = decay_n[..., None, None] * state + mm(
            "bhid,bhie->bhde", k_end_n, v_new.astype(dtype)
        )
        return after, (state, v_new)

    state0 = jnp.zeros((b, h, k.shape[-1], v.shape[-1]), F32)
    final, (states, v_new) = jax.lax.scan(
        hand_over, state0, (u, w, k_end, chunk_decay)
    )
    qk = mm("nbhid,nbhjd->nbhij", q, k) * decay
    o = mm(
        "nbhid,nbhde->nbhie", (grow[..., None] * q).astype(dtype),
        states.astype(dtype),
    ) + mm("nbhij,nbhje->nbhie", qk.astype(dtype), v_new.astype(dtype))
    # [n, b, h, C, d_v] -> [b, s, h, d_v]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)
    o = o.reshape(b, s + pad, h, -1)[:, :s].astype(dtype)
    return o, final
