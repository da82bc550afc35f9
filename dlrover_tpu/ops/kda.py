"""The delta rule whose decay is a vector, one log-decay a key
CHANNEL, head and token (Kimi Delta Attention: Kimi Linear,
arXiv:2510.26692), computed over chunks in Pallas kernels: the walk of
``ops/gated_delta_rule.py`` (Gated DeltaNet, arXiv:2412.06464; the
chunk-wise form, arXiv:2406.06484) with the decay inside the operands.

Per head, with a state ``S`` in ``R^{d_k x d_v}`` that starts at 0::

    S_t = Diag(exp(g_t)) S_{t-1}
          + beta_t k_t (v_t - (Diag(exp(g_t)) S_{t-1})^T k_t)^T
    o_t = S_t^T q_t

``g_t`` in ``[LOWER, 0]^{d_k}`` is the log of the decay, a number a
channel; ``beta_t`` the write strength.  Over a chunk of ``CHUNK``
tokens, ``Gamma [C, d_k]`` the running sum of ``g`` inside the chunk::

    KK_ij  = sum_c k_ic k_jc exp(Gamma_ic - Gamma_jc)     (i > j)
    QK_ij  = sum_c q_ic k_jc exp(Gamma_ic - Gamma_jc)     (i >= j)
    A      = diag(beta) KK
    (W, U) = (I + A)^-1 (diag(beta) K * exp(Gamma), diag(beta) V)
    V'     = U - W S
    O      = (Q * exp(Gamma)) S + QK V'
    S     <- Diag(exp(Gamma_C)) S + (K * exp(Gamma_C - Gamma))^T V'

With one ``g`` a head the decay of ``KK`` and ``QK`` is a ``[C, C]``
matrix multiplied into the products; with one a channel there is no
such matrix and it goes INTO the operands, ``(K * exp(Gamma_i -
Gamma_r)) (K * exp(Gamma_r - Gamma_j))^T`` about a reference token
``r``, and an exponent that is positive overflows.  So the products
are made in levels, as the inverse's recursion is:

- the lower-left ``b x b`` corner of every ``2b`` block, ``b`` = 64,
  32 and ``SUB`` = 16, about the corner's FIRST ROW (the later
  sub-block's first token or one before it): a row of the corner
  takes ``exp(Gamma_i - Gamma_r)``, a column ``exp(Gamma_r -
  Gamma_j)``, BOTH exponents non-positive whatever ``g`` is; every
  row of the chunk has one part a level, so a level is one ``[C,
  d_k]`` tile of exponentials, one scaled ``K``, one scaled ``Q`` and
  one full-size matmul each for ``KK`` and ``QK``, masked to the
  corners;
- the ``SUB x SUB`` blocks of the diagonal about each block's first
  token: the column's exponent is positive there, at most ``(SUB - 1)
  x -LOWER`` = 75, inside float32's 88.7.  That is what the family's
  ``kda_safe_gate`` / ``kda_lower_bound: -5`` are for, and it is this
  kernel's contract (the exponent is held to ``EXP_MAX``, which a
  ``g`` inside the bound never reaches).  These operands stay float32
  and their matmuls run in three bf16 passes (``HIGHEST`` for float32
  callers).

``kda_fwd`` walks a head's chunks in order (``HEADS`` heads a grid
step) with the state in a VMEM scratch, writes ``O`` and, for the
backward, the state each chunk STARTS from and the chunk's inverse
``T``, both in the operands' type (67 MB each a layer at 1 x 8192 x
32 x 128 | 128).  ``kda_bwd`` walks the chunks in reverse with ``dS``
in VMEM, recomputes the levels, ``W``, ``U``, ``V'`` and emits all
five gradients; ``d Gamma [C, d_k]`` needs no reference token: a pair
``(i, j)`` adds ``dKK_ij k_ic k_jc exp(Gamma_ic - Gamma_jc)`` to row
``i`` and takes it from row ``j``, which is ``dKs * Ks`` of the scaled
operand on either side, whatever it was scaled about.  A
``jax.custom_vjp`` joins them.

Outside the kernels, in XLA: the layout into heads-leading ``[b h, s,
d]``, the tail's padding and ``Gamma`` (a cumulative sum of ``[b h, n,
C, d_k]`` float32; its transpose, a reverse cumulative sum a channel,
turns ``d Gamma`` into ``d g``), between ``optimization_barrier``s.
What this file shares with the scalar rule (the inverse and its
gradient, the matmul helpers, the layouts and barriers) is imported
from ``ops/gated_delta_rule.py``.  The scalar rule is the case in
which a head's channels share one ``g``
(``tests/test_bailing_hybrid.py``).

Precision: decays, running sums, the exponentials, the state, ``dS``,
the diagonal blocks and the inverse are float32; the other matmuls
take their operands in the type ``q`` arrives in.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.gated_delta_rule import (
    CHUNK,
    F32,
    NN,
    NT,
    TN,
    _as_row,
    _barrier,
    _dot,
    _heads_lead,
    _interpret,
    _inverse_unit_lower,
    _iotas,
    _lanes,
    _params,
    _solve_bwd,
    _split,
)

# Tokens a diagonal block holds, and the least log-decay a step the
# positive exponent is sized for: (SUB - 1) x -LOWER = 75 < 88.7.
SUB = 16
LOWER = -5.0
EXP_MAX = 85.0
# Heads a grid step holds (32 a layer; the unrolled body is lowered at
# every launch and twice the scalar rule's size).
HEADS = 2


def _dot32(a, b, contract, exact):
    """``_dot`` of two float32 operands at float32's worth: ``HIGHEST``
    where ``exact``, else three bf16 passes (``lo x lo`` dropped)."""
    if exact:
        return _dot(a, b, contract, True)
    (a_hi, a_lo), (b_hi, b_lo) = _split(a), _split(b)
    return (
        _dot(a_hi, b_hi, contract, False) + _dot(a_hi, b_lo, contract, False)
        + _dot(a_lo, b_hi, contract, False)
    )


def _row_of_block(x, size, at):
    """``x [C, d]``: every row replaced by row ``at`` of its block of
    ``size`` rows."""
    c, d = x.shape
    blocks = x.reshape(c // size, size, d)
    return jnp.broadcast_to(blocks[:, at:at + 1], blocks.shape).reshape(c, d)


def _levels(gamma):
    """The decay of ``KK`` and ``QK`` as operand scalings (the module's
    docstring): ``(corners, diagonal)``; ``corners`` a list of ``(e [C,
    d_k], sign [C, d_k], mask [C, C])``, a level each: ``e`` scales a
    row's ``k`` and ``q``, ``sign`` is +1 where the row is a corner's
    row and -1 where it is a corner's column; ``diagonal`` = ``(rows'
    scale, columns' scale, mask of the SUB x SUB blocks, i >= j)``."""
    c = gamma.shape[0]
    row, col = _iotas(c)
    apart = row ^ col
    at = jax.lax.broadcasted_iota(jnp.int32, gamma.shape, 0)
    corners = []
    b = c // 2
    while b >= SUB:
        first = _row_of_block(gamma, 2 * b, b)
        lower = (at & b) != 0
        e = jnp.exp(jnp.where(lower, gamma - first, first - gamma))
        mask = (row > col) & (apart < 2 * b) & (apart >= b)
        corners.append((e, jnp.where(lower, 1.0, -1.0), mask))
        b //= 2
    first = _row_of_block(gamma, SUB, 0)
    return corners, (
        jnp.exp(gamma - first),
        jnp.exp(jnp.minimum(first - gamma, EXP_MAX)),
        (row >= col) & (apart < SUB),
    )


def _chunk(q, k, v, gamma, beta, state, exact, t=None):
    """One chunk of one head, everything the forward and the backward
    share: ``q, k [C, d_k]``, ``v [C, d_v]``, ``gamma [C, d_k]``,
    ``beta [1, C]``, ``state [d_k, d_v]`` float32, ``t`` the inverse
    where it is kept."""
    c, dk = q.shape
    dv = v.shape[1]
    dtype = q.dtype
    row, col = _iotas(c)
    b_row = jnp.broadcast_to(beta, (c, c)).T
    k32, q32 = k.astype(F32), q.astype(F32)
    corners, (d_row, d_col, d_mask) = _levels(gamma)
    # the diagonal blocks, float32
    kr, kc, qr = d_row * k32, d_col * k32, d_row * q32
    kk = jnp.where(d_mask & (row > col), _dot32(kr, kc, NT, exact), 0.0)
    qk = jnp.where(d_mask, _dot32(qr, kc, NT, exact), 0.0)
    scaled = []
    for e, _, mask in corners:
        ks32, qs32 = e * k32, e * q32
        ks, qs = ks32.astype(dtype), qs32.astype(dtype)
        kk = kk + jnp.where(mask, _dot(ks, ks, NT, exact), 0.0)
        qk = qk + jnp.where(mask, _dot(qs, ks, NT, exact), 0.0)
        scaled.append((ks32, qs32, ks, qs))
    if t is None:
        # (rounded to bf16 right after, three bf16 passes lose nothing)
        t = _inverse_unit_lower(b_row * kk, exact).astype(dtype)
    grow = jnp.exp(gamma)                                 # [C, d_k]
    g_end = gamma[c - 1:]                                 # [1, d_k]
    # exp(Gamma_C - Gamma): what each write is worth at the chunk's end
    to_end = jnp.exp(g_end - gamma)
    # the state's decay over the chunk, a row of S a channel
    end = jnp.exp(g_end)
    end_rows = _lanes(jnp.broadcast_to(end, (dk, dk)).T, dv)
    # what a key writes: beta exp(Gamma)
    write = _lanes(b_row, dk) * grow
    vb = (_lanes(b_row, dv) * v).astype(dtype)
    kb32 = write * k32
    u = _dot(t, vb, NN, exact)
    w = _dot(t, kb32.astype(dtype), NN, exact).astype(dtype)
    sb = state.astype(dtype)
    v_new = u - _dot(w, sb, NN, exact)
    q_in32, k_end32 = grow * q32, to_end * k32
    return dict(
        corners=corners, scaled=scaled, d_row=d_row, d_col=d_col,
        d_mask=d_mask, kr=kr, kc=kc, qr=qr, kk=kk, t=t, b_row=b_row,
        grow=grow, to_end=to_end, end=end, end_rows=end_rows,
        write=write, kb32=kb32, u=u, w=w, sb=sb,
        vn=v_new.astype(dtype), q_in32=q_in32, k_end32=k_end32,
        q_in=q_in32.astype(dtype), k_end=k_end32.astype(dtype),
        p=qk.astype(dtype),
    )


def _fwd_kernel(
    q_ref, k_ref, v_ref, gamma_ref, beta_ref,
    o_ref, final_ref, start_ref, t_ref, state, *, exact,
):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for h in range(q_ref.shape[0]):
        s = state[h]
        start_ref[h, 0] = s.astype(start_ref.dtype)
        x = _chunk(
            q_ref[h], k_ref[h], v_ref[h], gamma_ref[h], beta_ref[h, 0],
            s, exact,
        )
        t_ref[h, 0] = x["t"]
        o = _dot(x["q_in"], x["sb"], NN, exact) + _dot(
            x["p"], x["vn"], NN, exact
        )
        o_ref[h] = o.astype(o_ref.dtype)
        state[h] = x["end_rows"] * s + _dot(x["k_end"], x["vn"], TN, exact)

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        final_ref[...] = state[...]


def _bwd_kernel(
    q_ref, k_ref, v_ref, gamma_ref, beta_ref, start_ref, t_ref,
    do_ref, dfinal_ref,
    dq_ref, dk_ref, dv_ref, dgamma_ref, dbeta_ref, dstate, *, exact,
):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = dfinal_ref[...]

    for h in range(q_ref.shape[0]):
        q, k, v = q_ref[h], k_ref[h], v_ref[h]
        dtype = q.dtype
        c, dk = q.shape
        dv = v.shape[1]
        s = start_ref[h, 0].astype(F32)
        x = _chunk(
            q, k, v, gamma_ref[h], beta_ref[h, 0], s, exact,
            t=t_ref[h, 0],
        )
        do = do_ref[h]
        ds = dstate[h]
        dsb = ds.astype(dtype)
        row, col = _iotas(c)
        b_row, sb, w, vn = x["b_row"], x["sb"], x["w"], x["vn"]

        # O = q_in S + P V';  S' = end S + k_end^T V'
        dv_new = _dot(x["p"], do, TN, exact) + _dot(
            x["k_end"], dsb, NN, exact
        )
        dvn = dv_new.astype(dtype)
        dqk = jnp.where(row >= col, _dot(do, vn, NT, exact), 0.0)
        dq_in = _dot(do, sb, NT, exact)                   # [C, d_k]
        dk_end = _dot(vn, dsb, NT, exact)                 # [C, d_k]
        # V' = U - W S; (W, U) = T (Kb, Vb)
        dw = -_dot(dvn, sb, NT, exact)
        dvb, da_u = _solve_bwd(x["t"], x["u"], dv_new, exact)
        dkb, da_w = _solve_bwd(x["t"], w, dw, exact)
        da = da_u + da_w
        dstate[h] = (
            _dot(x["q_in"], do, TN, exact) + x["end_rows"] * ds
            - _dot(w, dvn, TN, exact)
        )

        # A = beta_i KK_ij;  P = QK_ij: through the levels' operands
        dkk = da * b_row
        at_end = x["k_end32"] * dk_end
        dq = x["grow"] * dq_in
        dk_total = x["to_end"] * dk_end + x["write"] * dkb
        dgamma = x["q_in32"] * dq_in + x["kb32"] * dkb - at_end
        for (e, sign, mask), (ks32, qs32, ks, qs) in zip(
            x["corners"], x["scaled"]
        ):
            mk = jnp.where(mask, dkk, 0.0).astype(dtype)
            mq = jnp.where(mask, dqk, 0.0).astype(dtype)
            # a row of the chunk is a corner's row or its column, so
            # the two transposes of dKK meet in one sum
            dks = (
                _dot(mk, ks, NN, exact) + _dot(mk, ks, TN, exact)
                + _dot(mq, qs, TN, exact)
            )
            dqs = _dot(mq, ks, NN, exact)
            dq = dq + e * dqs
            dk_total = dk_total + e * dks
            dgamma = dgamma + sign * (dks * ks32 + dqs * qs32)
        mk = jnp.where(x["d_mask"] & (row > col), dkk, 0.0)
        mq = jnp.where(x["d_mask"], dqk, 0.0)
        dkr = _dot32(mk, x["kc"], NN, exact)
        dkc = _dot32(mk, x["kr"], TN, exact) + _dot32(
            mq, x["qr"], TN, exact
        )
        dqr = _dot32(mq, x["kc"], NN, exact)
        dq = dq + x["d_row"] * dqr
        dk_total = dk_total + x["d_row"] * dkr + x["d_col"] * dkc
        dgamma = dgamma + dkr * x["kr"] + dqr * x["qr"] - dkc * x["kc"]
        dq_ref[h] = dq.astype(dq_ref.dtype)
        dk_ref[h] = dk_total.astype(dk_ref.dtype)
        dv_ref[h] = (_lanes(b_row, dv) * dvb).astype(dv_ref.dtype)

        def rows(y):
            return jnp.sum(y, axis=1, keepdims=True)

        dbeta = (
            rows(v.astype(F32) * dvb)
            + rows(x["grow"] * k.astype(F32) * dkb)
            + rows(da * x["kk"])
        )
        # Gamma_C: every write's worth at the end, and the state's decay
        d_end = jnp.sum(at_end, axis=0, keepdims=True) + x["end"] * (
            _as_row(rows(s * ds))
        )
        at = jax.lax.broadcasted_iota(jnp.int32, dgamma.shape, 0)
        dgamma_ref[h] = dgamma + jnp.where(at == c - 1, d_end, 0.0)
        dbeta_ref[h, 0] = _as_row(dbeta)


def _heads_a_step(bh: int) -> int:
    return max(n for n in range(1, HEADS + 1) if bh % n == 0)


# (jitted: traced once for all of a model's layers and call sites)
@jax.jit
def _forward(q, k, v, gamma, beta):
    """``(o, final state, chunk-start states [bh, n, d_k, d_v], T [bh,
    n, C, C])`` of heads-leading operands: ``q, k [bh, s, d_k]``, ``v
    [bh, s, d_v]``, ``gamma [bh, s, d_k]`` and ``beta [bh, n, 1, C]``
    float32."""
    bh, s, dk = q.shape
    dv = v.shape[-1]
    n = s // CHUNK
    hb = _heads_a_step(bh)
    exact = q.dtype == F32

    def tokens(d):
        return pl.BlockSpec((hb, CHUNK, d), lambda i, j: (i, j, 0))

    gate = pl.BlockSpec((hb, 1, 1, CHUNK), lambda i, j: (i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, exact=exact),
        grid=(bh // hb, n),
        in_specs=[tokens(dk), tokens(dk), tokens(dv), tokens(dk), gate],
        out_specs=[
            tokens(dv),
            pl.BlockSpec((hb, dk, dv), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((hb, 1, dk, dv), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((hb, 1, CHUNK, CHUNK), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), F32),
            jax.ShapeDtypeStruct((bh, n, dk, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, n, CHUNK, CHUNK), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), F32)],
        compiler_params=_params(),
        interpret=_interpret(),
        name="kda_fwd",
    )(q, k, v, gamma, beta)


@jax.jit
def _backward(q, k, v, gamma, beta, starts, t, do, dfinal):
    bh, s, dk = q.shape
    dv = v.shape[-1]
    n = s // CHUNK
    hb = _heads_a_step(bh)
    exact = q.dtype == F32

    # the chunks in reverse
    def tokens(d):
        return pl.BlockSpec(
            (hb, CHUNK, d), lambda i, j: (i, n - 1 - j, 0)
        )

    def chunks(*tile):
        return pl.BlockSpec(
            (hb, 1) + tile, lambda i, j: (i, n - 1 - j, 0, 0)
        )

    gate = chunks(1, CHUNK)
    whole = pl.BlockSpec((hb, dk, dv), lambda i, j: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, exact=exact),
        grid=(bh // hb, n),
        in_specs=[
            tokens(dk), tokens(dk), tokens(dv), tokens(dk), gate,
            chunks(dk, dv), chunks(CHUNK, CHUNK), tokens(dv), whole,
        ],
        out_specs=[tokens(dk), tokens(dk), tokens(dv), tokens(dk), gate],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(gamma.shape, F32),
            jax.ShapeDtypeStruct(beta.shape, F32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), F32)],
        compiler_params=_params(),
        interpret=_interpret(),
        name="kda_bwd",
    )(q, k, v, gamma, beta, starts, t, do, dfinal)


def _operands(q, k, v, g, beta):
    """``[b, s, h, ..]`` as the kernels take them: heads lead the
    sequence, the tail of the last chunk padded with tokens that
    neither decay nor write (``g = 0``, ``beta = 0``), ``g`` summed up
    inside each chunk a channel (``[b h, s, d_k]`` float32), ``beta``
    as ``[b h, n, 1, C]`` float32 rows."""
    pad = -g.shape[1] % CHUNK
    gamma = _heads_lead(g.astype(F32), pad)
    bh, s, dk = gamma.shape
    gamma = jnp.cumsum(
        gamma.reshape(bh, s // CHUNK, CHUNK, dk), axis=2
    ).reshape(bh, s, dk)
    beta = _heads_lead(beta.astype(F32), pad)
    return (
        _heads_lead(q, pad), _heads_lead(k, pad), _heads_lead(v, pad),
        gamma, beta.reshape(bh, -1, 1, CHUNK),
    )


@jax.custom_vjp
def kda_rule(q, k, v, g, beta):
    """``q, k [b, s, h, d_k]``, ``v [b, s, h, d_v]``, ``g [b, s, h,
    d_k]`` in ``[LOWER, 0]``, ``beta [b, s, h]`` -> ``(o [b, s, h,
    d_v] in q's type, S_T [b, h, d_k, d_v] float32)``: the rule's
    outputs and the state after the last token.

    ``q`` and ``k`` arrive normalised and scaled by the caller.  Any
    ``s``."""
    return _rule_fwd(q, k, v, g, beta)[0]


def _rule_fwd(q, k, v, g, beta):
    b, s, h, dk = q.shape
    given = _barrier(q, k, v, g, beta)
    o, final, starts, t = _forward(*_operands(*given))
    o = jnp.moveaxis(o.reshape(b, h, -1, o.shape[-1]), 1, 2)[:, :s]
    return (*_barrier(o), final.reshape(b, h, dk, -1)), (given, starts, t)


def _rule_bwd(kept, cotangents):
    given, starts, t = kept
    do, dfinal = cotangents
    # behind a barrier with the cotangent, or the compiler shares the
    # forward's heads-leading copies and they live until here
    *given, do = _barrier(*given, do)
    operands, back = jax.vjp(_operands, *given)
    do = _heads_lead(do, operands[0].shape[1] - do.shape[1])
    dfinal = dfinal.reshape((-1,) + dfinal.shape[2:])
    return _barrier(
        *back(tuple(_backward(*operands, starts, t, do, dfinal)))
    )


kda_rule.defvjp(_rule_fwd, _rule_bwd)
