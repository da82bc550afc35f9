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

What a rematted caller keeps.  The forward rule names what ``kda_fwd``
wrote and anything reads after it (``RESIDUAL_NAMES``: ``o``, the
final state, the chunk-start states, ``T``), and a ``jax.checkpoint``
whose policy saves those names (``models/layers.py::remat_policy``)
does not run the forward kernel again in its backward, for 3 x 67 MB
a layer kept from forward to backward.  The five operands are not
named: their producers (projections, convolutions, the gates' row
kernel) run again, read by gradients of their own.

The kernels take the caller's arrays as the caller holds them, viewed
``[b, s, h d]`` (free: a head is ``d`` whole lanes of a row), in
blocks ``(1, CHUNK, heads a step x d)`` whose index map picks batch,
chunk and the heads' column; head ``h`` of a block is the lane slice
``[:, h d:(h + 1) d]``, and ``o`` and the gradients of ``q``, ``k``,
``v`` and ``g`` are written the same way.  ``Gamma`` is made in VMEM a
head and chunk (:func:`_running_sum`: seven shifted, masked float32
adds down the tokens, the same on both precisions), and ``kda_bwd``
ends with its transpose, ``d g = L^T d Gamma`` (the reverse running
sum; ``d Gamma_C`` reaches every token of the chunk).  So no
heads-leading copy of ``q``, ``k``, ``v``, ``g`` or ``o`` exists, no
running sum runs in XLA and the backward recomputes nothing outside
its kernel.  Outside the kernels, in XLA: ``beta``'s rows (``[b h, n,
1, C]`` float32, 1 MB a layer; ``d beta`` comes back the same way)
and, where ``s`` is no multiple of ``CHUNK``, the tail's padding.
What this file shares with the scalar rule (the inverse and its
gradient, the matmul helpers, ``beta``'s layout) is imported from
``ops/gated_delta_rule.py``; the scalar rule's heads of 96 | 192 lanes
are no whole columns, so it keeps its own entry path.  The scalar rule
is the case in which a head's channels share one ``g``
(``tests/test_bailing_hybrid.py``).

Precision: decays, running sums, the exponentials, the state, ``dS``,
the diagonal blocks and the inverse are float32; the other matmuls
take their operands in the type ``q`` arrives in.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.flash_attention import _named
from dlrover_tpu.ops.gated_delta_rule import (
    CHUNK,
    F32,
    NN,
    NT,
    TN,
    _as_row,
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
# what the forward kernel writes, under the names a remat policy keeps
# it by: ``o`` (``[b, s, h d_v]``), the final state, and the two arrays
# only the backward kernel reads, the chunk-start states and ``T``
RESIDUAL_NAMES = ("kda_o", "kda_final", "kda_starts", "kda_t")


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


def _running_sum(x, reverse=False):
    """The running sum of ``x [C, d]`` float32 down the tokens, ``L x``
    with ``L`` the 0/1 lower triangle, diagonal included (from the
    last token up with ``reverse``: ``L^T x``): ``log2 C`` shifted,
    masked adds, each a float32 add of two partial sums."""
    c = x.shape[0]
    at = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    k = 1
    while k < c:
        if reverse:
            x = x + jnp.where(at < c - k, pltpu.roll(x, c - k, 0), 0.0)
        else:
            x = x + jnp.where(at >= k, pltpu.roll(x, k, 0), 0.0)
        k *= 2
    return x


def _head(h, d):
    """Where head ``h``'s ``[C, d]`` lies in a ``(1, C, heads x d)``
    block: whole lanes of its rows."""
    return 0, slice(None), slice(h * d, (h + 1) * d)


def _fwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref,
    o_ref, final_ref, start_ref, t_ref, state, *, exact,
):
    n = pl.program_id(1)
    heads, dk, dv = state.shape

    @pl.when(n == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for h in range(heads):
        s = state[h]
        start_ref[h, 0] = s.astype(start_ref.dtype)
        at_k, at_v = _head(h, dk), _head(h, dv)
        x = _chunk(
            q_ref[at_k], k_ref[at_k], v_ref[at_v],
            _running_sum(g_ref[at_k]), beta_ref[h, 0], s, exact,
        )
        t_ref[h, 0] = x["t"]
        o = _dot(x["q_in"], x["sb"], NN, exact) + _dot(
            x["p"], x["vn"], NN, exact
        )
        o_ref[at_v] = o.astype(o_ref.dtype)
        state[h] = x["end_rows"] * s + _dot(x["k_end"], x["vn"], TN, exact)

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        final_ref[...] = state[...]


def _bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, start_ref, t_ref,
    do_ref, dfinal_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, *, exact,
):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = dfinal_ref[...]

    heads, dk, dv = dstate.shape
    for h in range(heads):
        at_k, at_v = _head(h, dk), _head(h, dv)
        q, k, v = q_ref[at_k], k_ref[at_k], v_ref[at_v]
        dtype = q.dtype
        c = q.shape[0]
        s = start_ref[h, 0].astype(F32)
        x = _chunk(
            q, k, v, _running_sum(g_ref[at_k]), beta_ref[h, 0], s, exact,
            t=t_ref[h, 0],
        )
        do = do_ref[at_v]
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
        dq_ref[at_k] = dq.astype(dq_ref.dtype)
        dk_ref[at_k] = dk_total.astype(dk_ref.dtype)
        dv_ref[at_v] = (_lanes(b_row, dv) * dvb).astype(dv_ref.dtype)

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
        # g_t is in Gamma_i for every i >= t of the chunk, Gamma_C too
        dg_ref[at_k] = _running_sum(dgamma, reverse=True) + d_end
        dbeta_ref[h, 0] = _as_row(dbeta)


def _heads_a_step(heads: int, dk: int, dv: int) -> int:
    """Heads a grid step holds, from the shapes: up to ``HEADS`` where
    a head is whole 128-lane columns of ``[b, s, h d]``; else the
    fewest heads that are, or the whole head axis (a block as wide as
    the array always lowers)."""
    whole = [
        n for n in range(1, heads + 1)
        if heads % n == 0 and n * dk % 128 == 0 and n * dv % 128 == 0
    ]
    few = [n for n in whole if n <= HEADS]
    return max(few) if few else min(whole, default=heads)


def _specs(b, h, hb, dk, dv, chunk_of):
    """The block specs of both kernels over a grid of ``(b x h / hb,
    chunks)``; ``chunk_of`` maps the grid's second index to the chunk
    it works on.  ``tokens``: the caller's ``[b, s, h d]``, a column of
    ``hb`` heads; the others are the kernels' own ``[b h, n, ..]``."""
    columns = h // hb

    def tokens(d):
        return pl.BlockSpec(
            (1, CHUNK, hb * d),
            lambda i, j: (i // columns, chunk_of(j), i % columns),
        )

    def chunks(*tile):
        return pl.BlockSpec(
            (hb, 1) + tile, lambda i, j: (i, chunk_of(j), 0, 0)
        )

    return dict(
        k=tokens(dk), v=tokens(dv), gate=chunks(1, CHUNK),
        starts=chunks(dk, dv), t=chunks(CHUNK, CHUNK),
        state=pl.BlockSpec((hb, dk, dv), lambda i, j: (i, 0, 0)),
    )


def _sizes(q, v, beta):
    """``(b, h, heads a step, d_k, d_v)`` of the kernels' operands."""
    b = q.shape[0]
    h = beta.shape[0] // b
    dk, dv = q.shape[2] // h, v.shape[2] // h
    return b, h, _heads_a_step(h, dk, dv), dk, dv


# (jitted: traced once for all of a model's layers and call sites)
@jax.jit
def _forward(q, k, v, g, beta):
    """``(o [b, s, h d_v], final state [b h, d_k, d_v], chunk-start
    states [b h, n, d_k, d_v], T [b h, n, C, C])`` of ``q, k [b, s, h
    d_k]``, ``v [b, s, h d_v]``, ``g [b, s, h d_k]`` and ``beta [b h,
    n, 1, C]`` float32, ``s`` whole chunks."""
    b, h, hb, dk, dv = sizes = _sizes(q, v, beta)
    n = q.shape[1] // CHUNK
    spec = _specs(*sizes, lambda j: j)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, exact=q.dtype == F32),
        grid=(b * h // hb, n),
        in_specs=[spec["k"], spec["k"], spec["v"], spec["k"], spec["gate"]],
        out_specs=[spec["v"], spec["state"], spec["starts"], spec["t"]],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, q.dtype),
            jax.ShapeDtypeStruct((b * h, dk, dv), F32),
            jax.ShapeDtypeStruct((b * h, n, dk, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, n, CHUNK, CHUNK), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), F32)],
        compiler_params=_params(),
        interpret=_interpret(),
        name="kda_fwd",
    )(q, k, v, g, beta)


@jax.jit
def _backward(q, k, v, g, beta, starts, t, do, dfinal):
    """-> ``(dq, dk, dv, dg, dbeta)`` in the layouts of
    :func:`_forward`."""
    b, h, hb, dk, dv = sizes = _sizes(q, v, beta)
    n = q.shape[1] // CHUNK
    # the chunks in reverse
    spec = _specs(*sizes, lambda j: n - 1 - j)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, exact=q.dtype == F32),
        grid=(b * h // hb, n),
        in_specs=[
            spec["k"], spec["k"], spec["v"], spec["k"], spec["gate"],
            spec["starts"], spec["t"], spec["v"], spec["state"],
        ],
        out_specs=[
            spec["k"], spec["k"], spec["v"], spec["k"], spec["gate"]
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(g.shape, F32),
            jax.ShapeDtypeStruct(beta.shape, F32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), F32)],
        compiler_params=_params(),
        interpret=_interpret(),
        name="kda_bwd",
    )(q, k, v, g, beta, starts, t, do, dfinal)


def _tokens(x, pad):
    """``[b, s, h, d]`` as the kernels take it, ``[b, s + pad, h d]``:
    the tail of the last chunk padded with tokens that neither decay
    nor write (``g = 0``, ``beta = 0``)."""
    b, s = x.shape[:2]
    return jnp.pad(x.reshape(b, s, -1), ((0, 0), (0, pad), (0, 0)))


def _operands(q, k, v, g, beta):
    """The caller's arrays as the kernels take them: ``q``, ``k``,
    ``v`` and ``g`` (float32) a row a token, ``beta [b, s, h]`` as
    ``[b h, n, 1, C]`` float32 rows."""
    pad = -g.shape[1] % CHUNK
    rows = _heads_lead(beta.astype(F32), pad)
    return (
        _tokens(q, pad), _tokens(k, pad), _tokens(v, pad),
        _tokens(g.astype(F32), pad),
        rows.reshape(rows.shape[0], -1, 1, CHUNK),
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
    o, final, starts, t = _forward(*_operands(q, k, v, g, beta))
    o_name, final_name, starts_name, t_name = RESIDUAL_NAMES
    # ``o`` and the final state go on into the block, so they are
    # named as bits; the other two are the residuals' alone
    o, final = _named(o, o_name), _named(final, final_name)
    return (
        o[:, :s].reshape(v.shape), final.reshape(b, h, dk, -1)
    ), (
        q, k, v, g, beta, checkpoint_name(starts, starts_name),
        checkpoint_name(t, t_name),
    )


def _rule_bwd(kept, cotangents):
    *given, starts, t = kept
    do, dfinal = cotangents
    b, s, h = given[-1].shape
    dq, dk, dv, dg, dbeta = _backward(
        *_operands(*given), starts, t, _tokens(do, -s % CHUNK),
        dfinal.reshape((-1,) + dfinal.shape[2:]),
    )
    dbeta = jnp.moveaxis(dbeta.reshape(b, h, -1), 1, 2)
    return tuple(
        d[:, :s].reshape(x.shape).astype(x.dtype)
        for d, x in zip((dq, dk, dv, dg, dbeta), given)
    )


kda_rule.defvjp(_rule_fwd, _rule_bwd)
