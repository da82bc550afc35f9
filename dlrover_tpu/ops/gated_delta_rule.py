"""The gated delta rule of a linear-attention layer, computed over
chunks in Pallas kernels (Gated DeltaNet: Yang, Kautz, Hatamizadeh
2024, arXiv:2412.06464; the chunk-wise form: Yang et al. 2024,
arXiv:2406.06484).

Per head, with a state ``S`` in ``R^{d_k x d_v}`` that starts at 0::

    S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

``g_t <= 0`` is the log of the decay, ONE number a head and token
(the rule whose decay is a vector, a number a key channel, lives in
``ops/kda.py`` and imports this file's inverse, its gradient, the
matmul helpers, the layouts and the barriers), and ``beta_t`` the write
strength (up to 2 where the layer allows negative eigenvalues).  Token by token
this is a scan of ``seq`` steps of rank-one updates: no training path.
Over a chunk of ``CHUNK`` tokens, with ``gamma`` the running sum of
``g`` inside the chunk and ``S`` the state the chunk starts from::

    A      = strictLower(diag(beta) K K^T * exp(gamma_i - gamma_j))
    (W, U) = (I + A)^-1 (diag(beta exp(gamma)) K,  diag(beta) V)
    V'     = U - W S
    O      = (Q * exp(gamma)) S + (Q K^T * exp(gamma_i - gamma_j), i >= j) V'
    S     <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

``gdn_fwd`` walks a head's chunks in order, one grid step a chunk (and
``HEADS`` heads): the state lives in a VMEM scratch across the chunk
axis, and the decay matrix, ``K K^T``, ``A``, the inverse, ``W``,
``U``, ``V'`` and ``Q K^T`` never leave VMEM.  It writes ``O`` and,
for the backward, the state each chunk STARTS from and the chunk's
inverse ``T = (I + A)^-1``, both in the operands' type (what the
forward's matmuls read; 70.8 and 62.9 MB a layer in bf16 at 1 x 8192
x 30 x 96 | 192).  ``gdn_bwd`` walks the chunks in reverse with ``dS``
in VMEM, recomputes ``W``, ``U``, ``V'`` from the inputs, the saved
state and the saved ``T`` (the inverse is half of the forward kernel's
time: no second one) and emits all five gradients; the inverse's
gradient is the closed form ``dA = -T^T dT T^T`` with ``dT = dU Vb^T
+ dW Kb^T`` folded in (:func:`_solve_bwd`).  A ``jax.custom_vjp``
joins them, so what is kept for the backward is said here (the
caller's own operands, the chunk-start states, ``T``) and no caller
wraps the rule in a remat of its own.

What a rematted caller keeps.  The forward rule names what ``gdn_fwd``
wrote and anything reads after it (``RESIDUAL_NAMES``: ``o`` in the
caller's layout, the final state, the chunk-start states, ``T``), and
a ``jax.checkpoint`` whose policy saves those names
(``models/layers.py::remat_policy``) does not run the forward kernel
again in its backward, nor the layouts into and out of it, for the
three arrays a layer kept from forward to backward (94 MB of ``o``
beside the 70.8 and 62.9 above).  The five operands are not named:
their producers run again, read by gradients of their own.

Outside the kernels, in XLA and the same for every caller: the layout
into heads-leading ``[b h, s, d]`` operands (a ``[b, s, h, d]`` block
of one head is no legal block), the tail's padding, and ``gamma`` (a
cumulative sum of ``[b, s, h]`` float32; its transpose turns ``d
gamma`` into ``d g``).  The backward lays the operands out AGAIN
rather than keep the heads-leading copies (lanes of 96 and 192 are
padded to 128 and 256 there), and the layouts sit between
``optimization_barrier``s: fused into the convolutions, gates and the
norm round the rule they cost more than as copies of their own
(PERF.md, PR 40).  On the CPU the kernels run in interpreter mode.
Mosaic kernels are not auto-partitioned: batch and heads are
independent, so a caller whose mesh shards them puts the call under
``shard_map``, as the model's flash call is.

Precision: decays, running sums, the state, ``dS`` and the inverse of
``I + A`` are float32; the other matmuls take their operands in the
type ``q`` arrives in (bf16 on the training path, float32
accumulation).  Float32 operands run at ``HIGHEST`` throughout; with
bf16 operands the inverse, which is rounded to bf16 for the next
matmul, runs in three bf16 passes (what ``Precision.HIGH`` is).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import flash_attention as _flash

# Tokens a chunk holds: one 128 x 128 tile for everything C x C (128
# against 32 and 64 in the XLA form: PERF.md, PR 32).
CHUNK = 128
# Heads a grid step holds: their chains of small matmuls are
# independent, so the scheduler interleaves them, and a step's fixed
# cost is shared.  1 | 2 | 3 | 6 heads: PERF.md, PR 40; six are 5%
# faster than three in the forward, but the unrolled body is traced
# and lowered at every launch (4 s of a warm set-up's 27 where three
# cost 0.5), and six float32 heads do not fit the scoped VMEM.
HEADS = 3
F32 = jnp.float32
BF16 = jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
NN, NT, TN = (1, 0), (1, 1), (0, 0)
# what the forward kernel writes and anything reads after it, under
# the names a remat policy keeps them by: ``o`` as the block takes it
# (``[b, s, h d_v]`` rows), the final state, and the two arrays only
# the backward kernel reads, the chunk-start states and ``T``
RESIDUAL_NAMES = ("gdn_o", "gdn_final", "gdn_starts", "gdn_t")


def _interpret() -> bool:
    # one answer for every kernel of a step: what steers the flash
    # kernel (a compile for a described chip) steers these
    return _flash._interpret()


def _dot(a, b, contract, exact):
    """``a`` and ``b`` contracted over one axis each (``NN``: ``a @
    b``, ``NT``: ``a @ b.T``, ``TN``: ``a.T @ b``), float32 result."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=F32,
        precision=HIGHEST if exact else None,
    )


def _split(x):
    """Float32 as the sum of two bf16: the rounded value and what the
    rounding lost."""
    hi = x.astype(BF16)
    return hi, (x - hi.astype(F32)).astype(BF16)


def _dot3(a, b):
    """``a @ b`` of two split float32 matrices in three bf16 passes
    (``lo x lo`` dropped: 2**-16 of the result)."""
    return (
        _dot(a[0], b[0], NN, False) + _dot(a[0], b[1], NN, False)
        + _dot(a[1], b[0], NN, False)
    )


def _iotas(c):
    return (
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 0),
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 1),
    )


def _inverse_unit_lower(a, exact=True):
    """``(I + A)^-1`` for one strictly lower-triangular tile ``A [C,
    C]``, ``C`` a power of two.

    Block recursion, in matmuls: with ``X`` the inverse of the diagonal
    blocks of size ``b`` (block diagonal), the inverse at ``2b`` is ``X
    - X L21 X``, ``L21`` the lower-left ``b x b`` corner of each ``2b``
    block of ``A``.  As stable as forward substitution; the Neumann
    product ``(I - A)(I + A^2)(I + A^4)..`` is not (powers of ``A``
    grow with write strengths near 2 before they cancel).  ``exact``:
    float32 matmuls at ``HIGHEST``; else three bf16 passes each."""
    c = a.shape[-1]
    row, col = _iotas(c)
    # rows i and j share a block of size m where (i ^ j) < m
    apart = row ^ col
    below = row > col
    # blocks of 2: [[1, 0], [a, 1]]^-1 = [[1, 0], [-a, 1]]
    x = jnp.where(
        below & (apart < 2), -a, (row == col).astype(F32)
    )
    parts = (a,) if exact else _split(a)
    b = 2
    while b < c:
        corner = below & (apart < 2 * b) & (apart >= b)
        l21 = tuple(jnp.where(corner, part, 0) for part in parts)
        if exact:
            x = x - _dot(_dot(x, l21[0], NN, True), x, NN, True)
        else:
            xs = _split(x)
            x = x - _dot3(_split(_dot3(xs, l21)), xs)
        b *= 2
    return x


def _solve_bwd(t, x, dx, exact):
    """The gradient of ``X = (I + A)^-1 B`` (``T`` the inverse): ``dB
    = T^T dX`` and ``dA = -T^T dT T^T`` with ``dT = dX B^T``, which is
    ``-dB X^T`` below the diagonal; ``(dB, dA)``, float32."""
    db = _dot(t, dx.astype(t.dtype), TN, exact)
    da = -_dot(db.astype(t.dtype), x.astype(t.dtype), NT, exact)
    row, col = _iotas(t.shape[-1])
    return db, jnp.where(row > col, da, 0.0)


def _lanes(x, n):
    """A lane-dense ``[C, C]`` tile (every lane of a row the same) as
    ``[C, n]``."""
    if n <= x.shape[1]:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _as_row(column):
    """A ``[C, 1]`` column as the ``[1, C]`` row it travels as."""
    c = column.shape[0]
    return jnp.broadcast_to(column, (c, c)).T[:1]


def _chunk(q, k, v, gamma, beta, state, exact, t=None):
    """One chunk of one head, everything the forward and the backward
    share: ``q, k [C, d_k]``, ``v [C, d_v]``, ``gamma, beta [1, C]``,
    ``state [d_k, d_v]`` float32, ``t`` the inverse where it is kept.
    """
    c, dk = q.shape
    dv = v.shape[1]
    dtype = q.dtype
    row, col = _iotas(c)
    # gamma_j along the lanes, gamma_i down the rows; beta_i likewise
    g_lane = jnp.broadcast_to(gamma, (c, c))
    g_row = g_lane.T
    b_row = jnp.broadcast_to(beta, (c, c)).T
    # exp(gamma_i - gamma_j) where i >= j, 0 above the diagonal (the
    # exponent is masked, not the result: above it is positive)
    decay = jnp.exp(jnp.where(row >= col, g_row - g_lane, -jnp.inf))
    kk = _dot(k, k, NT, exact)
    if t is None:
        a = jnp.where(row > col, b_row * kk * decay, 0.0)
        # (rounded to bf16 right after, three bf16 passes lose nothing)
        t = _inverse_unit_lower(a, exact).astype(dtype)
    grow = jnp.exp(g_row)
    g_end = gamma[:, c - 1:]                              # [1, 1]
    # exp(gamma_C - gamma): what each write is worth at the chunk's end
    to_end = jnp.exp(g_end - g_row)
    # what a key writes: beta exp(gamma)
    write = _lanes(b_row * grow, dk)
    vb = (_lanes(b_row, dv) * v).astype(dtype)
    kb = (write * k).astype(dtype)
    u = _dot(t, vb, NN, exact)
    w = _dot(t, kb, NN, exact).astype(dtype)
    sb = state.astype(dtype)
    v_new = u - _dot(w, sb, NN, exact)
    qk = _dot(q, k, NT, exact)
    return dict(
        decay=decay, kk=kk, qk=qk, t=t, b_row=b_row, grow=grow,
        write=write, to_end=to_end, end=jnp.exp(g_end), u=u, w=w,
        sb=sb, vn=v_new.astype(dtype),
        q_in=(_lanes(grow, dk) * q).astype(dtype),
        k_end=(_lanes(to_end, dk) * k).astype(dtype),
        p=(qk * decay).astype(dtype),
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
            q_ref[h], k_ref[h], v_ref[h], gamma_ref[h, 0],
            beta_ref[h, 0], s, exact,
        )
        t_ref[h, 0] = x["t"]
        o = _dot(x["q_in"], x["sb"], NN, exact) + _dot(
            x["p"], x["vn"], NN, exact
        )
        o_ref[h] = o.astype(o_ref.dtype)
        state[h] = x["end"] * s + _dot(x["k_end"], x["vn"], TN, exact)

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
            q, k, v, gamma_ref[h, 0], beta_ref[h, 0], s, exact,
            t=t_ref[h, 0],
        )
        do = do_ref[h]
        ds = dstate[h]
        dsb = ds.astype(dtype)
        row, col = _iotas(c)
        b_row, grow, to_end = x["b_row"], x["grow"], x["to_end"]
        decay, sb, w, vn = x["decay"], x["sb"], x["w"], x["vn"]

        # O = q_in S + P V';  S' = end S + k_end^T V'
        dv_new = _dot(x["p"], do, TN, exact) + _dot(
            x["k_end"], dsb, NN, exact
        )
        dvn = dv_new.astype(dtype)
        dp = jnp.where(row >= col, _dot(do, vn, NT, exact), 0.0)
        dq_in = _dot(do, sb, NT, exact)                   # [C, d_k]
        dk_end = _dot(vn, dsb, NT, exact)                 # [C, d_k]
        # V' = U - W S; (W, U) = T (Kb, Vb)
        dw = -_dot(dvn, sb, NT, exact)
        dvb, da_u = _solve_bwd(x["t"], x["u"], dv_new, exact)
        dkb, da_w = _solve_bwd(x["t"], w, dw, exact)
        # (dA times the decay: what both of A's other factors gain)
        da = (da_u + da_w) * decay
        dstate[h] = (
            _dot(x["q_in"], do, TN, exact) + x["end"] * ds
            - _dot(w, dvn, TN, exact)
        )

        # A = beta_i KK_ij decay_ij (i > j);  P = QK_ij decay_ij
        dkk = da * b_row
        dqk = dp * decay
        dkkb, dqkb = dkk.astype(dtype), dqk.astype(dtype)
        dq = _lanes(grow, dk) * dq_in + _dot(dqkb, k, NN, exact)
        dk_total = (
            _lanes(to_end, dk) * dk_end + x["write"] * dkb
            + _dot(dqkb, q, TN, exact)
            + _dot(dkkb, k, NN, exact) + _dot(dkkb, k, TN, exact)
        )
        dq_ref[h] = dq.astype(dq_ref.dtype)
        dk_ref[h] = dk_total.astype(dk_ref.dtype)
        dv_ref[h] = (_lanes(b_row, dv) * dvb).astype(dv_ref.dtype)

        def rows(y):
            return jnp.sum(y, axis=1, keepdims=True)

        k32, grow_1, b_1 = k.astype(F32), grow[:, :1], b_row[:, :1]
        k_dkb = rows(k32 * dkb)
        dbeta = (
            rows(v.astype(F32) * dvb) + grow_1 * k_dkb
            + rows(da * x["kk"])
        )
        # through the decay matrix: d gamma_i gains row i's sum of
        # dD * D and loses column i's
        through = dkk * x["kk"] + dqk * x["qk"]
        at_end = to_end[:, :1] * rows(k32 * dk_end)
        dgamma = (
            rows(through)
            + grow_1 * (rows(q.astype(F32) * dq_in) + b_1 * k_dkb)
            - at_end
        )
        # gamma_C: every write's worth at the end, and the state's decay
        d_end = jnp.sum(at_end, axis=0, keepdims=True) + x["end"] * (
            jnp.sum(rows(s * ds), axis=0, keepdims=True)
        )
        dgamma_ref[h, 0] = (
            _as_row(dgamma) - jnp.sum(through, axis=0, keepdims=True)
            + jnp.where(col[:1] == c - 1, d_end, 0.0)
        )
        dbeta_ref[h, 0] = _as_row(dbeta)


def _heads_a_step(bh: int) -> int:
    return max(n for n in range(1, HEADS + 1) if bh % n == 0)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary")
    )


# (jitted: traced once for all of a model's layers and call sites)
@jax.jit
def _forward(q, k, v, gamma, beta):
    """``(o, final state, chunk-start states [bh, n, d_k, d_v], T [bh,
    n, C, C])`` of heads-leading operands: ``q, k [bh, s, d_k]``, ``v
    [bh, s, d_v]``, ``gamma, beta [bh, n, 1, C]`` float32."""
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
        in_specs=[tokens(dk), tokens(dk), tokens(dv), gate, gate],
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
        name="gdn_fwd",
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
            tokens(dk), tokens(dk), tokens(dv), gate, gate,
            chunks(dk, dv), chunks(CHUNK, CHUNK), tokens(dv), whole,
        ],
        out_specs=[tokens(dk), tokens(dk), tokens(dv), gate, gate],
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
        name="gdn_bwd",
    )(q, k, v, gamma, beta, starts, t, do, dfinal)


def _heads_lead(x, pad):
    """``[b, s, h, ..] -> [b h, s + pad, ..]``, zeros behind."""
    b, s, h = x.shape[:3]
    x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
    return jnp.moveaxis(x, 2, 1).reshape((b * h, s + pad) + x.shape[3:])


def _operands(q, k, v, g, beta):
    """``[b, s, h, ..]`` as the kernels take them: heads lead the
    sequence, the tail of the last chunk padded with tokens that
    neither decay nor write (``g = 0``, ``beta = 0``), the gates as
    ``[b h, n, 1, C]`` float32 rows with ``g`` summed up inside each
    chunk."""
    pad = -g.shape[1] % CHUNK

    def rows(x):
        x = _heads_lead(x.astype(F32), pad)
        return x.reshape(x.shape[0], -1, 1, CHUNK)

    return (
        _heads_lead(q, pad), _heads_lead(k, pad), _heads_lead(v, pad),
        jnp.cumsum(rows(g), axis=-1), rows(beta),
    )


@jax.custom_vjp
def gated_delta_rule(q, k, v, g, beta):
    """``q, k [b, s, h, d_k]``, ``v [b, s, h, d_v]``, ``g, beta [b, s,
    h]`` -> ``(o [b, s, h, d_v] in q's type, S_T [b, h, d_k, d_v]
    float32)``: the rule's outputs and the state after the last token.

    ``q`` and ``k`` arrive normalised and scaled by the caller.  Any
    ``s``."""
    return _rule_fwd(q, k, v, g, beta)[0]


def _barrier(*arrays):
    """``optimization_barrier`` on ``[b, s, h, d]`` arrays as the
    caller holds them, ``[b, s, h d]`` (whole 128-lane rows: the 4-d
    view of 96 or 192 lanes would be a relayout of its own)."""
    flat = jax.lax.optimization_barrier(
        tuple(x.reshape(x.shape[:2] + (-1,)) for x in arrays)
    )
    return tuple(y.reshape(x.shape) for x, y in zip(arrays, flat))


def _rule_fwd(q, k, v, g, beta):
    b, s, h, dk = q.shape
    given = _barrier(q, k, v, g, beta)
    o, final, starts, t = _forward(*_operands(*given))
    o = jnp.moveaxis(o.reshape(b, h, -1, o.shape[-1]), 1, 2)[:, :s]
    o_name, final_name, starts_name, t_name = RESIDUAL_NAMES
    # ``o`` and the final state go on into the block, so they are
    # named as bits (``o`` as the rows the barrier holds); the other
    # two are the residuals' alone
    rows = jax.lax.optimization_barrier(o.reshape(b, s, -1))
    o = _flash._named(rows, o_name).reshape(o.shape)
    final = _flash._named(final.reshape(b, h, dk, -1), final_name)
    return (o, final), (
        given, checkpoint_name(starts, starts_name),
        checkpoint_name(t, t_name),
    )


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


gated_delta_rule.defvjp(_rule_fwd, _rule_bwd)
