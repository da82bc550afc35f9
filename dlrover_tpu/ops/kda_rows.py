"""The element-wise float32 passes of the KDA mixer on either side of
its rule, as Pallas row kernels over the projections' own ``[b, s, h
d]`` arrays (a head ``d`` lanes of a row: whole 128-lane columns at the
published ``d`` = 128)::

    kda_gates:  q_h <- q_h / |q_h| * d^-1/2      k_h <- k_h / |k_h|
                g = lower x sigmoid(exp(A_log_h) (f + dt_bias))
    kda_norm:   y = (RMSNorm_d(o_h) * scale) * sigmoid(z)

Both are no work to speak of and bytes to move (``[8192, 4096]`` rows:
134 MB in float32, 67 in bf16): the gates read ``q``, ``k`` and ``f``
in float32 and write ``q`` and ``k`` in the model's type and ``g`` in
float32, 670 MB and 0.82 ms of a v5e's HBM a layer, the norm 201 MB.
The plain form (a reshape to ``[b, s, h, d]``, a reduction over ``d``,
a broadcast back, casts) took XLA 228 fusions, copies and reshapes a
step, five times the bytes' time (PERF.md, PR 62).  Here a grid step
is a block of rows by a column of whole heads in the caller's layout;
inside it a loop takes one head's ``[STRIP, d]`` slab at a time
through the arithmetic in registers: the sum of squares over the
head's lanes, the ``rsqrt``, the sigmoid, ONE rounding to the output's
type.  Nothing but the inputs is read and nothing but the outputs
written.

``kda_gates_bwd`` and ``kda_norm_bwd`` read the forward's INPUTS and
the outputs' cotangents, make the norms' factors and the sigmoids
again in VMEM and write the inputs' gradients; what is summed over
tokens (``d dt_bias``, ``d exp(A_log)``, ``d scale``) leaves as one
float32 row a block, ``[blocks, 1, h d]``, for XLA to add up (a grid
step owns its row: no step waits for another).  ``kda_gates_fwd``
writes a block's least ``g`` a lane the same way, so the model's
``log_decay_min`` reads 1 MB and not ``g`` again.  A ``jax.custom_vjp``
joins each pair; its residuals are the inputs alone, and a model calls
it inside a rematted block as it stands.

The shapes decide the blocks (:func:`_tiling`): whole heads of whole
128-lane columns up to ``MAX_LANES`` where ``d`` is a multiple of 128,
else the whole lane axis as one slab in which a head's lanes are found
by mask (the ``tiny`` configuration's 2 heads of 32); rows from the
VMEM that a call's blocks take at two buffers each.  Any ``s``: the
last block may hang over the end, the rows past it are dropped on the
way out and masked out of the sums and the minimum.  On the CPU the
kernels run in interpreter mode.  Mosaic kernels are not
auto-partitioned: under a mesh the calls need a ``shard_map``, none
yet (M6(b4)).

Precision: inputs in the caller's types, everything between the loads
and the stores in float32 (``jax.nn.sigmoid`` and ``jax.lax.rsqrt``,
``L2_EPS`` and ``eps`` inside the ``rsqrt`` as the plain form has
them), one rounding to each output's type, gradients in their
operand's type, the summed rows float32.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.gated_delta_rule import F32, _interpret

LANES = 128   # a lane tile
EDGE = 8      # float32's sublane tile: what a strip's sums fold down to
STRIP = 128   # rows taken through the arithmetic at a time
MAX_ROWS = 512          # of a block
MAX_LANES = 1024        # of a block that is not the whole width
BLOCK_BYTES = 8 << 20   # of a call's blocks, two buffers each
L2_EPS = 1e-6           # inside the l2 norm's rsqrt


def _params():
    # a grid step writes its own blocks and its own summed rows
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel")
    )


def _tiling(s, heads, d, bytes_a_lane):
    """``(rows, lanes)`` of a block of ``[b, s, heads x d]``.  Lanes:
    the most whole heads within ``MAX_LANES`` (one, if a head is wider)
    where a head is whole lane tiles, else every lane.  Rows: strips
    doubled up to ``MAX_ROWS`` while the call's blocks, ``bytes_a_lane``
    a row and lane over its token arrays, keep within ``BLOCK_BYTES``
    at two buffers each."""
    if d % LANES == 0:
        lanes = d * max(
            n for n in range(1, heads + 1)
            if heads % n == 0 and n * d <= max(MAX_LANES, d)
        )
    else:
        lanes = heads * d
    rows = STRIP
    while rows < min(s, MAX_ROWS) and (
        2 * 2 * rows * lanes * bytes_a_lane <= BLOCK_BYTES
    ):
        rows *= 2
    return rows, lanes


def _columns(lanes, d, column):
    """``column(at)`` for every slab of a block's lanes that the
    arithmetic takes at once: a head where a head is whole lane tiles
    (a loop: ONE trace of the body for all of them), else the block's
    whole width."""
    if d % LANES:
        column(slice(None))
        return

    def body(h, _):
        column(pl.ds(pl.multiple_of(h * d, LANES), d))
        return _

    jax.lax.fori_loop(0, lanes // d, body, 0)


def _head_sum(x, d):
    """``x [rows, w]`` summed over each head's ``d`` lanes, the sum on
    every lane of its head (``[rows, 1]`` where the slab is one
    head)."""
    if x.shape[1] == d:
        return jnp.sum(x, axis=1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    out = jnp.zeros_like(x)
    for h in range(x.shape[1] // d):
        mine = (lane >= h * d) & (lane < (h + 1) * d)
        out = jnp.where(mine, jnp.sum(
            jnp.where(mine, x, 0.0), axis=1, keepdims=True
        ), out)
    return out


def _fold(x, op):
    """``[STRIP, w]`` folded down to one sublane tile ``[EDGE, w]``:
    whole-register operations."""
    out = x[:EDGE]
    for i in range(EDGE, x.shape[0], EDGE):
        out = op(out, x[i:i + EDGE])
    return out


def _strips(ref, s):
    """``walk(strip, init)`` for a kernel's block ``ref``, made at the
    top of the kernel (the grid index is read there): ``strip(rows,
    live, carry) -> carry`` over the block's strips of rows, ``rows``
    the slice and ``live [STRIP, 1]`` which of them the sequence has
    (``None`` where every block is whole)."""
    block = ref.shape[1]
    left = s - pl.program_id(0) % pl.cdiv(s, block) * block

    def walk(strip, init):
        def body(n, carry):
            lo = pl.multiple_of(n * STRIP, STRIP)
            live = None
            if s % block:
                live = lo + jax.lax.broadcasted_iota(
                    jnp.int32, (STRIP, 1), 0
                ) < left
            return strip(pl.ds(lo, STRIP), live, carry)

        return jax.lax.fori_loop(0, block // STRIP, body, init)

    return walk


def _kept(x, live, other=0.0):
    return x if live is None else jnp.where(live, x, other)


def _l2(x, d):
    """``rsqrt(|x_h|^2 + eps)`` a head."""
    return jax.lax.rsqrt(_head_sum(x * x, d) + L2_EPS)


def _gates_fwd_kernel(
    q_ref, k_ref, f_ref, a_ref, bias_ref, qn_ref, kn_ref, g_ref, min_ref,
    *, s, d, lower,
):
    strips = _strips(q_ref, s)

    def column(at):
        a, bias = a_ref[:, at], bias_ref[:, at]

        def strip(rows, live, least):
            q = q_ref[0, rows, at].astype(F32)
            qn_ref[0, rows, at] = (
                q * _l2(q, d) * d ** -0.5
            ).astype(qn_ref.dtype)
            k = k_ref[0, rows, at].astype(F32)
            kn_ref[0, rows, at] = (k * _l2(k, d)).astype(kn_ref.dtype)
            g = lower * jax.nn.sigmoid(
                a * (f_ref[0, rows, at].astype(F32) + bias)
            )
            g_ref[0, rows, at] = g
            return jnp.minimum(
                least, _fold(_kept(g, live, jnp.inf), jnp.minimum)
            )

        least = strips(
            strip, jnp.full((EDGE, a.shape[1]), jnp.inf, F32)
        )
        min_ref[0, :, at] = jnp.min(least, axis=0, keepdims=True)

    _columns(q_ref.shape[2], d, column)


def _gates_bwd_kernel(
    q_ref, k_ref, f_ref, dqn_ref, dkn_ref, dg_ref, a_ref, bias_ref,
    dq_ref, dk_ref, df_ref, dbias_ref, da_ref, *, s, d, lower,
):
    strips = _strips(q_ref, s)

    def l2_bwd(x_ref, dy_ref, dx_ref, rows, at, scale=None):
        # y = x r, r = rsqrt(|x|^2 + eps): dx = r (dy - x r^2 <dy, x>)
        x = x_ref[0, rows, at].astype(F32)
        dy = dy_ref[0, rows, at].astype(F32)
        if scale is not None:
            dy = dy * scale
        r = _l2(x, d)
        dx_ref[0, rows, at] = (
            r * (dy - x * (r * r) * _head_sum(dy * x, d))
        ).astype(dx_ref.dtype)

    def column(at):
        a, bias = a_ref[:, at], bias_ref[:, at]

        def strip(rows, live, sums):
            l2_bwd(q_ref, dqn_ref, dq_ref, rows, at, d ** -0.5)
            l2_bwd(k_ref, dkn_ref, dk_ref, rows, at)
            shifted = f_ref[0, rows, at].astype(F32) + bias
            sig = jax.nn.sigmoid(a * shifted)
            # g = lower sigmoid(u), u = a (f + bias)
            du = dg_ref[0, rows, at].astype(F32) * (
                lower * sig * (1.0 - sig)
            )
            df = du * a
            df_ref[0, rows, at] = df.astype(df_ref.dtype)
            return (
                sums[0] + _fold(_kept(df, live), jnp.add),
                sums[1] + _fold(_kept(du * shifted, live), jnp.add),
            )

        zero = jnp.zeros((EDGE, a.shape[1]), F32)
        dbias, da = strips(strip, (zero, zero))
        dbias_ref[0, :, at] = jnp.sum(dbias, axis=0, keepdims=True)
        da_ref[0, :, at] = jnp.sum(da, axis=0, keepdims=True)

    _columns(q_ref.shape[2], d, column)


def _normalised(o, d, eps):
    """``(o r, r)``, ``r = rsqrt(mean_d(o_h^2) + eps)``."""
    r = jax.lax.rsqrt(_head_sum(o * o, d) / d + eps)
    return o * r, r


def _norm_fwd_kernel(o_ref, z_ref, scale_ref, y_ref, *, s, d, eps):
    strips = _strips(o_ref, s)

    def column(at):
        scale = scale_ref[:, at]

        def strip(rows, live, carry):
            n, _ = _normalised(o_ref[0, rows, at].astype(F32), d, eps)
            y_ref[0, rows, at] = (
                n * scale
                * jax.nn.sigmoid(z_ref[0, rows, at].astype(F32))
            ).astype(y_ref.dtype)
            return carry

        strips(strip, 0)

    _columns(o_ref.shape[2], d, column)


def _norm_bwd_kernel(
    o_ref, z_ref, dy_ref, scale_ref, do_ref, dz_ref, dscale_ref,
    *, s, d, eps,
):
    strips = _strips(o_ref, s)

    def column(at):
        scale = scale_ref[:, at]

        def strip(rows, live, total):
            n, r = _normalised(o_ref[0, rows, at].astype(F32), d, eps)
            sig = jax.nn.sigmoid(z_ref[0, rows, at].astype(F32))
            dy = dy_ref[0, rows, at].astype(F32)
            # y = n scale sigmoid(z)
            dz_ref[0, rows, at] = (
                dy * (n * scale) * (sig * (1.0 - sig))
            ).astype(dz_ref.dtype)
            dm = dy * sig
            dn = dm * scale
            # n = o r: do = r (dn - n mean_d(dn n))
            do_ref[0, rows, at] = (
                r * (dn - n * (_head_sum(dn * n, d) / d))
            ).astype(do_ref.dtype)
            return total + _fold(_kept(dm * n, live), jnp.add)

        total = strips(
            strip, jnp.zeros((EDGE, scale.shape[1]), F32)
        )
        dscale_ref[0, :, at] = jnp.sum(total, axis=0, keepdims=True)

    _columns(o_ref.shape[2], d, column)


def _call(kernel, name, shape, d, tokens_in, rows_in, tokens_out, sums):
    """The ``pallas_call`` all four share: token arrays ``[b, s, h d]``
    in blocks of rows by a column of heads, the ``[1, h d]`` rows of
    per-lane constants beside them, ``sums`` float32 rows a block
    behind the token outputs (``[blocks, 1, h d]``)."""
    b, s, width = shape
    rows, lanes = _tiling(s, width // d, d, sum(
        jnp.dtype(x).itemsize for x in tokens_in + tokens_out
    ))
    tiles = pl.cdiv(s, rows)
    tokens = pl.BlockSpec(
        (1, rows, lanes), lambda i, j: (i // tiles, i % tiles, j)
    )
    return pl.pallas_call(
        functools.partial(kernel, s=s, d=d),
        grid=(b * tiles, width // lanes),
        in_specs=[tokens] * len(tokens_in)
        + [pl.BlockSpec((1, lanes), lambda i, j: (0, j))] * rows_in,
        out_specs=[tokens] * len(tokens_out)
        + [pl.BlockSpec((1, 1, lanes), lambda i, j: (i, 0, j))] * sums,
        out_shape=[jax.ShapeDtypeStruct(shape, x) for x in tokens_out]
        + [jax.ShapeDtypeStruct((b * tiles, 1, width), F32)] * sums,
        compiler_params=_params(),
        interpret=_interpret(),
        name=name,
    )


def _types(*arrays):
    return [x.dtype for x in arrays]


# (jitted: traced once for all of a model's layers and call sites)
@functools.partial(jax.jit, static_argnames=("d", "lower", "dtype"))
def _gates_forward(q, k, f, a, bias, *, d, lower, dtype):
    """-> ``(q, k in dtype, g float32, a block's least g a lane)``."""
    return _call(
        functools.partial(_gates_fwd_kernel, lower=lower),
        "kda_gates_fwd", q.shape, d, _types(q, k, f), 2,
        [dtype, dtype, F32], 1,
    )(q, k, f, a, bias)


@functools.partial(jax.jit, static_argnames=("d", "lower"))
def _gates_backward(q, k, f, a, bias, dqn, dkn, dg, *, d, lower):
    """-> ``(dq, dk, df, d bias and d a as rows a block)``."""
    return _call(
        functools.partial(_gates_bwd_kernel, lower=lower),
        "kda_gates_bwd", q.shape, d, _types(q, k, f, dqn, dkn, dg), 2,
        _types(q, k, f), 2,
    )(q, k, f, dqn, dkn, dg, a, bias)


@functools.partial(jax.jit, static_argnames=("d", "eps", "dtype"))
def _norm_forward(o, z, scale, *, d, eps, dtype):
    return _call(
        functools.partial(_norm_fwd_kernel, eps=eps),
        "kda_norm_fwd", o.shape, d, _types(o, z), 1, [dtype], 0,
    )(o, z, scale)[0]


@functools.partial(jax.jit, static_argnames=("d", "eps"))
def _norm_backward(o, z, scale, dy, *, d, eps):
    """-> ``(do, dz, d scale as rows a block)``."""
    return _call(
        functools.partial(_norm_bwd_kernel, eps=eps),
        "kda_norm_bwd", o.shape, d, _types(o, z, dy), 1, _types(o, z), 1,
    )(o, z, dy, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _gates(q, k, f, a, bias, d, lower, dtype):
    return tuple(
        _gates_forward(q, k, f, a, bias, d=d, lower=lower, dtype=dtype)
    )


def _gates_fwd(q, k, f, a, bias, d, lower, dtype):
    return _gates(q, k, f, a, bias, d, lower, dtype), (q, k, f, a, bias)


def _gates_bwd(d, lower, dtype, kept, cotangents):
    dq, dk, df, dbias, da = _gates_backward(
        *kept, *cotangents[:3], d=d, lower=lower
    )
    return dq, dk, df, da.sum(axis=0), dbias.sum(axis=0)


_gates.defvjp(_gates_fwd, _gates_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _norm(o, z, scale, d, eps, dtype):
    return _norm_forward(o, z, scale, d=d, eps=eps, dtype=dtype)


def _norm_fwd(o, z, scale, d, eps, dtype):
    return _norm(o, z, scale, d, eps, dtype), (o, z, scale)


def _norm_bwd(d, eps, dtype, kept, dy):
    do, dz, dscale = _norm_backward(*kept, dy, d=d, eps=eps)
    return do, dz, dscale.sum(axis=0)


_norm.defvjp(_norm_fwd, _norm_bwd)


def kda_gates(
    q: jax.Array,        # [b, s, h d]
    k: jax.Array,        # [b, s, h d]
    f: jax.Array,        # [b, s, h d]
    a_log: jax.Array,    # [h]
    dt_bias: jax.Array,  # [h d]
    *,
    lower: float,
    dtype,
):
    """``(q_h / |q_h| * d^-1/2, k_h / |k_h|`` in ``dtype``, ``g = lower
    x sigmoid(exp(A_log_h) (f + dt_bias))`` float32, each ``[b, s, h
    d]``, and the least ``g`` of the call); differentiable in all five
    operands."""
    d = q.shape[2] // a_log.shape[0]
    a = jnp.repeat(jnp.exp(a_log.astype(F32)), d)[None]
    q, k, g, least = _gates(
        q, k, f, a, dt_bias.astype(F32)[None], d, float(lower),
        jnp.dtype(dtype),
    )
    return q, k, g, jnp.min(least)


def kda_norm(
    o: jax.Array,      # [b, s, h d]
    z: jax.Array,      # [b, s, h d]
    scale: jax.Array,  # [d]
    *,
    eps: float,
    dtype,
):
    """``(RMSNorm_d(o_h) * scale) * sigmoid(z)``, ``[b, s, h d]`` in
    ``dtype``; differentiable in all three operands."""
    d = scale.shape[0]
    row = jnp.tile(scale.astype(F32), o.shape[2] // d)[None]
    return _norm(o, z, row, d, float(eps), jnp.dtype(dtype))
