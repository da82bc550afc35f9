"""``ops/selective_scan.py``'s kernels (interpreted on the CPU) against
the token-by-token float32 recurrence: ``y``, the final state, the
chunk-start states and every operand's gradient, at two shapes (one
whose sequence fills no row tile and whose channels are one lane
tile), ``chunk`` in two sizes, with the skip inside the kernel and
without it, in float32 and in bf16; what the call refuses."""

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dlrover_tpu.ops import selective_scan as s6  # noqa: E402

F32 = jnp.float32


def plain(x, dt, A, B, C, D=None):
    """``(y, the final state, every token's state)``, one token a
    step, float32."""
    x32 = x.astype(F32)

    def one(x, dt, b, c):
        def token(h, at):
            x_t, dt_t, b_t, c_t = at
            h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * x_t)[:, None] * b_t
            return h, (h @ c_t, h)

        h, (y, states) = jax.lax.scan(
            token, jnp.zeros(A.shape, F32), (x, dt, b, c)
        )
        return y, h, states

    y, h, states = jax.vmap(one)(x32, dt, B.astype(F32), C.astype(F32))
    if D is not None:
        y = y + D * x32
    return y, h, states


def operands(b, s, e, n, skip, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    return (
        jax.random.normal(ks[0], (b, s, e)).astype(dtype),
        jax.nn.softplus(jax.random.normal(ks[1], (b, s, e)) - 2.0),
        -jnp.exp(0.5 * jax.random.normal(ks[2], (e, n))),
        jax.random.normal(ks[3], (b, s, n)),
        jax.random.normal(ks[4], (b, s, n)),
    ) + ((jax.random.normal(ks[5], (e,)),) if skip else ()), (
        jax.random.normal(ks[6], (b, s, e)),
        jax.random.normal(ks[7], (b, e, n)),
    )


def relative(a, b):
    a, b = a.astype(F32), b.astype(F32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# (batch, rows, channels, state lanes, chunk, the skip inside)
SHAPES = {
    # a sequence that fills no row tile, channels of one lane tile
    "40x128-chunk16-skip": (2, 40, 128, 16, 16, True),
    # three lane tiles (width 384), two chunks
    "64x384-chunk32": (1, 64, 384, 16, 32, False),
    # channels that fill no lane tile, eight state lanes
    "48x200x8-chunk48-skip": (1, 48, 200, 8, 48, True),
}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_forward_and_every_gradient_are_the_recurrences(shape):
    b, s, e, n, chunk, skip = SHAPES[shape]
    given, (wy, wh) = operands(b, s, e, n, skip, F32)

    def loss(scan):
        def of(*given):
            y, h = scan(*given)[:2]
            return jnp.sum(y.astype(F32) * wy) + jnp.sum(h * wh)
        return of

    def ours(*given):
        return s6.selective_scan(*given, chunk=chunk)

    y, h = ours(*given)
    want_y, want_h, _ = plain(*given)
    assert y.shape == (b, s, e) and h.shape == (b, e, n)
    assert relative(y, want_y) < 1e-6 and relative(h, want_h) < 1e-6
    every = tuple(range(len(given)))
    got = jax.grad(loss(ours), argnums=every)(*given)
    want = jax.grad(loss(plain), argnums=every)(*given)
    for name, a, w in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        assert a.shape == w.shape and a.dtype == w.dtype
        assert relative(a, w) < 2e-6, (name, relative(a, w))


def test_the_chunk_start_states_are_the_recurrences():
    """What ``s6_fwd`` keeps for the backward: the state before the
    first row of every chunk, float32, ``[b, chunks, N, E]``."""
    given, _ = operands(1, 64, 128, 16, False, F32, seed=3)
    chunk = 16
    _, final, starts = s6._forward(
        *s6._operands(*given, jnp.zeros(128), chunk), chunk=chunk
    )
    _, _, states = plain(*given)
    assert starts.shape == (1, 4, 16, 128) and starts.dtype == F32
    np.testing.assert_array_equal(starts[0, 0], 0.0)
    for c in range(1, 4):
        np.testing.assert_allclose(
            starts[0, c], states[0, c * chunk - 1].T, rtol=2e-6, atol=1e-7
        )
    np.testing.assert_allclose(
        final[0], states[0, -1].T, rtol=2e-6, atol=1e-7
    )


def test_chunk_sizes_agree():
    """The spacing of the kept states changes no number's meaning:
    ``y`` and the final state to float32's last bits."""
    given, _ = operands(1, 64, 128, 16, True, F32, seed=5)
    y16, h16 = s6.selective_scan(*given, chunk=16)
    y64, h64 = s6.selective_scan(*given, chunk=64)
    assert relative(y16, y64) < 1e-6 and relative(h16, h64) < 1e-6


def test_bf16_rounds_y_once_and_keeps_the_state_float32():
    """``x`` and ``y`` in bf16: ``y`` is the float32 recurrence's
    rounded once (half a bf16 ulp a value), the final state float32's
    own, and the float32 gradients (``dt``, ``A``, ``B``, ``C``,
    ``D``) see only the cotangent's rounding."""
    given, (wy, _) = operands(1, 48, 128, 16, True, jnp.bfloat16, seed=7)
    y, h = s6.selective_scan(*given, chunk=16)
    want_y, want_h, _ = plain(*given)
    assert y.dtype == jnp.bfloat16 and h.dtype == F32
    assert relative(y, want_y) < 4e-3 and relative(h, want_h) < 1e-6

    def loss(scan):
        return lambda *g: jnp.sum(scan(*g)[0].astype(F32) * wy)

    got = jax.grad(
        loss(lambda *g: s6.selective_scan(*g, chunk=16)), argnums=(1, 2)
    )(*given)
    want = jax.grad(loss(plain), argnums=(1, 2))(*given)
    for a, w in zip(got, want):
        assert a.dtype == F32 and relative(a, w) < 1e-2


def test_the_residual_names_are_what_the_forward_wrote():
    """``y``, the final state and the start states carry the names
    ``models/layers.py::remat_policy`` keeps (that a rematted block
    then runs ``s6_fwd`` once is ``test_remat_residuals.py``'s)."""
    given, _ = operands(1, 32, 128, 16, False, F32)
    jaxpr = jax.make_jaxpr(
        lambda *g: jax.vjp(lambda *g: s6.selective_scan(*g), *g)[0]
    )(*given)
    text = str(jaxpr)
    for name in s6.RESIDUAL_NAMES:
        assert f"name={name}" in text, name


@pytest.mark.parametrize("bad", ["chunk", "A", "C", "dt"])
def test_shapes_that_do_not_fit_are_refused(bad):
    (x, dt, A, B, C), _ = operands(1, 32, 128, 16, False, F32)
    kw = dict(chunk=24) if bad == "chunk" else dict(chunk=16)
    if bad == "A":
        A = A[:, :8]
    if bad == "C":
        C = C[:, :, :8]
    if bad == "dt":
        dt = dt[:, :16]
    with pytest.raises(ValueError, match="chunk"):
        s6.selective_scan(x, dt, A, B, C, **kw)
