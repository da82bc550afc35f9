"""The KDA mixer's row kernels (``ops/kda_rows.py``, interpreted on the
CPU) against the float32 ``jax.numpy`` form that stood in
``KdaAttention`` until PR 62: values and every gradient, at heads of
whole lane columns and at the ``tiny`` configuration's 2 x 32, at
sequences that are whole blocks, that one block hangs over and that
end inside the last of several blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import kda_rows
from dlrover_tpu.ops.kda_rows import kda_gates, kda_norm

LOWER, EPS = -5.0, 1e-6
# (batch, tokens, heads, d): a block is STRIP = 128 rows or more
SHAPES = {
    "2x128_whole": (2, 256, 2, 128),
    "2x128_one_block_over": (2, 200, 2, 128),
    "2x128_tail_of_several": (1, 300, 2, 128),
    "2x32_tiny": (2, 50, 2, 32),
}


def plain_gates(q, k, f, a_log, dt_bias, dtype):
    """``KdaAttention``'s ``kda_gates`` scope as XLA had it."""
    b, s, width = q.shape
    heads = a_log.shape[0]
    d = width // heads

    def l2_normalised(x):
        x = x.astype(jnp.float32).reshape(b, s, heads, d)
        return x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6
        )

    g = LOWER * jax.nn.sigmoid(
        jnp.exp(a_log)[:, None]
        * (f.astype(jnp.float32) + dt_bias).reshape(b, s, heads, d)
    )
    return (
        (l2_normalised(q) * d ** -0.5).astype(dtype).reshape(q.shape),
        l2_normalised(k).astype(dtype).reshape(q.shape),
        g.reshape(q.shape),
    )


def plain_norm(o, z, scale, dtype):
    """``KdaAttention``'s ``kda_norm`` scope as XLA had it."""
    b, s, width = o.shape
    d = scale.shape[0]
    o32 = o.astype(jnp.float32).reshape(b, s, width // d, d)
    o32 = o32 * jax.lax.rsqrt(
        jnp.mean(o32 * o32, axis=-1, keepdims=True) + EPS
    ) * scale
    return (
        o32.reshape(o.shape) * jax.nn.sigmoid(z.astype(jnp.float32))
    ).astype(dtype)


def operands(shape, seed=0):
    b, s, heads, d = SHAPES[shape]
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    tokens = [
        jax.random.normal(key, (b, s, heads * d), jnp.float32)
        for key in keys[:6]
    ]
    return dict(
        q=tokens[0], k=tokens[1], f=3.0 * tokens[2],
        a_log=jnp.log(
            jax.random.uniform(keys[6], (heads,), minval=0.1, maxval=4.0)
        ),
        dt_bias=jax.random.normal(keys[7], (heads * d,)),
        scale=1.0 + 0.1 * jax.random.normal(keys[8], (d,)),
        # the outputs' cotangents
        weights=tokens[3:6],
    )


def gates(x, dtype, f=None):
    return kda_gates(
        x["q"], x["k"], x["f"] if f is None else f, x["a_log"],
        x["dt_bias"], lower=LOWER, dtype=dtype,
    )


def close(found, wanted, dtype):
    """float32 to 1e-5 of the array's scale, bf16 to one rounding."""
    found, wanted = (
        np.asarray(x.astype(jnp.float32)) for x in (found, wanted)
    )
    if jnp.dtype(dtype) == jnp.float32:
        np.testing.assert_allclose(
            found, wanted, rtol=1e-5,
            atol=1e-5 * max(np.abs(wanted).max(), 1e-30),
        )
    else:
        np.testing.assert_allclose(found, wanted, rtol=2.0 ** -7, atol=0)


@pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"]
)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_gates_are_the_plain_forms(shape, dtype):
    """``q`` and ``k`` normalised a head and rounded once to the
    model's type, ``g`` float32 whatever that type is, and the least
    ``g`` of the call from the blocks' minima: rows past the end of
    the sequence are in none of them."""
    x = operands(shape)
    q, k, g, least = gates(x, dtype)
    wanted = plain_gates(
        x["q"], x["k"], x["f"], x["a_log"], x["dt_bias"], dtype
    )
    assert q.dtype == k.dtype == jnp.dtype(dtype)
    assert g.dtype == jnp.float32
    close(q, wanted[0], dtype)
    close(k, wanted[1], dtype)
    close(g, wanted[2], jnp.float32)
    assert float(least) == float(jnp.min(g)) == float(jnp.min(wanted[2]))


@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_gates_gradients_are_the_plain_forms(shape):
    """All five: ``d q``, ``d k``, ``d f`` a token, ``d A_log`` a head
    and ``d dt_bias`` a channel from the blocks' summed rows."""
    x = operands(shape)

    def loss(form):
        def of(q, k, f, a_log, dt_bias):
            outputs = form(q, k, f, a_log, dt_bias)
            return sum(
                jnp.sum(o * w) for o, w in zip(outputs, x["weights"])
            )

        return jax.grad(of, argnums=(0, 1, 2, 3, 4))(
            x["q"], x["k"], x["f"], x["a_log"], x["dt_bias"]
        )

    found = loss(lambda *a: kda_gates(
        *a, lower=LOWER, dtype=jnp.float32
    )[:3])
    wanted = loss(lambda *a: plain_gates(*a, jnp.float32))
    for name, a, b in zip(("q", "k", "f", "A_log", "dt_bias"), found, wanted):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        close(a, b, jnp.float32)


@pytest.mark.parametrize("shape", ["2x128_tail_of_several", "2x32_tiny"])
def test_the_gates_gradients_in_the_models_types(shape):
    """As the cell runs them: float32 in, ``q`` and ``k`` out in bf16
    with bf16 cotangents, ``g`` and its cotangent float32."""
    x = operands(shape)
    weights = [
        w.astype(t) for w, t in zip(
            x["weights"], (jnp.bfloat16, jnp.bfloat16, jnp.float32)
        )
    ]

    def grads(form):
        outputs, pull = jax.vjp(
            form, x["q"], x["k"], x["f"], x["a_log"], x["dt_bias"]
        )
        assert [o.dtype for o in outputs] == [w.dtype for w in weights]
        return pull(tuple(weights))

    found = grads(lambda *a: kda_gates(
        *a, lower=LOWER, dtype=jnp.bfloat16
    )[:3])
    wanted = grads(lambda *a: plain_gates(*a, jnp.bfloat16))
    for a, b in zip(found, wanted):
        assert a.dtype == b.dtype == jnp.float32
        close(a, b, jnp.float32)


@pytest.mark.parametrize("end, at", [(-1e4, 0.0), (1e4, LOWER)])
def test_the_sigmoids_flat_ends_have_finite_gradients(end, at):
    """``f`` so far out that ``g`` sits at 0 or at the bound: the
    gradient of the gate is 0 there, not a NaN of ``0 x inf``."""
    x = operands("2x128_one_block_over")
    f = jnp.full_like(x["f"], end)
    g, least = gates(x, jnp.bfloat16, f)[2:]
    assert float(jnp.min(g)) == float(jnp.max(g)) == float(least) == at

    def loss(f, a_log, dt_bias):
        return jnp.sum(kda_gates(
            x["q"], x["k"], f, a_log, dt_bias, lower=LOWER,
            dtype=jnp.bfloat16,
        )[2] * x["weights"][2])

    for grad in jax.grad(loss, argnums=(0, 1, 2))(
        f, x["a_log"], x["dt_bias"]
    ):
        assert np.all(np.asarray(grad) == 0.0)


@pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"]
)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_gated_head_norm_is_the_plain_forms(shape, dtype):
    x = operands(shape)
    o, z = x["q"].astype(dtype), x["k"].astype(dtype)
    found = kda_norm(o, z, x["scale"], eps=EPS, dtype=dtype)
    assert found.dtype == jnp.dtype(dtype)
    close(found, plain_norm(o, z, x["scale"], dtype), dtype)


@pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"]
)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_gated_head_norms_gradients_are_the_plain_forms(shape, dtype):
    """``d o`` and ``d z`` in their operands' type, ``d o_norm`` a
    channel from the blocks' summed rows (float32 over every head)."""
    x = operands(shape)
    o, z = x["q"].astype(dtype), x["k"].astype(dtype)
    dy = x["weights"][0].astype(dtype)

    def grads(form):
        y, pull = jax.vjp(form, o, z, x["scale"])
        return pull(dy)

    found = grads(lambda *a: kda_norm(*a, eps=EPS, dtype=dtype))
    wanted = grads(lambda *a: plain_norm(*a, dtype))
    for a, b, kind in zip(found, wanted, (dtype, dtype, jnp.float32)):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.dtype(kind)
    if jnp.dtype(dtype) == jnp.float32:
        for a, b in zip(found, wanted):
            close(a, b, jnp.float32)
    else:
        # the plain form rounds the cotangent of every intermediate to
        # bf16 on its way back; the kernel rounds d o and d z once
        exact = grads(lambda o, z, s: plain_norm(
            o.astype(jnp.float32), z.astype(jnp.float32), s, jnp.float32
        ).astype(dtype))
        for a, b in zip(found[:2], exact[:2]):
            close(a, b, dtype)
        np.testing.assert_allclose(found[2], exact[2], rtol=1e-4)


@pytest.mark.parametrize("heads, d, s, bytes_a_lane, wanted", [
    # the cell's four calls at 1 x 8192 x 32 x 128: eight heads a
    # block, rows by what the call's arrays take at two buffers
    (32, 128, 8192, 20, (128, 1024)),
    (32, 128, 8192, 32, (128, 1024)),
    (32, 128, 8192, 6, (512, 1024)),
    (32, 128, 8192, 10, (256, 1024)),
    # a head wider than MAX_LANES is a block of its own
    (4, 2048, 8192, 6, (256, 2048)),
    # heads that are no whole columns: the whole lane axis
    (2, 32, 50, 20, (128, 64)),
    (30, 96, 8192, 6, (128, 2880)),
])
def test_the_shapes_decide_the_blocks(heads, d, s, bytes_a_lane, wanted):
    rows, lanes = kda_rows._tiling(s, heads, d, bytes_a_lane)
    assert (rows, lanes) == wanted
    assert rows % kda_rows.STRIP == 0 and (heads * d) % lanes == 0
    assert lanes % d == 0
