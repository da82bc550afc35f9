"""The chunk-wise gated delta rule (``ops/gated_delta_rule.py``: the
``gdn_fwd`` / ``gdn_bwd`` kernels, in interpreter mode here) against
the recurrence it stands for, token by token: outputs, the final
state, all five gradients through the ``custom_vjp``, and the state at
every chunk boundary."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.ops import gated_delta_rule as gdr  # noqa: E402
from dlrover_tpu.ops.gated_delta_rule import gated_delta_rule  # noqa: E402

NAMES = ("q", "k", "v", "g", "beta")


def recurrence(q, k, v, g, beta, every=0):
    """``S_t = e^g S + beta k (v - e^g S^T k)^T``, ``o_t = S_t^T q_t``;
    ``(o, final state, the states after tokens every, 2 every, ..)``."""
    b, s, h, dk = q.shape

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[..., None, None] * state
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + (b_t[..., None] * k_t)[..., :, None] * (
            v_t - read
        )[..., None, :]
        return state, (jnp.einsum("bhkv,bhk->bhv", state, q_t), state)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        final, (o, states) = jax.lax.scan(
            token, jnp.zeros((b, h, dk, v.shape[-1])), xs
        )
    at = states[every - 1::every] if every else None
    return jnp.moveaxis(o, 0, 1), final, at


def operands(s, beta_at=0.0, g_scale=1.0, b=2, h=3, dk=8, dv=16, seed=0,
             dtype=jnp.float32):
    """``beta = 2 sigmoid(n + beta_at)``, ``g = -g_scale U(0, 1)``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (b, s, h, dk))
    k = jax.random.normal(keys[1], (b, s, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (b, s, h, dv))
    g = -g_scale * jax.random.uniform(keys[3], (b, s, h))
    beta = 2.0 * jax.nn.sigmoid(
        jax.random.normal(keys[4], (b, s, h)) + beta_at
    )
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


# a multiple of the chunk, one chunk and a bit, a ragged tail
LENGTHS = [gdr.CHUNK, 2 * gdr.CHUNK, gdr.CHUNK + 7, 3 * gdr.CHUNK + 41]
# the published head sizes (neither a multiple of 128), two heads and
# a ragged length; the small sizes of the other cases
SIZES = {
    "8x16": dict(),
    "96x192": dict(b=1, h=2, dk=96, dv=192),
}
# write strength near 1, near 2 and near 0; decay mild, none, strong
REGIMES = {
    "plain": (0.0, 1.0),
    "beta2-nodecay": (6.0, 0.01),
    "beta0-strong": (-6.0, 5.0),
    "beta2-strong": (6.0, 5.0),
}


def _cases(lengths, published):
    """Every regime at every length at the small sizes, and the
    published sizes at ``published`` (length, regime) pairs."""
    return [
        pytest.param(n, regime, "8x16", id=f"{n}-{regime}")
        for n in lengths for regime in REGIMES
    ] + [
        pytest.param(n, regime, "96x192", id=f"{n}-{regime}-96x192")
        for n, regime in published
    ]


@pytest.mark.parametrize("length,regime,sizes", _cases(LENGTHS, [
    (gdr.CHUNK + 7, "plain"), (2 * gdr.CHUNK + 41, "beta2-strong"),
]))
def test_outputs_and_final_state_equal_the_recurrence(
    length, regime, sizes
):
    x = operands(length, *REGIMES[regime], **SIZES[sizes])
    o, state = gated_delta_rule(*x)
    want_o, want_state, _ = recurrence(*x)
    assert o.shape == want_o.shape and o.dtype == jnp.float32
    assert relative(o, want_o) < 2e-5
    assert relative(state, want_state) < 2e-5


@pytest.mark.parametrize("length,regime,sizes", _cases(
    [gdr.CHUNK, 2 * gdr.CHUNK + 9],
    [(gdr.CHUNK + 9, "plain"), (gdr.CHUNK + 9, "beta2-nodecay")],
))
def test_all_five_gradients_equal_the_recurrences(length, regime, sizes):
    """Through the ``custom_vjp`` (``gdn_bwd``), write strengths near
    2 included (``beta2-*``: what breaks a Neumann product)."""
    x = operands(length, *REGIMES[regime], **SIZES[sizes])
    weights = jax.random.normal(
        jax.random.PRNGKey(9), x[2].shape
    )

    def scalar(fn):
        def total(*a):
            o, state = fn(*a)[:2]
            return jnp.sum(o * weights) + jnp.sum(state ** 2)
        return total

    got = jax.grad(scalar(gated_delta_rule), argnums=range(5))(*x)
    want = jax.grad(scalar(recurrence), argnums=range(5))(*x)
    for name, a, b in zip(NAMES, got, want):
        assert np.abs(np.asarray(b)).max() > 0, name
        assert relative(a, b) < 5e-5, name


@pytest.mark.parametrize("regime", ["plain", "beta2-strong"])
def test_state_handed_over_equals_the_recurrences_at_every_boundary(
    regime,
):
    """The states the forward saves for the backward (what each chunk
    STARTS from: zero, then the recurrence's after ``CHUNK, 2 CHUNK,
    ..`` tokens), float32 for float32 operands, and the final state
    of every prefix of whole chunks."""
    chunks = 4
    x = operands(chunks * gdr.CHUNK, *REGIMES[regime])
    _, final, at = recurrence(*x, every=gdr.CHUNK)
    assert at.shape[0] == chunks
    b, _, h, dk = x[0].shape
    (_, state), (_, starts, t) = gdr._rule_fwd(*x)
    assert starts.dtype == t.dtype == jnp.float32
    assert t.shape == (b * h, chunks, gdr.CHUNK, gdr.CHUNK)
    starts = starts.reshape(b, h, chunks, dk, -1)
    assert not np.asarray(starts[:, :, 0]).any()
    for n in range(1, chunks):
        assert relative(starts[:, :, n], at[n - 1]) < 2e-5, n
    assert relative(state, at[-1]) < 2e-5
    for n in range(1, chunks + 1):
        cut = tuple(a[:, :n * gdr.CHUNK] for a in x)
        _, state = gated_delta_rule(*cut)
        assert relative(state, at[n - 1]) < 2e-5, n
    np.testing.assert_allclose(at[-1], final)


@pytest.mark.parametrize("regime", ["plain", "beta2-strong"])
def test_gradients_under_an_enclosing_remat_are_the_same(regime):
    """``jax.grad`` of the rule inside a ``jax.checkpoint`` of the
    function around it (a remat that keeps nothing, as the model's
    per-block remat did before PR 65: the forward runs again, then the
    ``custom_vjp``'s backward) equals the same without."""
    x = operands(gdr.CHUNK + 9, *REGIMES[regime])
    weights = jax.random.normal(jax.random.PRNGKey(9), x[2].shape)

    def block(*a):
        o, state = gated_delta_rule(*(2.0 * y for y in a[:3]), *a[3:])
        return jnp.sum(jnp.tanh(o) * weights) + jnp.sum(state ** 2)

    want = jax.grad(block, argnums=range(5))(*x)
    got = jax.grad(jax.checkpoint(block), argnums=range(5))(*x)
    for name, a, b in zip(NAMES, got, want):
        assert np.abs(np.asarray(b)).max() > 0, name
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7, err_msg=name)


def test_state_decays_and_inverse_are_float32_in_the_kernels():
    """With bf16 operands: the state's and ``dS``'s scratch are
    float32, the chunk-start states and the inverse travel to the
    backward in the operands' type (what the matmuls read), the final
    state and the gates' gradients come out float32, and the inverse
    of a tile is float32 whichever way its matmuls run."""
    x = operands(gdr.CHUNK + 5, dtype=jnp.bfloat16)
    operands_ = gdr._operands(*x)
    assert [a.dtype for a in operands_] == [jnp.bfloat16] * 3 + [
        jnp.float32
    ] * 2
    def scratch_type(jaxpr):
        """Of the one kernel in a jitted wrapper's jaxpr."""
        (jitted,) = jaxpr.eqns
        (call,) = [
            e for e in jitted.params["jaxpr"].eqns
            if e.primitive.name == "pallas_call"
        ]
        return call.params["jaxpr"].invars[-1].aval.dtype

    forward = jax.make_jaxpr(gdr._forward)(*operands_)
    assert scratch_type(forward) == jnp.float32
    o, final, starts, t = gdr._forward(*operands_)
    assert (o.dtype, final.dtype, starts.dtype, t.dtype) == (
        jnp.bfloat16, jnp.float32, jnp.bfloat16, jnp.bfloat16
    )
    grads = jax.make_jaxpr(gdr._backward)(
        *operands_, starts, t, o, final
    )
    assert scratch_type(grads) == jnp.float32
    assert [v.aval.dtype for v in grads.jaxpr.outvars] == [
        jnp.bfloat16
    ] * 3 + [jnp.float32] * 2
    a = jnp.tril(jnp.full((gdr.CHUNK,) * 2, 0.25), -1)
    for exact in (True, False):
        assert gdr._inverse_unit_lower(a, exact).dtype == jnp.float32


def test_padding_neither_decays_nor_writes():
    """A ragged tail is padded with ``g = 0``, ``beta = 0``: the final
    state is the state after the last REAL token, and the outputs
    before the tail do not know the tail exists."""
    x = operands(gdr.CHUNK + 5, g_scale=3.0)
    o, state = gated_delta_rule(*x)
    whole = tuple(a[:, :gdr.CHUNK] for a in x)
    o_whole, _ = gated_delta_rule(*whole)
    np.testing.assert_allclose(
        o[:, :gdr.CHUNK], o_whole, rtol=1e-5, atol=1e-6
    )
    _, want, _ = recurrence(*x)
    assert relative(state, want) < 2e-5


def test_inverse_of_unit_lower_is_exact_where_the_series_is_not():
    """Write strengths of 2 on identical keys: ``A`` is 2 below the
    diagonal, ``(I + A)^-1`` alternates +-2 (bounded), while the
    powers ``A^k`` of a Neumann series pass 1e12 before they cancel.
    Forward substitution in float64 is the witness."""
    c = gdr.CHUNK
    a = 2.0 * np.tril(np.ones((c, c)), -1)
    want = np.linalg.inv(np.eye(c) + a)
    got = gdr._inverse_unit_lower(jnp.asarray(a, jnp.float32))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)
    assert np.abs(want).max() == pytest.approx(2.0)
    assert np.abs(np.linalg.matrix_power(a, 12)).max() > 1e12


def test_inverses_gradient_is_the_closed_form():
    """``X = (I + A)^-1 B`` as the backward kernel differentiates it
    (``_solve_bwd``: ``dB = T^T dX``, ``dA = -T^T (dX B^T) T^T = -dB
    X^T`` below the diagonal) against autodiff through a solve."""
    key = jax.random.PRNGKey(1)
    a = jnp.tril(jax.random.normal(key, (16, 16)) * 0.5, -1)
    b = jax.random.normal(jax.random.PRNGKey(3), (16, 24))
    weights = jax.random.normal(jax.random.PRNGKey(2), b.shape)

    def by_solve(a, b):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(jnp.linalg.solve(jnp.eye(16) + a, b) * weights)

    t = gdr._inverse_unit_lower(a)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            t @ (jnp.eye(16) + a), jnp.eye(16), atol=1e-5
        )
        db, da = gdr._solve_bwd(t, t @ b, weights, True)
    want_a, want_b = jax.grad(by_solve, argnums=(0, 1))(a, b)
    assert relative(da, jnp.tril(want_a, -1)) < 1e-5
    assert relative(db, want_b) < 1e-5
    assert not np.asarray(jnp.triu(da)).any()


@pytest.mark.parametrize("regime", ["plain", "beta2-strong"])
def test_bfloat16_operands_stay_within_their_rounding(regime):
    """bf16 q, k, v (8 bits of mantissa; decays, the inverse, the state
    and every accumulation float32) against the float32 recurrence on
    the SAME rounded operands.  Each output sums up to a few hundred
    products rounded at 2**-9: seen 3-6e-3 of the largest output;
    2e-2 is three times that and a tenth of what dropping the decay
    (``g = 0``) costs at these sizes (0.3)."""
    x = operands(3 * gdr.CHUNK + 5, *REGIMES[regime], dtype=jnp.bfloat16)
    o, state = gated_delta_rule(*x)
    assert o.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    exact = tuple(a.astype(jnp.float32) for a in x)
    want_o, want_state, _ = recurrence(*exact)
    assert relative(o.astype(jnp.float32), want_o) < 2e-2
    assert relative(state, want_state) < 2e-2
    no_decay = exact[:3] + (jnp.zeros_like(exact[3]), exact[4])
    wrong, _, _ = recurrence(*no_decay)
    assert relative(wrong, want_o) > 0.2
