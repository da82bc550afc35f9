"""The held-experts layer of ``parallel/moe.py`` and the grouped-matmul
kernels of ``ops/grouped_matmul.py`` against their own claims, at
routings made by hand: no row past ``tiles_used`` reaches the layer;
where a chip holds a range, the row side (dispatch and combine over
the used row tiles) is the plain routing as it stood until PR 38, and
its two movements are each other's transpose; no ``tokens x choices x
width`` array in the step; the defaults are the layer OLMoE has always
run; a gated expert is one call of the kernels; the layer's scopes are
in the compiled step.  The index work of a held range is
``test_moe_held_index.py``; the step these tests lower is
``test_sarvam_mla.py``'s toy."""

import functools
import math

import pytest

jax = pytest.importorskip("jax")

import flax.linen as nn  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from conftest import (  # noqa: E402
    fill_inside_an_expert, fill_past, primitives_under,
)
from test_moe_held_index import layout_by_sorting  # noqa: E402

from dlrover_tpu.models.sarvam_mla import (  # noqa: E402
    SarvamMla,
    SarvamMlaConfig,
    make_sarvam_mla_loss,
)
from dlrover_tpu.ops import grouped_matmul as gmm  # noqa: E402
from dlrover_tpu.parallel import moe  # noqa: E402
from dlrover_tpu.parallel.moe import dropless_moe  # noqa: E402


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


def layer_operands(t=96, d=32, m=16, e=16, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (
        jax.random.normal(ks[0], (t, d)),
        jax.random.normal(ks[1], (d, e)),
        jax.random.normal(ks[2], (e, d, m)) * 0.2,
        jax.random.normal(ks[3], (e, d, m)) * 0.2,
        jax.random.normal(ks[4], (e, m, d)) * 0.2,
    )


def share(operands, held, top_k=4, bias=None, **kw):
    x, router, w_gate, w_up, w_down = operands
    lo, count = held
    kw = {**dict(
        score="sigmoid", select_bias=bias, renormalise=True, scale=2.5,
    ), **kw}
    return dropless_moe(
        x, router, w_gate[lo:lo + count], w_up[lo:lo + count],
        w_down[lo:lo + count], top_k, jnp.float32, held=held, **kw,
    )


UNWRITTEN = {
    # OLMoE's tiny case: softmax, not renormalised, every expert held
    "every_expert_held": dict(
        operands=dict(t=128, d=64, m=32, e=8, seed=4), held=None,
        top_k=2, tiles=(8, 9),
    ),
    # 4 of 64 experts held: a sixteenth of 2048 assignments has a row
    "most_tiles_empty": dict(
        operands=dict(t=512, e=64, seed=2), held=(8, 4), top_k=4,
        tiles=(4, 12),
    ),
    # ... and the bias keeps every token from held expert 9
    "an_empty_expert": dict(
        operands=dict(t=512, e=64, seed=3), held=(8, 4), top_k=4,
        avoid=9, tiles=(4, 12),
    ),
}


@pytest.mark.parametrize("case", sorted(UNWRITTEN))
def test_no_row_past_tiles_used_reaches_the_layer(case, monkeypatch):
    """The experts' kernels do not write the rows of the tiles past
    ``tiles_used``, forward or backward.  That is safe because
    nothing reads one: with every such row of the experts' result
    and of its gradient to the rows, AND of every array between the
    kernels of ``grouped_expert`` (the hidden rows, the kept
    pre-activations of gate and up, their gradients, every row
    operand of the matrices' gradients) overwritten with NaN, the
    output and all five gradients are finite and bit-equal to the
    run with zeros there (the kernels' contract until PR 36) and to
    the run as it is.  Where the chip holds a range, the
    dispatch's output and the combine's gradient are not written
    there either (PR 38) and are overwritten alike.  A reduction over
    the padded rows, or a gather that names one, fails here."""
    c = UNWRITTEN[case]
    operands = layer_operands(**c["operands"])
    bias = None
    if "avoid" in c:
        bias = jnp.zeros(operands[1].shape[1:]).at[c["avoid"]].set(-9.0)
    real = gmm.grouped_expert
    held_dispatch, held_combine = moe._held_dispatch, moe._held_combine
    seen, kernels = [], []

    def layer(*ops):
        if c["held"] is None:
            return dropless_moe(*ops, c["top_k"], jnp.float32)
        return share(ops, c["held"], c["top_k"], bias=bias)

    def results(fill):
        def experts(rows, w_gate, w_up, w_down, tile_group, tiles_used):
            seen.append((tiles_used, tile_group.shape[0]))
            if fill is None:
                return real(rows, w_gate, w_up, w_down, tile_group, tiles_used)
            # the cotangent's fill first (d_rows), the result's last
            return fill_past(
                real(
                    fill_past(rows, tiles_used, fill), w_gate, w_up, w_down,
                    tile_group, tiles_used,
                ),
                tiles_used, fill,
            )

        def scored(*ops):
            out, stats = layer(*ops)
            return jnp.sum(out * cot), (out, stats)

        def dispatch(tokens, token_of_row, tiles_used, t):
            return fill_past(
                held_dispatch(tokens, token_of_row, tiles_used, t),
                tiles_used, fill,
            )

        def combine(rows, gate_of_row, token_of_row, tiles_used, t):
            # the fill of ``rows`` is the fill of their gradient
            return held_combine(
                fill_past(rows, tiles_used, fill), gate_of_row,
                token_of_row, tiles_used, t,
            )

        monkeypatch.setattr(moe.gmm, "grouped_expert", experts)
        fill_inside_an_expert(monkeypatch, fill, kernels)
        if fill is not None:
            monkeypatch.setattr(moe, "_held_dispatch", dispatch)
            monkeypatch.setattr(moe, "_held_combine", combine)
        cot = jax.random.normal(jax.random.PRNGKey(7), operands[0].shape)
        (_, (out, stats)), grads = jax.value_and_grad(
            scored, argnums=range(5), has_aux=True
        )(*operands)
        return [np.asarray(a) for a in (out, *grads)], stats

    as_it_is, stats = results(None)
    used, tiles = c["tiles"]
    assert [(int(u[0]), n) for u, n in seen] == [(used, tiles)]
    # ONE call, and these its kernels, forward rule and backward
    assert kernels == [
        "gmm_up_fwd", "gmm_fwd", "gmm_down_dlhs", "gmm_up_dlhs",
        "gmm_drhs", "gmm_drhs", "gmm_drhs",
    ]
    if "avoid" in c:
        assert float(stats["counts"][c["avoid"]]) == 0
    with_nan, _ = results(jnp.nan)
    with_zeros, _ = results(0.0)
    for got, zeros, plain in zip(with_nan, with_zeros, as_it_is):
        assert np.isfinite(got).all() and got.any()
        np.testing.assert_array_equal(got, zeros)
        np.testing.assert_array_equal(got, plain)


# The held layer's routing as it stood until PR 38, word for word: the
# plain reference of the row-side movements.  Every array has the
# static size: the dispatch gathers ``[padded rows, d]``, the combine
# gathers ``[t, k, d]`` (a choice held elsewhere reads zeros) and
# weights it.  Its ``source`` and ``slot`` come from the sort of the
# assignments (``test_moe_held_index.py::layout_by_sorting``: the index
# work as it stood until PR 58).


def _rows_at(rows, slot, some_absent: bool):
    if some_absent:
        return rows.at[slot].get(mode="fill", fill_value=0)
    return rows[slot]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_rows_at_pr_37(tokens, source, slot, some_absent=False):
    zero_row = jnp.zeros((1, tokens.shape[1]), tokens.dtype)
    return jnp.concatenate([tokens, zero_row])[source // slot.shape[1]]


def _dispatch_fwd(tokens, source, slot, some_absent):
    return _dispatch_rows_at_pr_37(tokens, source, slot, some_absent), slot


def _dispatch_bwd(some_absent, slot, g):
    return (
        _rows_at(g, slot, some_absent).sum(axis=1).astype(g.dtype),
        None, None,
    )


_dispatch_rows_at_pr_37.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _collect_rows_at_pr_37(rows, source, slot, some_absent=False):
    return _rows_at(rows, slot, some_absent)


def _collect_fwd(rows, source, slot, some_absent):
    return _rows_at(rows, slot, some_absent), source


def _collect_bwd(some_absent, source, g):
    flat = g.reshape((-1, g.shape[-1]))
    return flat.at[source].get(mode="clip"), None, None


_collect_rows_at_pr_37.defvjp(_collect_fwd, _collect_bwd)


def plain_layout(
    expert_ids, gate, lo, count, tile_group, tiles_used, padded_starts
):
    # in ``moe._held_layout``'s place: the sort's ``source`` and
    # ``slot`` where the row side's ``token_of_row`` goes, the weights
    # ``[t, k]`` as they are where ``gate_of_row`` does
    *_, source, slot = layout_by_sorting(
        expert_ids, lo + count + 1, lo, count
    )
    return (source, slot), gate


def plain_dispatch(tokens, source_and_slot, tiles_used, t):
    return _dispatch_rows_at_pr_37(tokens, *source_and_slot, True)


def plain_combine(rows, gate, source_and_slot, tiles_used, t):
    return jnp.einsum(
        "tkd,tk->td",
        _collect_rows_at_pr_37(rows, *source_and_slot, True),
        gate, preferred_element_type=jnp.float32,
    ).astype(rows.dtype)


def plain_routing(patch):
    patch.setattr(moe, "_held_layout", plain_layout)
    patch.setattr(moe, "_held_dispatch", plain_dispatch)
    patch.setattr(moe, "_held_combine", plain_combine)


HELD = {
    **{k: v for k, v in UNWRITTEN.items() if v["held"] is not None},
    # the bias sends every token's four choices to experts 0..3: all
    # 2048 rows land here, two tiles an expert, the 4 spare ones empty
    "every_assignment_lands_here": dict(
        operands=dict(t=512, e=16, seed=5), held=(0, 4), top_k=4,
        towards=slice(0, 4), tiles=(8, 12),
    ),
    # ... and to a range held elsewhere: one empty tile an expert
    "no_token_reaches_the_range": dict(
        operands=dict(t=512, e=16, seed=5), held=(8, 4), top_k=4,
        towards=slice(0, 4), tiles=(4, 12),
    ),
}


def held_case(case):
    c = HELD[case]
    operands = layer_operands(**c["operands"])
    bias = jnp.zeros(operands[1].shape[1:])
    if "avoid" in c:
        bias = bias.at[c["avoid"]].set(-9.0)
    if "towards" in c:
        bias = bias.at[c["towards"]].set(9.0)
    return c, operands, bias


@pytest.mark.parametrize("case", sorted(HELD))
def test_the_row_side_is_the_plain_routing(case, monkeypatch):
    """Where a chip holds a range, dispatch and combine walk the used
    row tiles (PR 38).  Against the routing as it stood, at the static
    size: the same output and the same five gradients, to 1e-6 of
    float32 where a token's held terms are summed in another order
    (by expert, no longer by choice) and BIT-equal where nothing is
    summed differently (the gradients to the experts' weights: the
    rows and the rows' gradients are the same numbers)."""
    c, operands, bias = held_case(case)
    cot = jax.random.normal(jax.random.PRNGKey(7), operands[0].shape)

    def results():
        def scored(*ops):
            out, stats = share(ops, c["held"], c["top_k"], bias=bias)
            return jnp.sum(out * cot), (out, stats)

        (_, (out, stats)), grads = jax.jit(jax.value_and_grad(
            scored, argnums=range(5), has_aux=True
        ))(*operands)
        return [np.asarray(a) for a in (out, *grads)], stats

    got, stats = results()
    assert (int(stats["tiles_used"]), int(stats["tiles"])) == c["tiles"]
    plain_routing(monkeypatch)
    want, _ = results()
    for name, a, b in zip(
        ("out", "tokens", "router", "w_gate", "w_up", "w_down"), got, want
    ):
        assert np.isfinite(a).all(), name
        if name.startswith("w_"):
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            assert relative(a, b) < 1e-6, name
    if case == "no_token_reaches_the_range":
        assert not any(a.any() for a in got)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("weighted", [False, True])
def test_the_two_movements_are_each_others_transpose(weighted, dtype):
    """``_rows_from_tokens`` and ``tokens_from_rows`` against numpy's
    loops: 5 row tiles of which 3 are used, a tile's tokens ascending
    and distinct, its padding named past the last token.  The rows of
    the tiles past ``tiles_used`` are neither written by the one nor
    read by the other (NaN there), a row of padding reads zeros and
    adds nothing (NaN there too)."""
    t, d, tile = 300, 128, gmm.ROW_TILE
    rng = np.random.default_rng(3)
    token_of_row = np.full((5 * tile,), -1)
    for i, n in enumerate((tile, 41, 0)):
        token_of_row[i * tile:i * tile + n] = np.sort(
            rng.choice(t, n, replace=False)
        )
    real = token_of_row >= 0
    token_of_row = np.where(real, token_of_row, t + np.arange(5 * tile))
    tiles_used = jnp.array([3], jnp.int32)
    x = jnp.asarray(rng.normal(size=(t, d)), dtype)
    rows = np.asarray(moe._rows_from_tokens(
        x, jnp.asarray(token_of_row, jnp.int32), tiles_used
    ))
    assert np.isnan(rows[3 * tile:].astype(np.float32)).all()
    want = np.where(
        real[:, None], np.asarray(x)[np.minimum(token_of_row, t - 1)], 0
    )
    np.testing.assert_array_equal(rows[:3 * tile], want[:3 * tile])

    y = rng.normal(size=(5 * tile, d)).astype(np.float32)
    y[~real] = np.nan
    y = np.asarray(jnp.asarray(y, dtype))
    weight = rng.uniform(0.5, 2, size=(5 * tile,)).astype(np.float32)
    got = gmm.tokens_from_rows(
        jnp.asarray(y), jnp.asarray(token_of_row, jnp.int32), tiles_used,
        t, jnp.asarray(weight) if weighted else None,
    )
    assert got.dtype == dtype
    want = np.zeros((t, d), np.float32)
    for p in np.flatnonzero(real):
        want[token_of_row[p]] += (
            weight[p] if weighted else 1.0
        ) * y[p].astype(np.float32)
    want = np.asarray(jnp.asarray(want, dtype))
    if weighted:
        # a compiler may fuse the product into the sum
        assert relative(got, want) < (1e-6 if dtype == jnp.float32 else 8e-3)
    else:
        np.testing.assert_array_equal(np.asarray(got), want)


def test_no_tokens_by_choices_by_width_array_in_the_step():
    """The toy's lowered step, forward and backward, holds no array of
    ``tokens x k`` rows of the model's width: no ``[t, k, d]`` and no
    ``[t * k, d]`` (what a gather or scatter of every assignment's row
    would make).  The layer with the plain routing does, so the search
    would find one."""
    from test_olmoe import shapes_in
    from test_sarvam_mla import toy_step

    model, step, state, batch = toy_step()
    cfg = model.config
    t, k, d = batch["x"].size, cfg.top_k, cfg.hidden_dim

    def every_assignment(text):
        return [
            s for s in shapes_in(text)
            if s[-1] == d and math.prod(s) == t * k * d
        ]

    assert not every_assignment(step.lower(state, batch).as_text())
    c, operands, bias = held_case("most_tiles_empty")
    t, d = operands[0].shape
    k = c["top_k"]

    def lowered():
        return jax.jit(jax.grad(
            lambda *ops: share(ops, c["held"], k, bias=bias)[0].sum(),
            argnums=range(5),
        )).lower(*operands).as_text()

    assert not every_assignment(lowered())
    with pytest.MonkeyPatch.context() as patch:
        plain_routing(patch)
        assert (t, k, d) in every_assignment(lowered())


def dropless_moe_at_pr_33(
    tokens, router_kernel, w_gate, w_up, w_down, top_k, dtype
):
    """``dropless_moe`` as it stood before it learnt of held experts
    and other routers (commit d0cb620), word for word."""
    t, _ = tokens.shape
    e = router_kernel.shape[-1]
    assignments = t * top_k
    logits = jnp.dot(
        tokens.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_ids = jax.lax.top_k(probs, top_k)
    flat_ids = expert_ids.reshape(-1)
    group_sizes = jnp.bincount(flat_ids, length=e).astype(jnp.int32)
    tile_group, tiles_used, padded_starts = gmm.group_layout(
        group_sizes, assignments
    )
    order = jnp.argsort(flat_ids, stable=True).astype(jnp.int32)
    sorted_ids = flat_ids[order]
    starts = jnp.cumsum(group_sizes) - group_sizes
    row = (
        padded_starts[sorted_ids] - starts[sorted_ids]
        + jnp.arange(assignments, dtype=jnp.int32)
    )
    slot = jnp.zeros_like(order).at[order].set(
        row, unique_indices=True
    ).reshape(t, top_k)
    source = jnp.full(
        (tile_group.shape[0] * gmm.ROW_TILE,), assignments, jnp.int32
    ).at[row].set(order, unique_indices=True)
    rows = moe._dispatch_rows(tokens.astype(dtype), source, slot)

    def expert(x, w):
        return gmm.grouped_matmul(
            x, w.astype(dtype), tile_group, tiles_used
        )

    rows = expert(
        nn.silu(expert(rows, w_gate)) * expert(rows, w_up), w_down
    )
    out = jnp.einsum(
        "tkd,tk->td", moe._collect_rows(rows, source, slot), gate,
        preferred_element_type=jnp.float32,
    )
    return out.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_defaults_are_the_layer_olmoe_has_always_run(dtype):
    """OLMoE's tiny case (8 experts, top-2, softmax, not renormalised,
    every expert held): output and all five gradients against the
    function as PR 33 had it, whose activation was XLA's between
    three grouped matmuls.  BIT-equal until PR 52; since then the
    activation is taken inside the kernel from the products' float32
    sums (not from their rounding to ``dtype``), its derivative is
    the down projection's kernel's epilogue and the rows' two
    gradients are summed in float32.  In float32 the two agree to a
    few roundings.  In bf16 the new path is held to being NO LESS
    EXACT than PR 33's: against PR 33's function run in float32 on
    the same operands, the output and every gradient lie closer (by
    their errors' root mean square: 15 to 45% closer here) and none
    of their elements further than 2 ** -6 of the largest."""
    operands = layer_operands(t=128, d=64, m=32, e=8, seed=4)

    def results(fn):
        loss = lambda *ops: fn(*ops).astype(jnp.float32).sum()  # noqa: E731
        return [
            np.asarray(leaf, np.float32) for leaf in jax.tree.leaves((
                jax.jit(fn)(*operands),
                jax.jit(jax.grad(loss, range(5)))(*operands),
            ))
        ]

    new = results(lambda *ops: dropless_moe(*ops, 2, dtype)[0])
    old = results(lambda *ops: dropless_moe_at_pr_33(*ops, 2, dtype))
    assert len(new) == len(old) == 6
    if dtype == jnp.float32:
        for a, b in zip(new, old):
            assert a.shape == b.shape and np.abs(b).max() > 0
            assert np.abs(a - b).max() <= 2.0 ** -20 * np.abs(b).max()
        return
    truth = results(
        lambda *ops: dropless_moe_at_pr_33(*ops, 2, jnp.float32)
    )
    for a, b, true in zip(new, old, truth, strict=True):
        assert a.shape == b.shape == true.shape

        def rms(x, true=true):
            return np.sqrt(np.mean(np.square(x - true)))

        assert 0 < rms(a) <= rms(b)
        assert np.abs(a - true).max() <= 2.0 ** -6 * np.abs(true).max()


def test_a_gated_expert_is_one_call_of_the_kernels_and_no_pass_beside():
    """The forward of the training loss under ``moe_experts`` and
    ``moe_shared``, an expert layer: ``grouped_expert`` (ONE
    ``custom_vjp_call``) with the three weights' casts and NOTHING
    else: the ``silu`` and the product are inside the up projections'
    kernel since PR 52 (before it: three grouped matmuls, a ``jit``
    and a ``mul`` over the padded rows); the shared expert three
    plain matmuls, one ``silu``, one product and the sum onto the
    routed output, as it was.  Five cells run this path: a change
    that moves the count has to be measured in them."""
    model = SarvamMla(SarvamMlaConfig.tiny(remat=True))
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), seq_len=64)
    )
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    jaxpr = jax.make_jaxpr(make_sarvam_mla_loss(model, num_chunks=4))(
        params, {"x": tokens, "y": tokens}
    ).jaxpr
    layers = model.config.num_layers - model.config.first_dense
    assert primitives_under(jaxpr, "moe_experts") == {
        "custom_vjp_call": layers, "convert_element_type": 3 * layers,
    }
    assert primitives_under(jaxpr, "moe_shared") == {
        "dot_general": 3 * layers, "convert_element_type": 3 * layers,
        "jit": layers, "mul": layers, "add": layers,
    }
    assert "experts_w_gate" in params["block_1"]["moe"]


def test_the_layers_scopes_are_in_the_compiled_step():
    """What the benchmark's readers join on: latent attention's five
    scopes, the held layer's four and the shared expert's name
    operations of the compiled step, forward (``jvp(..)``) and
    backward (``transpose(jvp(..))``)."""
    from test_sarvam_mla import toy_step

    from dlrover_tpu.common.aot_cache import op_names

    _, step, state, batch = toy_step()
    compiled = step.lower(state, batch).compile()
    stacks = list(op_names(compiled.as_text())["op_names"].values())
    for scope in (
        "mla_q", "mla_kv_down", "mla_kv_up", "mla_rope", "mla_out",
        "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
        "moe_shared",
    ):
        named = [s for s in stacks if f"/{scope}/" in s]
        assert named, scope
        assert any("transpose(" in s for s in named), scope
    assert any("/attn/" in s for s in stacks)
