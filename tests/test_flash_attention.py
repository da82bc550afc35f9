"""Flash-attention kernel tests (interpret mode on CPU): forward and
gradients vs the XLA reference attention, causal and non-causal,
multiple block splits."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import (
    REMAT_PRIMITIVE,
    equations,
    flash_forwards,
    jax_internal,
    pallas_calls,
)
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.ops.attention import xla_causal_attention
from dlrover_tpu.ops import flash_attention as fa
from dlrover_tpu.ops.flash_attention import flash_attention


def _rand_qkv(
    b=2, s=128, h=4, d=32, dtype=jnp.float32, seed=0, kv_heads=None
):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    heads = (h, kv_heads or h, kv_heads or h)
    return tuple(
        jax.random.normal(k, (b, s, hh, d), dtype=dtype) * 0.3
        for k, hh in zip(ks, heads)
    )


def _reference(q, k, v, causal=True, window=None):
    scale = q.shape[-1] ** -0.5
    group = q.shape[2] // k.shape[2]
    if group > 1:  # kv-head-major, as the Llama family lays q out
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        s = q.shape[1]
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        if window is not None:
            # query i sees keys (i - window, i]
            mask = mask & ~jnp.tril(jnp.ones((s, s), dtype=bool), -window)
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", probs, v.astype(jnp.float32)
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block", [64, 128])
def test_forward_matches_reference(causal, block):
    q, k, v = _rand_qkv(s=128)
    out = flash_attention(
        q, k, v, causal=causal, block_q=block, block_k=block
    )
    ref = _reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_forward_uneven_blocks():
    q, k, v = _rand_qkv(s=256)
    out = flash_attention(q, k, v, block_q=128, block_k=64)
    ref = _reference(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )
@pytest.mark.parametrize(
    "seq,block_q,block_k,visited,masked,total,computed",
    [
        # the old table's: the whole square, all of it masked
        (1024, 512, 1024, 2, 2, 2, 1.0),
        # one tile a head, a triangle of 4 chunks of 256: 10 of 16
        (1024, 1024, 1024, 1, 1, 1, 10 / 16),
        (1024, 512, 512, 3, 2, 4, (1 + 2 * 3 / 4) / 4),
        (1024, 256, 256, 10, 4, 16, 10 / 16),
        (1024, 128, 512, 12, 8, 16, 12 / 16),
        (4096, 1024, 1024, 10, 4, 16, (6 + 4 * 10 / 16) / 16),
        (4096, 512, 512, 36, 8, 64, (28 + 8 * 3 / 4) / 64),
        (4096, 256, 1024, 40, 16, 64, 40 / 64),
    ],
)
def test_block_schedule_counts_the_walk(
    seq, block_q, block_k, visited, masked, total, computed
):
    assert fa._CHUNK == 256
    assert fa.block_schedule(seq, block_q, block_k, True) == {
        "visited": visited, "masked": masked, "total": total,
        "computed": pytest.approx(computed),
    }
    assert fa.block_schedule(seq, block_q, block_k, False) == {
        "visited": total, "masked": 0, "total": total, "computed": 1.0,
    }
    # dkv walks the same set from the other side
    by_kv_tile = [
        fa._q_walk(k_start, block_q, block_k)
        for k_start in range(0, seq, block_k)
    ]
    num_q = seq // block_q
    assert sum(num_q - start for start, _ in by_kv_tile) == visited
    assert sum(full - start for start, full in by_kv_tile) == masked
    # and each sub-block is masked exactly when the diagonal crosses it
    for qi in range(num_q):
        full, end = fa._kv_walk(qi * block_q, block_q, block_k)
        for kj in range(seq // block_k):
            first_q, last_q = qi * block_q, (qi + 1) * block_q - 1
            first_k, last_k = kj * block_k, (kj + 1) * block_k - 1
            assert (kj < end) == (first_k <= last_q)
            assert (kj < full) == (last_k <= first_q)


@pytest.mark.parametrize(
    "seq,head_dim,itemsize,blocks,computed",
    [
        # GPT-2-XL's call: one tile a head, 4 passes over its triangle
        (1024, 64, 2, (1024, 1024), 10 / 16),
        # OLMoE's: 4 x 4 tiles, 6 below the diagonal and 4 triangles
        (4096, 128, 2, (1024, 1024), (6 + 4 * 10 / 16) / 16),
        # 4-byte operands take half the rows
        (4096, 128, 4, (512, 512), (28 + 8 * 3 / 4) / 64),
        (256, 64, 2, (256, 256), 1.0),
    ],
)
def test_default_blocks_read_the_calls_shape(
    seq, head_dim, itemsize, blocks, computed
):
    """The rule behind a call that names no blocks, pinned for the
    benchmark's two shapes with the walk it makes there."""
    assert fa.default_blocks(seq, itemsize) == blocks
    assert fa.block_schedule(seq, *blocks)["computed"] == (
        pytest.approx(computed)
    )
    # K and V of the whole sequence stay with the grid step up to the
    # budget: both cells' shapes do, float32 at 4096 x 128 does not
    rows = fa.resident_rows(seq, blocks[1], head_dim, itemsize)
    assert rows == (seq if itemsize == 2 else min(seq, 2048))


@pytest.mark.parametrize(
    "rows,cols,triangle,scores,passes",
    [
        # one pass while the scores fit
        (1024, 1024, False, 1024 * 1024, [(0, 1024, 0)]),
        # else passes of fewer columns, all rows each
        (1024, 1024, False, 512 * 1024, [(0, 512, 0), (512, 512, 0)]),
        (128, 64, False, 1, [(0, 64, 0)]),
        # the triangle: chunk c of the columns, from row c down
        (1024, 1024, True, 1,
         [(0, 256, 0), (256, 256, 256), (512, 256, 512),
          (768, 256, 768)]),
        (64, 64, True, 1, [(0, 64, 0)]),
    ],
)
def test_passes_over_a_sub_block(rows, cols, triangle, scores, passes):
    assert fa._passes(rows, cols, triangle, scores) == passes


def test_sub_block_in_several_passes_matches_reference(monkeypatch):
    """A sub-block too large for one pass: fewer columns a pass, the
    online softmax carried across them."""
    monkeypatch.setattr(fa, "_PASS_SCORES", 256 * 128)
    monkeypatch.setattr(fa, "_PASS_SCORES_DKV", 256 * 128)
    q, k, v = _rand_qkv(b=1, s=512, h=2, d=32)
    assert len(fa._passes(256, 256, False, fa._PASS_SCORES)) == 2

    def flash(q, k, v):
        return flash_attention(q, k, v, block_q=256, block_k=256)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(_reference(q, k, v)),
        atol=2e-5, rtol=2e-5,
    )
    g_flash = jax.grad(
        lambda *a: flash(*a).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    g_ref = jax.grad(
        lambda *a: _reference(*a).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-5, rtol=5e-4
        )


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    q, k, v = _rand_qkv(s=64, d=16)

    def loss_flash(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, block_q=32, block_k=32
        ).sum()

    def loss_ref(q, k, v):
        return _reference(q, k, v, causal=causal).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-5, rtol=5e-4,
            err_msg=f"grad mismatch for {name}",
        )


def test_bf16_forward_close():
    q, k, v = _rand_qkv(s=128, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v)
    ref = _reference(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref),
        atol=3e-2, rtol=3e-2,
    )


def test_model_integration_flash_impl():
    """GPT with attention_impl='flash' runs and matches the XLA impl."""
    from dlrover_tpu.models.gpt import GPT, GPTConfig

    cfg_x = GPTConfig.tiny(attention_impl="xla")
    cfg_f = GPTConfig.tiny(attention_impl="flash")
    model_x, model_f = GPT(cfg_x), GPT(cfg_f)
    params = model_x.init_params(jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 128), 0, cfg_x.vocab_size
    )
    lx = model_x.apply({"params": params}, tokens)
    lf = model_f.apply({"params": params}, tokens)
    np.testing.assert_allclose(
        np.asarray(lx), np.asarray(lf), atol=5e-2, rtol=5e-2
    )


def test_flash_attention_head_dim_128():
    """Llama-7B-class head_dim: kernel tiling must hold at d=128."""
    q, k, v = _rand_qkv(b=1, s=256, h=2, d=128, dtype=jnp.bfloat16)
    from dlrover_tpu.ops.attention import xla_causal_attention

    ref = xla_causal_attention(q, k, v)
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2,
    )
    # backward also traces/runs at d=128
    g = jax.grad(
        lambda q: flash_attention(q, k, v).astype(jnp.float32).sum()
    )(q)
    assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


def test_flash_gqa_matches_repeated_kv():
    """GQA path: k/v with fewer heads through the index maps must
    match the materialized-repeat MHA computation, forward and
    gradients (q, k AND v)."""
    b, s, h, kvh, d = 2, 256, 8, 2, 64
    group = h // kvh
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(kv_, (b, s, kvh, d), jnp.float32)
    # kv-head-major repeat (Llama layout: head = kvh_idx*group + g)
    k_rep = jnp.repeat(k, group, axis=2)
    v_rep = jnp.repeat(v, group, axis=2)

    # small blocks so the grid is multi-block and the //group index
    # map is exercised across kv blocks (incl. causal skipping)
    out_gqa = flash_attention(q, k, v, block_q=64, block_k=64)
    out_rep = flash_attention(q, k_rep, v_rep, block_q=64, block_k=64)
    np.testing.assert_allclose(
        np.asarray(out_gqa), np.asarray(out_rep), atol=1e-5,
        rtol=1e-5,
    )

    def loss_gqa(q, k, v):
        return (
            flash_attention(q, k, v, block_q=64, block_k=64) ** 2
        ).sum()

    def loss_rep(q, k, v):
        return (
            flash_attention(
                q, jnp.repeat(k, group, axis=2),
                jnp.repeat(v, group, axis=2),
                block_q=64, block_k=64,
            ) ** 2
        ).sum()

    g_gqa = jax.grad(loss_gqa, argnums=(0, 1, 2))(q, k, v)
    g_rep = jax.grad(loss_rep, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_gqa, g_rep):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-4, rtol=2e-4,
        )


def test_flash_gqa_rejects_nondivisible_heads():
    q = jnp.zeros((1, 128, 6, 64))
    k = jnp.zeros((1, 128, 4, 64))
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, k, k)
    # k/v head mismatch must be rejected, not silently mis-indexed
    q8 = jnp.zeros((2, 128, 8, 64))
    k2 = jnp.zeros((2, 128, 2, 64))
    v8 = jnp.zeros((2, 128, 8, 64))
    with pytest.raises(ValueError, match="heads"):
        flash_attention(q8, k2, v8)


# -- two head sizes: q and k of d_qk, v of d_v (latent attention) -------------


def _rand_two_sizes(s, h, d_qk, d_v, kv_heads=None, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    kvh = kv_heads or h
    q = jax.random.normal(ks[0], (1, s, h, d_qk)) * 0.3
    k = jax.random.normal(ks[1], (1, s, kvh, d_qk)) * 0.3
    v = jax.random.normal(ks[2], (1, s, kvh, d_v)) * 0.3
    do = jax.random.normal(ks[3], (1, s, h, d_v))
    return q, k, v, do


def _forward_and_grads(fn, q, k, v, do):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out, *vjp(do.astype(out.dtype)))


TWO_SIZES = {
    "24|16": (dict(s=256, h=2, d_qk=24, d_v=16), (64, 64), None),
    # latent attention's own: one and a half lane tiles beside one
    "192|128": (dict(s=256, h=2, d_qk=192, d_v=128), (128, 128), None),
    "192|128-default-blocks": (
        dict(s=256, h=2, d_qk=192, d_v=128), (None, None), None,
    ),
    # v the wider one, a group of 2, and major blocks on the grid
    "16|48-group-2-major-blocks": (
        dict(s=512, h=4, d_qk=16, d_v=48, kv_heads=2), (128, 64),
        2 * 128 * (16 + 48) * 4,
    ),
    # a window (a fourth entry) beside two head sizes
    "24|16-window": (
        dict(s=256, h=2, d_qk=24, d_v=16), (64, 64), None, 100,
    ),
    "192|128-window-group-3": (
        dict(s=256, h=3, d_qk=192, d_v=128, kv_heads=1), (128, 128),
        None, 128,
    ),
}


@pytest.mark.parametrize("case", list(TWO_SIZES))
def test_two_head_sizes_match_reference(case, monkeypatch):
    """``v.shape[-1] != q.shape[-1]``: the output and dv have v's
    size, dq and dk q's; forward and all three gradients against XLA
    attention, the default scale being ``d_qk ** -0.5``."""
    shape, (block_q, block_k), budget, *more = TWO_SIZES[case]
    window = more[0] if more else None
    if budget is not None:
        monkeypatch.setattr(fa, "_RESIDENT_BYTES", budget)
    q, k, v, do = _rand_two_sizes(**shape)
    got = _forward_and_grads(
        lambda *a: flash_attention(
            *a, block_q=block_q, block_k=block_k, window=window
        ), q, k, v, do,
    )
    want = _forward_and_grads(
        lambda *a: _reference(*a, window=window), q, k, v, do
    )
    assert got[0].shape == do.shape
    assert [g.shape for g in got[1:]] == [q.shape, k.shape, v.shape]
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=3e-5, rtol=3e-4
        )


def test_two_head_sizes_are_the_one_size_path_on_a_padded_v():
    """What ties the new path to the one every accepted cell runs: v
    of 16 beside q and k of 24 gives, BIT FOR BIT, what the one-size
    call gives on v padded with zero lanes to 24 (the same scores and
    probabilities; a zero lane adds exact zeros to dP)."""
    q, k, v, do = _rand_two_sizes(s=256, h=2, d_qk=24, d_v=16)
    pad = ((0, 0), (0, 0), (0, 0), (0, 8))
    narrow = _forward_and_grads(
        lambda *a: flash_attention(*a, block_q=64, block_k=64),
        q, k, v, do,
    )
    wide = _forward_and_grads(
        lambda *a: flash_attention(*a, block_q=64, block_k=64),
        q, k, jnp.pad(v, pad), jnp.pad(do, pad),
    )
    for n, w in zip(narrow, wide):
        w = w[..., :n.shape[-1]]
        assert np.array_equal(np.asarray(n), np.asarray(w))


def test_one_head_size_lowers_as_it_always_did():
    """With ``d_v == d_qk`` nothing of a call depends on the second
    size: ``resident_rows`` counts ``2 x (d + d)`` where it counted
    ``4 x d``, and the lowered call is the same text whether v's size
    is read from v or, as before, taken to be q's."""
    for seq, sub, d, itemsize in [
        (1024, 1024, 64, 2), (4096, 1024, 128, 2), (8192, 1024, 128, 2),
        (4096, 512, 128, 4),
    ]:
        assert fa.resident_rows(seq, sub, d, itemsize) == (
            fa.resident_rows(seq, sub, d, itemsize, d)
        )
    # 8192 x (192 | 128) in bf16: a quarter of the sequence resident
    assert fa.resident_rows(8192, 1024, 192, 2, 128) == 2048
    assert fa.resident_rows(8192, 1024, 128, 2) == 4096


@pytest.mark.parametrize("cell, seq, d, blocks, rows", [
    ("xl48_steady", 1024, 64, (1024, 1024), 1024),
    ("xl12_flash_save", 1024, 64, (1024, 1024), 1024),
    ("olmoe_steady_4k", 4096, 128, (1024, 1024), 4096),
    ("olmo_hybrid_steady_8k", 8192, 128, (1024, 1024), 4096),
])
def test_the_accepted_cells_keep_their_blocks_and_resident_rows(
    cell, seq, d, blocks, rows
):
    """At each accepted cell's sequence and head size (bf16) the block
    choice and the resident rows are the numbers the rule gave before
    it knew of a second head size (``4 x rows x d x itemsize`` within 4
    MiB, worked by hand): their kernels' static shapes did not move."""
    assert fa.default_blocks(seq, 2) == blocks
    assert fa.resident_rows(seq, blocks[1], d, 2) == rows
    assert fa.resident_rows(seq, blocks[1], d, 2, d) == rows
    assert 4 * rows * d * 2 <= 4 * 2**20 < 4 * (2 * rows) * d * 2 or (
        rows == seq
    )


def test_k_must_have_qs_head_size():
    q, k, v, _ = _rand_two_sizes(s=64, h=2, d_qk=24, d_v=16)
    with pytest.raises(ValueError, match="head size"):
        flash_attention(q, v, v)


# -- a window: query i sees keys (i - window, i] ------------------------------


@pytest.mark.parametrize("window", [256, 300])
def test_a_window_that_covers_the_sequence_is_the_plain_causal_call(
    window,
):
    """``window >= seq`` hides nothing: the call IS the causal one
    (the same program, so the same bits), forward and gradients."""
    q, k, v, do = _rand_two_sizes(s=256, h=4, d_qk=32, d_v=32, kv_heads=2)
    plain = _forward_and_grads(
        lambda *a: flash_attention(*a, block_q=64, block_k=64),
        q, k, v, do,
    )
    windowed = _forward_and_grads(
        lambda *a: flash_attention(
            *a, block_q=64, block_k=64, window=window
        ), q, k, v, do,
    )
    for a, b in zip(plain, windowed):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    text = [
        str(jax.make_jaxpr(lambda *a: flash_attention(*a, window=w))(
            q, k, v
        )) for w in (None, window)
    ]
    assert text[0] == text[1]


def _band(seq, window):
    ahead = np.arange(seq)[:, None] - np.arange(seq)[None, :]
    return (ahead >= 0) & (ahead < window)


@pytest.mark.parametrize("seq, block, window, chunk", [
    (8192, 1024, 512, 256), (8192, 512, 512, 256), (8192, 1024, 500, 256),
    (4096, 1024, 2048, 256), (2048, 256, 700, 256), (1024, 256, 1, 256),
    (1024, 128, 100, 32), (2048, 512, 1536, 128), (512, 512, 200, 128),
])
def test_block_schedule_counts_a_windowed_walk(
    seq, block, window, chunk, monkeypatch
):
    """``block_schedule(window=)`` against a brute-force count over
    the band itself: a sub-block is visited iff the band touches it,
    masked iff it is visited and not wholly inside, and ``computed``
    counts the chunk-square pieces the band touches.  The kv side (dkv
    walks q sub-blocks for a kv tile) visits the same sub-blocks and
    computes the same pieces."""
    monkeypatch.setattr(fa, "_CHUNK", chunk)
    band = _band(seq, window)
    tiles = seq // block

    def pieces(size):
        n = seq // size
        cut = band.reshape(n, size, n, size)
        return cut.any(axis=(1, 3)), cut.all(axis=(1, 3))

    touched, inside = pieces(block)
    got = fa.block_schedule(seq, block, block, True, window=window)
    assert got["visited"] == touched.sum()
    assert got["masked"] == (touched & ~inside).sum()
    assert got["total"] == tiles * tiles
    small = chunk if block % chunk == 0 else block
    assert got["computed"] == pytest.approx(
        pieces(small)[0].sum() * small * small / seq**2
    )
    back = fa._tiles_back(block, window)
    for side in (False, True):
        seen = np.zeros((tiles, tiles), bool)
        scores = 0
        for tile in range(tiles):
            for away in range(back + 1):
                other = tile + away if side else tile - away
                if not 0 <= other < tiles:
                    continue
                passes = fa._window_passes(
                    block, window, away if side else -away, side,
                    fa._PASS_SCORES,
                )
                assert passes
                seen[(other, tile) if side else (tile, other)] = True
                scores += sum(
                    width * (end - first)
                    for _, width, first, end, _ in passes
                )
        assert np.array_equal(seen, touched)
        assert scores == got["computed"] * seq**2
    # 15 of the causal walk's 36 tiles at Laguna's shape
    if (seq, block, window) == (8192, 1024, 512):
        assert got["visited"] == 15
        assert fa.block_schedule(seq, block, block)["visited"] == 36
        assert got["computed"] == pytest.approx(1.5 * band.sum() / seq**2,
                                                rel=0.01)
    assert fa.resident_rows(seq, block, 128, 2, window=window) == block


def test_a_window_takes_causal_square_tiles():
    q, k, v = _rand_qkv(s=128)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=32)
    with pytest.raises(ValueError, match="at least 1"):
        flash_attention(q, k, v, window=0)
    with pytest.raises(ValueError, match="square"):
        flash_attention(q, k, v, block_q=64, block_k=32, window=32)
    with pytest.raises(ValueError, match="square"):
        fa.block_schedule(128, 64, 32, True, window=32)
    # one block named: the other follows it
    out = flash_attention(q, k, v, block_q=64, window=32)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_reference(q, k, v, window=32)),
        atol=2e-5, rtol=2e-5,
    )


# sha256 over the three ``pallas_call`` equations (forward, dq, dkv) of
# value and gradients of a bf16 call at each accepted cell's attention
# shape (and one with a group): each equation printed on its own, so
# that no name of the surrounding jaxpr enters, with its index maps'
# jaxprs after it: grid, block shapes, kernel body, index maps, and no
# source location, so equal text is an equal Mosaic program.  First
# pinned as the whole jaxpr's text at the commit BEFORE the kernels
# knew of a window (f583308); re-pinned on the equations at fad06df
# (PR 44), where the whole text still read the hashes of f583308: the
# forward rule's two ``name`` equations are in the outer jaxpr, not in
# a kernel.
BEFORE_THE_WINDOW = {
    "xl48_steady": (
        (4, 1024, 25, 64), 25, 64, None,
        "3a0ce0a08463890b2975f11fc280514408f252078bdd0ea0638107d040d27538",
    ),
    "olmoe_steady_4k": (
        (2, 4096, 16, 128), 16, 128, None,
        "7ed94592c0aba16d986a84f005c517a3f22e3ed52c3fea37d61c4f92d34037b3",
    ),
    "olmo_hybrid_steady_8k": (
        (1, 8192, 30, 128), 30, 128, None,
        "f09c01f99925dd0075f560ae8338f9990628a47ed17ef83d0a73af1be991e924",
    ),
    "sarvam_steady_8k": (
        (1, 8192, 16, 192), 16, 128, 0.1352,
        "6212aa5470cdf8125d86276c588828d317323b0ee7df6372ac69a6c8afb566c9",
    ),
    "a-group-of-4": (
        (1, 2048, 8, 128), 2, 128, None,
        "8efcad089ccf1ce9fbe6ac0eacc39bcec83ebf1c8c66989bf0e38be14002c34a",
    ),
}


def kernel_texts(jaxpr):
    """Each ``pallas_call`` equation of a jaxpr as text of its own
    (a fresh naming context: the outer jaxpr's variable names do not
    enter), followed by its index maps."""
    pp_eqn, context, settings = (
        jax_internal("core", name)
        for name in ("pp_eqn", "JaxprPpContext", "JaxprPpSettings")
    )
    return [
        "\n".join([
            str(pp_eqn(eqn, context(), settings())),
            *(
                str(m.index_map_jaxpr)
                for m in eqn.params["grid_mapping"].block_mappings
            ),
        ])
        for _, eqn in pallas_calls(jaxpr)
    ]


@pytest.mark.parametrize("cell", list(BEFORE_THE_WINDOW))
def test_without_a_window_the_kernels_are_the_programs_they_were(
    cell, monkeypatch
):
    """``window=None`` keeps every accepted cell's attention: the
    three kernels trace, for the TPU, to the text they traced to
    before this file knew of a window."""
    import hashlib

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    q_shape, kv_heads, d_v, scale, before = BEFORE_THE_WINDOW[cell]
    b, s, _, d = q_shape
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, s, kv_heads, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, s, kv_heads, d_v), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(
            q, k, v, scale=scale
        ).astype(jnp.float32).sum()

    texts = kernel_texts(jax.make_jaxpr(
        jax.value_and_grad(loss, argnums=(0, 1, 2))
    )(q, k, v).jaxpr)
    assert len(texts) == 3
    assert hashlib.sha256(
        "\n".join(texts).encode()
    ).hexdigest() == before


# -- what a rematted caller keeps (PR 44, PR 45) ----------------------------


def _rematted_loss(policy, dtype=jnp.bfloat16, kv_heads=2):
    """A block's worth round the kernel under ``jax.checkpoint`` as the
    newer families wrap theirs (``prevent_cse=True``): one projection
    in (2 query heads, ``kv_heads`` for k and v), one out, so that a
    consumer's backward needs the kernel's output."""
    b, s, h, d = 1, 256, 2, 64
    width = (h + 2 * kv_heads) * d

    @functools.partial(jax.checkpoint, prevent_cse=True, policy=policy)
    def block(x, w_in, w_out):
        q, k, v = jnp.split(
            (x @ w_in).reshape(b, s, h + 2 * kv_heads, d),
            [h, h + kv_heads], axis=2,
        )
        return x + flash_attention(q, k, v).reshape(b, s, h * d) @ w_out

    def loss(args):
        return block(*args).astype(jnp.float32).sum()

    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    return loss, (
        jax.random.normal(keys[0], (b, s, h * d), dtype),
        jax.random.normal(keys[1], (h * d, width), dtype) * 0.1,
        jax.random.normal(keys[2], (h * d, h * d), dtype) * 0.1,
    )


def _projections_in(jaxpr):
    """Where a jaxpr of ``_rematted_loss`` computes ``x @ w_in``: the
    primitives round each ``dot_general`` that writes ``[b, s,
    width]`` (no gradient's matmul has that shape)."""
    return [
        under for under, eqn in equations(jaxpr)
        if eqn.primitive.name == "dot_general"
        and len(eqn.outvars[0].aval.shape) == 3
        and eqn.outvars[0].aval.shape[2] > 128
    ]


@pytest.mark.parametrize("dtype, bits, kv_heads", [
    (jnp.bfloat16, "uint16", 2),
    (jnp.bfloat16, "uint16", 1),  # a group of 2
    (jnp.float32, "uint32", 1),
], ids=["one-kv-head-a-query-head", "grouped-kv-heads", "grouped-float32"])
def test_a_rematted_caller_keeps_the_five_residuals_and_runs_nothing_twice(
    dtype, bits, kv_heads
):
    """Under the one remat policy the forward kernel is called once,
    outside the ``checkpoint``, and so is the projection that feeds
    it; what is saved into the backward beside the block's inputs is
    q, k and v as the kernel took them (k and v at the kv heads'
    count: no repeated array), ``out`` and ``lse`` as bits, each
    under its name, and nothing else; under the parent's
    ``policy=None`` kernel and projection run again inside it, and
    value and gradients are the same bits."""
    from dlrover_tpu.models.layers import remat_policy

    saved_residuals = jax_internal("ad_checkpoint", "saved_residuals")
    loss, args = _rematted_loss(remat_policy("full"), dtype, kv_heads)
    grad = jax.value_and_grad(loss)
    jaxpr = jax.make_jaxpr(grad)(args).jaxpr
    assert flash_forwards(jaxpr) == [()]
    assert _projections_in(jaxpr) == [()]
    kept = [
        (where, str(aval)) for aval, where in saved_residuals(loss, args)
        if "from the argument" not in where
    ]
    saved = {where.split("'")[1]: aval for where, aval in kept}
    number = jnp.dtype(dtype).name
    assert saved == dict(zip(fa.RESIDUAL_NAMES, (
        f"{number}[2,256,64]",
        f"{number}[{kv_heads},256,64]",
        f"{number}[{kv_heads},256,64]",
        f"{bits}[2,256,64]",
        "uint32[2,1,256]",
    )))
    assert len(saved) == len(kept)
    parents, _ = _rematted_loss(None, dtype, kv_heads)
    theirs = jax.make_jaxpr(jax.value_and_grad(parents))(args).jaxpr
    assert flash_forwards(theirs) == [(), (REMAT_PRIMITIVE,)]
    assert _projections_in(theirs) == [(), (REMAT_PRIMITIVE,)]
    for ours, theirs in zip(
        jax.tree.leaves(jax.jit(grad)(args)),
        jax.tree.leaves(jax.jit(jax.value_and_grad(parents))(args)),
    ):
        np.testing.assert_array_equal(
            np.asarray(ours), np.asarray(theirs)
        )


@pytest.mark.parametrize("kv_heads", [2, 1])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_a_saved_residual_costs_no_pass_over_it(dtype, kv_heads):
    """``jax.checkpoint`` guards a floating-point residual it saves
    with a ``reduce_precision`` behind its producer where the forward
    goes on to read it, on the chip a pass over the array every layer.
    ``out`` and ``lse`` are named as their bits and get none; q, k and
    v are named on arrays that only the residuals hold (the kernel
    reads the unnamed ones) and get none as the numbers they are (no
    bitcast either: the test above reads their types).  A program
    that is not rematted has no trace of a name: its lowering holds
    no ``reduce_precision`` either way."""
    from dlrover_tpu.models.layers import remat_policy

    loss, args = _rematted_loss(remat_policy("full"), dtype, kv_heads)
    text = str(jax.make_jaxpr(jax.grad(loss))(args))
    assert all(f"name={name}" in text for name in fa.RESIDUAL_NAMES)
    assert "reduce_precision" not in text
    q, k, v = _rand_qkv(b=1, s=128, h=2, d=32, dtype=dtype)
    plain = jax.jit(jax.grad(
        lambda q: flash_attention(q, k, v).astype(jnp.float32).sum()
    )).lower(q).as_text()
    assert "reduce_precision" not in plain
    assert not any(name in plain for name in fa.RESIDUAL_NAMES)

    # the control: the same name on a number the forward goes on to
    # read gets the pass (where a jax upgrade drops it, ``_named``'s
    # bitcasts can go; where one guards a residual nothing reads,
    # q, k and v need ``_named`` too)
    @functools.partial(
        jax.checkpoint, prevent_cse=True, policy=remat_policy("full")
    )
    def plainly(x):
        return jnp.sin(checkpoint_name(jnp.cos(x), fa.RESIDUAL_NAMES[3]))

    assert "reduce_precision" in str(jax.make_jaxpr(
        jax.grad(lambda x: plainly(x).astype(jnp.float32).sum())
    )(jnp.ones(8, dtype)))


@pytest.mark.parametrize(
    "policy, refused", [("full", None), ("offload", None),
                        ("save_attn", "full | offload")],
)
def test_the_remat_policies_are_full_and_offload(policy, refused):
    """``save_attn`` is gone: what it promised is what ``full`` does."""
    from dlrover_tpu.models.gpt import GPTConfig
    from dlrover_tpu.models.layers import remat_policy

    if refused is None:
        assert GPTConfig.tiny(
            remat=True, remat_policy=policy
        ).remat_policy == policy
        assert callable(remat_policy(policy))
        return
    with pytest.raises(ValueError, match=re.escape(refused)):
        GPTConfig.tiny(remat=True, remat_policy=policy)
    with pytest.raises(ValueError, match=re.escape(refused)):
        remat_policy(policy)
