"""The walk inside the flash-attention kernels (interpret mode on the
CPU): forward and all three gradients against the plain reference over
the cases the loop bounds inside the kernels create.  In a file of its
own: its 29 cases are over half of what ``test_flash_attention.py``
cost, and under ``--dist loadfile`` a file is one worker's."""

import jax
import numpy as np
import pytest

from test_flash_attention import _rand_qkv, _reference

from dlrover_tpu.ops import flash_attention as fa
from dlrover_tpu.ops.flash_attention import flash_attention


# what the walk inside the kernel can meet: (shape, blocks, causal,
# residency budget in bytes or None for the module's own, rows a chunk
# of the loop body takes or None for the module's own)
WALKS = {
    # 4 x 4 sub-blocks: tiles on the diagonal (masked), below it
    # (plain) and above it (never visited)
    "on-and-off-the-diagonal": (dict(s=256), (64, 64), True, None, None),
    # the diagonal crosses two kv sub-blocks of every q tile
    "q-tile-wider": (dict(s=256), (128, 64), True, None, None),
    # and two q tiles share every kv sub-block
    "kv-sub-block-wider": (dict(s=256), (64, 128), True, None, None),
    "one-sub-block": (dict(s=64), (64, 64), True, None, None),
    "not-causal": (dict(s=256), (64, 128), False, None, None),
    "group-4": (dict(s=128, h=8, kv_heads=2), (64, 32), True, None, None),
    "head-64": (dict(s=128, h=2, d=64), (64, 64), True, None, None),
    # 0.0884 is no power of two: the scale stays on the scores
    "head-128": (dict(b=1, s=256, h=2, d=128), (128, 128), True, None, None),
    # float32 at 4096 x 128 is 8 MB of K and V: two kv-major blocks
    # of 2048 on the grid, each walked by the same loop
    "past-the-budget": (
        dict(b=1, s=4096, h=1, d=128), (512, 512), True, None, None,
    ),
    # the same branch at a size the gradients' tolerances were set at:
    # 4 major blocks of 2 sub-blocks, group 2, both block orders
    "major-blocks-q-wider": (
        dict(s=512, h=4, kv_heads=2), (128, 64), True,
        4 * 128 * 32 * 4, 32,
    ),
    "major-blocks-kv-wider": (
        dict(s=512, h=4, kv_heads=2), (64, 128), True,
        4 * 256 * 32 * 4, None,
    ),
    "major-blocks-not-causal": (
        dict(s=256), (64, 64), False, 4 * 128 * 32 * 4, None,
    ),
    # nothing fits: one sub-block a grid step
    "major-block-is-the-sub-block": (
        dict(s=256), (64, 64), True, 1, None,
    ),
    # block_q == block_k: the diagonal block is the tile's own, walked
    # as a triangle of chunks (4 chunks: 10 of 16 chunk pairs, the
    # mask on 4 of them); tiles below it take whole chunks
    "triangle-of-chunks": (dict(s=256), (128, 128), True, None, 32),
    "triangle-is-the-whole-walk": (
        dict(s=128, h=2, d=64), (128, 128), True, None, 32,
    ),
    "triangle-in-major-blocks": (
        dict(s=512, h=4, kv_heads=2), (128, 128), True,
        4 * 256 * 32 * 4, 64,
    ),
    # chunks without a triangle: the mask's offset moves with the chunk
    "chunks-q-tile-wider": (dict(s=256), (128, 64), True, None, 32),
    "chunks-kv-sub-block-wider": (
        dict(s=256), (64, 128), True, None, 32,
    ),
    "chunks-not-causal": (dict(s=256), (128, 128), False, None, 32),
    # a window (a sixth entry): query i sees keys (i - window, i].
    # Below the block: the band lies in a tile's own sub-block and
    # the one before it, both edges in the own one
    "window-below-the-block": (
        dict(s=256), (64, 64), True, None, 32, 24,
    ),
    # the trailing edge runs along the diagonal of the tile before
    "window-is-the-block": (dict(s=256), (64, 64), True, None, 32, 64),
    # two tiles back, the trailing edge through chunk-square pieces
    "window-above-the-block-not-a-multiple": (
        dict(s=256), (64, 64), True, None, 32, 150,
    ),
    # a sub-block wholly inside the band goes in plain passes
    "window-of-three-blocks": (
        dict(s=512), (64, 64), True, None, 32, 192,
    ),
    "window-one-chunk-a-block": (
        dict(s=256), (64, 64), True, None, None, 40,
    ),
    "window-of-one-key": (dict(s=128), (64, 64), True, None, 32, 1),
    "window-one-tile-a-head": (
        dict(s=128, h=2, d=64), (128, 128), True, None, 32, 50,
    ),
    # Laguna's two groups: 6 query heads a kv head in a full layer, 9
    # in a sliding one
    "window-group-9": (
        dict(b=1, s=256, h=9, kv_heads=1), (64, 64), True, None, 32, 72,
    ),
    "group-6": (
        dict(b=1, s=128, h=12, kv_heads=2), (64, 64), True, None, 32,
    ),
    "window-group-6-head-128": (
        dict(b=1, s=256, h=6, kv_heads=1, d=128), (128, 128), True,
        None, 64, 96,
    ),
}


@pytest.mark.parametrize("walk", list(WALKS))
def test_walk_matches_reference(walk, monkeypatch):
    """Forward and all three gradients against the plain reference,
    over the cases the loop bounds inside the kernels create."""
    shape, (block_q, block_k), causal, budget, chunk, *more = WALKS[walk]
    window = more[0] if more else None
    if budget is not None:
        monkeypatch.setattr(fa, "_RESIDENT_BYTES", budget)
    if chunk is not None:
        monkeypatch.setattr(fa, "_CHUNK", chunk)
    q, k, v = _rand_qkv(**shape)
    if budget is not None or walk.startswith("past"):
        rows = fa.resident_rows(
            q.shape[1], block_k, q.shape[3], q.dtype.itemsize
        )
        assert rows < q.shape[1]

    def flash(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            window=window,
        )

    def ref(q, k, v):
        return _reference(q, k, v, causal=causal, window=window)

    np.testing.assert_allclose(
        np.asarray(jax.jit(flash)(q, k, v)), np.asarray(ref(q, k, v)),
        atol=2e-5, rtol=2e-5,
    )
    # a random cotangent (all ones would weigh every row alike)
    do = jax.random.normal(jax.random.PRNGKey(9), q.shape[:3] + v.shape[3:])
    g_flash = jax.jit(jax.grad(
        lambda *a: (flash(*a) * do).sum(), argnums=(0, 1, 2)
    ))(q, k, v)
    g_ref = jax.jit(jax.grad(
        lambda *a: (ref(*a) * do).sum(), argnums=(0, 1, 2)
    ))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-5, rtol=5e-4,
            err_msg=f"grad mismatch for {name}",
        )
