"""Chunked fused-head cross entropy: matches the full loss exactly,
never materializes [B, S, V] logits (peak-memory assertion via
compiled memory analysis where the backend reports it)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import (
    GPT,
    GPTConfig,
    Llama,
    LlamaConfig,
    chunked_cross_entropy,
    chunked_loss_fn,
)
from dlrover_tpu.models.gpt import cross_entropy_loss


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_chunked_ce_matches_full(family):
    if family == "llama":
        cfg = LlamaConfig(
            vocab_size=128, max_seq_len=32, num_layers=2,
            num_heads=4, num_kv_heads=2, hidden_dim=64,
            intermediate_dim=128,
        )
        model = Llama(cfg)
    else:
        cfg = GPTConfig.tiny(max_seq_len=32)
        model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(0), seq_len=32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)

    logits = model.apply({"params": params}, x)
    full = cross_entropy_loss(logits, y)
    loss_fn = chunked_loss_fn(model, num_chunks=4)
    chunked = loss_fn(params, {"x": x, "y": y})
    np.testing.assert_allclose(
        float(full), float(chunked), rtol=2e-3
    )

    # gradients agree too (the whole point is training with it)
    g_full = jax.grad(
        lambda p: cross_entropy_loss(
            model.apply({"params": p}, x), y
        )
    )(params)
    g_chunk = jax.grad(
        lambda p: loss_fn(p, {"x": x, "y": y})
    )(params)
    for kf, kc in zip(
        jax.tree.leaves(g_full), jax.tree.leaves(g_chunk)
    ):
        np.testing.assert_allclose(
            np.asarray(kf), np.asarray(kc), atol=2e-2, rtol=2e-2
        )


def test_chunked_ce_rejects_bad_chunking():
    h = jnp.zeros((2, 30, 8))
    k = jnp.zeros((8, 16))
    t = jnp.zeros((2, 30), jnp.int32)
    with pytest.raises(ValueError):
        chunked_cross_entropy(h, k, t, num_chunks=4)


def test_chunked_ce_reduces_peak_memory():
    """Compiled grad of the chunked loss allocates far less temp
    memory than the full-logits loss (big vocab, long seq)."""
    vocab, b, s, h = 8192, 2, 512, 64
    rng = np.random.default_rng(0)
    hidden = jnp.asarray(rng.normal(size=(b, s, h)), jnp.float32)
    kernel = jnp.asarray(
        rng.normal(size=(h, vocab)) * 0.02, jnp.float32
    )
    t = jnp.asarray(rng.integers(0, vocab, (b, s)), jnp.int32)

    def full(kernel):
        logits = (hidden @ kernel).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, t[..., None], -1).mean()

    def chunked(kernel):
        return chunked_cross_entropy(hidden, kernel, t, num_chunks=16)

    # compile outside the try: a trace/compile failure is a real bug
    cf = jax.jit(jax.grad(full)).lower(kernel).compile()
    cc = jax.jit(jax.grad(chunked)).lower(kernel).compile()
    try:
        mf = cf.memory_analysis()
        mc = cc.memory_analysis()
    except (AttributeError, NotImplementedError):
        pytest.skip("backend does not report memory analysis")
    if mf is None or mc is None:
        pytest.skip("backend does not report memory analysis")
    # full path holds [b, s, vocab] fp32 twice (logits + softmax bwd)
    assert mc.temp_size_in_bytes < mf.temp_size_in_bytes / 4, (
        mc.temp_size_in_bytes, mf.temp_size_in_bytes,
    )


def test_chunked_loss_trains_through_auto_accelerate():
    from dlrover_tpu.accel import Strategy, auto_accelerate

    cfg = GPTConfig.tiny(max_seq_len=32)
    model = GPT(cfg)
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (8, 33), dtype=np.int32)
    batch = {"x": jnp.asarray(data[:, :-1]),
             "y": jnp.asarray(data[:, 1:])}
    result = auto_accelerate(
        model, lambda: optax.adamw(1e-3),
        chunked_loss_fn(model, num_chunks=4), batch,
        strategy=Strategy(opts=[("fsdp", {}), ("amp_native", {})]),
        devices=jax.devices()[:4],
    )
    state = result.state
    pb = result.place_batch(batch)
    losses = []
    for _ in range(4):
        state, m = result.train_step(state, pb)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


def test_chunked_loss_rejects_pipelined_model():
    from dlrover_tpu.accel import Strategy, auto_accelerate

    cfg = GPTConfig.tiny(max_seq_len=32)
    model = GPT(cfg)
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (8, 33), dtype=np.int32)
    batch = {"x": jnp.asarray(data[:, :-1]),
             "y": jnp.asarray(data[:, 1:])}
    result = auto_accelerate(
        model, lambda: optax.sgd(1e-2),
        chunked_loss_fn(model, num_chunks=4), batch,
        strategy=Strategy(
            opts=[("pipeline_parallel",
                   {"size": 2, "microbatches": 2})]
        ),
        devices=jax.devices()[:2],
    )
    # jit is lazy: the clear incompatibility error surfaces at the
    # first trace of the step, not at build time
    with pytest.raises(ValueError, match="pipelined"):
        result.train_step(result.state, result.place_batch(batch))


def test_chunked_ce_carries_the_loss_head_scope():
    """Every operation of the chunked head, forward and backward, is
    lowered under the device scope ``loss_head`` (what
    ``losshead.ms_per_step`` sums in a trace), as the unchunked
    ``cross_entropy_loss`` is."""
    from dlrover_tpu.common.aot_cache import op_names

    hidden = jnp.ones((2, 16, 8), jnp.float32)
    kernel = jnp.ones((8, 32), jnp.float32)
    targets = jnp.zeros((2, 16), jnp.int32)
    compiled = jax.jit(jax.grad(
        lambda h, k: chunked_cross_entropy(h, k, targets, num_chunks=4),
        argnums=(0, 1),
    )).lower(hidden, kernel).compile()
    stacks = op_names(compiled.as_text())["op_names"].values()
    dots = [s for s in stacks if "dot_general" in s]
    assert dots and all("loss_head" in s for s in dots)
    assert any("transpose(" in s for s in dots)
