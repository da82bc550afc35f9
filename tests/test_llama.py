"""Llama model tests: forward shapes, GQA, RoPE properties, training
step on the TP+FSDP mesh, flash-attention impl equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding

from dlrover_tpu.models.gpt import cross_entropy_loss
from dlrover_tpu.models.layers import rope
from dlrover_tpu.models.llama import Llama, LlamaConfig
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.sharding import (
    batch_spec,
    gpt_tp_rules,
    sharding_tree,
    tree_paths,
)
from dlrover_tpu.trainer.elastic_trainer import TrainState, make_train_step


def test_llama_forward_shapes():
    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), dtype=jnp.int32)
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    # GQA: kv projections smaller than q
    kp = params["block_0"]["attn"]["k_proj"]["kernel"]
    qp = params["block_0"]["attn"]["q_proj"]["kernel"]
    assert kp.shape[1] == qp.shape[1] // 2  # num_kv_heads = heads/2


def test_rope_preserves_norm_and_relative_phase():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 16))
    pos = jnp.arange(8)
    out = rope(x, pos, 10000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )
    # position 0 is unrotated
    np.testing.assert_allclose(
        np.asarray(out[:, 0]), np.asarray(x[:, 0]), atol=1e-6
    )


def test_llama_tp_rules_cover_params():
    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rules = gpt_tp_rules()
    paths = tree_paths(params)
    qp = next(p for p in paths if p.endswith("q_proj/kernel"))
    assert tuple(rules.spec_for(qp)) == ("fsdp", "tensor")
    gate = next(p for p in paths if p.endswith("gate/kernel"))
    assert tuple(rules.spec_for(gate)) == ("fsdp", "tensor")
    down = next(p for p in paths if p.endswith("down/kernel"))
    assert tuple(rules.spec_for(down)) == ("tensor", "fsdp")
    norm = next(p for p in paths if p.endswith("ln_attn/scale"))
    assert tuple(rules.spec_for(norm)) == ()


def test_llama_trains_on_mesh():
    mesh = build_mesh(MeshConfig(data=-1, fsdp=2, tensor=2))
    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    optimizer = optax.adamw(1e-3)
    state = TrainState.create(params, optimizer)

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["x"])
        return cross_entropy_loss(logits, batch["y"])

    rules = gpt_tp_rules()
    _, jit_builder = make_train_step(
        loss_fn, optimizer, mesh=mesh, rules=rules
    )
    step = jit_builder(state)
    state = jax.device_put(state, sharding_tree(state, mesh, rules))
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (8, 17), dtype=np.int32)
    batch = jax.device_put(
        {"x": jnp.asarray(data[:, :-1]), "y": jnp.asarray(data[:, 1:])},
        NamedSharding(mesh, batch_spec()),
    )
    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_llama_flash_attention_matches_xla():
    cfg_x = LlamaConfig.tiny(attention_impl="xla")
    cfg_f = LlamaConfig.tiny(attention_impl="flash")
    model_x, model_f = Llama(cfg_x), Llama(cfg_f)
    params = model_x.init_params(jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 128), 0, cfg_x.vocab_size
    )
    lx = model_x.apply({"params": params}, tokens)
    lf = model_f.apply({"params": params}, tokens)
    np.testing.assert_allclose(
        np.asarray(lx), np.asarray(lf), atol=5e-2, rtol=5e-2
    )


def test_llama_kv_cache_decode_matches_full_forward():
    """Llama decode path (RoPE positions continued across chunks,
    GQA-aware cache) reproduces the full forward, and generate()
    samples through it."""
    import numpy as np

    from dlrover_tpu.rl.generation import decode_variant, generate

    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(0), seq_len=16)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 10), dtype=np.int32
        )
    )
    full = model.apply({"params": params}, toks)
    dec = decode_variant(model)
    pre, vars_ = dec.apply(
        {"params": params}, toks[:, :8], mutable=["cache"]
    )
    np.testing.assert_allclose(
        np.asarray(pre), np.asarray(full[:, :8]), atol=3e-2
    )
    cache = vars_["cache"]
    for i in (8, 9):
        logits, vars_ = dec.apply(
            {"params": params, "cache": cache},
            toks[:, i:i + 1], mutable=["cache"],
        )
        cache = vars_["cache"]
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, i]),
            atol=3e-2,
        )
    seqs, logps = generate(
        dec, params, toks, jax.random.PRNGKey(1), max_new_tokens=6
    )
    assert seqs.shape == (2, 16)
    assert bool(jnp.isfinite(logps).all())


def test_mixtral_moe_llama_forward_and_params():
    """Mixtral-class sparse Llama: gated (SwiGLU) experts replace the
    MLP, expert kernels carry the leading expert dim for the expert
    mesh axis."""
    from dlrover_tpu.parallel.sharding import moe_rules, tree_paths

    cfg = LlamaConfig.tiny(moe_experts=4, moe_top_k=2)
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(0), seq_len=16)
    paths = tree_paths(params)
    gate_paths = [p for p in paths if "experts_w_gate" in p]
    assert gate_paths, sorted(paths)[:12]
    rules = moe_rules()
    assert tuple(rules.spec_for(gate_paths[0])) == (
        "expert", "fsdp", "tensor",
    )
    # dense SwiGLU MLP is fully replaced in MoE blocks (moe_every=1)
    assert not any("/mlp/" in p for p in paths), [
        p for p in paths if "/mlp/" in p
    ][:4]
    x = jnp.zeros((2, 16), jnp.int32)
    logits, st = model.apply(
        {"params": params}, x, mutable=["intermediates"]
    )
    assert logits.shape == (2, 16, cfg.vocab_size)
    from dlrover_tpu.parallel.moe import collect_moe_aux_loss

    aux = collect_moe_aux_loss(st["intermediates"])
    assert float(aux) > 0.0


def test_mixtral_trains_via_auto_accelerate_on_expert_mesh():
    import optax

    from dlrover_tpu.accel import Strategy, auto_accelerate
    from dlrover_tpu.models.gpt import cross_entropy_loss
    from dlrover_tpu.parallel.moe import collect_moe_aux_loss

    cfg = LlamaConfig.tiny(moe_experts=2, moe_every=2)
    model = Llama(cfg)
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (8, 17), dtype=np.int32)
    batch = {"x": jnp.asarray(data[:, :-1]),
             "y": jnp.asarray(data[:, 1:])}

    def loss_fn(p, batch, model=model):
        logits, st = model.apply(
            {"params": p}, batch["x"], mutable=["intermediates"]
        )
        ce = cross_entropy_loss(logits, batch["y"])
        return ce + 0.01 * collect_moe_aux_loss(st["intermediates"])

    result = auto_accelerate(
        model, lambda: optax.adamw(1e-3), loss_fn, batch,
        strategy=Strategy(opts=[
            ("mixed_parallel", {"expert": 2, "data": -1}),
            ("amp_native", {}),
        ]),
        devices=jax.devices()[:4],
    )
    # expert kernels actually sharded over the expert axis
    expert_specs = [
        x.sharding.spec
        for x in jax.tree.leaves(result.state.params)
        if x.ndim == 3
    ]
    assert expert_specs and all(
        "expert" in (s[0] or ()) or s[0] == "expert"
        for s in expert_specs
    ), expert_specs
    state = result.state
    pb = result.place_batch(batch)
    losses = []
    for _ in range(4):
        state, m = result.train_step(state, pb)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


def test_mixtral_decode_no_token_dropping():
    """One-token decode steps reproduce the full forward: without the
    no_drop capacity bump the trained formula collapses to ~1
    slot/expert at t=batch tokens and silently zeroes routed tokens'
    expert contributions (which would stay finite — so assert
    equality with the full forward, not finiteness)."""
    # ample capacity_factor so the full (training-mode) forward drops
    # nothing either; then decode must match it exactly
    cfg = LlamaConfig.tiny(
        moe_experts=4, moe_top_k=2, moe_capacity_factor=8.0
    )
    model = Llama(cfg)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, 8), dtype=np.int32)
    )
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    full = model.apply({"params": params}, toks)

    from dataclasses import replace as dc_replace

    # tiny capacity factor: the trained formula alone would give the
    # decode steps 1 slot/expert and drop tokens — only the no_drop
    # guard makes decode match the full forward
    dec = Llama(
        dc_replace(cfg, decode=True, moe_capacity_factor=0.01)
    )
    pre, vars_ = dec.apply(
        {"params": params}, toks[:, :5], mutable=["cache"]
    )
    np.testing.assert_allclose(
        np.asarray(pre), np.asarray(full[:, :5]), atol=3e-2
    )
    cache = vars_["cache"]
    for i in (5, 6, 7):  # one-token decode steps
        logits, vars_ = dec.apply(
            {"params": params, "cache": cache},
            toks[:, i:i + 1], mutable=["cache"],
        )
        cache = vars_["cache"]
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, i]),
            atol=3e-2,
        )


def test_pipelined_llama_matches_plain_and_trains_1f1b():
    """Llama over the pipeline axis: the stage-stacked forward
    reproduces the plain model's logits, and both pipeline schedules
    train through auto_accelerate with coinciding loss trajectories."""
    import optax as _optax

    from dlrover_tpu.accel import Strategy, auto_accelerate
    from dlrover_tpu.parallel.mesh import set_global_mesh

    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (8, 33), dtype=np.int32)
    batch = {"x": jnp.asarray(data[:, :-1]),
             "y": jnp.asarray(data[:, 1:])}

    # forward parity: plain vs pipelined layout on the same weights
    # (fp32 so op-reassociation noise cannot mask a real defect)
    mesh = build_mesh(MeshConfig(data=-1, pipeline=2))
    set_global_mesh(mesh)
    cfg32 = LlamaConfig.tiny(dtype=jnp.float32)
    model32 = Llama(cfg32)
    pp_model = model32.to_pipelined(
        num_stages=2, num_microbatches=2, batch_axis=None
    )
    pp = pp_model.init_params(jax.random.PRNGKey(0), seq_len=32)
    plain = model32.init_params(jax.random.PRNGKey(0), seq_len=32)
    ref = model32.apply({"params": plain}, batch["x"])
    out = pp_model.apply({"params": pp}, batch["x"])
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4
    )

    # both schedules train via auto_accelerate and coincide
    def run(schedule):
        m = Llama(cfg)

        def loss_fn(p, batch, model=m):
            # `model` is the (pipelined) model auto_accelerate injects
            logits = model.apply({"params": p}, batch["x"])
            return cross_entropy_loss(logits, batch["y"])

        result = auto_accelerate(
            m, lambda: _optax.sgd(0.05), loss_fn, batch,
            strategy=Strategy(opts=[
                ("pipeline_parallel",
                 {"size": 2, "microbatches": 2,
                  "schedule": schedule}),
            ]),
            devices=jax.devices()[:4],
        )
        state = result.state
        pb = result.place_batch(batch)
        losses = []
        for _ in range(3):
            state, metrics = result.train_step(state, pb)
            losses.append(float(metrics["loss"]))
        return losses

    l_g = run("gpipe")
    l_i = run("1f1b")
    assert l_i[-1] < l_i[0], l_i
    np.testing.assert_allclose(l_i, l_g, rtol=2e-4)
