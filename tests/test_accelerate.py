"""Strategy engine tests: opt library plan emission, strategy
serialization, analyser, auto_accelerate end-to-end (semi-auto and
searched) on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.accel import (
    AccelPlan,
    ModelContext,
    OptimizationLibrary,
    Strategy,
    auto_accelerate,
)
from dlrover_tpu.accel.analyser import analyse, fits_in_hbm
from dlrover_tpu.models.gpt import GPT, GPTConfig, cross_entropy_loss


def _context():
    cfg = GPTConfig.tiny()
    model = GPT(cfg)

    def loss_fn(p, batch, model=model):
        logits = model.apply({"params": p}, batch["x"])
        return cross_entropy_loss(logits, batch["y"])

    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (8, 17), dtype=np.int32)
    batch = {"x": jnp.asarray(data[:, :-1]), "y": jnp.asarray(data[:, 1:])}
    return model, loss_fn, batch


def test_opt_library_builds_plans():
    lib = OptimizationLibrary()
    assert "fsdp" in lib and "tensor_parallel" in lib
    plan = lib.apply_strategy(
        Strategy(opts=[
            ("fsdp", {"size": 4}),
            ("checkpoint", {}),
            ("module_replace", {"attention": "flash"}),
            ("amp_native", {}),
        ])
    )
    assert plan.mesh_config.fsdp == 4
    assert plan.remat is True
    assert plan.attention_impl == "flash"
    assert plan.compute_dtype == "bfloat16"


def test_zero1_shards_only_opt_state():
    lib = OptimizationLibrary()
    plan = lib.apply_strategy(Strategy(opts=[("zero1", {"size": 4})]))
    # params replicated, opt state fsdp-sharded
    assert plan.param_rules.rules == []
    assert plan.opt_state_rules is not None
    assert plan.effective_opt_rules().rules


def test_strategy_json_roundtrip(tmp_path):
    s = Strategy(opts=[("fsdp", {"size": 8}), ("checkpoint", {})])
    path = str(tmp_path / "strategy.json")
    s.save(path)
    s2 = Strategy.load(path)
    assert s2.names() == ["fsdp", "checkpoint"]
    assert s2.opts[0][1] == {"size": 8}


def test_analyser_reports_model_size():
    model, loss_fn, batch = _context()
    ctx = ModelContext(
        model=model, optim_factory=lambda: optax.adam(1e-3),
        loss_fn=loss_fn, sample_batch=batch,
    )
    a = analyse(ctx)
    assert a.num_params > 10_000
    assert a.opt_state_bytes == 2 * a.num_params * 4
    assert a.batch_size == 8
    # a tiny model fits anywhere; an impossible HBM bound fails
    assert fits_in_hbm(a, 1, 1, False)
    a.per_device_hbm = 1024
    assert not fits_in_hbm(a, 1, 1, False)


def test_auto_accelerate_semiauto_fsdp():
    model, loss_fn, batch = _context()
    result = auto_accelerate(
        model, lambda: optax.adam(1e-3), loss_fn, batch,
        strategy=Strategy(opts=[
            ("fsdp", {"size": 4}), ("amp_native", {}),
        ]),
    )
    assert result.mesh.shape["fsdp"] == 4
    placed = result.place_batch(batch)
    state, metrics = result.train_step(result.state, placed)
    assert np.isfinite(float(metrics["loss"]))
    # params actually sharded
    emb = state.params["wte"]["embedding"]
    assert not emb.sharding.is_fully_replicated


def test_auto_accelerate_search_picks_runnable():
    model, loss_fn, batch = _context()
    result = auto_accelerate(
        model, lambda: optax.adam(1e-3), loss_fn, batch,
        dry_run_candidates=False,  # fast path: first feasible
    )
    placed = result.place_batch(batch)
    state, metrics = result.train_step(result.state, placed)
    assert np.isfinite(float(metrics["loss"]))
    assert result.strategy.names()


def test_auto_accelerate_grad_accum():
    model, loss_fn, batch = _context()
    result = auto_accelerate(
        model, lambda: optax.sgd(1e-2), loss_fn, batch,
        strategy=Strategy(opts=[("parallel_mode", {})]),
        grad_accum=2,
    )
    placed = result.place_batch(batch)
    state, metrics = result.train_step(result.state, placed)
    assert np.isfinite(float(metrics["loss"]))


def test_plan_rebuilds_model_config():
    model, loss_fn, batch = _context()
    result = auto_accelerate(
        model, lambda: optax.adam(1e-3), loss_fn, batch,
        strategy=Strategy(opts=[("checkpoint", {})]),
    )
    assert result.model.config.remat is True


def test_mesh_factorizations_cover_device_count():
    from dlrover_tpu.accel.strategy_search import mesh_factorizations

    triples = mesh_factorizations(8)
    assert all(d * f * t == 8 for d, f, t in triples)
    assert (8, 1, 1) in triples and (1, 8, 1) in triples
    assert (2, 2, 2) in triples


def test_search_prefers_sharded_when_model_does_not_fit(monkeypatch):
    """A model too big to replicate must make the search pick an
    fsdp/tp factorization over pure DP (VERDICT #6 done-criterion)."""
    import dlrover_tpu.accel.analyser as analyser_mod
    from dlrover_tpu.accel.strategy_search import (
        generate_candidates,
        search_strategy,
    )

    model, loss_fn, batch = _context()
    context = ModelContext(
        model=model, optim_factory=lambda: optax.sgd(1e-2),
        loss_fn=loss_fn, sample_batch=batch,
        # int8-moment candidates are opt-in (they swap the optimizer)
        extra={"search_optimizer": True},
    )
    # shrink the "chip" so the replicated state does not fit but a
    # >=4-way shard does
    real = analyser_mod.analyse

    def tight_analyse(ctx):
        a = real(ctx)
        a.per_device_hbm = int(a.model_state_bytes() / 2)
        a.batch_bytes = 0
        return a

    monkeypatch.setattr(analyser_mod, "analyse", tight_analyse)
    monkeypatch.setattr(
        "dlrover_tpu.accel.strategy_search.analyse", tight_analyse
    )
    cands = generate_candidates(context, 8)
    # every surviving candidate pays the tight HBM some other way:
    # >=4-way state sharding, or the precision levers (bf16 params +
    # int8 moments shrink state ~3.4x)
    assert all(
        c.fsdp * c.tensor >= 4 or (c.half and c.low_bit_opt)
        or (c.half and c.fsdp * c.tensor >= 2)
        for c in cands
    ), [c.describe() for c in cands]
    assert any(c.fsdp * c.tensor >= 4 for c in cands)
    result = search_strategy(
        context, 8, dry_run_budget=3, grad_accums=(1,)
    )
    assert result.best.step_time_s is not None


def test_search_bo_respects_budget():
    from dlrover_tpu.accel.strategy_search import search_strategy

    model, loss_fn, batch = _context()
    context = ModelContext(
        model=model, optim_factory=lambda: optax.sgd(1e-2),
        loss_fn=loss_fn, sample_batch=batch,
    )
    result = search_strategy(
        context, 8, dry_run_budget=4, grad_accums=(1, 2)
    )
    assert len(result.evaluated) <= 4
    assert result.best.step_time_s is not None


def test_fp8_opt_and_model_path():
    """fp8 strategy knob rebuilds the model with Fp8Dense MLPs and the
    step still trains to a finite loss."""
    from dlrover_tpu.accel import Strategy, auto_accelerate
    from dlrover_tpu.ops.fp8 import fp8_dot

    # kernel-level sanity: fp8 dot close to fp32 reference
    a = jnp.asarray(np.random.default_rng(0).normal(size=(8, 16)),
                    jnp.float32)
    b = jnp.asarray(np.random.default_rng(1).normal(size=(16, 4)),
                    jnp.float32)
    np.testing.assert_allclose(
        np.asarray(fp8_dot(a, b)), np.asarray(a @ b),
        rtol=0.15, atol=0.15,
    )

    model, loss_fn, batch = _context()
    result = auto_accelerate(
        model, lambda: optax.sgd(1e-2), loss_fn, batch,
        strategy=Strategy(opts=[
            ("parallel_mode", {}), ("fp8", {}), ("amp_native", {}),
        ]),
    )
    assert result.model.config.fp8 is True
    placed = result.place_batch(batch)
    _, metrics = result.train_step(result.state, placed)
    assert np.isfinite(float(metrics["loss"]))


def test_tp_rules_registry_resolution():
    """Model-family registry resolves custom rules; unknown families
    fall back to the shared transformer contract (reference role:
    modules_registry.py)."""
    from dlrover_tpu.models.bert import Bert, BertConfig
    from dlrover_tpu.parallel.registry import (
        register_tp_rules,
        rules_for_model,
    )
    from dlrover_tpu.parallel.sharding import (
        PartitionRules,
        gpt_tp_rules,
    )

    bert = Bert(BertConfig.tiny())
    # unknown family -> shared contract
    assert rules_for_model(bert).rules == gpt_tp_rules().rules

    custom = PartitionRules(rules=[(r"special", ("tensor",))])
    register_tp_rules("Bert", custom)
    try:
        assert rules_for_model(bert) is custom
        # the opt library picks it up through the context
        lib = OptimizationLibrary()
        ctx = ModelContext(
            model=bert, optim_factory=lambda: optax.sgd(0.1),
            loss_fn=lambda p, b: 0.0, sample_batch={},
        )
        plan = lib.apply_strategy(
            Strategy(opts=[("tensor_parallel", {"size": 2})]), ctx
        )
        assert plan.param_rules is custom
    finally:
        from dlrover_tpu.parallel.registry import unregister_tp_rules

        unregister_tp_rules("Bert")


def test_generate_candidates_model_aware_axes():
    """MoE models get expert-parallel variants; long sequences get
    ring-SP variants (the search explores every mesh axis the model
    can use)."""
    from dlrover_tpu.accel.strategy_search import generate_candidates
    from dlrover_tpu.models.gpt import GPT, GPTConfig

    moe_cfg = GPTConfig.tiny(moe_experts=2, max_seq_len=64)
    model = GPT(moe_cfg)
    data = np.random.default_rng(0).integers(
        0, moe_cfg.vocab_size, (8, 33), dtype=np.int32
    )
    batch = {
        "x": jnp.asarray(data[:, :-1]),
        "y": jnp.asarray(data[:, 1:]),
    }
    ctx = ModelContext(
        model=model, optim_factory=lambda: optax.sgd(0.1),
        loss_fn=lambda p, b: 0.0, sample_batch=batch,
    )
    cands = generate_candidates(ctx, 8, grad_accums=(1,))
    assert any(c.expert > 1 for c in cands), [
        c.describe() for c in cands
    ]
    # long-sequence model -> ring SP variants appear
    cands2 = generate_candidates(
        ctx, 8, grad_accums=(1,), long_seq_threshold=16
    )
    assert any(c.sequence > 1 for c in cands2)
    sp_cand = next(c for c in cands2 if c.sequence > 1)
    assert ("sequence_parallel", {"size": sp_cand.sequence,
                                  "mode": "ring"}) in (
        sp_cand.strategy.opts
    )


def test_estimate_plan_cost_model():
    """Static tier: compile-only XLA cost analysis gives finite
    flops/bytes and a roofline estimate; remat visibly adds
    recompute flops."""
    from dlrover_tpu.accel.dry_runner import estimate_plan
    from dlrover_tpu.accel.model_context import ModelContext
    from dlrover_tpu.accel.opt_lib import OptimizationLibrary

    cfg = GPTConfig.tiny(max_seq_len=32)
    model = GPT(cfg)
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (8, 33), dtype=np.int32)
    batch = {"x": jnp.asarray(data[:, :-1]),
             "y": jnp.asarray(data[:, 1:])}

    def loss_fn(p, batch, model=model):
        logits = model.apply({"params": p}, batch["x"])
        return cross_entropy_loss(logits, batch["y"])

    context = ModelContext(
        model=model, optim_factory=lambda: optax.adamw(1e-3),
        loss_fn=loss_fn, sample_batch=batch,
        extra={"target_chip": "TPU v5e"},
    )
    lib = OptimizationLibrary()
    plan = lib.apply_strategy(
        Strategy(opts=[("fsdp", {}), ("amp_native", {})]), context
    )
    r1 = estimate_plan(plan, context, devices=jax.devices()[:4])
    assert r1.ok, r1.error
    assert r1.flops > 0 and r1.bytes_accessed > 0
    assert r1.est_step_time_s > 0
    assert r1.step_time_s == 0.0  # never executed

    plan2 = lib.apply_strategy(
        Strategy(opts=[
            ("fsdp", {}), ("amp_native", {}), ("checkpoint", {}),
        ]),
        context,
    )
    r2 = estimate_plan(plan2, context, devices=jax.devices()[:4])
    assert r2.ok, r2.error
    # rematerialization recomputes the forward in the backward pass
    assert r2.flops > 1.1 * r1.flops, (r1.flops, r2.flops)


def test_cost_model_refuses_an_unknown_chip():
    """The roofline needs a chip: a device kind the peak table does
    not know (the CPU mesh, with no target named) is an error, never
    a v5e by default."""
    from dlrover_tpu.accel.dry_runner import chip_spec, estimate_plan
    from dlrover_tpu.accel.model_context import ModelContext

    assert chip_spec("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(ValueError, match="no peak spec"):
        chip_spec("cpu")
    context = ModelContext(
        model=None, optim_factory=None, loss_fn=None,
        sample_batch=None,
    )
    with pytest.raises(ValueError, match="target_chip"):
        estimate_plan(None, context, devices=jax.devices()[:1])


def test_search_strategy_cost_model_mode():
    from dlrover_tpu.accel.model_context import ModelContext
    from dlrover_tpu.accel.strategy_search import search_strategy

    cfg = GPTConfig.tiny(max_seq_len=32)
    model = GPT(cfg)
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (8, 33), dtype=np.int32)
    batch = {"x": jnp.asarray(data[:, :-1]),
             "y": jnp.asarray(data[:, 1:])}

    def loss_fn(p, batch, model=model):
        logits = model.apply({"params": p}, batch["x"])
        return cross_entropy_loss(logits, batch["y"])

    context = ModelContext(
        model=model, optim_factory=lambda: optax.adamw(1e-3),
        loss_fn=loss_fn, sample_batch=batch,
        extra={"target_chip": "TPU v5e"},
    )
    result = search_strategy(
        context, num_devices=4, devices=jax.devices()[:4],
        dry_run_budget=3, rank_mode="cost_model",
    )
    assert result.best is not None
    import math as _math

    assert _math.isfinite(result.best.step_time_s)


def test_search_strategy_hybrid_profiles_top_k_only():
    """Hybrid tier: every candidate gets a cost-model rank, but only
    profile_top_k pay for on-chip execution — the bounded-search shape
    for an expensive shared chip (VERDICT r3 #4)."""
    import math as _math

    from dlrover_tpu.accel.model_context import ModelContext
    from dlrover_tpu.accel.strategy_search import search_strategy

    cfg = GPTConfig.tiny(max_seq_len=32)
    model = GPT(cfg)
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (8, 33), dtype=np.int32)
    batch = {"x": jnp.asarray(data[:, :-1]),
             "y": jnp.asarray(data[:, 1:])}

    def loss_fn(p, batch, model=model):
        logits = model.apply({"params": p}, batch["x"])
        return cross_entropy_loss(logits, batch["y"])

    context = ModelContext(
        model=model, optim_factory=lambda: optax.adamw(1e-3),
        loss_fn=loss_fn, sample_batch=batch,
        extra={"target_chip": "TPU v5e"},
    )
    result = search_strategy(
        context, num_devices=2, devices=jax.devices()[:2],
        rank_mode="hybrid", profile_top_k=1, profile_steps=1,
        grad_accums=(1,), cost_budget=4,
    )
    profiled = [
        c for c in result.evaluated
        if c.step_time_s is not None
    ]
    est_ranked = [
        c for c in result.evaluated
        if c.est_step_time_s is not None
        and _math.isfinite(c.est_step_time_s)
    ]
    assert len(profiled) == 1, [c.describe() for c in profiled]
    assert len(est_ranked) >= 2  # the static tier saw the space
    # the profiled one is the static tier's pick, and it wins
    assert profiled[0].est_step_time_s == min(
        c.est_step_time_s for c in est_ranked
    )
    assert result.best is profiled[0]
    assert _math.isfinite(result.best.step_time_s)
