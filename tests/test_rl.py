"""RLHF engine tests: GAE math, PPO losses, four-role model engine
with trainable actor/critic and frozen ref/reward."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.rl import (
    ModelRole,
    RLModelEngine,
    gae_advantages,
    ppo_critic_loss,
    ppo_policy_loss,
)
from dlrover_tpu.rl.model_engine import RoleSpec
from dlrover_tpu.rl.ppo import kl_penalty, token_logprobs


def test_gae_single_step_matches_closed_form():
    # one-step episode: advantage = reward - value (normalized after)
    rewards = jnp.array([[1.0]])
    values = jnp.array([[0.4]])
    dones = jnp.array([[1.0]])
    adv, ret = gae_advantages(rewards, values, dones)
    np.testing.assert_allclose(np.asarray(ret), [[1.0]], atol=1e-6)


def test_gae_propagates_backwards():
    rewards = jnp.array([[0.0, 0.0, 1.0]])
    values = jnp.zeros((1, 3))
    dones = jnp.array([[0.0, 0.0, 1.0]])
    adv, ret = gae_advantages(rewards, values, dones, gamma=0.9,
                              lam=1.0)
    r = np.asarray(ret)[0]
    # discounted returns: 0.81, 0.9, 1.0
    np.testing.assert_allclose(r, [0.81, 0.9, 1.0], atol=1e-5)


def test_ppo_policy_loss_clipping():
    old = jnp.zeros((2, 4))
    adv = jnp.ones((2, 4))
    # big ratio gets clipped: increasing logprob beyond clip has no
    # extra benefit
    l_small = ppo_policy_loss(jnp.full((2, 4), 0.1), old, adv)
    l_big = ppo_policy_loss(jnp.full((2, 4), 5.0), old, adv)
    assert float(l_big) >= -1.21  # clip bound 1+0.2
    assert float(l_small) > float(l_big) - 1.2


def test_critic_loss_and_kl():
    v = jnp.array([[1.0, 2.0]])
    r = jnp.array([[1.5, 1.5]])
    assert float(ppo_critic_loss(v, r)) > 0
    kl = kl_penalty(jnp.array([0.0]), jnp.array([-1.0]), 0.1)
    np.testing.assert_allclose(np.asarray(kl), [0.1], atol=1e-6)


def test_token_logprobs_shape():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 11))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, 11)
    lp = token_logprobs(logits, tokens)
    assert lp.shape == (2, 5)
    assert (np.asarray(lp) <= 0).all()


def test_rl_engine_four_roles_ppo_step():
    cfg = GPTConfig.tiny()
    actor, critic_m = GPT(cfg), GPT(cfg)
    ref, reward_m = GPT(cfg), GPT(cfg)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (8, 16), dtype=np.int32)
    batch = {
        "tokens": jnp.asarray(tokens),
        "old_logprobs": jnp.zeros((8, 16)),
        "advantages": jnp.ones((8, 16)),
        "returns": jnp.ones((8, 16)),
    }

    def actor_loss(p, b, model=actor):
        logits = model.apply({"params": p}, b["tokens"])
        lp = token_logprobs(logits, b["tokens"])
        return ppo_policy_loss(lp, b["old_logprobs"], b["advantages"])

    def critic_loss(p, b, model=critic_m):
        logits = model.apply({"params": p}, b["tokens"])
        values = logits.mean(-1)  # toy value head
        return ppo_critic_loss(values, b["returns"])

    engine = RLModelEngine(
        batch,
        {
            ModelRole.ACTOR: RoleSpec(
                model=actor, loss_fn=actor_loss,
                optim_factory=lambda: optax.adam(1e-4),
            ),
            ModelRole.CRITIC: RoleSpec(
                model=critic_m, loss_fn=critic_loss,
                optim_factory=lambda: optax.adam(1e-4),
            ),
            ModelRole.REF: RoleSpec(model=ref),
            ModelRole.REWARD: RoleSpec(model=reward_m),
        },
    ).build()

    # frozen roles infer
    ref_logits = engine.infer(ModelRole.REF, batch["tokens"])
    assert ref_logits.shape == (8, 16, cfg.vocab_size)

    # trainable roles step
    for role in (ModelRole.ACTOR, ModelRole.CRITIC):
        placed = engine.place_batch(role, batch)
        state, metrics = engine.train_step(role)(
            engine.state(role), placed
        )
        engine.set_state(role, state)
        assert np.isfinite(float(metrics["loss"]))

    # ref refresh copies actor params
    engine.sync_ref_from_actor()
    a = jax.tree_util.tree_leaves(
        engine.state(ModelRole.ACTOR).params
    )[0]
    r = jax.tree_util.tree_leaves(
        engine._frozen_params[ModelRole.REF]
    )[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(r))


def test_kv_cache_decode_matches_full_forward():
    """Prefill + single-token decode steps reproduce the full-forward
    logits (the KV-cache path is numerically the same policy)."""
    from dlrover_tpu.rl.generation import decode_variant

    cfg = GPTConfig.tiny()
    model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(0), seq_len=16)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 10), dtype=np.int32
        )
    )
    dec = decode_variant(model)
    pre, vars_ = dec.apply({"params": params}, toks[:, :8],
                           mutable=["cache"])
    full = model.apply({"params": params}, toks)
    np.testing.assert_allclose(
        np.asarray(pre), np.asarray(full[:, :8]), atol=2e-2
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
            atol=2e-2,
        )


def test_ppo_iteration_improves_reward():
    """Tiny end-to-end RLHF: reward = frequency of a target token in
    the response; PPO iterations must raise it (rollout generation,
    ref KL, GAE, actor+critic steps all wired through the engine)."""
    import optax as _optax

    from dlrover_tpu.accel import Strategy
    from dlrover_tpu.rl.rollout import (
        make_actor_loss,
        make_critic_loss,
        ppo_iteration,
        sample_rollout_batch,
    )

    cfg = GPTConfig.tiny(max_seq_len=64, vocab_size=32)
    actor_model = GPT(cfg)
    critic_model = GPT(
        GPTConfig.tiny(max_seq_len=64, vocab_size=32, head="value")
    )
    ref_model = GPT(cfg)

    prompt_len, max_new = 4, 8
    prompts = jnp.asarray(
        np.random.default_rng(0).integers(
            0, cfg.vocab_size, (8, prompt_len), dtype=np.int32
        )
    )
    sample = sample_rollout_batch(prompts, max_new)
    dp = Strategy(opts=[("parallel_mode", {})])
    actor_params = actor_model.init_params(jax.random.PRNGKey(1))
    engine = RLModelEngine(sample, {
        ModelRole.ACTOR: RoleSpec(
            model=actor_model,
            loss_fn=make_actor_loss(actor_model, prompt_len),
            optim_factory=lambda: _optax.adam(5e-3),
            strategy=dp,
        ),
        ModelRole.CRITIC: RoleSpec(
            model=critic_model,
            loss_fn=make_critic_loss(critic_model, prompt_len),
            optim_factory=lambda: _optax.adam(1e-3),
            strategy=dp,
        ),
        ModelRole.REF: RoleSpec(model=ref_model, params=actor_params),
    }).build()

    def reward_fn(sequences):
        # dense signal: fraction of response tokens in the low half
        # of the vocab (learnable within a few iterations)
        resp = sequences[:, prompt_len:]
        return (resp < 16).mean(axis=1).astype(jnp.float32)

    rng = jax.random.PRNGKey(2)
    rewards = []
    for i in range(12):
        rng, sub = jax.random.split(rng)
        metrics = ppo_iteration(
            engine, prompts, sub, max_new_tokens=max_new,
            kl_coef=0.01, reward_fn=reward_fn,
        )
        rewards.append(metrics["mean_reward"])
    early = np.mean(rewards[:3])
    late = np.mean(rewards[-3:])
    assert late > early + 0.05, rewards
    # ref sync is a real copy, not an alias of live actor params
    engine.sync_ref_from_actor()
    ref_leaf = jax.tree_util.tree_leaves(
        engine._frozen_params[ModelRole.REF]
    )[0]
    actor_leaf = jax.tree_util.tree_leaves(
        engine.state(ModelRole.ACTOR).params
    )[0]
    assert ref_leaf is not actor_leaf


def test_ppo_hybrid_rollout_resharding_improves_reward():
    """Train and rollout run on DIFFERENT layouts (reference:
    atorch/rl/ds_hybrid_engine + model_engine.py:35): the actor
    trains fsdp-sharded on a dp x fsdp mesh, generation swaps its
    params into a tensor-parallel layout on a dp x tensor mesh via
    one timed device_put, and PPO still improves the reward."""
    import optax as _optax
    from jax.sharding import Mesh

    from dlrover_tpu.accel import Strategy
    from dlrover_tpu.rl.hybrid_engine import HybridRolloutEngine
    from dlrover_tpu.rl.rollout import (
        make_actor_loss,
        make_critic_loss,
        ppo_iteration,
        sample_rollout_batch,
    )

    cfg = GPTConfig.tiny(max_seq_len=64, vocab_size=32)
    actor_model = GPT(cfg)
    critic_model = GPT(
        GPTConfig.tiny(max_seq_len=64, vocab_size=32, head="value")
    )
    ref_model = GPT(cfg)

    prompt_len, max_new = 4, 8
    prompts = jnp.asarray(
        np.random.default_rng(0).integers(
            0, cfg.vocab_size, (8, prompt_len), dtype=np.int32
        )
    )
    sample = sample_rollout_batch(prompts, max_new)
    actor_params = actor_model.init_params(jax.random.PRNGKey(1))
    engine = RLModelEngine(sample, {
        ModelRole.ACTOR: RoleSpec(
            model=actor_model,
            loss_fn=make_actor_loss(actor_model, prompt_len),
            optim_factory=lambda: _optax.adam(5e-3),
            # TRAIN layout: fsdp-sharded state
            strategy=Strategy(opts=[("fsdp", {})]),
        ),
        ModelRole.CRITIC: RoleSpec(
            model=critic_model,
            loss_fn=make_critic_loss(critic_model, prompt_len),
            optim_factory=lambda: _optax.adam(1e-3),
            strategy=Strategy(opts=[("parallel_mode", {})]),
        ),
        ModelRole.REF: RoleSpec(model=ref_model, params=actor_params),
    }).build()

    # ROLLOUT layout: 2-way batch x 4-way tensor slicing
    rollout_mesh = Mesh(
        np.array(jax.devices()[:8]).reshape(2, 4),
        ("data", "tensor"),
    )
    train_mesh = engine._accel[ModelRole.ACTOR].mesh
    assert rollout_mesh.shape != dict(train_mesh.shape)
    hybrid = HybridRolloutEngine(engine, rollout_mesh)

    def reward_fn(sequences):
        resp = sequences[:, prompt_len:]
        return (resp < 16).mean(axis=1).astype(jnp.float32)

    rng = jax.random.PRNGKey(2)
    rewards, reshards = [], []
    for i in range(10):
        rng, sub = jax.random.split(rng)
        metrics = ppo_iteration(
            engine, prompts, sub, max_new_tokens=max_new,
            kl_coef=0.01, reward_fn=reward_fn, hybrid=hybrid,
        )
        rewards.append(metrics["mean_reward"])
        reshards.append(metrics["reshard_s"])
    assert np.mean(rewards[-3:]) > np.mean(rewards[:3]) + 0.05, rewards
    assert hybrid.stats()["reshards"] == 10
    # the swap actually changed a leaf's layout: the rollout copy of
    # a tensor-sliced kernel is sharded differently from the train
    # (fsdp) state's same leaf
    rolled = hybrid.reshard_actor_for_rollout()
    train_params = engine.state(ModelRole.ACTOR).params
    paths_r = jax.tree_util.tree_leaves_with_path(rolled)
    paths_t = dict(
        ("/".join(str(k) for k in p), l)
        for p, l in jax.tree_util.tree_leaves_with_path(train_params)
    )
    changed = 0
    for p, leaf in paths_r:
        key = "/".join(str(k) for k in p)
        if not leaf.sharding.is_equivalent_to(
            paths_t[key].sharding, leaf.ndim
        ):
            changed += 1
    assert changed > 0
    specs = jax.tree_util.tree_leaves(
        hybrid._target_shardings,
        is_leaf=lambda s: hasattr(s, "spec"),
    )
    assert any(
        "tensor" in str(s.spec) for s in specs
    ), [str(s.spec) for s in specs[:5]]


def test_replay_buffer_minibatches():
    from dlrover_tpu.rl.trainer import ReplayBuffer

    buf = ReplayBuffer()
    for i in range(3):
        buf.add({"a": np.full((4, 2), i), "b": np.arange(4) + 10 * i})
    assert buf.num == 12
    rng = np.random.default_rng(0)
    mbs = list(buf.minibatches(5, rng))
    assert len(mbs) == 2  # 12 // 5, remainder dropped
    seen = np.concatenate([mb["b"] for mb in mbs])
    assert len(set(seen.tolist())) == 10  # no duplicates
    buf.reset()
    assert buf.num == 0 and not list(buf.minibatches(2, rng))
    with pytest.raises(ValueError, match="ragged"):
        buf.add({"a": np.zeros((4, 2)), "b": np.zeros(3)})


def test_rl_train_config_yaml(tmp_path):
    from dlrover_tpu.rl.trainer import RLTrainConfig

    p = tmp_path / "rl.yaml"
    p.write_text(
        "epochs: 2\nnum_rollouts: 16\nppo_epochs: 3\n"
        "train_batch_size: 4\nkl_coef: 0.01\nlogdir: /tmp/x\n"
    )
    cfg = RLTrainConfig.from_yaml(str(p))
    assert cfg.epochs == 2 and cfg.num_rollouts == 16
    assert cfg.ppo_epochs == 3 and cfg.kl_coef == 0.01
    assert cfg.extra == {"logdir": "/tmp/x"}


def test_ppo_trainer_buffer_cycle_improves_reward():
    """The reference trainer shape: fill the replay buffer with
    several rollouts, then PPO epochs over shuffled minibatches —
    reward improves across cycles and the buffer resets per phase."""
    import optax as _optax

    from dlrover_tpu.accel import Strategy
    from dlrover_tpu.rl.rollout import (
        make_actor_loss,
        make_critic_loss,
        sample_rollout_batch,
    )
    from dlrover_tpu.rl.trainer import PPOTrainer, RLTrainConfig

    cfg = GPTConfig.tiny(max_seq_len=64, vocab_size=32)
    actor_model = GPT(cfg)
    critic_model = GPT(
        GPTConfig.tiny(max_seq_len=64, vocab_size=32, head="value")
    )
    ref_model = GPT(cfg)

    prompt_len, max_new = 4, 8
    rng_np = np.random.default_rng(0)
    prompts = [
        jnp.asarray(rng_np.integers(
            0, cfg.vocab_size, (8, prompt_len), dtype=np.int32
        ))
        for _ in range(4)
    ]
    sample = sample_rollout_batch(prompts[0], max_new)
    dp = Strategy(opts=[("parallel_mode", {})])
    actor_params = actor_model.init_params(jax.random.PRNGKey(1))
    engine = RLModelEngine(sample, {
        ModelRole.ACTOR: RoleSpec(
            model=actor_model,
            loss_fn=make_actor_loss(actor_model, prompt_len),
            optim_factory=lambda: _optax.adam(5e-3),
            strategy=dp,
        ),
        ModelRole.CRITIC: RoleSpec(
            model=critic_model,
            loss_fn=make_critic_loss(critic_model, prompt_len),
            optim_factory=lambda: _optax.adam(1e-3),
            strategy=dp,
        ),
        ModelRole.REF: RoleSpec(model=ref_model, params=actor_params),
    }).build()

    def reward_fn(sequences):
        resp = sequences[:, prompt_len:]
        return (resp < 16).mean(axis=1).astype(jnp.float32)

    trainer = PPOTrainer(
        engine,
        RLTrainConfig(
            epochs=4, num_rollouts=16, ppo_epochs=2,
            train_batch_size=8, max_new_tokens=max_new,
            kl_coef=0.01,
        ),
        reward_fn=reward_fn,
    )
    history = trainer.train(prompts)
    # 4 prompt batches x 8 = 32 rollouts per epoch -> 2 training
    # phases per epoch x 4 epochs
    assert len(history) >= 6, history
    assert all(h["ppo_steps"] > 0 for h in history)
    rewards = [h["mean_reward"] for h in history if "mean_reward" in h]
    assert np.mean(rewards[-2:]) > np.mean(rewards[:2]), rewards
    # buffer reset between phases
    assert trainer.replay_buffer.num == 0


def test_ppo_trainer_hybrid_reshards_once_per_phase():
    """The phase hook amortizes the layout swap: one reshard per
    experience phase, reused by every rollout in it."""
    import optax as _optax
    from jax.sharding import Mesh

    from dlrover_tpu.accel import Strategy
    from dlrover_tpu.rl.hybrid_engine import HybridRolloutEngine
    from dlrover_tpu.rl.rollout import (
        make_actor_loss,
        make_critic_loss,
        sample_rollout_batch,
    )
    from dlrover_tpu.rl.trainer import PPOTrainer, RLTrainConfig

    cfg = GPTConfig.tiny(max_seq_len=64, vocab_size=32)
    actor_model = GPT(cfg)
    critic_model = GPT(
        GPTConfig.tiny(max_seq_len=64, vocab_size=32, head="value")
    )
    prompt_len, max_new = 4, 8
    rng_np = np.random.default_rng(0)
    prompts = [
        jnp.asarray(rng_np.integers(
            0, cfg.vocab_size, (8, prompt_len), dtype=np.int32
        ))
        for _ in range(3)
    ]
    sample = sample_rollout_batch(prompts[0], max_new)
    actor_params = actor_model.init_params(jax.random.PRNGKey(1))
    engine = RLModelEngine(sample, {
        ModelRole.ACTOR: RoleSpec(
            model=actor_model,
            loss_fn=make_actor_loss(actor_model, prompt_len),
            optim_factory=lambda: _optax.adam(5e-3),
            strategy=Strategy(opts=[("fsdp", {})]),
        ),
        ModelRole.CRITIC: RoleSpec(
            model=critic_model,
            loss_fn=make_critic_loss(critic_model, prompt_len),
            optim_factory=lambda: _optax.adam(1e-3),
            strategy=Strategy(opts=[("parallel_mode", {})]),
        ),
        ModelRole.REF: RoleSpec(
            model=GPT(cfg), params=actor_params
        ),
    }).build()
    hybrid = HybridRolloutEngine(
        engine,
        Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
             ("data", "tensor")),
    )
    trainer = PPOTrainer(
        engine,
        RLTrainConfig(
            epochs=2, num_rollouts=24, ppo_epochs=1,
            train_batch_size=8, max_new_tokens=max_new,
        ),
        reward_fn=lambda s: (s[:, prompt_len:] < 16).mean(
            axis=1
        ).astype(jnp.float32),
        hybrid=hybrid,
    )
    history = trainer.train(prompts)
    # 3 batches x 8 = 24 rollouts/epoch -> exactly 1 training phase
    # per epoch -> exactly 1 reshard per phase, 2 total
    assert len(history) == 2
    assert hybrid.stats()["reshards"] == 2, hybrid.stats()
    assert trainer._rollout_params is None


def test_per_role_strategies_and_reshard_accounting():
    """Each role runs under its OWN strategy (reference:
    atorch/rl/model_engine/model_engine.py:35 accelerates every model
    type separately): actor declares fsdp, critic SEARCHES its own
    strategy, the frozen ref gets a tensor-sliced inference layout —
    and every cross-layout transition lands in the per-role reshard
    stats."""
    import optax as _optax
    from jax.sharding import Mesh

    from dlrover_tpu.accel import Strategy
    from dlrover_tpu.parallel.sharding import gpt_tp_rules
    from dlrover_tpu.rl.hybrid_engine import HybridRolloutEngine
    from dlrover_tpu.rl.rollout import (
        make_actor_loss,
        make_critic_loss,
        ppo_iteration,
        sample_rollout_batch,
    )

    cfg = GPTConfig.tiny(max_seq_len=64, vocab_size=32)
    actor_model = GPT(cfg)
    critic_model = GPT(
        GPTConfig.tiny(max_seq_len=64, vocab_size=32, head="value")
    )
    ref_model = GPT(cfg)

    prompt_len, max_new = 4, 8
    prompts = jnp.asarray(
        np.random.default_rng(0).integers(
            0, cfg.vocab_size, (8, prompt_len), dtype=np.int32
        )
    )
    sample = sample_rollout_batch(prompts, max_new)
    actor_params = actor_model.init_params(jax.random.PRNGKey(1))
    ref_mesh = Mesh(
        np.array(jax.devices()[:8]).reshape(2, 4), ("data", "tensor")
    )
    engine = RLModelEngine(sample, {
        ModelRole.ACTOR: RoleSpec(
            model=actor_model,
            loss_fn=make_actor_loss(actor_model, prompt_len),
            optim_factory=lambda: _optax.adam(5e-3),
            strategy=Strategy(opts=[("fsdp", {})]),
        ),
        ModelRole.CRITIC: RoleSpec(
            model=critic_model,
            loss_fn=make_critic_loss(critic_model, prompt_len),
            optim_factory=lambda: _optax.adam(1e-3),
            search=True, rank_mode="cost_model", cost_budget=3,
            extra={"target_chip": "TPU v5e"},
        ),
        ModelRole.REF: RoleSpec(
            model=ref_model, params=actor_params,
            mesh=ref_mesh, rules=gpt_tp_rules(),
        ),
    }).build()

    report = engine.role_report()
    # >=2 distinct role strategies (actor declared, critic searched)
    assert report[ModelRole.ACTOR]["strategy"] != \
        report[ModelRole.CRITIC]["strategy"] or \
        report[ModelRole.CRITIC]["searched"]
    assert report[ModelRole.CRITIC]["searched"] is True
    assert report[ModelRole.REF]["layout"] == "sharded"

    # the ref params actually live tensor-sliced
    ref_leaves = jax.tree_util.tree_leaves(
        engine._frozen_params[ModelRole.REF]
    )
    assert any(
        "tensor" in str(l.sharding.spec) for l in ref_leaves
        if hasattr(l.sharding, "spec")
    )

    # a PPO iteration through the per-role layouts still works
    rollout_mesh = Mesh(
        np.array(jax.devices()[:8]).reshape(2, 4), ("data", "tensor")
    )
    hybrid = HybridRolloutEngine(engine, rollout_mesh)

    def reward_fn(sequences):
        resp = sequences[:, prompt_len:]
        return (resp < 16).mean(axis=1).astype(jnp.float32)

    metrics = ppo_iteration(
        engine, prompts, jax.random.PRNGKey(2),
        max_new_tokens=max_new, kl_coef=0.01,
        reward_fn=reward_fn, hybrid=hybrid,
    )
    assert np.isfinite(metrics["mean_reward"])

    # ref refresh is a cross-layout reshard (actor fsdp -> ref tp)
    engine.sync_ref_from_actor()
    stats = engine.role_report()
    assert stats[ModelRole.ACTOR]["reshards"] >= 1   # rollout swap
    assert stats[ModelRole.REF]["reshards"] == 1     # ref refresh
    assert stats[ModelRole.REF]["mean_reshard_s"] >= 0
    # the refreshed ref kept its tensor-sliced layout
    ref_leaves = jax.tree_util.tree_leaves(
        engine._frozen_params[ModelRole.REF]
    )
    assert any(
        "tensor" in str(l.sharding.spec) for l in ref_leaves
        if hasattr(l.sharding, "spec")
    )
