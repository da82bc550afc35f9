"""RL model engine: per-role models with PER-ROLE strategies.

Reference: ``ModelEngine`` (``atorch/rl/model_engine/
model_engine.py:35``) manages actor/critic/ref/reward models, each
accelerated with its OWN ATorch strategy (the reference's
``auto_accelerate`` runs per model-type).  The TPU engine builds:

- trainable roles (actor, critic): an accelerated sharded train step
  via :func:`dlrover_tpu.accel.auto_accelerate` — each role either
  declares an explicit :class:`Strategy` or opts into the bounded
  strategy SEARCH (``RoleSpec.search=True``), so the inference-heavy
  critic can land on a different sharding/remat than the actor;
- frozen roles (ref, reward): a jitted apply, optionally under an
  explicit inference layout (``RoleSpec.mesh`` + ``RoleSpec.rules``
  — e.g. tensor-sliced for wide single-token matmuls) instead of
  replicated.

All four can share one device set (per-role strategies emit
compatible meshes over the same chips) — on TPU the roles are
time-multiplexed rather than placed on separate GPU groups.  Moving
state between role layouts (e.g. refreshing the frozen ref from the
actor) is one ``device_put`` per leaf; the engine times those
transitions per role in :attr:`reshard_stats`.
"""

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax

from dlrover_tpu.accel import Strategy, auto_accelerate
from dlrover_tpu.common.log import default_logger as logger


class ModelRole:
    ACTOR = "actor"
    CRITIC = "critic"
    REF = "ref"
    REWARD = "reward"

    TRAINABLE = (ACTOR, CRITIC)
    FROZEN = (REF, REWARD)


@dataclass
class RoleSpec:
    model: Any
    loss_fn: Optional[Callable] = None       # trainable roles
    optim_factory: Optional[Callable] = None
    strategy: Optional[Strategy] = None
    params: Any = None                       # frozen roles: given params
    # per-role strategy SEARCH (trainable roles): generate/prune/rank
    # candidates for THIS role's model+loss instead of accepting the
    # declared strategy — reference ModelEngine accelerates each role
    # with its own searched strategy
    search: bool = False
    rank_mode: str = "cost_model"   # chip-free default for searches
    cost_budget: int = 0
    # passed to auto_accelerate(extra=...): e.g. the chip the cost
    # model ranks for when the search runs off the chip
    extra: Dict[str, Any] = field(default_factory=dict)
    # frozen roles: explicit inference layout (mesh + partition
    # rules); None = replicated jit (single-chip shape)
    mesh: Any = None
    rules: Any = None


class RLModelEngine:
    def __init__(self, sample_batch, roles: Dict[str, RoleSpec]):
        self._sample_batch = sample_batch
        self._roles = roles
        self._accel: Dict[str, Any] = {}
        self._frozen_apply: Dict[str, Callable] = {}
        self._frozen_params: Dict[str, Any] = {}
        self._frozen_shardings: Dict[str, Any] = {}
        # per-role layout-transition timings (seconds), e.g. the
        # ref refresh from the actor's train layout
        self.reshard_stats: Dict[str, List[float]] = {}

    def build(self):
        for name, spec in self._roles.items():
            if name in ModelRole.TRAINABLE:
                if spec.loss_fn is None or spec.optim_factory is None:
                    raise ValueError(
                        f"trainable role {name} needs loss_fn and "
                        "optim_factory"
                    )
                if spec.search:
                    # this role's own bounded search: candidates are
                    # generated against ITS model/loss, so e.g. the
                    # critic (scalar head, no generation) ranks a
                    # different winner than the actor
                    self._accel[name] = auto_accelerate(
                        spec.model,
                        spec.optim_factory,
                        spec.loss_fn,
                        self._sample_batch,
                        strategy=None,
                        dry_run_candidates=True,
                        rank_mode=spec.rank_mode,
                        cost_budget=spec.cost_budget,
                        extra=spec.extra,
                    )
                else:
                    self._accel[name] = auto_accelerate(
                        spec.model,
                        spec.optim_factory,
                        spec.loss_fn,
                        self._sample_batch,
                        strategy=spec.strategy
                        or Strategy(opts=[("parallel_mode", {})]),
                        dry_run_candidates=False,
                    )
                logger.info(
                    "built trainable role %s with strategy %s%s",
                    name, self._accel[name].strategy.names(),
                    " (searched)" if spec.search else "",
                )
            else:
                params = (
                    spec.params
                    if spec.params is not None
                    else spec.model.init_params(jax.random.PRNGKey(0))
                )
                if spec.mesh is not None:
                    # explicit inference layout: tensor-sliced (or
                    # whatever the rules say) params instead of a
                    # replicated copy per chip
                    from dlrover_tpu.parallel.sharding import (
                        sharding_tree,
                    )

                    shardings = sharding_tree(
                        params, spec.mesh,
                        spec.rules if spec.rules is not None
                        else _default_frozen_rules(),
                    )
                    params = jax.device_put(params, shardings)
                    self._frozen_shardings[name] = shardings
                self._frozen_params[name] = params
                model = spec.model

                def apply_fn(p, batch, model=model):
                    return model.apply({"params": p}, batch)

                self._frozen_apply[name] = jax.jit(apply_fn)
        return self

    # -- accessors ---------------------------------------------------------

    def train_step(self, role: str):
        return self._accel[role].train_step

    def state(self, role: str):
        return self._accel[role].state

    def set_state(self, role: str, state):
        self._accel[role].state = state

    def place_batch(self, role: str, batch):
        return self._accel[role].place_batch(batch)

    def infer(self, role: str, inputs):
        """Frozen-role forward (ref logprobs / reward scores)."""
        return self._frozen_apply[role](
            self._frozen_params[role], inputs
        )

    def sync_ref_from_actor(self):
        """Refresh the frozen reference policy from the actor (the
        periodic ref update some RLHF recipes use).  A real device
        copy, not aliasing: the actor's train step donates its state,
        so held references to the live params would be invalidated on
        the next step.  When the ref has its own inference layout the
        copy is a cross-layout reshard (one device_put against the
        ref's sharding tree — XLA inserts the collectives); the
        transition is timed into :attr:`reshard_stats`."""
        import time

        import jax.numpy as jnp

        actor_params = self._accel[ModelRole.ACTOR].state.params
        t0 = time.perf_counter()
        shardings = self._frozen_shardings.get(ModelRole.REF)
        if shardings is not None:
            out = jax.device_put(actor_params, shardings)
        else:
            out = jax.tree.map(jnp.copy, actor_params)
        jax.block_until_ready(out)
        self.reshard_stats.setdefault(ModelRole.REF, []).append(
            time.perf_counter() - t0
        )
        self._frozen_params[ModelRole.REF] = out

    def record_reshard(self, role: str, seconds: float) -> None:
        """External layout transitions (e.g. the hybrid rollout
        engine's actor train->rollout swap) report here so the
        per-role accounting is complete."""
        self.reshard_stats.setdefault(role, []).append(seconds)

    def role_report(self) -> Dict[str, Dict[str, Any]]:
        """Per-role strategy + layout + reshard accounting — the
        multi-model ModelEngine contract (reference:
        atorch/rl/model_engine/model_engine.py:35 builds a strategy
        per model type; this is the observable record of it)."""
        report: Dict[str, Dict[str, Any]] = {}
        for name in self._roles:
            entry: Dict[str, Any] = {}
            if name in self._accel:
                entry["kind"] = "trainable"
                entry["strategy"] = self._accel[name].strategy.names()
                entry["searched"] = bool(self._roles[name].search)
            else:
                entry["kind"] = "frozen"
                entry["layout"] = (
                    "sharded" if name in self._frozen_shardings
                    else "replicated"
                )
            ts = self.reshard_stats.get(name, [])
            entry["reshards"] = len(ts)
            if ts:
                entry["mean_reshard_s"] = round(sum(ts) / len(ts), 4)
            report[name] = entry
        return report


def _default_frozen_rules():
    from dlrover_tpu.parallel.sharding import gpt_tp_rules

    return gpt_tp_rules()
