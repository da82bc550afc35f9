"""Dry runner: profile or cost-estimate a candidate plan.

Reference: ``dry_runner/dry_runner.py`` (``atorch/auto/``) profiles N
training steps for throughput/memory; the engine's analyzers also
carry static cost models.  Two tiers here:

- :func:`profile_plan` — jit the sharded train step for the plan's
  mesh and time real executions (ground truth, pays compile + run).
- :func:`estimate_plan` — compile WITHOUT executing and read XLA's
  own cost analysis (flops, bytes accessed) plus the memory analysis
  from the compiled executable; a roofline estimate
  ``max(flops/peak_flops, bytes/hbm_bw)`` ranks candidates
  deterministically even on a noisy shared machine, and never
  touches the chips.
"""

import time
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.common.log import default_logger as logger


@dataclass
class DryRunResult:
    ok: bool = False
    step_time_s: float = 0.0
    compile_time_s: float = 0.0
    error: str = ""
    device_peak_bytes: int = 0
    # static-cost tier (estimate_plan)
    flops: float = 0.0
    bytes_accessed: float = 0.0
    est_step_time_s: float = 0.0

    @property
    def steps_per_second(self) -> float:
        return 1.0 / self.step_time_s if self.step_time_s else 0.0


def profile_plan(
    plan, context, profile_steps: int = 3, devices=None
) -> DryRunResult:
    """Build + run the plan's train step on the given (default: all)
    devices."""
    from dlrover_tpu.accel.accelerate import build_from_plan

    try:
        built = build_from_plan(plan, context, devices=devices)
    except Exception as e:  # noqa: BLE001 - any build error fails cand.
        logger.info("plan build failed: %s", e)
        return DryRunResult(ok=False, error=str(e))

    state, batch, step = built.state, built.place_batch(
        context.sample_batch
    ), built.train_step
    try:
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics)
        compile_time = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(profile_steps):
            state, metrics = step(state, batch)
        jax.block_until_ready(metrics)
        step_time = (time.perf_counter() - t0) / profile_steps
    except Exception as e:  # noqa: BLE001
        logger.info("plan execution failed: %s", e)
        return DryRunResult(ok=False, error=str(e))

    peak = 0
    stats = getattr(jax.devices()[0], "memory_stats", lambda: None)()
    if stats:
        peak = int(stats.get("peak_bytes_in_use", 0))
    return DryRunResult(
        ok=True, step_time_s=step_time, compile_time_s=compile_time,
        device_peak_bytes=peak,
    )


# per-chip peak specs for the roofline estimate (bf16 flops, HBM B/s;
# Google Cloud TPU documentation).  A device that is not in the table
# is an error, never a default.
_CHIP_SPECS = {
    "TPU v5p": (459e12, 2765e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v4": (137.5e12, 1228e9),
}


def chip_spec(kind: str) -> tuple:
    """``(peak bf16 FLOP/s, HBM bytes/s)`` of a chip by its
    ``device_kind``; raises on a kind the table does not know."""
    for name in sorted(_CHIP_SPECS, key=len, reverse=True):
        if kind.startswith(name):
            return _CHIP_SPECS[name]
    raise ValueError(
        f"no peak spec for device kind {kind!r} (known: "
        f"{sorted(_CHIP_SPECS)}); off the chip, name the chip the "
        "cost model ranks for: extra={'target_chip': 'TPU v5e'}"
    )


def _target_chip_spec(context, devices) -> tuple:
    """The chip the roofline is FOR: the devices' own kind on a TPU;
    elsewhere (a CPU mesh rehearsing the search) the one the caller
    named in ``context.extra["target_chip"]``."""
    dev = (list(devices) if devices is not None else jax.devices())[0]
    if dev.platform == "tpu":
        return chip_spec(dev.device_kind)
    return chip_spec(context.extra.get("target_chip", dev.device_kind))


def estimate_plan(plan, context, devices=None) -> DryRunResult:
    """Compile the plan's step (no execution) and rank it with XLA's
    cost analysis: per-device flops and HBM bytes into a roofline
    time.  Deterministic and chip-free — the static tier of the
    strategy search."""
    from dlrover_tpu.accel.accelerate import build_from_plan

    peak_flops, hbm_bw = _target_chip_spec(context, devices)
    try:
        built = build_from_plan(plan, context, devices=devices)
        batch = built.place_batch(context.sample_batch)
        t0 = time.perf_counter()
        compiled = built.train_step.lower(built.state, batch).compile()
        compile_time = time.perf_counter() - t0
    except Exception as e:  # noqa: BLE001
        logger.info("plan compile failed: %s", e)
        return DryRunResult(ok=False, error=str(e))

    try:
        cost = compiled.cost_analysis() or {}
        flops = float(cost.get("flops", 0.0))
        bytes_accessed = float(cost.get("bytes accessed", 0.0))
        est = max(flops / peak_flops, bytes_accessed / hbm_bw)
    except Exception as e:  # noqa: BLE001 - backend-optional API
        logger.info("cost analysis failed: %s", e)
        return DryRunResult(ok=False, error=f"cost analysis: {e}")
    if flops <= 0.0 and bytes_accessed <= 0.0:
        # an empty analysis must not rank as a zero-cost "best"
        return DryRunResult(
            ok=False,
            error="backend reported no cost analysis; use "
                  "rank_mode='profile'",
        )
    peak_bytes = 0
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            peak_bytes = int(
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
            )
    except Exception:  # noqa: BLE001 - backend-optional API
        pass
    return DryRunResult(
        ok=True, compile_time_s=compile_time,
        flops=flops, bytes_accessed=bytes_accessed,
        est_step_time_s=est, device_peak_bytes=peak_bytes,
    )
