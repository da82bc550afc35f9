"""Host-offloaded optimizer states.

Reference behavior: ``atorch/atorch/optimizers/adam_offload.py``
(PartitionAdam — optimizer states live in CPU DRAM, streamed to the
GPU per step to cut accelerator memory).  TPU-native design: instead
of a custom optimizer with host-side apply, wrap ANY optax
transformation and move its state pytree to the host memory space
(``jax.memory.Space.Host``) between steps.  XLA compiles the
host<->HBM transfers into the step program, overlapping them with
compute where it can; the state keeps its GSPMD sharding (each host
holds only its shards), so this composes with ZeRO/FSDP sharding
rules from :mod:`dlrover_tpu.accel`.

HBM saved: the full optimizer state (2x params fp32 for Adam) at the
cost of PCIe/host bandwidth per step — the classic recipe when the
model fits but Adam states don't.
"""

import jax
import optax

from jax.memory import Space


def _to(kind: str):
    space = Space.Host if kind == "pinned_host" else Space.Device

    def move(x):
        # Scalars (step counts) stay put: offloading them saves
        # nothing and committing them to one device breaks jit when
        # params span a mesh.
        if not (isinstance(x, jax.Array) or hasattr(x, "dtype")):
            return x
        if getattr(x, "ndim", 0) == 0:
            return x
        if isinstance(x, jax.core.Tracer):
            # in-jit transfer; memory kinds are part of the array
            # type, so the update math cannot consume host-space
            # operands without this.  NOTE: sharded (multi-device)
            # states should go through auto_accelerate's offload_opt
            # knob instead, which transfers with concrete shardings —
            # the sharding-less Space annotation does not partition
            # on all backends.
            return jax.device_put(x, space)
        if not hasattr(x, "sharding"):
            # numpy leaves (e.g. a state restored from checkpoint):
            # land on the default device first, then pin
            x = jax.numpy.asarray(x)
        return jax.device_put(x, x.sharding.with_memory_kind(kind))

    return move


def offload(
    inner: optax.GradientTransformation,
) -> optax.GradientTransformation:
    """Wrap ``inner`` so its state lives in host memory between steps.

    Eager calls (init, or a non-jitted update) place the state
    buffers in ``pinned_host`` memory — so the full fp32 moments
    never occupy HBM, including at init time.  Under jit, pair this
    with host-memory-kind in/out shardings for the opt-state leaves
    (``auto_accelerate`` does this when the ``offload_opt`` strategy
    knob is set)."""

    def init_fn(params):
        return jax.tree.map(_to("pinned_host"), inner.init(params))

    def update_fn(grads, state, params=None):
        on_device = jax.tree.map(_to("device"), state)
        updates, new_state = inner.update(grads, on_device, params)
        return updates, jax.tree.map(_to("pinned_host"), new_state)

    return optax.GradientTransformation(init_fn, update_fn)


def adamw_offload(
    learning_rate: float = 1e-3, **kwargs
) -> optax.GradientTransformation:
    """AdamW with host-resident moments (the reference's headline
    offload config)."""
    return offload(optax.adamw(learning_rate, **kwargs))
