"""Device time of the loss head per traced step: the operations whose
name stack holds the program's ``loss_head`` scope (the logits
projection and the cross entropy), forward, backward and the
compiler's remat copies together.  Printed above the result: how many
times a step the ``[b, s, vocab]`` matmul ran."""

import scopes

NAME = "losshead.ms_per_step"
UNIT = "ms"
LAYER = "model + kernels"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    value = scopes.scope_ms_per_step(run, "loss_head")
    if value is None:
        return None
    reduced = scopes.of_run(run)
    dots = {
        k: v / reduced["steps"]
        for k, v in reduced["loss_head_dots"].items()
    }
    run.note(
        f"loss head: {reduced['scope_ops']['loss_head'] / reduced['steps']:.1f}"
        f" device operations a step; matmuls a step under it: "
        f"{dots['forward']:.1f} forward (the logits projection, the "
        f"compiler's remat copies included), {dots['backward']:.1f} "
        f"backward, {dots['remat']:.1f} in jax's rematted computation"
    )
    return value
