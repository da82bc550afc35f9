"""Device time per traced step of what the step computes a second
time: operations whose name stack holds jax's ``rematted_computation``
(the per-block ``nn.remat``'s forward, run again in the backward
pass) and operations the COMPILER rematerialized on its own
(instructions it copied as ``<name>.remat<n>``; they keep the
original's name stack).  On the v5e the second kind is all there is
(my chip run, PR 25): XLA merges jax's rematted matmuls with the
forward ones and then recomputes what it chooses.  Printed above the
result: the two parts, the whole ``forward_backward`` scope beside
them, and how many operations carried a name stack."""

import scopes

NAME = "model.recompute_ms_per_step"
UNIT = "ms"
LAYER = "model + kernels"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    reduced = scopes.of_run(run)
    if not reduced or not reduced["steps"] or not (
        reduced["ops_with_stack"]
    ):
        return None
    steps = reduced["steps"]
    run.note(
        f"scopes: {reduced['ops_with_stack']} of {reduced['ops']} "
        f"device operations carry a name stack "
        f"({reduced['stack_sources']}); forward_backward "
        f"{reduced['scope_s']['forward_backward'] / steps * 1e3:.3f} "
        f"ms a step; under jax's rematted_computation "
        f"{reduced['scope_s']['rematted_computation'] / steps * 1e3:.3f}"
        f" ms; the compiler's own .remat copies "
        f"{reduced['compiler_remat']['ops'] / steps:.1f} operations, "
        f"{reduced['compiler_remat']['seconds'] / steps * 1e3:.3f} ms"
    )
    return reduced["recompute_s"] / steps * 1e3
