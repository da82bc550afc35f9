"""Device time of the optimizer pass per traced step: the operations
whose name stack holds the program's ``optimizer`` scope (update,
apply, gradient norm), from ``scopes.py``."""

import scopes

NAME = "optimizer.ms_per_step"
UNIT = "ms"
LAYER = "model + kernels"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return scopes.scope_ms_per_step(run, "optimizer")
