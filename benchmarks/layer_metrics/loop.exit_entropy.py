"""How spread the exit distribution is at the run's first step: the
program's own counter ``loop.exit_entropy`` (the mean over tokens of
``H(p) = -sum_t p_t log p_t``, in nats; ``log 4`` = 1.386 at most for
four exits, 1.213 where every gate reads 0.5) for the initial
parameters on the fixed batch, the value the family's reference
checks.  A counter of the layer's state, not of its speed: the step
runs every pass whatever the gates say, so this moves NO end-to-end
metric (``MOVES`` names the cell's one because an entry must name
one)."""

import ouro_flops

NAME = "loop.exit_entropy"
UNIT = "nat"
LAYER = "looped stack"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(run):
    return ouro_flops.first_counter(run, NAME)
