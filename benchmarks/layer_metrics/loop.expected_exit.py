"""Where a token is expected to leave the loop at the run's first
step: the program's own counter ``loop.expected_exit`` (the mean over
tokens of ``sum_t t x p_t``, exits counted from 1; 1.875 of 4 where
every gate reads 0.5) for the initial parameters on the fixed batch,
the value the family's reference checks.  A counter of the layer's
state, not of its speed: the step runs every pass whatever the gates
say, so this moves NO end-to-end metric (``MOVES`` names the cell's
one because an entry must name one).  Printed above the result: the
counter and the first and the last exit's mean cross entropy at the
run's last step (what a further pass buys, and what a window on one
batch does to the gate)."""

import ouro_flops

NAME = "loop.expected_exit"
UNIT = "exit"
LAYER = "looped stack"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(run):
    value = ouro_flops.first_counter(run, NAME)
    if value is None:
        return None
    last = max(
        (e for e in run.of("train_step") if "loop.nll_last" in e),
        key=lambda e: e["step"],
    )
    run.note(
        f"exits: expected exit {value:.4f} of "
        f"{run.config['total_ut_steps']} at the first step; at step "
        f"{last['step']} it reads {last[NAME]:.4f}, exit 1 a cross "
        f"entropy of {last['loop.nll_first']:.4f}, the last exit "
        f"{last['loop.nll_last']:.4f}"
    )
    return value
