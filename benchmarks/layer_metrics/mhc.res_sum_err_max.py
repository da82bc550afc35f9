"""How far ``H_res`` is from doubly stochastic: the program's own
counter ``mhc.res_sum_err_max`` (the worst ``|row or column sum - 1|``
over every token, sub-layer and block after Sinkhorn's iterations) at
the window's last step.  A program that skips the normalisation reads
near ``n - 1``."""

import motif_flops

NAME = "mhc.res_sum_err_max"
UNIT = "abs"
LAYER = "residual streams"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(run):
    found = motif_flops.counter_at_last_step(run, NAME)
    if found is None:
        return None
    step, value = found
    run.note(
        f"streams: H_res's rows and columns sum to 1 within {value:.2e} "
        f"at step {step}"
    )
    return value
