"""How much of the noise head's output is taken away: the program's
own counter ``gdla.lambda_mean`` (``sigmoid(u W_lambda)``, mean over
layers, tokens and signal heads) at the window's last step; beside it
``gdla.noise_share`` (mean ``|lambda A_noise|`` over mean
``|A_signal|``).  A program that drops the noise term reads 0."""

import motif_flops

NAME = "gdla.lambda_mean"
UNIT = "share"
LAYER = "differential attention"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(run):
    found = motif_flops.counter_at_last_step(run, NAME)
    if found is None:
        return None
    step, value = found
    share = motif_flops.counter_at_last_step(run, "gdla.noise_share")
    run.note(
        f"differential attention: lambda {value:.5f} at step {step}"
        + ("" if share is None else
           f", |lambda A_noise| / |A_signal| {share[1]:.5f}")
    )
    return value
