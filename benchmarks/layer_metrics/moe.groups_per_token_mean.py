"""How many distinct groups a token's k choices lie in, mean over
tokens and expert layers: the program's own counter
``moe.groups_per_token_mean``, median over the ``train_step`` events
of the window.  Group-limited routing holds it at or under
``topk_group`` (4 of 8: a token's choices reach at most that many
hosts of the deployment)."""

import statistics

import ling_flops

NAME = "moe.groups_per_token_mean"
UNIT = "groups"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(run):
    events = ling_flops.counter_over_window(run, NAME)
    if not events:
        return None
    value = statistics.median(e[NAME] for e in events)
    run.note(
        f"groups a token: {value:.4f} of {run.config['n_group']}, "
        f"median of {len(events)} steps; the limit is "
        f"{run.config['topk_group']}"
    )
    return value
