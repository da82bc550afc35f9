"""The share of a step's ``tokens x k`` assignments that reached an
expert held on this chip: the program's own counter
``moe.held_rows_share`` (mean over the expert layers; ``held / router
outputs`` under uniform routing), median over the ``train_step``
events of the window."""

import sarvam_flops

NAME = "moe.held_rows_share"
UNIT = "share"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(run):
    found = sarvam_flops.counted_share(run)
    if found is None:
        return None
    share, events = found
    run.note(
        f"held rows: share {share:.5f} of the assignments, median of "
        f"{events} steps; uniform routing would give "
        f"{sarvam_flops.expected_share(run.config):.5f}"
    )
    return share
