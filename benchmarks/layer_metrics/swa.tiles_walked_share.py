"""The share of a causal walk's sub-blocks that the sliding layers'
three kernels walk: the program's own counter
``attn.window_tiles_share`` (``block_schedule`` with the window over
``block_schedule`` without, at the tiles the kernels take), median over
the ``train_step`` events of the window.  1.0 says the window skips
nothing."""

import statistics

import laguna_flops

NAME = "swa.tiles_walked_share"
UNIT = "share"
LAYER = "window attention"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(run):
    steps = {s["step"] for s in run.report["window"]["steps"]}
    values = [
        e[laguna_flops.COUNTER] for e in run.of("train_step")
        if e.get("step") in steps and laguna_flops.COUNTER in e
    ]
    if not values:
        return None
    share = statistics.median(values)
    run.note(
        f"window tiles: {share:.5f} of a causal walk's sub-blocks are "
        f"walked, median of {len(values)} steps"
    )
    return share
