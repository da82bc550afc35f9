"""The share of the held layer's row tiles that hold a row: the
program's own counter ``moe.held_tiles_share`` (mean over the expert
layers of ``tiles_used / tiles``: what the layer's row movements and
its grouped-matmul kernels walk, of the static layout they could),
median over the ``train_step`` events of the window.  A program
without the counter (before PR 38) reads nothing."""

import statistics

NAME = "moe.held_tiles_share"
UNIT = "share"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "program_counter"
ROWS = "moe.held_rows_share"


def read(run):
    steps = {s["step"] for s in run.report["window"]["steps"]}
    events = [
        e for e in run.of("train_step")
        if e.get("step") in steps and NAME in e
    ]
    if not events:
        return None
    share = statistics.median(e[NAME] for e in events)
    rows = [e[ROWS] for e in events if ROWS in e]
    run.note(
        f"held tiles: share {share:.5f} of the layout's row tiles are "
        f"walked, median of {len(events)} steps"
        + (
            f"; {statistics.median(rows):.5f} of its rows hold an "
            "assignment (the difference is each expert's last tile)"
            if rows else ""
        )
    )
    return share
