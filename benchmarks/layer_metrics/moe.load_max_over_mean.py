"""How unevenly the router loads the experts: the program's own
counter ``moe.load_max_over_mean`` (in the worst layer, the busiest
expert's rows over the mean ``tokens x k / experts``; 1.0 is perfect
balance), median over the ``train_step`` events of the window."""

import statistics

NAME = "moe.load_max_over_mean"
UNIT = "x"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(run):
    steps = {s["step"] for s in run.report["window"]["steps"]}
    values = [
        e[NAME] for e in run.of("train_step")
        if e.get("step") in steps and NAME in e
    ]
    if not values:
        return None
    run.note(
        f"expert load, max over mean: median of {len(values)} steps, "
        f"{min(values):.3f} to {max(values):.3f}"
    )
    return statistics.median(values)
