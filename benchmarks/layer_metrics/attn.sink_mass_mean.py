"""The share of a row's softmax that the learned sink takes: the
program's own counter ``attn.sink_mass_mean`` (``exp(sink - lse)``,
mean over the sinked layers, heads and rows) at the window's last
step; beside it ``attn.sink_abs_max``.  A program whose forward has no
sink reads 0."""

import mimo_flops

NAME = "attn.sink_mass_mean"
UNIT = "share"
LAYER = "window attention"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(run):
    steps = {s["step"] for s in run.report["window"]["steps"]}
    found = [
        (e["step"], e[NAME], e.get(mimo_flops.ABS_COUNTER))
        for e in run.of("train_step")
        if e.get("step") in steps and NAME in e
    ]
    if not found:
        return None
    step, mass, largest = max(found)
    run.note(
        f"sink: {mass:.5f} of a row's softmax at step {step}"
        + ("" if largest is None else f", largest |sink| {largest:.4f}")
    )
    return mass
