"""The looped stack's device time per traced step: every operation
under the passes' scope ``ut`` (the block applications and the final
norm that closes a pass), forward, remat copy and backward.  The
passes are one scan, the same instructions run ``total_ut_steps``
times: the trace gives their sum, and a pass is that over the number
of passes.  Printed above the result: forward, remat copy and
backward apart, and where the step's other device time lies (the four
exits' head, the optimizer, the exit gate, what carries none of these
scopes, what carries no name stack), against the device's busy time.
The tied weights' gradient sums are the backward scan's carry, under
``ut``."""

import ouro_flops

NAME = "loop.blocks_ms_per_step"
UNIT = "ms"
LAYER = "looped stack"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = ouro_flops.by_scope(run)
    if found is None:
        return None
    parts = found["blocks"]
    blocks = sum(parts.values())
    if not blocks:
        return None
    ms = lambda s: f"{s * 1e3:.2f}"  # noqa: E731
    passes = run.config["total_ut_steps"]
    run.note(
        f"looped stack, ms a step over {passes} passes, forward | remat "
        f"copy | backward: {ms(parts['forward'])} | {ms(parts['remat'])} "
        f"| {ms(parts['backward'])}; a pass "
        f"{ms(parts['forward'] / passes)} | {ms(parts['remat'] / passes)} "
        f"| {ms(parts['backward'] / passes)}"
    )
    rest = {k: v for k, v in found.items() if k != "blocks"}
    busy = run.trace["busy_s"] / run.trace["steps"]
    total = blocks + sum(rest.values())
    run.note(
        f"looped stack: blocks {ms(blocks)} + " + " + ".join(
            f"{name} {ms(seconds)}" for name, seconds in rest.items()
        ) + f" = {ms(total)} ms a step of operations (a scan's "
        f"container left out, its body counted); the device was busy "
        f"{ms(busy)} ms a step: {100 * total / busy:.1f}% accounted for"
    )
    return blocks * 1e3
