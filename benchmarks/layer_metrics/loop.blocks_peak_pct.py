"""The looped stack's share of its roofline: the least time the chip
could take for the FLOPs and bytes that ``total_ut_steps x
num_hidden_layers`` block applications of one step REQUIRE (forward
and backward, the remat copy not counted; ``ouro_flops.py``) over the
device time under the scope ``ut``."""

import ouro_flops

NAME = "loop.blocks_peak_pct"
UNIT = "%"
LAYER = "looped stack"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    seconds = ouro_flops.blocks_seconds_per_step(run)
    if seconds is None:
        return None
    cfg, traffic = run.config, run.traffic
    batch, seq = traffic["batch"], traffic["seq"]
    least, bound = run.flops.roofline_seconds(
        ouro_flops.blocks_flops_per_step(cfg, batch, seq),
        ouro_flops.blocks_bytes_per_step(cfg, batch, seq),
        run.report["device"]["kind"],
    )
    run.note(
        f"looped stack roofline: {ouro_flops.applications(cfg)} "
        f"applications need at least {least * 1e3:.3f} ms a step, bound "
        f"by {bound}; the operations took {seconds * 1e3:.3f} ms"
    )
    return 100.0 * least / seconds
