"""The windowed flash kernels' share of their roofline: the least time
the chip could take for the attention the sliding layers REQUIRE under
their mask (``min(i + 1, window)`` keys a query, forward and backward,
recompute not counted; ``laguna_flops.py``) over the time their
kernels took."""

import laguna_flops

NAME = "swa.flash_roofline_pct"
UNIT = "%"
LAYER = "window attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = laguna_flops.kernel_seconds_by_scope(
        run, laguna_flops.SWA_SCOPE
    )
    if found is None:
        return None
    seconds, _ = found
    cfg, traffic = run.config, run.traffic
    batch, seq = traffic["batch"], traffic["seq"]
    least, bound = run.flops.roofline_seconds(
        laguna_flops.window_flops_per_step(cfg, batch, seq),
        laguna_flops.window_bytes_per_step(cfg, batch, seq),
        run.report["device"]["kind"],
    )
    run.note(
        f"window roofline: least {least * 1e3:.3f} ms a step, bound "
        f"by {bound}; the sliding layers' kernels took "
        f"{seconds * 1e3:.3f} ms"
    )
    return 100.0 * least / seconds
