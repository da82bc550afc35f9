"""Flash attention's share of its roofline: the least time the chip
could take for the causal attention one step REQUIRES (forward and
backward of every layer, recompute not counted; ``flops.py``) over
the time its kernels took."""

import kernels

NAME = "kernel.flash_roofline_pct"
UNIT = "%"
LAYER = "model + kernels"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if not run.trace:
        return None
    seconds = kernels.kernel_seconds_per_step(run.trace, "flash")
    if seconds is None:
        return None
    cfg, traffic, f = run.config, run.traffic, run.flops
    batch, seq = traffic["batch"], traffic["seq"]
    least, bound = f.roofline_seconds(
        f.attention_flops_per_step(cfg, batch, seq),
        f.attention_bytes_per_step(cfg, batch, seq),
        run.report["device"]["kind"],
    )
    run.note(
        f"flash roofline: least {least * 1e3:.3f} ms a step, bound by "
        f"{bound}; kernels took {seconds * 1e3:.3f} ms"
    )
    return 100.0 * least / seconds
