"""The grouped expert matmuls' share of their roofline: the least time
the chip could take for the FLOPs and bytes the experts of one step
REQUIRE (forward and both gradients of every layer, the remat copy
not counted; ``moe_flops.py``) over the device time under
``moe_experts``."""

import moe_flops

NAME = "moe.expert_roofline_pct"
UNIT = "%"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = moe_flops.seconds_per_step(run, moe_flops.EXPERT_SCOPE)
    if found is None:
        return None
    seconds, _ = found
    cfg, traffic = run.config, run.traffic
    batch, seq = traffic["batch"], traffic["seq"]
    least, bound = run.flops.roofline_seconds(
        moe_flops.expert_flops_per_step(cfg, batch, seq),
        moe_flops.expert_bytes_per_step(cfg, batch, seq),
        run.report["device"]["kind"],
    )
    run.note(
        f"expert roofline: least {least * 1e3:.3f} ms a step, bound by "
        f"{bound}; the operations took {seconds * 1e3:.3f} ms"
    )
    return 100.0 * least / seconds
