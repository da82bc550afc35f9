"""The channel-wise delta rule's share of its roofline: the least time
the chip could take for the FLOPs and bytes the RECURRENCE of one step
requires (every KDA layer, forward and backward: ``q``, ``k``, ``v``,
``g`` a channel, ``beta``, ``o`` and their gradients once each;
neither the chunk-wise form's extra work nor the remat copy counted;
``ling_flops.py``) over the device time under ``kda_rule``: the same
work whatever implements it."""

import ling_flops

NAME = "kda.rule_roofline_pct"
UNIT = "%"
LAYER = "linear attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = ling_flops.rule_seconds_per_step(run)
    if found is None:
        return None
    seconds = found[0]
    cfg, traffic = run.config, run.traffic
    batch, seq = traffic["batch"], traffic["seq"]
    least, bound = run.flops.roofline_seconds(
        ling_flops.rule_flops_per_step(cfg, batch, seq),
        ling_flops.rule_bytes_per_step(cfg, batch, seq),
        run.report["device"]["kind"],
    )
    run.note(
        f"channel-wise rule roofline: least {least * 1e3:.3f} ms a "
        f"step, bound by {bound}; the operations took "
        f"{seconds * 1e3:.3f} ms"
    )
    return 100.0 * least / seconds
