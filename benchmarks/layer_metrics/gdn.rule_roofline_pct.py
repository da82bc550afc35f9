"""The gated delta rule's share of its roofline: the least time the
chip could take for the FLOPs and bytes the RECURRENCE of one step
requires (every linear layer, forward and backward, neither the
chunk-wise form's extra work nor the remat copy counted;
``gdn_flops.py``) over the device time under ``gdn_rule``."""

import gdn_flops

NAME = "gdn.rule_roofline_pct"
UNIT = "%"
LAYER = "linear attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = gdn_flops.seconds_per_step(run, gdn_flops.RULE_SCOPE)
    if found is None:
        return None
    seconds = found[0]
    cfg, traffic = run.config, run.traffic
    batch, seq = traffic["batch"], traffic["seq"]
    least, bound = run.flops.roofline_seconds(
        gdn_flops.rule_flops_per_step(cfg, batch, seq),
        gdn_flops.rule_bytes_per_step(cfg, batch, seq),
        run.report["device"]["kind"],
    )
    run.note(
        f"rule roofline: least {least * 1e3:.3f} ms a step, bound by "
        f"{bound}; the operations took {seconds * 1e3:.3f} ms"
    )
    return 100.0 * least / seconds
