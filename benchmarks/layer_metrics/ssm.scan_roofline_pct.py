"""The state-space scan's share of its roofline: the least time the
chip could take for the FLOPs and bytes the RECURRENCE of one step
requires (every state-space layer, forward and backward; neither the
chunk-wise form's scores and decays nor the remat copy counted, so
the number reads the same required work whatever implements the scan;
``nemotron_flops.py``) over the device time under ``ssm_scan``."""

import nemotron_flops

NAME = "ssm.scan_roofline_pct"
UNIT = "%"
LAYER = "state-space layers"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = nemotron_flops.by_scope(run)
    seconds = found and nemotron_flops.scope_seconds(
        found, (nemotron_flops.SCAN_SCOPE,)
    )
    if not seconds:
        return None
    cfg, traffic = run.config, run.traffic
    batch, seq = traffic["batch"], traffic["seq"]
    least, bound = run.flops.roofline_seconds(
        nemotron_flops.recurrence_flops_per_step(cfg, batch, seq),
        nemotron_flops.recurrence_bytes_per_step(cfg, batch, seq),
        run.report["device"]["kind"],
    )
    run.note(
        f"scan roofline: least {least * 1e3:.3f} ms a step, bound by "
        f"{bound}; the operations took {seconds * 1e3:.3f} ms"
    )
    return 100.0 * least / seconds
