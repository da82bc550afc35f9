"""The selective scan's share of its roofline: the least time the chip
could take for the recurrence one step REQUIRES (per token, channel
and state lane the ``exp``, the decay, the write and the read-out once
forward and twice that backward; ``x``, ``dt``, ``y`` and their
gradients once each way in HBM; the remat copy and the backward's
second pass over a chunk's states not counted: ``jamba_flops.py``)
over the device time under ``s6_scan``: the same work whatever
implements the scan.  The chip's published peaks are the MXU's and
HBM's and this work is the VPU's and the EUP's, bound by neither, so
the share reads LOW by design and cannot pass 100%."""

import jamba_flops

NAME = "s6.scan_roofline_pct"
UNIT = "%"
LAYER = "selective scan layers"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    cfg, traffic = run.config, run.traffic
    if "mamba_d_state" not in cfg:
        return None
    found = jamba_flops.by_scope(run)
    if found is None:
        return None
    seconds = sum(found[jamba_flops.SCAN_SCOPE].values())
    if not seconds:
        return None
    batch, seq = traffic["batch"], traffic["seq"]
    flops = jamba_flops.scan_flops_per_step(cfg, batch, seq)
    nbytes = jamba_flops.scan_bytes_per_step(cfg, batch, seq)
    least, bound = run.flops.roofline_seconds(
        flops, nbytes, run.report["device"]["kind"]
    )
    run.note(
        f"selective scan roofline: least {least * 1e3:.3f} ms a step, "
        f"bound by {bound} ({flops / 1e9:.1f} G operations of which "
        f"{jamba_flops.scan_exps_per_step(cfg, batch, seq) / 1e9:.1f} G "
        f"exp, {nbytes / 1e9:.3f} GB required; the peaks are the MXU's "
        "and HBM's, the work the VPU's and the EUP's); the operations "
        f"took {seconds * 1e3:.3f} ms"
    )
    return 100.0 * least / seconds
