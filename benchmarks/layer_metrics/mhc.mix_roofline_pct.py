"""The residual streams' mixing against its roofline: the least time
the chip could take for the bytes the mixing REQUIRES (the read and
the write of the ``n`` streams round every sub-layer, forward and
backward, the remat copy not counted: ``motif_flops.py``; the same
work whatever implements it) over the device time under the three
``mhc_*`` scopes."""

import motif_flops

NAME = "mhc.mix_roofline_pct"
UNIT = "%"
LAYER = "residual streams"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    cfg, traffic = run.config, run.traffic
    if "mhc_expansion_rate" not in cfg:
        return None
    parts = [
        motif_flops.sarvam_flops.seconds_per_step(run, scope)
        for scope in motif_flops.MIX_SCOPES
    ]
    seconds = sum(found[0] for found in parts if found)
    if not seconds:
        return None
    batch, seq = traffic["batch"], traffic["seq"]
    least, bound = run.flops.roofline_seconds(
        motif_flops.mix_flops_per_step(cfg, batch, seq),
        motif_flops.mix_bytes_per_step(cfg, batch, seq),
        run.report["device"]["kind"],
    )
    run.note(
        f"streams' mixing roofline: least {least * 1e3:.3f} ms a step, "
        f"bound by {bound} "
        f"({motif_flops.mix_bytes_per_step(cfg, batch, seq) / 1e9:.3f} GB "
        f"required); the operations took {seconds * 1e3:.3f} ms"
    )
    return 100.0 * least / seconds
