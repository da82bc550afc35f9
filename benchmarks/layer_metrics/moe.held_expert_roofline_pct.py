"""The held experts' share of their roofline: the least time the chip
could take for the FLOPs and bytes that the rows COUNTED at a held
expert require (forward and both gradients of every expert layer, the
remat copy not counted; ``sarvam_flops.py``, from the window's median
of the program's ``moe.held_rows_share``) over the device time under
``moe_experts``."""

import sarvam_flops

NAME = "moe.held_expert_roofline_pct"
UNIT = "%"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = sarvam_flops.seconds_per_step(run, sarvam_flops.EXPERT_SCOPE)
    counted = sarvam_flops.counted_share(run)
    if found is None or counted is None:
        return None
    seconds, _ = found
    share, _ = counted
    cfg, traffic = run.config, run.traffic
    batch, seq = traffic["batch"], traffic["seq"]
    least, bound = run.flops.roofline_seconds(
        sarvam_flops.held_expert_flops_per_step(cfg, batch, seq, share),
        sarvam_flops.held_expert_bytes_per_step(cfg, batch, seq, share),
        run.report["device"]["kind"],
    )
    run.note(
        f"held expert roofline: {share * 100:.3f}% of the assignments "
        f"counted here, least {least * 1e3:.3f} ms a step, bound by "
        f"{bound}; the operations took {seconds * 1e3:.3f} ms"
    )
    return 100.0 * least / seconds
