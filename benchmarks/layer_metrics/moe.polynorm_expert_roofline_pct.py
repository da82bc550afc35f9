"""The PolyNorm held experts' share of their roofline: the least time
the chip could take for the FLOPs and bytes that the rows COUNTED at a
held expert require through THREE matrices (forward and both gradients
of every sparse layer, the prediction layer's too, the remat copy not
counted; ``motif_flops.py``, from the window's median of the program's
``moe.held_rows_share``) over the device time under ``moe_experts``:
the six grouped-matmul kernels, PolyNorm and its derivative inside
two of them."""

import motif_flops

NAME = "moe.polynorm_expert_roofline_pct"
UNIT = "%"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    cfg, traffic = run.config, run.traffic
    if cfg.get("hidden_act") != "poly_norm":
        return None
    found = motif_flops.sarvam_flops.seconds_per_step(
        run, motif_flops.EXPERT_SCOPE
    )
    counted = motif_flops.counted_share(run)
    if found is None or counted is None:
        return None
    seconds, operations = found
    share, _ = counted
    batch, seq = traffic["batch"], traffic["seq"]
    least, bound = run.flops.roofline_seconds(
        motif_flops.polynorm_expert_flops_per_step(cfg, batch, seq, share),
        motif_flops.polynorm_expert_bytes_per_step(cfg, batch, seq, share),
        run.report["device"]["kind"],
    )
    run.note(
        f"polynorm expert roofline: {share * 100:.3f}% of the "
        f"assignments counted here (uniform routing "
        f"{motif_flops.expected_share(cfg) * 100:.3f}%), least "
        f"{least * 1e3:.3f} ms a step, bound by {bound}; the "
        f"{operations:.0f} operations took {seconds * 1e3:.3f} ms"
    )
    motif_flops.scopes_ms_per_step(
        run, (motif_flops.POLYNORM_SCOPE,),
        "PolyNorm outside the kernels (dense and shared)",
    )
    return 100.0 * least / seconds
