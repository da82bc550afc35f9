"""The ungated held experts' share of their roofline: the least time
the chip could take for the FLOPs and bytes that the rows COUNTED at a
held expert require through TWO matrices (forward and both gradients
of every expert layer, the remat copy not counted;
``nemotron_flops.py``, from the window's median of the program's
``moe.held_rows_share``) over the device time under ``moe_experts``.
The note gives that time, the routing by scope, the shared expert, and
the rows' and the row tiles' shares."""

import statistics

import nemotron_flops

NAME = "moe.relu2_expert_roofline_pct"
UNIT = "%"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "device_trace"
TILES = "moe.held_tiles_share"


def read(run):
    found = nemotron_flops.by_scope(run)
    counted = nemotron_flops.counted_share(run)
    seconds = found and nemotron_flops.scope_seconds(
        found, (nemotron_flops.EXPERT_SCOPE,)
    )
    if not seconds or counted is None:
        return None
    share, _ = counted
    cfg, traffic = run.config, run.traffic
    batch, seq = traffic["batch"], traffic["seq"]
    least, bound = run.flops.roofline_seconds(
        nemotron_flops.relu2_expert_flops_per_step(cfg, batch, seq, share),
        nemotron_flops.relu2_expert_bytes_per_step(cfg, batch, seq, share),
        run.report["device"]["kind"],
    )
    tiles = [e[TILES] for e in run.of("train_step") if TILES in e]
    scopes = nemotron_flops.ROUTE_SCOPES + (
        nemotron_flops.EXPERT_SCOPE, nemotron_flops.SHARED_SCOPE,
    )
    run.note(
        f"relu2 expert roofline: {share * 100:.3f}% of the assignments "
        f"counted here (uniform routing "
        f"{nemotron_flops.expected_share(cfg) * 100:.3f}%), row tiles "
        f"used {statistics.median(tiles):.5f}; least {least * 1e3:.3f} "
        f"ms a step, bound by {bound}; the operations took "
        f"{seconds * 1e3:.3f} ms; forward | remat copy | backward: "
        + nemotron_flops.parts_note(found, scopes)
    )
    return 100.0 * least / seconds
