"""Model FLOP/s utilization of the traced run's window: tokens per
second x FLOPs a trained token requires (``flops.py``: 6 per matmul
parameter, the output head included and embedding rows not, plus
causal attention; recompute not counted) over the chips' bf16 peak."""

NAME = "model.mfu_pct"
UNIT = "%"
LAYER = "model + kernels"
MOVES = "tokens_per_s"
SOURCE = "host_clock"


def read(run):
    f = run.flops
    per_token = f.train_flops_per_token(run.config, run.traffic["seq"])
    device = run.report["device"]
    peak = f.peak(device["kind"])["flops_per_s"] * device["count"]
    run.note(
        f"mfu: {per_token / 1e9:.4f} GFLOP a token required, "
        f"{run.tokens_per_s():.1f} tokens/s in this run's window"
    )
    return 100.0 * run.tokens_per_s() * per_token / peak
