"""The longest a save call blocked the step loop in the window (the
worker's clock around ``save_checkpoint``)."""

NAME = "ckpt.stall_max_ms"
UNIT = "ms"
LAYER = "checkpoint"
MOVES = "save_stall_ms"
SOURCE = "host_clock"


def read(run):
    stalls = [s["stall_s"] for s in run.report["window"]["saves"]]
    if not stalls:
        return None
    return max(stalls) * 1e3
