"""What the multi-token-prediction layer costs: device time per traced
step under the program's ``mtp`` scope (``W_eh`` and its two norms,
one more sparse full-attention block with its streams' mixing, the
second pass of the shared head), forward, remat copy and backward;
the note gives the program's ``mtp.loss`` at the window's last
step."""

import motif_flops

NAME = "mtp.ms_per_step"
UNIT = "ms"
LAYER = "prediction layer"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    value = motif_flops.scopes_ms_per_step(
        run, (motif_flops.MTP_SCOPE,), "prediction layer"
    )
    found = motif_flops.counter_at_last_step(run, "mtp.loss")
    if value is not None and found is not None:
        run.note(f"prediction layer: loss {found[1]:.5f} at step {found[0]}")
    return value
