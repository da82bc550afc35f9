"""What sits between the flash kernels and ``o_proj``: device time per
traced step under the program's ``gdla_diff`` (``lambda``'s projection
and sigmoid, ``A_signal - lambda A_noise`` over a group's heads, its
counters) and ``gdla_gate`` (the element-wise gate's projection, its
sigmoid and the product) scopes."""

import motif_flops

NAME = "gdla.diff_ms_per_step"
UNIT = "ms"
LAYER = "differential attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return motif_flops.scopes_ms_per_step(
        run, motif_flops.DIFF_SCOPES, "difference and gate"
    )
