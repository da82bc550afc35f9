"""What a conv layer costs between its two matmuls: device time per
traced step under the program's ``sconv_mix`` scope (the gated short
convolution ``y = C * conv3(B * u)``, forward, the block's remat copy
and backward, and the counter's reduction)."""

import lfm2_flops
import sarvam_flops

NAME = "sconv.mix_ms_per_step"
UNIT = "ms"
LAYER = "short convolution"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return sarvam_flops.scopes_ms_per_step(
        run, (lfm2_flops.MIX_SCOPE,), "short convolution mix"
    )
