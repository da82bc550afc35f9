"""The conv layers' two matmuls: device time per traced step under the
program's ``sconv_proj`` scope (``W_in`` hidden x 3 hidden and ``W_out``
hidden x hidden, forward, the remat copy and both gradients)."""

import lfm2_flops
import sarvam_flops

NAME = "sconv.proj_ms_per_step"
UNIT = "ms"
LAYER = "short convolution"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return sarvam_flops.scopes_ms_per_step(
        run, (lfm2_flops.PROJ_SCOPE,), "short convolution projections"
    )
