"""What attention's two projections cost: device time per traced step
under the program's ``attn_qkv`` (the fused q | k | v projection, the
split, the value scale) and ``attn_out`` scopes.  The notes give each,
and each by layer kind with ``attn_rope`` beside them (the rotation
and the layouts into the kernels, which read the split's three
parts)."""

import mimo_flops

NAME = "attn.qkv_ms_per_step"
UNIT = "ms"
LAYER = "window attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    value = mimo_flops.scopes_ms_per_step(
        run, (mimo_flops.QKV_SCOPE, mimo_flops.OUT_SCOPE),
        "attention's projections",
    )
    if value is None:
        return None
    by_kind = {
        f"{scope} in {kind}": mimo_flops.ms_under_all(run, kind, scope)
        for kind in (mimo_flops.SWA_SCOPE, mimo_flops.FULL_SCOPE)
        for scope in (
            mimo_flops.QKV_SCOPE, mimo_flops.OUT_SCOPE,
            mimo_flops.ROPE_SCOPE,
        )
    }
    run.note("by layer kind, ms a step: " + ", ".join(
        f"{name} {ms:.3f}" for name, ms in by_kind.items() if ms
    ))
    return value
