"""Device time of the SLIDING layers' flash-attention kernels (forward,
its remat copy, backward dq, backward dkv) per traced step: the flash
kernels' operations whose name stack holds the program's ``swa`` scope.
Beside it in the note: the full layers' (scope ``full_attn``), and
both per query head, whose ratio says what the window's walk saves
(1.0: nothing is skipped)."""

import laguna_flops

NAME = "swa.flash_ms_per_step"
UNIT = "ms"
LAYER = "window attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = laguna_flops.kernel_seconds_by_scope(
        run, laguna_flops.SWA_SCOPE
    )
    if found is None:
        return None
    seconds, calls = found
    cfg = run.config
    sliding = sum(laguna_flops.sliding_layers(cfg))
    note = (
        f"sliding layers' flash kernels: {seconds * 1e3:.3f} ms a "
        f"step in {calls:.0f} calls, {sliding} query heads"
    )
    full = laguna_flops.kernel_seconds_by_scope(
        run, laguna_flops.FULL_SCOPE
    )
    full_heads = sum(cfg["num_attention_heads_per_layer"]) - sliding
    if full and full_heads:
        ratio = (seconds / sliding) / (full[0] / full_heads)
        note += (
            f"; full layers' {full[0] * 1e3:.3f} ms, {full_heads} "
            f"heads: a sliding head takes {ratio:.3f} of a full one"
        )
    run.note(note)
    return seconds * 1e3
