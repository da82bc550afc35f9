"""The sinked window layers' flash kernels' share of their roofline:
the least time the chip could take for the attention the window layers
REQUIRE under their mask (``min(i + 1, window)`` keys a query at heads
of ``head_dim`` | ``v_head_dim``, forward and backward, recompute not
counted, the sink no matmul; ``mimo_flops.py``) over the time the flash
kernels whose name stack holds ``swa`` took.  The kernels walk a window
narrower than their chunk in chunk-wide pieces, so the share is low by
construction: the configuration's ``window_walk`` says by how much.

Its note is the cell's account of the step: the window layers'
kernels, the full layers', and the WHOLE STEP BY SCOPE against the
device's busy time: every operation once, those no scope names
last."""

import mimo_flops

NAME = "swa.sink_roofline_pct"
UNIT = "%"
LAYER = "window attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    cfg, traffic = run.config, run.traffic
    if "hybrid_layer_pattern" not in cfg:
        return None
    found = mimo_flops.kernel_seconds_by_scope(run, mimo_flops.SWA_SCOPE)
    if found is None:
        return None
    seconds, calls = found
    batch, seq = traffic["batch"], traffic["seq"]
    least, bound = run.flops.roofline_seconds(
        mimo_flops.window_flops_per_step(cfg, batch, seq),
        mimo_flops.window_bytes_per_step(cfg, batch, seq),
        run.report["device"]["kind"],
    )
    note = (
        f"sinked window roofline: least {least * 1e3:.3f} ms a step, "
        f"bound by {bound}; the window layers' kernels took "
        f"{seconds * 1e3:.3f} ms in {calls:.0f} calls"
    )
    full = mimo_flops.kernel_seconds_by_scope(run, mimo_flops.FULL_SCOPE)
    if full:
        note += (
            f", the full layers' {full[0] * 1e3:.3f} ms in "
            f"{full[1]:.0f} calls"
        )
    walk = cfg.get("window_walk")
    if walk:
        note += (
            f"; the walk computes {walk['computed']:.4f} of the "
            f"square's scores where the band is {walk['required']:.4f}: "
            f"{walk['computed'] / walk['required']:.2f} x the required"
        )
    run.note(note)
    parts = mimo_flops.step_by_scope(run)
    busy = run.trace["busy_s"] / run.trace["steps"] * 1e3
    run.note(
        f"step by scope, ms of {busy:.3f} busy a step, every operation "
        "once: " + ", ".join(
            f"{name} {ms:.3f}" for name, ms in parts.items()
        ) + f"; together {sum(parts.values()):.3f}"
    )
    return 100.0 * least / seconds
