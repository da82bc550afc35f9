"""Rate of the native copy into the shm segment (``ops/fastcopy`` ->
``native/``): bytes over the seconds inside ``copy_into``, which the
``ckpt.save.memcpy`` children (one per ~256 MB chunk) of the window's
MEMORY saves carry as ``copy_s``.  Printed above the result: what
else those spans hold, the seconds spent making the fetched arrays
C-contiguous (``contiguous_s``: a full copy in numpy wherever the
transfer hands back another layout)."""

import scopes

NAME = "native.memcpy_GBps"
UNIT = "GB/s"
LAYER = "native"
MOVES = "save_stall_ms"
SOURCE = "program_span"


def read(run):
    copies = [
        e for _, _, children in scopes.window_saves(run, "memory")
        for e in children if e["name"] == "ckpt.save.memcpy"
    ]
    nbytes = sum(e["attributes"].get("bytes", 0) for e in copies)
    native = sum(e["attributes"].get("copy_s", 0.0) for e in copies)
    if not native or not nbytes:
        return None
    spans = sum(e["duration_s"] for e in copies)
    contiguous = sum(
        e["attributes"].get("contiguous_s", 0.0) for e in copies
    )
    run.note(
        f"memcpy: {len(copies)} chunks, {nbytes / 1e9:.2f} GB; "
        f"{native:.3f} s in the native copy, {contiguous:.3f} s "
        f"making arrays contiguous, {spans:.3f} s in the spans "
        f"({nbytes / spans / 1e9:.3f} GB/s over all of it)"
    )
    return nbytes / native / 1e9
