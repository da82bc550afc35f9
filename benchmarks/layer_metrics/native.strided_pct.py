"""How much of what a MEMORY save copies into the shm segment came
back from the device in another order than row-major and went through
the native strided pass (``ops/fastcopy.copy_into`` ->
``native/fastcopy.cc``): ``strided_bytes`` over ``bytes`` of the
``ckpt.save.memcpy`` children of the window's MEMORY saves.  Printed
above the result: the leaves, the seconds and the rate of that pass,
and the rate of the plain copy of the rest.  A program whose spans
carry no ``strided_bytes`` (before PR 27) reports nothing."""

import scopes

NAME = "native.strided_pct"
UNIT = "%"
LAYER = "native"
MOVES = "save_stall_ms"
SOURCE = "program_span"


def read(run):
    copies = [
        e["attributes"]
        for _, _, children in scopes.window_saves(run, "memory")
        for e in children
        if e["name"] == "ckpt.save.memcpy"
        and "strided_bytes" in e["attributes"]
    ]
    nbytes = sum(a.get("bytes", 0) for a in copies)
    if not nbytes:
        return None
    strided = sum(a["strided_bytes"] for a in copies)
    seconds = sum(a.get("strided_s", 0.0) for a in copies)
    rest = sum(a.get("copy_s", 0.0) for a in copies) - seconds
    line = (
        f"strided pass: {sum(a.get('strided_leaves', 0) for a in copies)}"
        f" leaves, {strided / 1e9:.3f} of {nbytes / 1e9:.3f} GB in "
        f"{seconds:.3f} s"
    )
    if seconds > 0:
        line += f" ({strided / seconds / 1e9:.3f} GB/s)"
    if rest > 0:
        line += (
            f"; the row-major rest in {rest:.3f} s "
            f"({(nbytes - strided) / rest / 1e9:.3f} GB/s)"
        )
    run.note(line)
    return 100.0 * strided / nbytes
