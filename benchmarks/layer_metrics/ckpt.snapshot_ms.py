"""What a DISK save's call blocks the loop for: the on-device
snapshot (``ckpt.save.snapshot``) plus the transfer kick-off
(``ckpt.save.d2h_kickoff``), median over the window's DISK saves;
the two parts and the hand-over to the writer thread are printed
above the result."""

import statistics

import scopes

NAME = "ckpt.snapshot_ms"
UNIT = "ms"
LAYER = "checkpoint"
MOVES = "save_stall_ms"
SOURCE = "program_span"


def read(run):
    parts = [
        {
            name: scopes.child_seconds(children, name)
            for name in ("snapshot", "d2h_kickoff", "enqueue")
        }
        for _, _, children in scopes.window_saves(run, "disk")
    ]
    parts = [p for p in parts if p["snapshot"] > 0]
    if not parts:
        return None
    median = {
        name: statistics.median(p[name] for p in parts)
        for name in parts[0]
    }
    run.note(
        f"disk saves: {len(parts)}; median snapshot "
        f"{median['snapshot'] * 1e3:.2f} ms, d2h kick-off "
        f"{median['d2h_kickoff'] * 1e3:.2f} ms, enqueue "
        f"{median['enqueue'] * 1e3:.2f} ms"
    )
    return statistics.median(
        p["snapshot"] + p["d2h_kickoff"] for p in parts
    ) * 1e3
