"""How much of a MEMORY save's stall the program's own spans leave
unnamed: for each ``StorageType.MEMORY`` save of the window, the
``ckpt.save`` span's duration minus the union of its children
(``ckpt.save.<part>``), over the duration; median.  The parts are
printed above the result: each child's median seconds a save, the
device->host rate over the ``fetch`` children and, in a traced run,
how well the trace's clock and the event log's agree."""

import statistics

import scopes

NAME = "ckpt.unattributed_pct"
UNIT = "%"
LAYER = "checkpoint"
MOVES = "save_stall_ms"
SOURCE = "program_span"


def read(run):
    saves = scopes.window_saves(run, "memory")
    if not saves:
        return None
    shares, parts = [], {}
    fetch_bytes = fetch_seconds = 0.0
    for _, root, children in saves:
        if root["duration_s"] <= 0:
            continue
        covered = scopes.covered_seconds(root, children)
        shares.append(1.0 - covered / root["duration_s"])
        names = {e["name"] for e in children}
        for name in names:
            parts.setdefault(name, []).append(sum(
                e["duration_s"] for e in children if e["name"] == name
            ))
        for e in children:
            if e["name"] == "ckpt.save.fetch":
                fetch_bytes += e["attributes"].get("bytes", 0)
                fetch_seconds += e["duration_s"]
    if not shares:
        return None
    run.note(
        f"memory saves: {len(shares)}; ckpt.save median "
        f"{statistics.median(r['duration_s'] for _, r, _ in saves):.3f}"
        " s; children, median seconds a save: " + ", ".join(
            f"{name[len('ckpt.save.'):]} {statistics.median(v):.4f}"
            for name, v in sorted(
                parts.items(), key=lambda kv: -statistics.median(kv[1])
            )
        )
    )
    if fetch_seconds:
        run.note(
            f"d2h fetch: {fetch_bytes / fetch_seconds / 1e9:.3f} GB/s "
            f"over {fetch_bytes / 1e9:.2f} GB"
        )
    scopes.clock_note(run)
    return 100.0 * statistics.median(shares)
