"""The longest wait for the shard's shm lock among the window's saves
(the ``ckpt.save.lock_wait`` child; a DISK save's runs on the writer
thread, in the same trace).  Printed above the result: who held the
lock (``held_by``: ``persist:<step>`` is the agent's in-RAM copy for
that step's persist) and how much of the wait lay inside the agent's
``ckpt.persist.lock_hold`` spans (its in-RAM copy of the segment)."""

import scopes

NAME = "ckpt.lock_wait_ms"
UNIT = "ms"
LAYER = "checkpoint"
MOVES = "save_stall_ms"
SOURCE = "program_span"


def read(run):
    waits = [
        (e, save) for save, _, children in scopes.window_saves(run)
        for e in children if e["name"] == "ckpt.save.lock_wait"
    ]
    if not waits:
        return None
    longest, save = max(waits, key=lambda w: w[0]["duration_s"])
    inside = sum(
        scopes.overlap(scopes.interval(longest), scopes.interval(e))
        for e in scopes.span_events(run, "agent")
        if e["name"] == "ckpt.persist.lock_hold"
    )
    run.note(
        f"longest lock wait: {longest['duration_s'] * 1e3:.1f} ms in "
        f"the {save['kind']} save of step {save['step']}, held by "
        f"{longest['attributes'].get('held_by')}; of it "
        f"{inside * 1e3:.1f} ms inside the agent's "
        f"ckpt.persist.lock_hold"
    )
    return longest["duration_s"] * 1e3
