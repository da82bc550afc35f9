"""From the save call to the save's commit in shared memory: the
``checkpoint_shm_save`` event of that step against the time the
worker made the call, median over the window's saves."""

import statistics

NAME = "ckpt.commit_s"
UNIT = "s"
LAYER = "checkpoint"
MOVES = "save_stall_ms"
SOURCE = "program_span"


def read(run):
    commits = {e["step"]: e for e in run.of("checkpoint_shm_save")}
    waits, fetch, memcpy = [], [], []
    for save in run.report["window"]["saves"]:
        event = commits.get(save["step"])
        if event is None:
            continue
        waits.append(event["ts"] - save["called"])
        fetch.append(event.get("fetch_s", 0.0))
        memcpy.append(event.get("memcpy_s", 0.0))
    if not waits:
        return None
    run.note(
        f"shm commits: {len(waits)}; median d2h fetch "
        f"{statistics.median(fetch):.3f} s, memcpy "
        f"{statistics.median(memcpy):.3f} s"
    )
    return statistics.median(waits)
