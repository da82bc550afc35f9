"""How long the agent took to persist a DISK save (shared memory ->
storage -> commit): ``checkpoint_persist.seconds``, median over the
window's DISK saves."""

import statistics

NAME = "agent.persist_s"
UNIT = "s"
LAYER = "launcher / agent"
MOVES = "tokens_per_s"
SOURCE = "program_span"


def read(run):
    disk = {
        s["step"] for s in run.report["window"]["saves"]
        if s["kind"] == "disk"
    }
    seconds = [
        e["seconds"] for e in run.of("checkpoint_persist", ok=True)
        if e.get("step") in disk
    ]
    if not seconds:
        return None
    return statistics.median(seconds)
