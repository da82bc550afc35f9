"""From the launch of ``run.py`` to the worker's ``worker_backend``
event: tpurun, the local master, the agent's rendezvous, the worker's
spawn and its imports, up to the moment it owns the chip."""

NAME = "agent.start_s"
UNIT = "s"
LAYER = "launcher / agent"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    backends = run.of("worker_backend")
    if not backends:
        return None
    return backends[0]["ts"] - run.t_launch
