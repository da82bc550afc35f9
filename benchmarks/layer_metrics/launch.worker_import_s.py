"""The worker's interpreter start and imports (jax, flax, the
package, the training script's own): the ``recovery_phase``
``import`` of ``restart_count`` 0, which ends where
``init_jax_distributed()`` begins (the backend's opening is
``launch.backend_open_s``).  The note gives ``spawn``: the start of
``agent.spawn_workers`` to the worker process's kernel start time
(``import``'s beginning), what a respawn books as ``recovery_phase``
``spawn`` from the death's witness."""

import loader

NAME = "launch.worker_import_s"
UNIT = "s"
LAYER = "trainer loop"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    launch = loader.load_module("layer_metrics", "launch.unattributed_pct")
    imported = launch.phase_of(run, "import")
    if imported is None:
        return None
    spawned = launch.span_of(run, "agent.spawn_workers")
    if spawned is not None:
        run.note(
            f"worker: spawn {imported[0] - spawned['start_ts']:.3f} s "
            f"(agent.spawn_workers began -> the process exists), "
            f"import {imported[1] - imported[0]:.3f} s"
        )
    return imported[1] - imported[0]
