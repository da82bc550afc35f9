"""The worker's first ``jax.local_devices()``: the
``trainer.backend_open`` span of ``restart_count`` 0 (creating the
backend; on a TPU host, taking the chip).  The note gives the two
spans around it, ``trainer.distributed_init`` and ``trainer.init``,
and how much of the stretch from the end of ``recovery_phase``
``import`` to the ``worker_backend`` event the three cover."""

import loader
import scopes

NAME = "launch.backend_open_s"
UNIT = "s"
LAYER = "device"
MOVES = "setup_s"
SOURCE = "program_span"

AROUND = ("trainer.distributed_init", "trainer.backend_open",
          "trainer.init")


def read(run):
    launch = loader.load_module("layer_metrics", "launch.unattributed_pct")
    spans = [launch.span_of(run, name) for name in AROUND]
    opened = spans[1]
    if opened is None:
        return None
    line = ", ".join(
        f"{name} " + ("missing" if e is None else f"{e['duration_s']:.3f}")
        for name, e in zip(AROUND, spans)
    ) + f" s on {opened['attributes'].get('kind')}"
    imported = launch.phase_of(run, "import")
    backends = run.of("worker_backend", restart_count=0)
    if imported is not None and backends:
        t0, t1 = imported[1], backends[0]["ts"]
        named = launch.seconds(
            [scopes.interval(e) for e in spans if e is not None], t0, t1
        )
        if t1 > t0:
            line += (
                f"; import's end -> worker_backend {t1 - t0:.3f} s, "
                f"{100 * named / (t1 - t0):.1f}% of it under these spans"
            )
    run.note("backend: " + line)
    return opened["duration_s"]
