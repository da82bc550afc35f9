"""Profiler trace capture.

:func:`trace` wraps ``jax.profiler`` (TensorBoard-compatible output,
works on CPU too).  While it is active the program's own spans are in
the trace beside the device's operations, as ``dlrover.<span name>``
and ``dlrover.step.<phase>`` annotations that carry the wall clock at
their entry (:func:`dlrover_tpu.telemetry.tracing.annotation`).
Reading a trace is the benchmark's business: ``benchmarks/xplane.py``
(busy and idle time, time per device operation) and
``benchmarks/scopes.py`` (device time per named scope, the program's
spans, the clock offset to the event log).
"""

from contextlib import contextmanager


@contextmanager
def trace(logdir: str):
    """Capture an XLA profile for the enclosed block."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()
