"""Share of the traced span in which no operation ran on the device:
1 - (union of device-operation intervals) / span, from the profiler
trace the worker took (``xplane.py``)."""

NAME = "device.idle_pct"
UNIT = "%"
LAYER = "device"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    trace = run.trace
    if not trace:
        return None
    run.note(
        f"trace: {trace['steps']} steps in {trace['window_s']:.4f} s, "
        f"device busy {trace['busy_s']:.4f} s; idle by host phase "
        f"{ {k: round(v, 4) for k, v in trace['idle_s'].items()} }; "
        f"longest gap {trace['longest_gap_s'] * 1e3:.2f} ms"
    )
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
