"""Time the window lost to slow steps, over the WHOLE window and not
the traced steps: the sum of (interval - median) over the step
intervals longer than 1.5 x the median, the intervals that carry a
save left out.  Printed above the result: the three longest such
intervals with the program's own ``step_phases`` (an interval runs
from one step's completion to the next: the ``report`` of the step
before, then this step's ``other``, ``gc`` and ``compute``) and
every span of the agent, the master or the worker's writer thread
that overlaps them."""

import statistics

import scopes

NAME = "trainer.stall_ms_in_window"
UNIT = "ms"
LAYER = "trainer loop"
MOVES = "tokens_per_s"
SOURCE = "host_clock"

BESIDE = ("agent", "master")


def read(run):
    rep = run.report
    steps = rep["window"]["steps"]
    if not steps:
        return None
    saved = {s["step"] for s in rep["window"]["saves"]}
    # the worker's perf counter -> the event log's clock
    to_epoch = rep["window_t0_epoch"] - rep["window_t0"]
    ends = [rep["window_t0"]] + [s["done"] for s in steps]
    plain = [
        (s["step"], a + to_epoch, b + to_epoch)
        for s, a, b in zip(steps, ends, ends[1:])
        if s["step"] - 1 not in saved
    ]
    if not plain:
        return None
    median = statistics.median(b - a for _, a, b in plain)
    slow = [p for p in plain if p[2] - p[1] > 1.5 * median]
    phases = {e["step"]: e for e in run.of("step_phases")}
    beside = [
        e for e in scopes.span_events(run)
        if e["source"] in BESIDE or e["name"] == "ckpt.save.write"
    ]
    run.note(
        f"slow steps: {len(slow)} of {len(plain)} intervals without "
        f"a save are over 1.5 x the median ({median * 1e3:.2f} ms)"
    )
    longest = sorted(plain, key=lambda p: p[1] - p[2])[:3]
    for step, t0, t1 in longest:
        ph, before = phases.get(step, {}), phases.get(step - 1, {})
        over = [
            f"{e['source']}:{e['name']} "
            f"{scopes.overlap((t0, t1), scopes.interval(e)) * 1e3:.1f}"
            f" of its {e['duration_s'] * 1e3:.1f} ms"
            for e in beside
            if scopes.overlap((t0, t1), scopes.interval(e)) > 0
        ]
        run.note(
            f"  step {step}: {(t1 - t0) * 1e3:.2f} ms (report of step "
            f"{step - 1} {before.get('report', 0) * 1e3:.2f}: events "
            f"{before.get('report.events', 0) * 1e3:.2f}, metrics "
            f"file {before.get('report.metrics_file', 0) * 1e3:.2f}; "
            f"compute {ph.get('compute', 0) * 1e3:.2f}, gc "
            f"{ph.get('gc', 0) * 1e3:.2f}, other "
            f"{ph.get('other_s', 0) * 1e3:.2f}); beside it: "
            + ("; ".join(over) or "no agent or master span")
        )
    return sum(b - a - median for _, a, b in slow) * 1e3
