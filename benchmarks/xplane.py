"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, idle share, time per device operation, and
the idle gaps named by what the host was doing.

The trace is what ``jax.profiler.start_trace`` wrote in the worker.
On a TPU each chip is a plane ``/device:TPU:<n>`` whose line
``XLA Ops`` holds one event per executed HLO operation (Pallas kernels
among them, as custom calls); the worker's own host spans
(``jax.profiler.TraceAnnotation("bench.<phase>")``) are events on the
host plane's thread lines, on the same clock.

- span      first ``bench.*`` start to last ``bench.*`` end: the
            traced steps, without the profiler's own start and stop;
- busy      the union of the device-operation intervals inside the
            span (operations can overlap: a union, not a sum),
            averaged over the device planes;
- idle gaps the complement of that union on the first device, each
            gap's seconds booked to the host span that covers it
            (``outside`` where none does);
- ops       total seconds and count per operation (HLO instruction),
            with the group a breakdown sums it under.

``python benchmarks/xplane.py reduce <trace dir or file> <out.json>``
is how ``run.py`` calls it (in a child held to the CPU backend: the
only jax this needs is the reader of the file format).  ``dump``
prints what a trace holds, for reading one by hand; ``cut`` writes a
small text-proto excerpt, which is how the fixture under ``tests/``
was made.
"""

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_SPAN = "bench."
# A device operation's event name is its whole HLO text:
#   %attn.223 = (bf16[100,1024,64]{...}, ...) custom-call(...),
#       custom_call_target="tpu_custom_call", ...
#   %fusion.13 = bf16[50304,1600]{...} fusion(...), kind=kOutput, ...
# (the excerpt ``cut`` writes keeps the text in a stat, ``long_name``)
INSTRUCTION = re.compile(r"^(%?[\w\-.]+) = ")
OPCODE = re.compile(r"[)}\]] ([a-z][a-z\-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
KIND = re.compile(r"kind=(k\w+)")
SUFFIX = re.compile(r"(\.remat\d*|\.\d+)+$")


def find_trace(path):
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(
        os.path.join(path, "**", "*.xplane.pb"), recursive=True
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def read_space(path):
    """The trace as plain data: ``[{"name", "lines": [{"name",
    "events": [(name, start_ns, end_ns, stats)]}]}]``."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    path = find_trace(path)
    if path.endswith(".txt"):
        with open(path) as f:
            data = ProfileData.from_text_proto(f.read())
    else:
        data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 {k: v for k, v in e.stats})
                for e in line.events
            ]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def device_ops(space):
    """``{plane name: [(name, start, end, stats)]}`` from each
    device's operations line, sorted by start."""
    out = {}
    for plane in space:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                out[plane["name"]] = sorted(
                    line["events"], key=lambda e: e[1]
                )
    return out


def host_spans(space):
    """The worker's own spans, ``[(phase, start, end)]``, sorted."""
    spans = []
    for plane in space:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, end, _ in line["events"]:
                if name.startswith(HOST_SPAN):
                    spans.append((name[len(HOST_SPAN):], start, end))
    return sorted(spans, key=lambda s: s[1])


def union(intervals):
    """Merged ``[(start, end)]`` of possibly overlapping intervals."""
    merged = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def clip(intervals, t0, t1):
    return [
        (max(s, t0), min(e, t1)) for s, e in intervals
        if e > t0 and s < t1
    ]


def gaps(merged, t0, t1):
    """The complement of a merged interval list inside [t0, t1]."""
    out, cursor = [], t0
    for start, end in merged:
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if t1 > cursor:
        out.append((cursor, t1))
    return out


def book_gaps(idle, spans):
    """Seconds of idle per host phase: each gap is split over the
    host spans that cover it; what none covers is ``outside``."""
    booked = {}
    for g0, g1 in idle:
        covered = 0.0
        for phase, s0, s1 in spans:
            if s1 <= g0:
                continue
            if s0 >= g1:
                break
            part = min(g1, s1) - max(g0, s0)
            if part > 0:
                booked[phase] = booked.get(phase, 0.0) + part
                covered += part
        rest = (g1 - g0) - covered
        if rest > 0:
            booked["outside"] = booked.get("outside", 0.0) + rest
    return booked


def describe(name, stats):
    """``(instruction, group, target)`` of a device operation from
    its HLO text: the instruction's own name (``%attn.223``), the
    group it is summed under in a breakdown (the name without the
    compiler's numbering, with the fusion kind or the custom call's
    target: ``%attn custom-call tpu_custom_call``, ``%fusion
    kOutput``), and the custom call's target or None."""
    text = str(stats.get("long_name") or name)
    found = INSTRUCTION.match(text)
    instruction = found.group(1) if found else text[:60]
    opcode = OPCODE.search(text)
    target = TARGET.search(text)
    kind = KIND.search(text)
    group = SUFFIX.sub("", instruction)
    if target:
        group += f" custom-call {target.group(1)}"
    elif kind:
        group += f" {kind.group(1)}"
    elif opcode and opcode.group(1) not in group:
        group += f" {opcode.group(1)}"
    return instruction, group, target.group(1) if target else None


def reduce(space):
    """Everything ``run.py`` and the per-layer readers take from a
    trace, in seconds."""
    ops = device_ops(space)
    spans = host_spans(space)
    if not ops:
        raise ValueError(
            f"no device plane with an {OPS_LINE!r} line in the trace: "
            f"planes {[p['name'] for p in space]}"
        )
    if not spans:
        raise ValueError(f"no {HOST_SPAN}* host span in the trace")
    t0 = min(s[1] for s in spans)
    t1 = max(s[2] for s in spans)
    busy = {}
    for plane, events in ops.items():
        merged = union(clip([(e[1], e[2]) for e in events], t0, t1))
        busy[plane] = sum(e - s for s, e in merged)
    first = sorted(ops)[0]
    merged = union(clip([(e[1], e[2]) for e in ops[first]], t0, t1))
    idle = gaps(merged, t0, t1)
    per_op = {}
    for name, start, end, stats in ops[first]:
        if end <= t0 or start >= t1:
            continue
        instruction, group, target = describe(name, stats)
        entry = per_op.setdefault(instruction, {
            "seconds": 0.0, "count": 0, "group": group,
            "target": target,
        })
        entry["seconds"] += (min(end, t1) - max(start, t0)) / 1e9
        entry["count"] += 1
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy.values()) / len(busy) / 1e9,
        "devices": len(busy),
        "steps": sum(1 for s in spans if s[0] == "compute"),
        "host_s": {
            phase: sum(
                (s[2] - s[1]) for s in spans if s[0] == phase
            ) / 1e9
            for phase in sorted({s[0] for s in spans})
        },
        "idle_s": {
            k: v / 1e9 for k, v in book_gaps(idle, spans).items()
        },
        "longest_gap_s": max(
            ((e - s) / 1e9 for s, e in idle), default=0.0
        ),
        "ops": per_op,
    }


def dump(space, top=40):
    for plane in space:
        print(f"PLANE {plane['name']}")
        for line in plane["lines"]:
            events = line["events"]
            print(f"  LINE {line['name']!r}: {len(events)} events")
            for name, start, end, stats in events[:3]:
                print(f"    {name[:80]!r} {start:.0f} +{end - start:.0f}"
                      f" ns {dict(list(stats.items())[:8])}")
    for plane, events in device_ops(space).items():
        totals = {}
        for name, start, end, stats in events:
            instruction, group, _ = describe(name, stats)
            entry = totals.setdefault(instruction, [0.0, 0, group, name])
            entry[0] += end - start
            entry[1] += 1
        print(f"TOP OPS of {plane} ({len(totals)} names)")
        for instruction, (ns, n, group, text) in sorted(
            totals.items(), key=lambda kv: -kv[1][0]
        )[:top]:
            print(f"  {ns / 1e6:10.3f} ms x{n:<5d} {instruction} "
                  f"[{group}] {text[:200]}")
    print("HOST SPANS", host_spans(space)[:12])


def cut(space, out_path, t0_ns, t1_ns):
    """A text-proto excerpt: the device operations lines and the
    ``bench.*`` host spans that START inside [t0, t1), each device
    operation's HLO text shortened into one stat.  Small enough to commit as a fixture."""
    planes, plane_id = [], 0
    for plane in space:
        is_device = bool(DEVICE_PLANE.match(plane["name"]))
        lines_txt, names = [], {}
        for line_id, line in enumerate(plane["lines"], 1):
            if is_device and line["name"] != OPS_LINE:
                continue
            events_txt = []
            for name, start, end, stats in line["events"]:
                if not (t0_ns <= start < t1_ns):
                    continue
                if not is_device and not name.startswith(HOST_SPAN):
                    continue
                stat = ""
                if is_device:
                    # the event is named by its instruction; the HLO
                    # text, shortened, rides in the one stat
                    instruction, _, target = describe(name, stats)
                    text = str(stats.get("long_name") or name)[:240]
                    if target:
                        text += f' ... custom_call_target="{target}"'
                    name = instruction
                    stat = (
                        " stats { metadata_id: 1 str_value: "
                        f"{json.dumps(text)} }}"
                    )
                meta = names.setdefault(name, len(names) + 1)
                events_txt.append(
                    f"    events {{ metadata_id: {meta} offset_ps: "
                    f"{int((start - t0_ns) * 1000)} duration_ps: "
                    f"{int((end - start) * 1000)}{stat} }}"
                )
            if events_txt:
                lines_txt.append(
                    f"  lines {{ id: {line_id} name: "
                    f"{json.dumps(line['name'])} timestamp_ns: 0\n"
                    + "\n".join(events_txt) + "\n  }"
                )
        if not lines_txt:
            continue
        plane_id += 1
        meta_txt = [
            f"  event_metadata {{ key: {i} value {{ id: {i} name: "
            f"{json.dumps(n)} }} }}" for n, i in names.items()
        ]
        planes.append(
            f"planes {{ id: {plane_id} name: "
            f"{json.dumps(plane['name'])}\n" + "\n".join(lines_txt)
            + "\n" + "\n".join(meta_txt)
            + '\n  stat_metadata { key: 1 value { id: 1 name: '
            '"long_name" } }\n}'
        )
    with open(out_path, "w") as f:
        f.write("\n".join(planes) + "\n")


def main(argv):
    if len(argv) >= 3 and argv[1] == "reduce":
        result = reduce(read_space(argv[2]))
        with open(argv[3], "w") as f:
            json.dump(result, f)
        return 0
    if len(argv) >= 3 and argv[1] == "dump":
        dump(read_space(argv[2]))
        return 0
    if len(argv) >= 6 and argv[1] == "cut":
        space = read_space(argv[2])
        spans = host_spans(space)
        base = min(s[1] for s in spans)
        cut(space, argv[3], base + float(argv[4]) * 1e9,
            base + float(argv[5]) * 1e9)
        return 0
    print(__doc__)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv))
