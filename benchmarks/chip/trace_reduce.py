"""From a profiler trace to numbers: device busy and idle time, time per
device operation, and the idle gaps labelled by what the train thread was
doing. Two steps, so that the arithmetic can be checked on a small
recorded trace (``tests/recorded_trace.json.gz``, cut by
``tools/describe_trace.py``):

``load(xplane_path, span_names)`` keeps what the reduction reads, as plain
lists: per device plane the events of its ``XLA Ops`` and ``XLA Modules``
lines, and from the host planes the events named like one of the
benchmark's spans (``jax.profiler.TraceAnnotation`` puts them on the
profiler's clock).

``reduce(trace)`` works on that alone. Times in the result are seconds.
"""

import re
import statistics

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def load(xplane_path, span_names):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is not None:
                    dev[key] = [[e.name, float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        spans.append([e.name, float(e.start_ns),
                                      float(e.duration_ns)])
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _label(gap_start, gap_end, spans):
    """The span that covers half of the gap or more, or ``other``."""
    cover = {}
    for name, start, dur in spans:
        lo, hi = max(start, gap_start), min(start + dur, gap_end)
        if hi > lo:
            cover[name] = cover.get(name, 0.0) + (hi - lo)
    best = max(cover, key=cover.get, default="other")
    if cover.get(best, 0.0) < 0.5 * (gap_end - gap_start):
        return "other"
    return best


def on_tables(name, table_shapes):
    """Whether an operation's HLO text has an operand or a result of a
    table's shape: the gather, the gradient's scatter-add and the row
    optimizer all do, and nothing of the tower does."""
    return any(f"f32[{rows},{dim}]" in name for rows, dim in table_shapes)


def group_of(name, table):
    """A short name for a family of operations: the instruction's name
    without its number, the fusion's kind, and whether it is table work."""
    lhs = name.split(" = ")[0].lstrip("%")
    base = lhs.split(".")[0]
    kind = re.search(r"kind=(\w+)", name)
    return (base + (f":{kind.group(1)}" if kind else "")
            + (" [tables]" if table else ""))


def reduce(trace, table_shapes=()):
    """Over the traced window: ``busy_s`` and ``window_s`` (first to last
    device operation; means over the devices), ``steps`` (runs of the
    module that takes most device time, the cut first one counted as the
    fraction it is), ``ops`` [[group, seconds]] of the first device,
    longest first, ``table_s`` (time in operations on tables, first
    device), ``gaps`` [[label, seconds]] longest first and
    ``idle_by_label``. None where no operation ran on a device."""
    table_shapes = set(map(tuple, table_shapes))
    per_device = []
    for dev in trace["devices"]:
        if dev["ops"]:
            merged = _union([(s, s + d) for _, s, d in dev["ops"]])
            per_device.append((dev, merged, sum(e - s for s, e in merged),
                               merged[-1][1] - merged[0][0]))
    if not per_device:
        return None
    n = len(per_device)
    dev, merged, _, _ = per_device[0]
    groups, table_intervals = {}, []
    for name, start, dur in dev["ops"]:
        table = on_tables(name, table_shapes)
        key = group_of(name, table)
        groups[key] = groups.get(key, 0.0) + dur / 1e9
        if table:
            table_intervals.append((start, start + dur))
    gaps, idle_by_label = [], {}
    for (_, end), (start, _) in zip(merged, merged[1:]):
        label = _label(end, start, trace["spans"])
        gaps.append([label, (start - end) / 1e9])
        idle_by_label[label] = idle_by_label.get(label, 0.0) + (
            start - end) / 1e9
    gaps.sort(key=lambda g: -g[1])
    modules = {}
    for name, _, dur in dev["modules"]:
        modules.setdefault(name, []).append(dur)
    steps = 0.0
    if modules:
        main = max(modules.values(), key=sum)
        steps = sum(main) / statistics.median(main)
    return {"busy_s": sum(p[2] for p in per_device) / n / 1e9,
            "window_s": sum(p[3] for p in per_device) / n / 1e9,
            "steps": steps,
            "ops": sorted(map(list, groups.items()), key=lambda x: -x[1]),
            "table_s": sum(e - s for s, e in _union(table_intervals)) / 1e9,
            "gaps": gaps, "idle_by_label": idle_by_label}


def breakdown(reduced):
    """The result line's ``breakdown``: at most ten of each. Idle time is
    given as each label's total and its longest single gap."""
    idle = sorted(reduced["idle_by_label"].items(), key=lambda kv: -kv[1])
    longest = {}
    for label, seconds in reduced["gaps"]:
        longest.setdefault(label, seconds)
    gaps = [[f"{k}.total", v] for k, v in idle][:5]
    gaps += [[f"{k}.longest", v] for k, v in longest.items()][:5]
    return {"device_ops": reduced["ops"][:10], "idle_gaps": gaps}
