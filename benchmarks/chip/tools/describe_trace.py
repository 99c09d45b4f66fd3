#!/usr/bin/env python3
"""What to look at by hand before trusting a rule written against a
trace, and how the recorded trace of ``tests/`` was cut: runs one traced
run of a cell through the harness, then writes

- ``<out>``: the trace's planes, lines and the first events of each;
- ``<out>.trim.json.gz``: two whole steps of what ``trace_reduce.load``
  keeps of it, small enough to commit (where a device plane exists).

    python3 benchmarks/chip/tools/describe_trace.py --out chiprun_out/t.txt
        --workload <cell> --seed <n> --seconds <s> [--rehearse]
"""

import argparse
import gzip
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import manifest  # noqa: E402
import run as harness  # noqa: E402
import trace_reduce  # noqa: E402


def describe(xplane_path, limit=40):
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name}: {len(events)} events")
            for e in events[:limit]:
                stats = {k: (v if len(str(v)) < 160 else str(v)[:160])
                         for k, v in e.stats}
                out.append(f"    {e.name} start {e.start_ns} dur "
                           f"{e.duration_ns} {stats}")
    return "\n".join(out)


def trim(trace, steps=2):
    """The first ``steps`` whole runs of the main module on the first
    device, with the spans beside them."""
    dev = trace["devices"][0]
    whole = sorted(dev["modules"], key=lambda m: m[1])[1:steps + 1]
    lo, hi = whole[0][1], whole[-1][1] + whole[-1][2]

    def inside(events):
        return [e for e in events if e[1] >= lo and e[1] + e[2] <= hi]

    return {"devices": [{"name": dev["name"], "ops": inside(dev["ops"]),
                         "modules": inside(dev["modules"])}],
            "spans": [s for s in trace["spans"]
                      if s[1] + s[2] >= lo and s[1] <= hi]}


def main(argv=None, root=None):
    p = argparse.ArgumentParser()
    p.add_argument("--out", required=True)
    args, run_args = p.parse_known_args(argv)
    root = root or manifest.repo_root(harness.BENCH_DIR)
    traces = os.path.join(root, ".bench_trace")
    rc = harness.main(run_args + ["--trace", "1"], root=root,
                      keep_trace=True)
    if rc != 0:
        return rc
    try:
        xplane = harness.find_xplane(traces)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(describe(xplane))
        loaded = trace_reduce.load(xplane, harness.SPANS)
        if loaded["devices"]:
            with gzip.open(args.out + ".trim.json.gz", "wt") as f:
                json.dump(trim(loaded), f)
    finally:
        shutil.rmtree(traces, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
