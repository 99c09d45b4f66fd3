#!/usr/bin/env python3
"""Device time of one traced run of a cell by the program's own scopes:
runs the cell through the harness with ``--trace 1``, keeps the
profiler's files, and once the run is over and the program's state freed
prints ``persia_tpu.tracing.device_time_by_scope`` over the first
device's events, with the step's ``DeviceStep.scopes()`` as the table:
ms a step by scope, forward and backward, their sum beside the run's
``device_step_ms``, what carries no scope and what the table lacks. The
same goes to ``--out`` as JSON, with whole paths beside the printed
depth. Never run by the benchmark.

    python3 benchmarks/chip/tools/step_scopes.py --out chiprun_out/s.json
        --workload <cell> --seed <n> --seconds <s> [--rehearse]
        [--depth 1] [--roll-up tower,optimizer] [--hlo <compiled text>]

``--depth`` keeps a path's innermost names (0: whole paths). ``--roll-up``
cuts every path after the last of the given names in it, so that a
DLRM tower's ``MLP_0/Dense_1`` reads as ``tower``. ``--hlo`` takes the
table from a compiled module's text made elsewhere (the step compiled
for a described v5e, as ``tests/test_compile_v5e*.py`` do) where asking
the step itself would not fit or fails. A rehearsal's trace has no device
plane: the tool then prints the table's instructions by scope, no time.
"""

import argparse
import io
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import manifest  # noqa: E402
import run as harness  # noqa: E402


def rolled_up(table, names):
    """The table with every path cut after the last of ``names`` in it."""
    names = set(names)

    def cut(path):
        parts = path.split("/")
        for i in range(len(parts) - 1, -1, -1):
            if parts[i] in names:
                return "/".join(parts[:i + 1])
        return path

    return {k: (cut(path), backward) for k, (path, backward) in table.items()}


def render(report, device_step_ms=None, top=40):
    steps = report["steps"] or 1.0

    def ms(seconds):
        return 1e3 * seconds / steps

    total = ms(report["total_s"])
    out = [f"{report['steps']:.2f} steps; ms a step: total {total:.3f}"
           + (f" (device_step_ms {device_step_ms:.3f}, "
              f"{100 * (total / device_step_ms - 1):+.2f} %)"
              if device_step_ms else ""),
           f"{'scope':<44}{'forward':>10}{'backward':>10}{'both':>10}"
           f"{'share':>8}"]
    rows = [[path, ms(f), ms(b)] for path, f, b in report["scopes"]]
    rest = [["(no scope)", ms(report["unscoped_s"]), 0.0],
            ["(not in the table)", ms(report["unmatched_s"]), 0.0]]
    for path, f, b in rows[:top] + rest:
        out.append(f"{path:<44}{f:>10.3f}{b:>10.3f}{f + b:>10.3f}"
                   f"{100 * (f + b) / total if total else 0:>7.2f}%")
    if len(rows) > top:
        out.append(f"... and {len(rows) - top} more scopes, "
                   f"{sum(f + b for _, f, b in rows[top:]):.3f} ms")
    for name, seconds in report["unmatched"][:10]:
        out.append(f"  not in the table: {name} {ms(seconds):.3f}")
    return "\n".join(out)


def main(argv=None, root=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", required=True)
    p.add_argument("--depth", type=int, default=1)
    p.add_argument("--roll-up", default="")
    p.add_argument("--hlo")
    args, run_args = p.parse_known_args(argv)
    root = root or manifest.repo_root(harness.BENCH_DIR)
    traces = os.path.join(root, ".bench_trace")
    kept, line = {}, io.StringIO()
    rc = harness.main(run_args + ["--trace", "1"], root=root, out=line,
                      wrap_runner=lambda r: kept.update(runner=r),
                      keep_trace=True)
    sys.stdout.write(line.getvalue())
    if rc != 0:
        return rc
    from persia_tpu import tracing

    try:
        result = json.loads(line.getvalue().strip().splitlines()[-1])
        if args.hlo:
            with open(args.hlo) as f:
                table = tracing.scope_table(f.read())
        else:
            # the run is over and the runner closed: only now is there
            # room for a second copy of the step's executable
            table = kept["runner"]._step.scopes()
        if args.roll_up:
            table = rolled_up(table, args.roll_up.split(","))
        doc = {"run": run_args, "device": result["device"],
               "instructions": len(table)}
        try:
            ops, modules = tracing.load_device_events(
                harness.find_xplane(traces))
        except LookupError as e:
            by_scope = {}
            for path, backward in table.values():
                if args.depth:
                    path = "/".join(path.split("/")[-args.depth:])
                n = by_scope.setdefault(path or "(no scope)", [0, 0])
                n[backward] += 1
            print(f"step_scopes: {e}; {len(table)} instructions by scope "
                  f"(forward, backward):")
            for path, (f, b) in sorted(by_scope.items(),
                                       key=lambda kv: -sum(kv[1])):
                print(f"  {path:<60}{f:>7}{b:>7}")
            doc["instructions_by_scope"] = by_scope
        else:
            step_ms = result["metrics"].get("device_step_ms", {}).get("value")
            doc["device_step_ms"] = step_ms
            doc["depth"] = args.depth
            doc["by_scope"] = tracing.device_time_by_scope(
                ops, modules, table, args.depth)
            doc["by_path"] = tracing.device_time_by_scope(
                ops, modules, table, 0)
            print(render(doc["by_scope"], step_ms))
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(doc, f)
    finally:
        shutil.rmtree(traces, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
