#!/usr/bin/env python3
"""Readings that a cell's limits are set from and held against, taken on
the chip at the cell's own size, many seeds in one process (training's
readings need no measured window):

- ``program``: the timed path's first steps against the plain reference,
  one line a seed: the lower readings. Has to come out correct;
- ``control``: the reference computed in fp8, put in the program's place;
- ``half_batch``, ``unchanged``: the reference with the second half of
  every batch left out, or with every step returning its state unchanged,
  put in the program's place. These three have to come out not correct.

    python3 benchmarks/chip/tools/calibrate.py --workload <cell>
        --first-seed N --seeds 12 --control-seeds 3 [--rehearse]

Every side of every seed is judged by the cell's own ``limits`` (its
``rehearsal_limits`` with ``--rehearse``), as a run judges the program.
One JSON object a line on standard output, the summary on standard
error; exits 1 where a verdict is not what it has to be. The benchmark's
own runs never run this; PERF.md records what it read.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import check  # noqa: E402
import manifest  # noqa: E402
import run as harness  # noqa: E402
import traffic  # noqa: E402

FAULTS = ("half_batch", "unchanged")


def main(argv=None, root=None, out=sys.stdout):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--first-seed", type=int, required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    root = root or manifest.repo_root(harness.BENCH_DIR)
    sys.path.insert(0, root)
    import jax

    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache(root)
    read, wrong = {}, []
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        env = harness.load_env(args.workload, seed, args.rehearse, root)
        env.devices = devices[:env.mesh_shape[0] * env.mesh_shape[1]]
        runner = env.placement.build(env)
        pre = traffic.Prefetcher(env.stream, 1, 4, convert=runner.convert)
        try:
            prog = harness.check_steps(env, runner, pre)
        finally:
            pre.close()
            runner.close()
        batches = [env.stream.batch(i)
                   for i in range(env.sizes["check_steps"])]
        ref = env.placement.reference_side(env, batches)
        sides = {"program": prog}
        if k < args.control_seeds:
            sides["control"] = env.placement.reference_side(
                env, batches, precision="fp8")
            for fault in FAULTS:
                sides[fault] = env.placement.reference_side(
                    env, batches, fault=fault)
        for side, readings in sides.items():
            numbers, where = check.compare(readings, ref)
            correct, compared = check.judge(numbers, env.limits)
            over = [n for n, c in compared.items()
                    if not c["value"] <= c["limit"]]
            if correct != (side == "program"):
                wrong.append((side, seed))
            for n, v in numbers.items():
                read.setdefault(side, {}).setdefault(n, []).append(v)
            print(json.dumps({"cell": args.workload, "seed": seed,
                              "side": side, "correct": correct,
                              "over": over, "numbers": numbers,
                              "at": where, "losses": readings["losses"],
                              "ref_losses": ref["losses"]}),
                  file=out, flush=True)
    for side, numbers in read.items():
        for n, v in numbers.items():
            print(f"calibrate: {side:10s} {n:18s} {len(v):3d} seeds  "
                  f"least {min(v):.4g}  most {max(v):.4g}  "
                  f"limit {env.limits.get(n, 'none')}", file=sys.stderr)
    for side, seed in wrong:
        print(f"calibrate: {side} of seed {seed} came out "
              f"{'not correct' if side == 'program' else 'correct'}",
              file=sys.stderr)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
