#!/usr/bin/env python3
"""One run of one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n>
        --seconds <s> --trace <0|1>

A new process loads the cell's files (``BENCHMARK.json``, ``cells/``,
``configs/``, ``mixes/``, ``generators/``, ``placements/``,
``layer_metrics/``), builds the program through the cell's placement
with weights made from the seed, drives the first steps that ``correct``
compares, warms up, measures for ``--seconds``, frees the program's
state, runs the plain reference over the same first steps, and prints
one JSON object as its last line. It needs a TPU with as many chips as
the cell asks for: otherwise it exits 2 and prints no result.
``--rehearse`` is the builder's CPU rehearsal: the cell's ``rehearsal``
sizes, any platform, and no device metric printed.

No cell, configuration, mix or metric is named in this file, and no key
of a configuration is read in it: what a configuration holds is between
its generator, its placement and its readers.
"""

import argparse
import gc
import importlib.util
import json
import math
import os
import queue
import shutil
import statistics
import sys
import threading
import time
import types

_T0 = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import check  # noqa: E402
import costs  # noqa: E402
import manifest  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

SPANS = ("data_wait", "train_call")
# the host's clock is good to half a millisecond, so a time read from it
# spans this long or more
SPAN_S = 0.25


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def load_module(path):
    name = "bench_" + os.path.splitext(os.path.basename(path))[0].replace(
        "-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class CompileMeter:
    """Counts JAX's backend compilations (persistent-cache loads fire the
    same event): any inside the window is a shape that set-up missed."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


class Marks:
    """Where set-up's time went, on standard error."""

    def __init__(self):
        self._last = time.perf_counter()

    def __call__(self, what):
        now = time.perf_counter()
        log(f"bench: +{now - self._last:6.2f}s {what}")
        self._last = now


def load_env(workload, seed, rehearse, root):
    """Everything a run knows before it touches JAX."""
    man = manifest.Manifest(root)
    man.validate()
    entry, cell, config, mix_file = man.cell(workload)
    sizes = dict(cell["sizes"])
    if rehearse:
        sizes.update(cell["rehearsal"])
    chips = entry["chips"]
    env = types.SimpleNamespace(
        manifest=man, name=workload, entry=entry, cell=cell, config=config,
        mix=traffic.load_mix(mix_file), seed=int(seed), rehearse=rehearse,
        chips=chips, sizes=sizes, mesh_shape=tuple(sizes["mesh"]),
        batch=int(sizes["batch"]),
        max_ind_range=sizes.get("max_ind_range"),
        limits=cell["rehearsal_limits" if rehearse else "limits"],
        mark=Marks())
    env.stream = traffic.stream_for(env.mix, config, env.batch, env.seed)
    env.placement = load_module(
        man.path("placements", f"{cell['placement']}.py"))
    return env


def enable_compile_cache(root):
    """JAX's persistent cache at a fixed path inside the checkout, unless
    the environment names one; every compilation is stored."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def check_steps(env, runner, pre):
    """The first steps, through the window's own call and feed, each
    waited for; the runner reads what ``correct`` needs between them."""
    n = env.sizes["check_steps"]
    losses = []
    for k in range(1, n + 1):
        loss = runner.step(pre.get())
        losses.append(float(loss))
        runner.after_step(k, n)
    return dict(runner.program, losses=losses)


def warm_up(env, runner, pre):
    """Pipelined steps until the placement says no new shape will come."""
    done, cap = 0, env.sizes["warmup_steps_max"]
    loss = None
    while done < env.sizes["warmup_steps"] or not runner.settled():
        if done >= cap:
            raise RuntimeError(f"warm-up did not settle in {cap} steps")
        loss = runner.step(pre.get())
        done += 1
    if loss is not None:
        loss.block_until_ready()
    return done


def measure(env, runner, pre, seconds, trace_dir):
    """The window: from the first timed dispatch to the last step's loss.
    The train thread fetches a batch (``data_wait``), takes a slot of the
    in-flight cap, and trains it (``train_call``); a watcher thread waits
    for each loss in order and stamps its completion."""
    import jax

    cap = threading.Semaphore(env.sizes["max_in_flight"])
    pending, stamps, losses = queue.Queue(), [], []
    spans = {name: [] for name in SPANS}
    late = 0

    def watch():
        while True:
            loss = pending.get()
            if loss is None:
                return
            loss.block_until_ready()
            stamps.append(time.perf_counter())
            losses.append(loss)
            cap.release()

    def span(name):
        return (jax.profiler.TraceAnnotation(name) if tracing
                else _NullSpan())

    watcher = threading.Thread(target=watch, name="bench-watcher")
    watcher.start()
    tracing, trace_from, traced_at = False, None, None
    if trace_dir is not None:
        trace_from = max(0.0, seconds - env.sizes["trace_seconds"])
    steps = 0
    t0 = time.perf_counter()
    try:
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            if trace_from is not None and not tracing and \
                    now - t0 >= trace_from:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                traced_at = time.perf_counter()
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=options)
                tracing = True
            with span(SPANS[0]):
                t = time.perf_counter()
                late += not pre.ready()
                feed = pre.get()
                spans[SPANS[0]].append(time.perf_counter() - t)
            cap.acquire()
            with span(SPANS[1]):
                t = time.perf_counter()
                loss = runner.step(feed)
                spans[SPANS[1]].append(time.perf_counter() - t)
            pending.put(loss)
            steps += 1
    finally:
        pending.put(None)
        watcher.join()
        if tracing:
            jax.profiler.stop_trace()
    return types.SimpleNamespace(
        t0=t0, steps=steps, stamps=stamps, spans=spans, late=late,
        window_s=stamps[-1] - t0, traced_at=traced_at,
        losses=[float(x) for x in losses])


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def single_gaps(stamps, t0, until=None):
    """Seconds between consecutive step completions, the window's start
    standing for the one before the first; only those that ended before
    ``until`` where it is given."""
    times = [t0] + [s for s in stamps if until is None or s < until]
    return [b - a for a, b in zip(times, times[1:])]


def span_steps(gaps):
    """The fewest steps that take ``SPAN_S`` or more at the median gap:
    worked out from the run itself, so a faster program's spans stay as
    long on the clock."""
    return max(1, min(math.ceil(SPAN_S / statistics.median(gaps)),
                      len(gaps)))


def step_ms_p95(stamps, t0):
    """95th percentile, over every run of ``span_steps`` consecutive step
    completions in the window (sliding, all steps), of the run's time per
    step."""
    times = [t0] + list(stamps)
    k = span_steps(single_gaps(stamps, t0))
    per_step = [(times[i + k] - times[i]) / k * 1e3
                for i in range(len(times) - k)]
    if len(per_step) < 2:
        return per_step[0]
    return statistics.quantiles(per_step, n=20)[-1]


def memory_peak(devices):
    peaks, limits = [], []
    for d in devices:
        stats = d.memory_stats()
        if stats is None:
            if d.platform == "tpu":
                raise RuntimeError(f"{d} reports no memory_stats()")
            return None, None
        peaks.append(int(stats["peak_bytes_in_use"]))
        limits.append(int(stats["bytes_limit"]))
    i = max(range(len(peaks)), key=peaks.__getitem__)
    return peaks[i], limits[i]


def mean_unique_rows(env, runner, batches=3):
    """Distinct table rows a batch touches, a mean over the first
    batches; None where the placement has no tables to name rows of."""
    row_ids = getattr(runner, "row_ids", None)
    if row_ids is None:
        return None
    rows = [row_ids(env.stream.batch(i)) for i in range(batches)]
    if any(r is None for r in rows):
        return None
    return statistics.mean(costs.unique_rows(r) for r in rows)


def verdict(env, prog, window_losses):
    """Runs the plain reference over the first steps' batches and judges
    the program's side against it."""
    n = env.sizes["check_steps"]
    batches = [env.stream.batch(i) for i in range(n)]
    ref = env.placement.reference_side(env, batches)
    numbers, where = check.compare(prog, ref)
    finite = all(math.isfinite(x) for x in window_losses)
    ok, compared = check.judge(numbers, env.limits, finite)
    for name, c in compared.items():
        c["at"] = where[name]
    return ok, compared


def read_layer_metrics(env, reading):
    """Each per-layer metric of the cell through its own reader file; one
    that finds nothing to read is left out."""
    out = {}
    for m in env.manifest.metrics_of(env.name, "per_layer"):
        reader = load_module(env.manifest.path(
            "layer_metrics", f"{m['name']}.py"))
        value = reader.read(reading)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def find_xplane(trace_dir):
    for base, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(base, f)
    raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")


def run(args, root, out=sys.stdout, wrap_runner=None, keep_trace=False):
    """``wrap_runner`` is the tests' way in: it gets the built runner and
    may break the timed path underneath, to see ``correct`` come out
    false. ``keep_trace`` is ``tools/describe_trace.py``'s: the profiler's
    files stay under ``.bench_trace/`` for it to read."""
    env = load_env(args.workload, args.seed, args.rehearse, root)
    sys.path.insert(0, env.manifest.root)
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"bench: {env.name} seed {env.seed} on {device}")
    if not args.rehearse and (device["platform"] != "tpu"
                              or len(devices) < env.chips):
        log(f"bench: {env.name} needs {env.chips} TPU chip(s); "
            f"nothing was run")
        return 2
    env.devices = devices[:env.mesh_shape[0] * env.mesh_shape[1]]
    enable_compile_cache(env.manifest.root)
    env.meter = meter = CompileMeter()
    env.mark("imports, manifest, traffic laws, backend up")
    runner = env.placement.build(env)
    env.mark("placement built")
    if wrap_runner is not None:
        wrap_runner(runner)
    pre = traffic.Prefetcher(env.stream, env.sizes["generator_threads"],
                             env.sizes["prefetch_depth"],
                             convert=runner.convert)
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(env.manifest.root, ".bench_trace",
                                 f"{env.name}.{env.seed}")
    try:
        prog = check_steps(env, runner, pre)
        env.mark("first steps and their readings")
        warmed = warm_up(env, runner, pre)
        # set-up leaves a million objects behind, and a full collection
        # over them stops every thread for 0.1 s: keep them out of the
        # collector's way, as a training script does once its loop is
        # about to start (the 0.12-0.16 s stalls that one run in three
        # shows on the chip's host remain, so they have another cause)
        gc.collect()
        gc.freeze()
        env.mark("warm-up")
        before = runner.counters()
        compiles_before = meter.compiles
        setup_s = time.perf_counter() - _T0
        log(f"bench: set-up {setup_s:.1f}s ({warmed} warm-up steps)")
        win = measure(env, runner, pre, args.seconds, trace_dir)
        compiles = meter.compiles - compiles_before
        after = runner.counters()
        peak, limit = memory_peak(env.devices)
        unique_rows = mean_unique_rows(env, runner) if args.trace else None
    finally:
        pre.close()
        runner.close()
    log(f"bench: window {win.window_s:.2f}s, {win.steps} steps, "
        f"{win.late} batches late, {compiles} compilations in it")
    gaps = single_gaps(win.stamps, win.t0)
    ends = [s - win.t0 for s in win.stamps]
    log(f"bench: median gap between completions "
        f"{statistics.median(gaps) * 1e3:.3f} ms, spans of "
        f"{span_steps(gaps)} steps; longest gaps: " + ", ".join(
            f"{g * 1e3:.1f} ms ending at {at:.2f}s" for g, at in
            sorted(zip(gaps, ends), reverse=True)[:4]))
    # whether a run's rate wanders inside the window or from process to
    # process: only the first is cured by a longer window
    third = win.window_s / 3
    done = [0, 0, 0]
    for s in win.stamps:
        done[min(2, int((s - win.t0) / third))] += 1
    log("bench: samples/s by third of the window: " + ", ".join(
        f"{n * env.batch / third:.0f}" for n in done))
    env.mark("window, state freed")
    correct, compared = verdict(env, prog, win.losses)
    env.mark("reference and comparison")
    failed = sum(not math.isfinite(x) for x in win.losses)
    result = {"correct": bool(correct), "attempted": win.steps,
              "failed": failed, "metrics": {}, "device": device}
    if peak is not None:
        device["memory_peak_bytes"] = peak
    if not args.trace:
        values = {
            "samples_per_s": win.steps * env.batch / win.window_s,
            "step_ms_p95": step_ms_p95(win.stamps, win.t0),
            "setup_s": setup_s}
        for m in env.manifest.metrics_of(env.name, "end_to_end"):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        loaded = trace_reduce.load(find_xplane(trace_dir), SPANS)
        reduced = trace_reduce.reduce(loaded, runner.table_shapes())
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is None and not args.rehearse:
            raise RuntimeError("no operation ran on the device in the "
                               "traced window")
        reading = types.SimpleNamespace(
            env=env, config=env.config, batch=env.batch, chips=env.chips,
            spans=win.spans, steps=win.steps, window_s=win.window_s,
            untraced_gaps=single_gaps(win.stamps, win.t0, win.traced_at),
            compiles_in_window=compiles, late=win.late,
            counters={k: after[k] - before.get(k, 0) for k in after},
            trace=reduced, memory_peak=peak, memory_limit=limit,
            unique_rows=unique_rows,
            peaks=(None if args.rehearse else costs.peaks_for(
                env.manifest.bench_dir, device["kind"])))
        result["metrics"] = read_layer_metrics(env, reading)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = trace_reduce.breakdown(reduced)
    if args.rehearse:
        # a CPU run's numbers never stand under a device metric's name
        result["read"] = sorted(result["metrics"])
        result["metrics"] = {}
        result.pop("breakdown", None)
        result["rehearsal"] = True
    result["compared"] = compared
    for name, c in compared.items():
        log(f"bench: compared {name} {c['value']:.6g} limit {c['limit']:.6g}"
            f" at {c['at']}")
    log(f"bench: correct {correct}")
    print(json.dumps(result), file=out, flush=True)
    return 0


def main(argv=None, root=None, out=sys.stdout, wrap_runner=None,
         keep_trace=False):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="builder's CPU rehearsal at the cell's tiny sizes")
    args = p.parse_args(argv)
    return run(args, root or manifest.repo_root(BENCH_DIR), out, wrap_runner,
               keep_trace)


if __name__ == "__main__":
    sys.exit(main())
