"""What a tool that times kernels alone on the chip needs besides its
shapes: the checkout to import (``--tree``), the chip or a refusal, the
peaks, a timed loop that ends in ``block_until_ready``, and the lines
written to ``--out``; and :func:`expert_layers`, the sequence cells'
expert layers as the benchmark runs them. ``tools/expert_rows_times.py``
and ``tools/gmm_times.py`` are built on it; the older ``*_times.py``
tools still carry their own copies.
"""

import argparse
import glob
import importlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parser(doc, shapes):
    """``--tree``, ``--shapes`` (default ``shapes``), ``--iters`` and
    ``--out``; a tool adds its own options."""
    ap = argparse.ArgumentParser(description=doc.splitlines()[0])
    ap.add_argument("--tree", default=REPO)
    ap.add_argument("--shapes", default=shapes)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", default=None)
    return ap


def chip(args, name):
    """Puts ``args.tree`` and its benchmark first on the import path and
    returns ``(device, its row of peaks.json)``, or ``None`` with a line on
    stderr where the first device is no TPU: a CPU time says nothing
    about the chip."""
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    sys.path.insert(0, os.path.join(tree, "benchmarks", "chip"))
    import jax

    import costs

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"{name}: no TPU here, nothing timed", file=sys.stderr)
        return None
    peaks = costs.peaks_for(os.path.join(tree, "benchmarks", "chip"),
                            dev.device_kind)
    return dev, peaks


def timed(fn, *xs, iters):
    """ms a call of ``fn(*xs)`` over ``iters`` calls after one that
    compiles, and the last call's result."""
    import jax

    out = jax.block_until_ready(fn(*xs))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*xs)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3, out


def emit(lines, out=None):
    """Prints each line as JSON, and writes them all to ``out``."""
    for line in lines:
        print(json.dumps(line), flush=True)
    if out:
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "a") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)


def expert_layers(tree=REPO):
    """{configuration: (tokens, pair-buffer rows, hidden width, expert
    width, activation, experts a token, routed experts, held experts)}
    of the expert layers of each cell the benchmark under ``tree`` runs on
    a sequence placement, read from the cell, its configuration and the
    tower its placement builds."""
    from persia_tpu.models.hybrid_seq import pair_buffer_rows

    chip_dir = os.path.join(tree, "benchmarks", "chip")
    if chip_dir not in sys.path:
        sys.path.insert(0, chip_dir)
    layers = {}
    for path in sorted(glob.glob(os.path.join(chip_dir, "cells", "*.json"))):
        with open(path) as f:
            cell = json.load(f)
        if not cell["placement"].startswith("device_seq"):
            continue
        with open(os.path.join(chip_dir, "configs",
                               cell["config"] + ".json")) as f:
            config = json.load(f)
        placement = importlib.import_module(
            "placements." + cell["placement"])
        sz = placement.weights.sizes_of(config)
        tokens, held = cell["sizes"]["batch"], len(sz["experts_held"])
        per_token, routed = sz["experts_per_token"], sz["experts_routed"]
        layers[cell["config"]] = (
            tokens, pair_buffer_rows(tokens, per_token, held, routed),
            sz["hidden"], sz["expert_width"],
            placement.build_tower(sz).expert_activation, per_token, routed,
            held)
    return layers
