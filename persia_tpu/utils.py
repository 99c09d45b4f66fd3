"""Small shared helpers (reference: persia/utils.py)."""

import os
import random
import socket
import subprocess
import time
from typing import Any, List, Optional

import numpy as np
import yaml


def setup_seed(seed: int):
    """Deterministic seeding across python/numpy (reference: utils.py:13-32).

    JAX PRNG keys are explicit (functional), so unlike the torch reference
    there is no global framework RNG to pin — training code derives all
    device randomness from ``jax.random.key(seed)``.
    """
    random.seed(seed)
    np.random.seed(seed)
    os.environ["PYTHONHASHSEED"] = str(seed)


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at a directory that a
    later process finds again; returns the directory in effect.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself — the
    directory setting is not touched. Otherwise, in a source checkout
    (``pyproject.toml`` beside the package), the cache lives at
    ``<checkout>/.jax_cache``, a fixed path (the path is part of what
    makes a later run hit, so it is never built from a temp name, pid
    or time). An installed package has no checkout — its parent is
    ``site-packages``, possibly read-only — so there nothing is set and
    None is returned: a deployment names its cache with
    ``JAX_COMPILATION_CACHE_DIR`` (docs/DEPLOY.md). Called by the
    process entry points that initialise JAX (chip_smoke.py, the
    benchmark's run.py, the trainer service, the serving binary, the example trainers),
    before their first compilation; never at import time.

    JAX caches only compilations slower than 1 s by default. Model init
    and the eager ops around a step are hundreds of faster ones — 40%
    of a cold chip_smoke run's compile seconds (PERF.md, PR 21) — so
    the threshold drops to 0 unless the environment names its own.
    """
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir and not os.path.exists(
            os.path.join(_CHECKOUT, "pyproject.toml")):
        return None
    if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if env_dir:
        return env_dir
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_cpu_platform(n_devices: int = 8, verify: bool = True) -> None:
    """Force JAX onto a virtual ``n_devices``-device CPU platform: sets
    ``JAX_PLATFORMS=cpu`` and the ``--xla_force_host_platform_device_count``
    XLA flag the multi-device CPU tests need.

    Must run before the JAX backend initializes; raises loudly if the
    backend was already initialized with fewer devices (at that point
    the flags are dead letters). Shared by tests/conftest.py,
    dryrun_multichip, and any multi-process CPU-cluster harness.

    ``verify=False`` skips the device-count check, which itself
    initializes the backend — required when ``jax.distributed.initialize``
    must still run after this (it rejects any prior backend init).
    """
    import re

    os.environ["JAX_PLATFORMS"] = "cpu"
    flag = "--xla_force_host_platform_device_count"
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(re.escape(flag) + r"=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (flags + f" {flag}={n_devices}").strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"{flag}={n_devices}")

    import jax

    # the env var is only read at import; pin the config too in case jax
    # was imported (not yet initialized) under another value
    jax.config.update("jax_platforms", "cpu")
    if not verify:
        return
    have = len(jax.devices("cpu"))
    if have < n_devices:
        raise RuntimeError(
            f"virtual CPU mesh has {have} device(s), need {n_devices}: the "
            "JAX backend initialized before force_cpu_platform() could set "
            f"XLA_FLAGS; export JAX_PLATFORMS=cpu XLA_FLAGS={flag}="
            f"{n_devices} (or call this earlier), before any jax device use"
        )


def load_yaml(path: str) -> Any:
    if not os.path.exists(path):
        raise FileNotFoundError(f"yaml file not found: {path}")
    with open(path, "r") as f:
        return yaml.safe_load(f)


def dump_yaml(content: Any, path: str):
    with open(path, "w") as f:
        yaml.safe_dump(content, f)


def run_command(cmd: List[str], env: Optional[dict] = None) -> subprocess.Popen:
    full_env = dict(os.environ)
    if env:
        full_env.update({k: str(v) for k, v in env.items()})
    return subprocess.Popen(cmd, env=full_env)


def arm_watchdog(max_seconds: int, label: str = "tool"):
    """Two-tier in-process watchdog for the chip-touching tools.

    A backend call that hangs inside native code cannot be interrupted
    from Python. Tier 1 (threading.Timer) dumps stacks and exits
    non-zero with a diagnostic — but needs the GIL, which a stuck
    native call may hold. Tier 2 (faulthandler's pure-C watchdog) needs
    no GIL and hard-exits 60s later as the backstop. Used by
    chip_smoke.py and the PERSIA_TEST_TPU pytest runs (conftest).
    Returns a zero-arg ``cancel``.
    """
    import faulthandler
    import sys
    import threading

    def fire():
        print(f"{label}: watchdog fired after {max_seconds}s — "
              "dumping stacks and exiting non-zero",
              file=sys.stderr, flush=True)
        faulthandler.dump_traceback(file=sys.stderr)
        # raising in a timer thread wouldn't stop the main thread
        os._exit(17)

    t = threading.Timer(max_seconds, fire)
    t.daemon = True
    t.start()
    faulthandler.dump_traceback_later(max_seconds + 60, exit=True)

    def cancel():
        t.cancel()
        faulthandler.cancel_dump_traceback_later()

    return cancel


def write_addr_file(addr: str, path: str) -> None:
    """Atomically publish a bound server address for a waiting parent
    (the race-free alternative to probing a free port before spawn)."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(addr)
    os.replace(tmp, path)


def wait_addr_file(path: str, timeout: float = 60.0,
                   proc: Optional[subprocess.Popen] = None) -> str:
    """Poll for an addr-file written by :func:`write_addr_file`; if
    ``proc`` is given, fail fast when the child exits first."""
    deadline = time.monotonic() + timeout
    while not os.path.exists(path):
        if proc is not None and proc.poll() is not None:
            raise TimeoutError(
                f"server exited (rc={proc.returncode}) before "
                f"publishing {path}")
        if time.monotonic() > deadline:
            raise TimeoutError(f"no addr-file at {path} after {timeout}s")
        time.sleep(0.05)
    with open(path) as f:
        return f.read().strip()


def find_free_port(start: int = 10000, end: int = 65535) -> int:
    """Pick a currently-free TCP port (reference: utils.py:83-91).

    NOTE: inherently racy (the port can be taken between probe and the
    caller's bind). Prefer binding port 0 + :func:`write_addr_file` for
    parent↔child port handoff; keep this only where a pre-known port is
    semantically required (e.g. restart-on-same-port tests)."""
    for _ in range(128):
        port = random.randint(start, end)
        with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
            try:
                s.bind(("127.0.0.1", port))
                return port
            except OSError:
                continue
    raise RuntimeError("could not find a free port")


def resolve_binary_path(name: str) -> str:
    """Locate a native service binary shipped inside the package.

    Native binaries are built into ``persia_tpu/native_bin/`` by the
    Makefile (reference resolves rust binaries next to the package,
    persia/utils.py:64-66).
    """
    here = os.path.dirname(os.path.abspath(__file__))
    candidates = [
        os.path.join(here, "native_bin", name),
        os.path.join(os.path.dirname(here), "native", "build", name),
    ]
    for c in candidates:
        if os.path.exists(c):
            return c
    raise FileNotFoundError(
        f"native binary {name!r} not found; run `make -C native` first "
        f"(searched {candidates})"
    )


def roc_auc(labels, preds) -> float:
    """Rank-based ROC AUC (Mann-Whitney U), replacing the reference's
    sklearn.metrics dependency in examples (train.py:66-68)."""
    labels = np.asarray(labels).ravel()
    preds = np.asarray(preds).ravel()
    n_pos = int((labels == 1).sum())
    n_neg = len(labels) - n_pos
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    order = np.argsort(preds, kind="mergesort")
    ranks = np.empty(len(preds), dtype=np.float64)
    ranks[order] = np.arange(1, len(preds) + 1)
    # average ranks for ties
    sorted_preds = preds[order]
    i = 0
    while i < len(sorted_preds):
        j = i
        while j + 1 < len(sorted_preds) and sorted_preds[j + 1] == sorted_preds[i]:
            j += 1
        if j > i:
            avg = (i + 1 + j + 1) / 2.0
            ranks[order[i : j + 1]] = avg
        i = j + 1
    rank_sum_pos = ranks[labels == 1].sum()
    return float((rank_sum_pos - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg))
