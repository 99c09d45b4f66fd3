"""Cross-tier tracing + stage timing + stall/deadlock detection.

Reference observability surface: per-stage Prometheus gauges
(embedding_worker_service/mod.rs:83-100, persia-core/src/metrics.rs) and
an opt-in deadlock detector thread (persia-common/src/utils.rs:22-48,
enabled by PERSIA_DEADLOCK_DETECTION=1).

On top of the reference surface this module adds **distributed
tracing**: one logical training step spans three tiers (trainer ↔
embedding worker ↔ sharded PS), and aggregate histograms cannot tell you
*which* tier made *this* batch slow. A :class:`Span` carries
``(trace_id, span_id, parent_id)``; the active span lives in a
thread-local so nested ``with span(...)`` blocks parent naturally; the
context crosses process boundaries through the RPC envelope (rpc.py
negotiates the extra envelope slot per connection, like ``__tags__``, so
legacy peers never see it). Finished spans land in a process-wide ring
buffer (:class:`TraceCollector`) that the HTTP sidecar
(:mod:`persia_tpu.obs_http`) serves at ``/trace`` and
:func:`chrome_trace` exports as Chrome-trace/Perfetto JSON.

Tracing is OFF by default (``PERSIA_TRACING=1`` or
:func:`enable_tracing` turns it on): every ``span(...)`` call site then
returns a shared no-op context manager, and the RPC client never probes
``__trace__`` — the disabled wire is byte-identical to the untraced one.

Spans also record while a ``jax.profiler`` session is open in this
process (:func:`profiler_live`), whoever opened it. Such a span is a
``jax.profiler.TraceAnnotation`` too, so it lies in the profiler's
xplane on the device operations' clock, and is marked ``profiled`` in
the ring. That switch is local to the process: the RPC envelope follows
``PERSIA_TRACING`` alone.

:class:`StepProfiler` is the device-side companion: opt-in
``jax.profiler`` start/stop keyed to a trainer step window; the spans of
those steps land in the same xplane as the device operations.

The device's own account is kept here too, as plain functions over plain
lists (no JAX at import): :func:`compile_watch` counts the process's
backend compilations, :func:`scope_table` reads a compiled module's text
for the ``jax.named_scope`` path of every instruction, and
:func:`device_time_by_scope` sums a trace's device events by those
paths, each event by its self time.
"""

import bisect
import json
import os
import re
import statistics
import struct
import sys
import threading
import time
import traceback
from collections import deque
from typing import Dict, List, Optional, Tuple

from persia_tpu.logger import get_default_logger
from persia_tpu import knobs
from persia_tpu.metrics import default_registry

_logger = get_default_logger(__name__)


# --- span context ---------------------------------------------------------

# frozen at import ON PURPOSE (registered import_time_safe): the
# disabled path must cost nothing, so the gate is a module constant
_enabled = knobs.get("PERSIA_TRACING")
_tls = threading.local()
# chrome-trace "pid" label; set_service_name() names this process's track
_service = [f"pid{os.getpid()}"]

# distinct sentinel: span(ctx=None) means "suppress unless propagated",
# while an OMITTED ctx falls back to the thread-local parent
_UNSET = object()


def tracing_enabled() -> bool:
    return _enabled


# jax.profiler.TraceAnnotation, cached at first sight. Never imported
# from here: the PS and worker children import no JAX, and a process
# without it has no profiler session to ride.
_annotation = None


def profiler_live() -> bool:
    """True while a ``jax.profiler`` session is open in this process,
    whoever opened it (``TraceAnnotation.is_enabled`` is the profiler's
    own switch for host events). False, without touching JAX, in a
    process that has not imported it."""
    global _annotation
    if _annotation is None:
        _annotation = getattr(sys.modules.get("jax.profiler"),
                              "TraceAnnotation", None)
        if _annotation is None:
            return False
    return _annotation.is_enabled()


def enable_tracing(on: bool = True):
    """Flip span recording process-wide. Turn on BEFORE dialing RPC
    clients that should propagate context: the ``__trace__`` capability
    is negotiated per connection at dial time."""
    global _enabled
    _enabled = bool(on)


def set_service_name(name: str):
    """Name this process's track in exported traces (e.g. ``ps0``,
    ``worker1``, ``trainer``)."""
    _service[0] = name


def service_name() -> str:
    return _service[0]


def _rand64() -> int:
    # non-zero 63-bit id: fits signed int64 consumers and msgpack ints
    while True:
        (v,) = struct.unpack("<Q", os.urandom(8))
        v &= (1 << 63) - 1
        if v:
            return v


def current_context() -> Optional[Tuple[int, int]]:
    """(trace_id, span_id) of the active span on THIS thread, or None.
    This is what the RPC client injects into the envelope and what
    fan-out code captures before handing work to a pool thread. It
    follows ``PERSIA_TRACING`` alone: a profiler session propagates
    nothing."""
    if not _enabled:
        return None
    return getattr(_tls, "ctx", None)


class _NullSpan:
    """Shared no-op for disabled tracing — one attribute read + two
    no-op method calls per instrumented block."""

    __slots__ = ()
    trace_id = None
    span_id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    @property
    def ctx(self):
        return None

    def tag(self, **kw):
        return self


_NULL_SPAN = _NullSpan()


class Span:
    """One timed region. ``__enter__`` installs it as the thread's
    active context (its children parent to it); ``__exit__`` restores
    the previous context and hands the finished span to the collector.

    Wall-clock start (``time.time_ns``) makes spans from different
    processes line up on one timeline; the duration is measured with
    the monotonic perf counter so it never jumps with clock slew. A
    ``profiled`` span was opened under a live profiler session and is a
    ``TraceAnnotation`` of the same name there, its tags the event's
    stats."""

    __slots__ = ("name", "service", "trace_id", "span_id", "parent_id",
                 "start_ns", "dur_ns", "tags", "pid", "tid", "profiled",
                 "_prev", "_t0", "_anno")

    def __init__(self, name: str, trace_id: int, span_id: int,
                 parent_id: int, tags: Optional[Dict] = None,
                 service: Optional[str] = None, profiled: bool = False):
        self.name = name
        self.service = service if service is not None else _service[0]
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.tags = tags
        self.pid = os.getpid()
        self.tid = threading.current_thread().name
        self.start_ns = 0
        self.dur_ns = 0
        self.profiled = profiled
        self._anno = None

    @property
    def ctx(self) -> Tuple[int, int]:
        """Propagation handle: what children (local or remote) parent to."""
        return (self.trace_id, self.span_id)

    def tag(self, **kw):
        if self.tags is None:
            self.tags = {}
        self.tags.update(kw)
        if self._anno is not None:
            self._anno.set_metadata(**kw)
        return self

    def __enter__(self):
        self._prev = getattr(_tls, "ctx", None)
        _tls.ctx = (self.trace_id, self.span_id)
        self.start_ns = time.time_ns()
        self._t0 = time.perf_counter_ns()
        if self.profiled:
            self._anno = _annotation(self.name, **(self.tags or {}))
            self._anno.__enter__()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        if exc_type is not None:
            self.tag(error=f"{exc_type.__name__}: {exc_val}")
        if self._anno is not None:
            self._anno.__exit__(exc_type, exc_val, exc_tb)
            self._anno = None
        self.dur_ns = time.perf_counter_ns() - self._t0
        _tls.ctx = self._prev
        _collector.add(self)
        return False

    def to_dict(self) -> Dict:
        """JSON-safe form (ids as hex strings: u64s do not survive
        JavaScript JSON consumers)."""
        return {
            "name": self.name,
            "service": self.service,
            "trace_id": f"{self.trace_id:016x}",
            "span_id": f"{self.span_id:016x}",
            "parent_id": f"{self.parent_id:016x}" if self.parent_id else None,
            "start_ns": self.start_ns,
            "dur_ns": self.dur_ns,
            "pid": self.pid,
            "tid": self.tid,
            "tags": self.tags,
            "profiled": self.profiled,
        }


def span(name: str, ctx=_UNSET, root: bool = False, service: Optional[str] = None,
         **tags):
    """Open a span as a context manager.

    - default: child of the thread's active span; with no active span,
      starts a NEW trace (a fresh root).
    - ``ctx=(trace_id, parent_span_id)``: child of a PROPAGATED context
      (an RPC envelope, a captured fan-out parent). ``ctx=None``
      (explicitly) suppresses the span entirely — fan-out helpers pass
      whatever :func:`current_context` returned, so untraced requests
      stay untraced instead of spawning orphan roots.
    - ``root=True``: force a fresh trace id even under an active span
      (step boundaries).

    Records when ``PERSIA_TRACING`` is on or a profiler session is live
    (:func:`profiler_live`).
    """
    profiled = profiler_live()
    if not (_enabled or profiled) or ctx is None:
        return _NULL_SPAN
    if root or ctx is _UNSET:
        cur = None if root else getattr(_tls, "ctx", None)
        if cur is None:
            trace_id, parent = _rand64(), 0
        else:
            trace_id, parent = cur
    else:
        trace_id, parent = ctx
    return Span(name, trace_id, _rand64(), parent, tags or None,
                service=service, profiled=profiled)


# --- collector + export ---------------------------------------------------


class TraceCollector:
    """Bounded ring of finished spans, process-wide. Old spans fall off
    the back; ``/trace?n=K`` and the bench read the recent window.

    Eviction is COUNTED, not silent: ``dropped_total`` (mirrored to the
    ``tracing_spans_dropped_total`` registry counter) tells a consumer
    whether the window it scraped is complete — a merge that quietly
    lost spans reads as a pipeline that skipped work."""

    def __init__(self, capacity: int = 8192):
        self._dq: "deque[Span]" = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._dropped = 0
        self._drop_counter = default_registry().counter(
            "tracing_spans_dropped_total",
            help_text="spans evicted from the bounded trace ring before "
                      "any consumer read them")

    def add(self, s: Span):
        with self._lock:
            if (self._dq.maxlen is not None
                    and len(self._dq) == self._dq.maxlen):
                self._dropped += 1
                self._drop_counter.inc()
            self._dq.append(s)

    @property
    def dropped_total(self) -> int:
        return self._dropped

    def recent(self, n: Optional[int] = None) -> List[Span]:
        with self._lock:
            spans = list(self._dq)
        if n is not None and n < len(spans):
            spans = spans[-n:]
        return spans

    def clear(self):
        with self._lock:
            self._dq.clear()

    def __len__(self) -> int:
        return len(self._dq)


_collector = TraceCollector()


def default_collector() -> TraceCollector:
    return _collector


def chrome_trace(spans=None) -> Dict:
    """Spans (Span objects or ``to_dict()`` dicts — the raw form the
    sidecar serves, so multi-process merges need no re-parsing) ->
    Chrome-trace/Perfetto JSON object. Complete ``ph: X`` duration
    events on one wall-clock timeline; process tracks are named by
    service via metadata events."""
    if spans is None:
        spans = _collector.recent()
    events = []
    named_pids = {}
    for s in spans:
        d = s.to_dict() if isinstance(s, Span) else s
        if d["pid"] not in named_pids:
            named_pids[d["pid"]] = d["service"]
            events.append({
                "ph": "M", "name": "process_name", "pid": d["pid"],
                "tid": 0, "args": {"name": d["service"]},
            })
        args = {"trace_id": d["trace_id"], "span_id": d["span_id"],
                "parent_id": d["parent_id"]}
        if d.get("tags"):
            args.update({str(k): v for k, v in d["tags"].items()})
        events.append({
            "name": d["name"],
            "cat": d["service"],
            "ph": "X",
            "ts": d["start_ns"] / 1e3,   # microseconds
            "dur": d["dur_ns"] / 1e3,
            "pid": d["pid"],
            "tid": d["tid"],
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# --- multi-process merge (library form of the bench's trace scrape) -------


def as_span_dicts(spans) -> List[Dict]:
    """Normalize a span source to ``to_dict()`` form: Span objects, raw
    dicts, or a ``/trace?format=raw`` response body (either the legacy
    bare list or the ``{"spans": [...], "dropped_total": N}`` object)."""
    if isinstance(spans, dict):
        spans = spans.get("spans", [])
    return [s.to_dict() if isinstance(s, Span) else s for s in spans]


def merge_span_dicts(groups, trace_id: Optional[str] = None) -> List[Dict]:
    """Merge span captures from several processes (each element of
    ``groups`` is one process's spans in any :func:`as_span_dicts`-
    accepted form) into one flat list, optionally filtered to a single
    ``trace_id`` (hex string)."""
    merged: List[Dict] = []
    for g in groups:
        merged.extend(as_span_dicts(g))
    if trace_id is not None:
        merged = [s for s in merged if s["trace_id"] == trace_id]
    return merged


def promote_remote_parents(spans: List[Dict]) -> List[Dict]:
    """Resolve cross-process parentage for a PARTIAL capture: a span
    whose parent was recorded in a process that is not part of the
    capture (a crashed peer, a scrape that raced the ring) is promoted
    to a root, keeping the original parent id as a ``remote_parent``
    tag. The result always validates orphan-free — the contract the
    postmortem bundle's trace relies on."""
    have = {s["span_id"] for s in spans}
    out = []
    for s in spans:
        if s.get("parent_id") and s["parent_id"] not in have:
            s = dict(s)
            tags = dict(s.get("tags") or {})
            tags["remote_parent"] = s["parent_id"]
            s["tags"] = tags
            s["parent_id"] = None
        out.append(s)
    return out


# --- device profiler hooks ------------------------------------------------


class StepProfiler:
    """Opt-in ``jax.profiler`` window keyed to trainer step indices.

    ``on_step(i)`` is called at each step BOUNDARY (before step ``i``
    runs): the device trace starts when ``i == start_step`` and stops
    after ``num_steps`` steps, so the captured TPU timeline aligns with
    the host spans of exactly that step window. ``close()`` stops an
    open capture (ctx exit / teardown). Environment wiring:
    ``PERSIA_PROFILE_DIR`` (enables), ``PERSIA_PROFILE_START_STEP``
    (default 10), ``PERSIA_PROFILE_NUM_STEPS`` (default 5) — see
    :func:`profiler_from_env`.

    ``scopes``, if given, is a callable that returns the profiled step's
    :func:`scope_table`. ``close()`` then reads the xplane it just wrote
    and leaves ``device_scopes.json`` beside it: what
    :func:`device_time_by_scope` makes of the first device's events,
    with the ten longest scopes logged in ms a step. A failure there is
    a warning, as the profiler's own are."""

    def __init__(self, logdir: str, start_step: int = 10,
                 num_steps: int = 5, scopes=None):
        self.logdir = logdir
        self.start_step = int(start_step)
        self.num_steps = max(1, int(num_steps))
        self.scopes = scopes
        self.active = False
        self._done = False

    def on_step(self, step_idx: int):
        if self._done:
            return
        if not self.active and step_idx >= self.start_step:
            try:
                import jax

                jax.profiler.start_trace(self.logdir)
                self.active = True
                self._stop_at = step_idx + self.num_steps
                _logger.info("device profiler started at step %d -> %s",
                             step_idx, self.logdir)
            except Exception as e:  # profiling must never kill training
                _logger.warning("jax.profiler start failed: %s", e)
                self._done = True
        elif self.active and step_idx >= self._stop_at:
            self.close()

    def close(self):
        if not self.active:
            return
        self.active = False
        self._done = True
        try:
            import jax

            jax.profiler.stop_trace()
            _logger.info("device profiler stopped -> %s", self.logdir)
        except Exception as e:
            _logger.warning("jax.profiler stop failed: %s", e)
            return
        if self.scopes is not None:
            try:
                self._report_scopes()
            except Exception as e:
                _logger.warning("no device time by scope from %s: %s",
                                self.logdir, e)

    def _report_scopes(self):
        xplane = max((os.path.join(base, f)
                      for base, _, files in os.walk(self.logdir)
                      for f in files if f.endswith(".xplane.pb")),
                     key=os.path.getmtime)
        ops, modules = load_device_events(xplane)
        report = device_time_by_scope(ops, modules, self.scopes())
        out = os.path.join(os.path.dirname(xplane), "device_scopes.json")
        with open(out, "w") as f:
            json.dump(report, f)
        steps = report["steps"] or 1.0
        _logger.info(
            "device time by scope over %.1f steps (ms a step) -> %s: %s",
            report["steps"], out, ", ".join(
                f"{path} {1e3 * (fwd + bwd) / steps:.2f}"
                for path, fwd, bwd in report["scopes"][:10]))


def profiler_from_env() -> Optional[StepProfiler]:
    """Build a StepProfiler from PERSIA_PROFILE_* env vars, or None."""
    logdir = knobs.get("PERSIA_PROFILE_DIR")
    if not logdir:
        return None
    return StepProfiler(
        logdir,
        start_step=knobs.get("PERSIA_PROFILE_START_STEP"),
        num_steps=knobs.get("PERSIA_PROFILE_NUM_STEPS"),
    )


# --- the device's account: compilations, scopes, device time --------------


class CompileWatch:
    """The process's backend compilations as JAX's own monitoring events
    report them (``/jax/core/compile/backend_compile_duration``, which a
    persistent-cache load fires too, and
    ``/jax/compilation_cache/cache_hits``). ``compiles``, ``seconds``
    and ``cache_hits`` are plain attributes, so that a caller can compare
    a count before and after a call for the price of two reads; the
    registry counters ``jax_backend_compiles_total``,
    ``jax_backend_compile_seconds_total`` and
    ``jax_compile_cache_hits_total`` carry the same to ``/metrics``.
    Process-wide: a compilation on another thread counts as well."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        reg = default_registry()
        self._compiles = reg.counter(
            "jax_backend_compiles_total",
            help_text="backend compilations in this process, persistent-"
                      "cache loads included")
        self._seconds = reg.counter(
            "jax_backend_compile_seconds_total",
            help_text="seconds inside those backend compilations")
        self._hits = reg.counter(
            "jax_compile_cache_hits_total",
            help_text="programs found in JAX's persistent compile cache")
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration
            self._compiles.inc()
            self._seconds.inc(duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
            self._hits.inc()


_watch: Optional[CompileWatch] = None
_watch_lock = threading.Lock()


def compile_watch() -> CompileWatch:
    """The process's one :class:`CompileWatch`, registered at the first
    call: whoever builds a jitted step asks for it
    (``make_device_mode_trainer``). A process that builds none (a PS, a
    worker) never calls this, imports no JAX and registers nothing."""
    global _watch
    with _watch_lock:
        if _watch is None:
            _watch = CompileWatch()
        return _watch


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
# transformations that JAX writes around a scope's name, and what it
# calls the pieces of its own control flow: neither is a named_scope
_TRANSFORMS = frozenset(("jvp", "transpose", "vmap"))
_STRUCTURAL = frozenset((
    "while", "body", "cond", "closed_call", "checkpoint", "remat",
    "rematted_computation", "custom_jvp_call", "custom_vjp_call"))


def _scope_path(op_name: str) -> Tuple[str, bool]:
    backward = "transpose(" in op_name
    names: List[str] = []
    # a fused instruction may list several paths: the first; and the
    # last component is the primitive (or a jitted callee's name)
    for part in op_name.split(";")[0].split("/")[:-1]:
        wrapped = False
        while True:
            m = _WRAPPED.match(part)
            if m is None or m.group(1) not in _TRANSFORMS:
                break
            part, wrapped = m.group(2), True
        # what is left in brackets is a call (``jit(step)``, ``jit(_take)``)
        if not part or "(" in part or part in _STRUCTURAL:
            continue
        if wrapped and names and part == names[0]:
            # the root again under a transformation: ``jax.checkpoint``'s
            # body brings the whole stack it was traced under
            names = []
        names.append(part)
    return "/".join(names), backward


def scope_table(hlo_text: str) -> Dict[str, Tuple[str, bool]]:
    """``{instruction name: (scope path, backward)}`` over a compiled
    module's text (``compiled.as_text()``), every computation of it:
    instruction names are unique in a module, and a device event's name
    begins with its instruction's.

    The path is the instruction's ``op_name`` with JAX's own wrapping
    taken off (``jit(...)`` calls, ``jvp(...)``/``transpose(...)``
    around a name, ``checkpoint``, the ``while``/``body``/``cond`` of a
    loop) and the trailing primitive dropped, so that what is left is
    the program's ``jax.named_scope`` names and its modules' names,
    outermost first: ``tower/layer_3/experts/experts_grouped``.
    ``backward`` says whether ``transpose(`` stood in the path, which it
    does for the forward pass that ``jax.checkpoint`` runs again inside
    the backward one too. An instruction without metadata (a layout copy
    the compiler made) maps to ``("", False)``."""
    table = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        table[m.group(1)] = _scope_path(op.group(1)) if op else ("", False)
    return table


def _instruction_of(event_name: str) -> str:
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name.strip().lstrip("%")


def _self_times(ops) -> List[float]:
    """Each event's self time in ns, in the order given: its duration
    less the time in which an event that started later runs on the same
    line. For events that nest, as a ``while`` and the operations of its
    body do, that is the duration less the union of what lies inside, so
    the wrapper keeps its own overhead and the body counts once; at
    every moment exactly one running event is charged, so the self times
    sum to the union of the busy intervals."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [0.0] * len(ops)
    stack: List[Tuple[int, float]] = []   # (index, end), by start

    def charge_until(t, cursor):
        while stack:
            i, end = stack[-1]
            if end <= cursor:
                stack.pop()
                continue
            if cursor >= t:
                break
            upto = min(end, t)
            own[i] += upto - cursor
            cursor = upto
        return max(cursor, t)

    cursor = float("-inf")
    for i in order:
        _, start, dur = ops[i]
        cursor = charge_until(start, cursor)
        stack.append((i, start + dur))
    charge_until(float("inf"), cursor)
    return own


def device_time_by_scope(ops, modules, table, depth=None) -> Dict:
    """Device time by the program's scopes. ``ops`` and ``modules`` are
    one device's events as plain lists ``[[name, start_ns, dur_ns]]``
    (its ``XLA Ops`` and ``XLA Modules`` lines: :func:`load_device_events`),
    ``table`` a :func:`scope_table`. Each event's self time goes to the
    scope of the instruction its name begins with, cut to the path's
    innermost ``depth`` names (default 1; 0 keeps the whole path). The
    table is that of the module that takes most of the time: an event
    that starts outside its runs belongs to another program (instruction
    names repeat from program to program) and is counted under that
    program's name among the unmatched.

    Returns, in seconds: ``steps`` (runs of that module, a cut one
    counted as the fraction it is), ``total_s`` (the sum of all self
    times, which is the union of the device's busy intervals),
    ``unscoped_s`` and ``unscoped`` ``[[instruction without its number,
    s]]`` (instructions without a scope, by the compiler's name for
    them), ``unmatched_s`` and
    ``unmatched`` ``[[instruction or program, s]]`` (events the table
    lacks: counted, never dropped), and ``scopes`` ``[[path, forward_s,
    backward_s]]``, longest first; the scopes, ``unscoped_s`` and
    ``unmatched_s`` add up to ``total_s``."""
    depth = 1 if depth is None else depth
    runs: Dict[str, List[float]] = {}
    for name, _, dur in modules:
        runs.setdefault(name, []).append(dur)
    main = max(runs, key=lambda name: sum(runs[name])) if runs else None
    steps = (sum(runs[main]) / statistics.median(runs[main])
             if main is not None else 0.0)
    others = sorted((start, start + dur, name)
                    for name, start, dur in modules if name != main)
    begins = [o[0] for o in others]

    def other_program(start):
        i = bisect.bisect_right(begins, start) - 1
        return others[i][2] if i >= 0 and start < others[i][1] else None

    by_scope: Dict[str, List[float]] = {}
    unmatched: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}
    total = 0.0
    for (name, start, _), own in zip(ops, _self_times(ops)):
        total += own
        instruction = other_program(start) or _instruction_of(name)
        found = table.get(instruction)
        if found is None:
            unmatched[instruction] = unmatched.get(instruction, 0.0) + own
            continue
        path, backward = found
        if not path:
            family = instruction.split(".")[0]
            unscoped[family] = unscoped.get(family, 0.0) + own
            continue
        if depth:
            path = "/".join(path.split("/")[-depth:])
        by_scope.setdefault(path, [0.0, 0.0])[backward] += own
    return {
        "steps": steps,
        "total_s": total / 1e9,
        "unscoped_s": sum(unscoped.values()) / 1e9,
        "unscoped": sorted(([k, v / 1e9] for k, v in unscoped.items()),
                           key=lambda kv: -kv[1]),
        "unmatched_s": sum(unmatched.values()) / 1e9,
        "unmatched": sorted(([k, v / 1e9] for k, v in unmatched.items()),
                            key=lambda kv: -kv[1]),
        "scopes": sorted(([k, f / 1e9, b / 1e9]
                          for k, (f, b) in by_scope.items()),
                         key=lambda x: -(x[1] + x[2])),
    }


def load_device_events(xplane_path: str):
    """``(ops, modules)``: the ``XLA Ops`` and ``XLA Modules`` lines of
    a profiler trace's first ``/device:TPU:`` plane, as
    ``[[name, start_ns, dur_ns]]``. Raises ``LookupError`` where the
    trace has no such plane (a session on the CPU)."""
    from jax.profiler import ProfileData

    for p in ProfileData.from_file(xplane_path).planes:
        if p.name.startswith("/device:TPU:"):
            lines = {line.name: [[e.name, float(e.start_ns),
                                  float(e.duration_ns)] for e in line.events]
                     for line in p.lines
                     if line.name in ("XLA Ops", "XLA Modules")}
            return lines.get("XLA Ops", []), lines.get("XLA Modules", [])
    raise LookupError(f"no /device:TPU: plane in {xplane_path}")


# --- stall/deadlock detection (pre-existing surface) ----------------------

_beat = 0
_inflight = 0
_lock = threading.Lock()


def heartbeat():
    global _beat
    _beat += 1  # benign race: any change counts as progress


def work_started():
    global _inflight
    with _lock:
        _inflight += 1


def work_finished():
    global _inflight
    with _lock:
        _inflight -= 1


def dump_all_stacks(out=sys.stderr):
    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    print("==== persia_tpu thread dump ====", file=out)
    for tid, frame in frames.items():
        print(f"--- thread {names.get(tid, tid)} ---", file=out)
        traceback.print_stack(frame, file=out)
    out.flush()


def start_deadlock_detection(interval_sec: float = 30.0) -> Optional[threading.Thread]:
    """Start the stall watchdog (no-op unless PERSIA_DEADLOCK_DETECTION=1,
    matching the reference's env gate)."""
    if not knobs.get("PERSIA_DEADLOCK_DETECTION"):
        return None

    def run():
        last = _beat
        while True:
            time.sleep(interval_sec)
            if _inflight > 0 and _beat == last:
                _logger.error(
                    "no pipeline progress for %.0fs with %d items in "
                    "flight — dumping stacks", interval_sec, _inflight)
                dump_all_stacks()
            last = _beat

    t = threading.Thread(target=run, daemon=True, name="deadlock-watchdog")
    t.start()
    return t


class StageTimer:
    """Histogram-backed context timer for pipeline stages.

    Metric names follow the reference's gauge names
    (lookup_preprocess_time_cost_sec, lookup_rpc_time_cost_sec,
    lookup_postprocess_time_cost_sec, forward_client_time_cost_sec,
    backward_client_time_cost_sec, ...; the serving tier adds
    inference_request_time_cost_sec, inference_queue_wait_time_cost_sec,
    inference_lookup_time_cost_sec, inference_forward_time_cost_sec —
    see serving.py).
    """

    def __init__(self, name: str):
        self.hist = default_registry().histogram(name)

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.hist.observe(time.perf_counter() - self._t0)
        return False
