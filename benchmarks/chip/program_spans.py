"""The program's own spans (``persia_tpu.tracing``) as a layer metric
reads them: those recorded while the profiler's session was live, which
the program marks ``profiled``, so the traced seconds' spans and no
others. A program that records no such span (one from before its spans
rode the profiler) gives no reading."""


def mean_ms(name):
    """Mean duration in ms of the traced seconds' spans called ``name``;
    None where there is none, or where the ring dropped a span (an
    incomplete window is no reading)."""
    from persia_tpu import tracing

    ring = tracing.default_collector()
    if ring.dropped_total:
        return None
    durations = [s.dur_ns for s in ring.recent()
                 if s.name == name and getattr(s, "profiled", False)]
    if not durations:
        return None
    return 1e-6 * sum(durations) / len(durations)
