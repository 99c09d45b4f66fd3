"""Device buffers a step's call handles: the mean of the tags ``args`` +
``results`` (leaves of the jitted step's arguments and of its result)
over the traced seconds' ``trainer/dispatch`` spans. The TPU runtime's
host side charges by the buffer (PERF.md section 5), so this is what
``dispatch_ms`` should follow. None where no span carries the tags, or
where the ring dropped a span (an incomplete window is no reading)."""


def read(r):
    from persia_tpu import tracing

    ring = tracing.default_collector()
    if ring.dropped_total:
        return None
    counts = [s.tags["args"] + s.tags["results"] for s in ring.recent()
              if s.name == "trainer/dispatch"
              and getattr(s, "profiled", False)
              and s.tags and "args" in s.tags and "results" in s.tags]
    if not counts:
        return None
    return sum(counts) / len(counts)
