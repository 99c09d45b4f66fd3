"""The program's own gauges (``persia_tpu.metrics.default_registry()``)
as a layer metric reads them: what the registry would render, so nothing
is registered by asking. A program that has no such gauge (one from
before it kept it), or never set it, gives no reading."""


def value(name):
    """The unlabelled gauge ``name``; None where the registry lacks it or
    it still holds the 0 it was made with (the gauges read here are
    durations, never 0 once set)."""
    from persia_tpu import metrics

    samples, _ = metrics.parse_exposition(
        metrics.default_registry().render())
    for sample, labels, v in samples:
        if sample == name and not labels:
            return v if v > 0 else None
    return None
