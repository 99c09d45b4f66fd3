"""Item events the placement counted per step of the window: the
runner's ``counters()`` delta over the steps."""


def read(r):
    if not r.steps or "events_total" not in r.counters:
        return None
    return r.counters["events_total"] / r.steps
