"""Device time per step: the union of the device-operation intervals in
the traced window over the steps run in it (runs of the module that takes
most of the device time)."""


def read(r):
    if r.trace is None or not r.trace["steps"]:
        return None
    return 1e3 * r.trace["busy_s"] / r.trace["steps"]
