"""Seconds ``make_device_mode_trainer`` took up to its build span:
``model.init``, the parameters' placement and ``optimizer.init`` (the
program's gauge ``device_mode_init_seconds``). The program's part of the
benchmark's mark ``program's trainer built``, inside ``setup_s``."""

import program_gauges


def read(r):
    return program_gauges.value("device_mode_init_seconds")
