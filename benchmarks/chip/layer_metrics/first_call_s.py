"""Seconds the device-mode step's first call took on the host: trace,
lower, compile or cache load, dispatch (the program's gauge
``device_mode_first_call_seconds``). The step's part of the benchmark's
mark ``first step``, inside ``setup_s``."""

import program_gauges


def read(r):
    return program_gauges.value("device_mode_first_call_seconds")
