"""Backend-compile seconds inside the device-mode step's first call, a
persistent-cache load included (the program's gauge
``device_mode_first_call_compile_seconds``): the part of
``first_call_s`` that a warm compile cache shortens."""

import program_gauges


def read(r):
    return program_gauges.value("device_mode_first_call_compile_seconds")
