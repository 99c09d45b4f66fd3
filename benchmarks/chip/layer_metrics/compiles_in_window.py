"""Backend compilations (JAX's ``backend_compile_duration`` events,
persistent-cache loads included) between the window's start and end.
0 is expected: anything else is a shape that set-up did not warm."""


def read(r):
    return r.compiles_in_window
