"""Executables built or loaded inside the window (JAX's
``backend_compile_duration`` events): must be 0, or the run is not
correct."""


def read(ev, params):
    return ev["compiles_in_window"]
