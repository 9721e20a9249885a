"""Seconds of set-up spent compiling programs or loading them from the
persistent compilation cache (JAX's ``backend_compile_duration`` events)."""


def read(run):
    return run.compile_s
