"""Seconds of XLA backend compilation during set-up, from JAX's own
``backend_compile_duration`` events (programs served from the persistent
cache do not compile)."""


def read(run):
    return run.compile_s["setup"]
