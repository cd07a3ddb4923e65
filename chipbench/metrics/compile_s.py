"""Seconds of set-up spent compiling or loading programs from the persistent
cache (``jax.monitoring``: backend compile and cache retrieval events)."""


def read(name, run):
    return run.compile_s
