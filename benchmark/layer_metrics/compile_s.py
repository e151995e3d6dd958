"""Seconds XLA spent compiling, or fetching from the persistent compile
cache, in the whole run (`jax.monitoring` backend-compile events)."""


def read(result, cell):
    return result["compile_s"]
