"""Seconds JAX spent producing programs during set-up, compiling them or
loading them from the persistent cache (`jax.monitoring`'s backend-compile
durations, summed).  Cold it is the compile wall; warm it is what the
cache still costs."""


def read(run):
    compiles = run.facts.get("setup_compiles")
    return None if compiles is None else compiles.seconds
