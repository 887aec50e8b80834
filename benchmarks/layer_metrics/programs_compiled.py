"""Programs JAX produced during set-up, compiled or loaded from the cache
(`jax.monitoring`, one backend-compile event each): the size of the
program zoo a run has to bring up."""


def read(run):
    compiles = run.facts.get("setup_compiles")
    return None if compiles is None else compiles.programs
