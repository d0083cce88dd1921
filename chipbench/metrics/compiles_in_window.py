"""Programs lowered for compilation inside the window (JAX's own compile
events, through ``jax.monitoring``); should be 0."""


def read(run):
    return run.counters.get("compiles_in_window")
