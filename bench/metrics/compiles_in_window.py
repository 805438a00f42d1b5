"""XLA backend compiles inside the window (``jax.monitoring`` events);
every shape the window uses should have compiled in set-up."""


def read(rec):
    return float(rec.compiles_in_window)
