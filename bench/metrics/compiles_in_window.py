"""Programs compiled or loaded from the compilation cache inside the
measured window (``jax.monitoring`` compile events).  Every shape the
window reaches should have been warmed in set-up, so this reads 0."""


def read(run):
    return run.compiles_in_window
