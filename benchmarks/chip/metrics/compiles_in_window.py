"""Programs compiled or fetched from the compile cache inside the window
(``jax.monitoring``'s backend-compile events); every shape should have
been warmed up, so this reads 0."""


def read(run):
    return float(run.compiles_in_window)
