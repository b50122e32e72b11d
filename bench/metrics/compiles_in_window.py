"""Programs JAX compiled or loaded from its cache inside the window (its
backend-compile and cache-retrieval events); set-up should leave none."""


def read(run):
    return run.compiles_in_window
