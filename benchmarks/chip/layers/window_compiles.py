"""Programs compiled, or loaded from the persistent cache, inside the
window (JAX's monitoring events)."""


def read(run):
    return float(run.window.compiles)
