"""engine: programs compiled or loaded from the compile cache inside the
window (``jax.monitoring`` events on the host clock)."""


def read(r):
    return float(r.win.compiles)
