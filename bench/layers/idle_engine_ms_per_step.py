"""engine: device-idle ms per engine step whose gap midpoint lies in an
``engine.*`` host span (the engine's phases on the profiler's clock), from
the trace (``bench/scopes.py``)."""
from bench import scopes


def read(r):
    s = scopes.idle_split(r)
    return 1e3 * s.of("engine.") / s.steps if s else None
