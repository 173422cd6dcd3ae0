"""step programs: device ms per decode step in the decode program's ops
under the ``attention`` scope (the paged gather included), from the trace
and the engine's scope map (``bench/scopes.py``)."""
from bench import scopes

PROGRAM = "jit_decode_paged"


def read(r):
    s = scopes.program_split(r, PROGRAM)
    n = r.win.decode_steps
    return 1e3 * s.of("attention") / n if s and n else None
