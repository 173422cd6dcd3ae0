"""step programs: device ms per decode step in the decode program's ops
under no scope of the vocabulary: the layer scan's slicing and stacking of
the weights and the pool, and copies the compiler inserted
(``bench/scopes.py``)."""
from bench import scopes

PROGRAM = "jit_decode_paged"


def read(r):
    s = scopes.program_split(r, PROGRAM)
    n = r.win.decode_steps
    return 1e3 * s.of(scopes.UNSCOPED) / n if s and n else None
