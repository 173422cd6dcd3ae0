"""step programs: device ms per decode step in the decode program's ops
under the ``matmul`` scope (its nested ``dequant`` included): every served
projection and the head's logits dot (``bench/scopes.py``)."""
from bench import scopes

PROGRAM = "jit_decode_paged"


def read(r):
    s = scopes.program_split(r, PROGRAM)
    n = r.win.decode_steps
    return 1e3 * s.of(*scopes.MATMUL) / n if s and n else None
