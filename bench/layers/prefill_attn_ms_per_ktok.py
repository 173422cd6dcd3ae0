"""step programs: device ms in the chunked-prefill program's ops under the
``attention`` scope per 1000 prompt tokens it ran in the window
(``bench/scopes.py``)."""
from bench import scopes

PROGRAM = "jit_chunk_fn"


def read(r):
    s = scopes.program_split(r, PROGRAM)
    tok = r.win.counters["prefill_tokens"]
    return 1e3 * s.of("attention") / (tok / 1e3) if s and tok else None
