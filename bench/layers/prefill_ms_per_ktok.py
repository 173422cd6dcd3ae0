"""step programs: device time of the chunked-prefill programs per 1000
prompt tokens they ran in the window."""
from bench import trace

PROGRAM = "jit_chunk_fn"


def read(r):
    s = trace.module_seconds(r.trace, r.window).get(PROGRAM)
    tok = r.win.counters["prefill_tokens"]
    return 1e3 * s / (tok / 1e3) if s and tok else None
