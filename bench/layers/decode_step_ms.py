"""step programs: device time of the decode program per decode step."""
from bench import trace

PROGRAM = "jit_decode_paged"


def read(r):
    s = trace.module_seconds(r.trace, r.window).get(PROGRAM)
    n = r.win.decode_steps
    return 1e3 * s / n if s and n else None
