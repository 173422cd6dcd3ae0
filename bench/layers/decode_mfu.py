"""step programs: the least time the chip needs for the window's decode
steps (larger of operations over peak and bytes over bandwidth, per step)
over the decode program's device time.  The operations and bytes of a step
come from the counts module the configuration names (``r.work``, its
``work`` key); the peaks and the least time are the device's
(``bench/work.py``)."""
import sys
from collections import Counter

from bench import trace, work

PROGRAM = "jit_decode_paged"


def read(r):
    dev = trace.module_seconds(r.trace, r.window).get(PROGRAM)
    steps = [ctx for t, ctx in r.drv.decode_ctx
             if r.win.start < t <= r.win.end and ctx]
    if not dev or not steps:
        return None
    least = [work.least_seconds(*r.work.decode_step(r.config, ctx), r.peaks)
             for ctx in steps]
    bound = Counter(b for _, b in least).most_common(1)[0][0]
    print(f"[decode_mfu] {len(steps)} decode steps, {bound} bound on "
          f"most; least {sum(s for s, _ in least):.6f}s of {dev:.6f}s "
          f"device time", file=sys.stderr)
    return 100.0 * sum(s for s, _ in least) / dev
