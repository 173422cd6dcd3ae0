"""scheduler: 90th percentile of arrival -> admission over the requests due
in the window, from the engine Tracer's ``queued`` spans (written when a
request completes, so a request still running at the end is missing)."""
import numpy as np


def read(r):
    spans = [s.dur for s in r.spans
             if s.name == "queued" and s.track == "requests"
             and r.win.start <= s.start < r.win.end]
    return 1e3 * float(np.percentile(spans, 90)) if spans else None
