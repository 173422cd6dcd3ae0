"""Check a served run's exports (``launch/serve.py --metrics-out`` and
``--trace-out``): metrics that must not read zero in the snapshot, and
host spans that must appear in the profiler trace.

    PYTHONPATH=src python -m repro.analysis.tracecheck \\
        --metrics m.json --require-nonzero decode_steps,ttft_s \\
        --trace-dir t --require-span engine.step

Exit 0 when every check holds; otherwise one line per failure and exit 1.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys
from typing import Dict, List, Sequence


def zero_metrics(snapshot: Dict, names: Sequence[str]) -> List[str]:
    """Failures among ``names``: missing from the snapshot, or reading
    zero (a counter's or gauge's value, a histogram's count)."""
    problems = []
    for name in names:
        for kind in ("counters", "gauges", "histograms"):
            if name in snapshot.get(kind, {}):
                v = snapshot[kind][name]
                if (v["count"] if kind == "histograms" else v) == 0:
                    problems.append(f"metric {name} reads zero")
                break
        else:
            problems.append(f"metric {name} is not in the snapshot")
    return problems


def host_span_counts(trace_dir: str) -> collections.Counter:
    """Events per name on the host threads of every ``.xplane.pb`` under
    ``trace_dir`` (the annotations a traced run opened)."""
    from jax.profiler import ProfileData
    counts: collections.Counter = collections.Counter()
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    counts.update(e.name for e in line.events)
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.analysis.tracecheck",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--metrics", help="metrics snapshot JSON")
    ap.add_argument("--require-nonzero", default="",
                    help="comma-separated metrics that must not read zero")
    ap.add_argument("--trace-dir", help="profiler trace directory")
    ap.add_argument("--require-span", default="",
                    help="comma-separated host spans the trace must hold")
    args = ap.parse_args(argv)
    problems: List[str] = []
    nonzero = [n for n in args.require_nonzero.split(",") if n]
    if nonzero:
        with open(args.metrics) as fh:
            problems += zero_metrics(json.load(fh), nonzero)
    spans = [n for n in args.require_span.split(",") if n]
    if spans:
        counts = host_span_counts(args.trace_dir)
        problems += [f"trace holds no {n} event" for n in spans
                     if not counts[n]]
    for p in problems:
        print(f"tracecheck: {p}", file=sys.stderr)
    if not problems:
        print("tracecheck: OK")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
