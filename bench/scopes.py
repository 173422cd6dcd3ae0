"""Device time of the step programs split by the engine's named scopes, and
the device's idle time split by the host span it fell in.

The step programs open a fixed vocabulary of ``jax.named_scope``s
(``serve.engine.OP_SCOPES``); ``Engine.op_scopes()`` maps every HLO
instruction name of each program it dispatched to its innermost scope ("" for
none), read from the compiled module text.  Here each ``XLA Ops`` event of the
trace is assigned to the ``XLA Modules`` event it lies in and its name looked
up in that program's map.  The engine's host spans are on the trace's clock
as ``engine.<span>`` annotations when its ``profile_annotations`` is on (the
``--trace 1`` run turns it on); the harness's own are ``bench.<span>``.

An engine without ``op_scopes``, or a trace without ``engine.*`` spans, gives
nothing: the readers then return None.  Each split is computed once per run,
kept on the run's namespace, and printed to stderr in full.
"""

from __future__ import annotations

import bisect
import dataclasses
import sys
import time
from collections import defaultdict
from typing import Dict, Optional

from bench import trace

MATMUL = ("matmul", "dequant")      # dequant opens only under matmul
UNSCOPED = ""
AMBIGUOUS = "?"                     # serve.engine.AMBIGUOUS
NO_NAME = "(no name)"               # an op name the program's map lacks
NO_SPAN = "no span"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _memo(r, key, compute):
    memo = vars(r).setdefault("_scopes_memo", {})
    if key not in memo:
        memo[key] = compute()
    return memo[key]


def op_scopes(r) -> Optional[Dict[str, Dict[str, str]]]:
    """The engine's ``op_scopes()``, timed; None where it has none."""
    def compute():
        read = getattr(r.drv.eng, "op_scopes", None)
        if read is None:
            return None
        t0 = time.perf_counter()
        scopes = read()
        log(f"[scopes] op_scopes: {len(scopes)} programs, "
            f"{sum(len(m) for m in scopes.values())} instruction names, in "
            f"{time.perf_counter() - t0:.3f}s")
        return scopes
    return _memo(r, "op_scopes", compute)


@dataclasses.dataclass
class Split:
    """One program's ops in the window, by scope."""
    program: str
    runs: int
    module_s: float                 # device time of its module events
    covered_s: float                # of which some op of it ran
    seconds: Dict[str, float]       # op device seconds by scope

    @property
    def op_s(self) -> float:
        return sum(self.seconds.values())

    def of(self, *scopes: str) -> float:
        return sum(self.seconds.get(s, 0.0) for s in scopes)

    @property
    def mapped_s(self) -> float:
        return self.op_s - self.of(AMBIGUOUS, NO_NAME)


def program_split(r, program: str) -> Optional[Split]:
    """``program``'s op seconds in the window by scope (``Split``); None
    where the engine has no scope map for it or it did not run."""
    def compute():
        scopes = op_scopes(r)
        if not scopes or program not in scopes:
            return None
        split = split_ops(r.trace, r.window, program, scopes[program])
        if split.runs:
            print_split(split)
        return split if split.runs else None
    return _memo(r, ("split", program), compute)


def split_ops(tr: trace.DeviceTrace, window, program: str,
              scopes: Dict[str, str]) -> Split:
    mods = [(a, b) for a, b, n in trace.clip(tr.modules, window)
            if n == program]
    starts = [a for a, _ in mods]
    seconds: Dict[str, float] = defaultdict(float)
    covered, reach = 0.0, float("-inf")     # ops come sorted by start
    for a, b, name in trace.clip(tr.ops, window):
        if trace.op_family(name) in trace.CONTAINERS:
            continue
        i = bisect.bisect_right(starts, a) - 1
        if i < 0 or a >= mods[i][1]:
            continue
        seconds[scopes.get(name, NO_NAME)] += b - a
        end = min(b, mods[i][1])
        covered += max(0.0, end - max(a, reach))
        reach = max(reach, end)
    return Split(program, len(mods), sum(b - a for a, b in mods), covered,
                 dict(seconds))


def print_split(s: Split) -> None:
    op_s = s.op_s or float("nan")
    log(f"[scopes] {s.program}: {s.runs} runs, {s.module_s:.6f}s device "
        f"time in the window; its ops cover "
        f"{100 * s.covered_s / s.module_s:.2f}% of it; "
        f"{100 * s.mapped_s / op_s:.2f}% of op time maps to a name "
        f"(ambiguous {100 * s.of(AMBIGUOUS) / op_s:.2f}%, no name "
        f"{100 * s.of(NO_NAME) / op_s:.2f}%)")
    for scope, sec in sorted(s.seconds.items(), key=lambda kv: -kv[1]):
        log(f"[scopes] {s.program} {scope or '(unscoped)'}: {sec:.6f}s, "
            f"{1e3 * sec / s.runs:.4f} ms per run, "
            f"{100 * sec / op_s:.2f}% of op time")
    log(f"[scopes] {s.program} sum over scopes {s.op_s:.6f}s = "
        f"{1e3 * s.op_s / s.runs:.4f} ms per run")


@dataclasses.dataclass
class IdleSplit:
    idle_s: float                   # window minus busy
    seconds: Dict[str, float]       # by innermost span, NO_SPAN for none
    steps: int                      # engine.step spans in the window

    def of(self, prefix: str) -> float:
        return sum(v for k, v in self.seconds.items()
                   if k.startswith(prefix))


def idle_split(r) -> Optional[IdleSplit]:
    """Every idle second of the window, charged to the innermost
    ``engine.*`` host span holding the gap's midpoint, else the innermost
    ``bench.*`` span (the window's own aside), else ``NO_SPAN``.  Python
    tracer frames and other host events are looked through.  None where
    the trace holds no ``engine.step`` span."""
    def compute():
        split = split_idle(r.trace, r.window)
        if split.steps:
            print_idle(split, r.window_s)
        return split if split.steps else None
    return _memo(r, "idle", compute)


def split_idle(tr: trace.DeviceTrace, window) -> IdleSplit:
    lo, hi = window
    spans = sorted((a, b, n) for a, b, n in tr.host
                   if n.startswith("engine.")
                   or (n.startswith("bench.") and n != "bench.window"))
    steps = sum(1 for a, _, n in spans
                if n == "engine.step" and lo <= a < hi)
    busy = trace.union((a, b) for a, b, _ in trace.clip(tr.ops, window))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    seconds: Dict[str, float] = defaultdict(float)
    active: list = []
    i = 0
    for a, b in zip(edges[0::2], edges[1::2]):     # gaps in time order
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        while i < len(spans) and spans[i][0] <= mid:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] > mid]
        eng = [(sb - sa, n) for sa, sb, n in active
               if n.startswith("engine.")]
        har = [(sb - sa, n) for sa, sb, n in active
               if n.startswith("bench.")]
        seconds[min(eng)[1] if eng else min(har)[1] if har
                else NO_SPAN] += b - a
    idle = (hi - lo) - sum(b - a for a, b in busy)
    return IdleSplit(idle, dict(seconds), steps)


def print_idle(s: IdleSplit, window_s: float) -> None:
    total = sum(s.seconds.values())
    log(f"[idle] {s.idle_s:.6f}s idle of the {window_s:.6f}s window, "
        f"{s.steps} engine steps: engine.* {s.of('engine.'):.6f}s, "
        f"bench.* {s.of('bench.'):.6f}s, {NO_SPAN} "
        f"{s.seconds.get(NO_SPAN, 0.0):.6f}s; sum {total:.6f}s")
    for name, sec in sorted(s.seconds.items(), key=lambda kv: -kv[1]):
        log(f"[idle] {name}: {sec:.6f}s, {1e3 * sec / s.steps:.4f} ms per "
            f"engine step, {100 * sec / (s.idle_s or float('nan')):.2f}% of "
            f"idle")
