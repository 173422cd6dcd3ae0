"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

What a TPU trace holds (read by hand from a v5e trace, kept as the small
``tests/data/small_v5e.xplane.pb``):

  plane ``/device:TPU:<i>``  line ``XLA Modules``: one event per program run,
                             named ``jit_<function>(<fingerprint>)``;
                             line ``XLA Ops``: one event per operation, named
                             by its HLO text (``%<op name> = <shape> ...``);
                             a Pallas kernel is a custom call named after its
                             wrapper, e.g. ``%paged_quant_attention.1 = ...``
  plane ``/host:CPU``        one line per host thread; the Python thread
                             holds the ``TraceAnnotation`` spans

Device and host timestamps share the trace's base but not exactly: they were
seen about a millisecond apart.  The window is the host annotation that the
harness opens around it; device intervals are clipped to it.
"""

from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]          # seconds on the trace's clock

_OP_NAME = re.compile(r"%?([^\s=]+)")
_MODULE_NAME = re.compile(r"(.*?)(\(\d+\))?$")
CONTAINERS = ("while", "conditional", "call")


def xplane_path(trace_dir: str) -> str:
    paths = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = _OP_NAME.match(text.strip())
    return m.group(1) if m else text


def module_name(text: str) -> str:
    """``jit_decode_paged(1752...)`` -> ``jit_decode_paged``."""
    return _MODULE_NAME.match(text).group(1)


def op_family(name: str) -> str:
    """An op's name without its instance number (``fusion.12`` ->
    ``fusion``)."""
    return re.sub(r"\.\d+$", "", name)


@dataclasses.dataclass
class DeviceTrace:
    """Events of one device and the host thread's spans, in seconds."""
    ops: List[Tuple[float, float, str]]          # (start, end, op name)
    modules: List[Tuple[float, float, str]]      # (start, end, module name)
    host: List[Tuple[float, float, str]]         # (start, end, span name)

    def window(self, name: str = "bench.window") -> Optional[Interval]:
        spans = [(a, b) for a, b, n in self.host if n == name]
        return (spans[0][0], spans[-1][1]) if spans else None


def load(path: str, device: int = 0) -> DeviceTrace:
    """Read the events of ``/device:TPU:<device>`` and the host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in data.planes:
        if plane.name == f"/device:TPU:{device}":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.start_ns * 1e-9, e.end_ns * 1e-9,
                                op_name(e.name)) for e in line.events)
                elif line.name == "XLA Modules":
                    modules.extend((e.start_ns * 1e-9, e.end_ns * 1e-9,
                                    module_name(e.name))
                                   for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("python"):
                    host.extend((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                                for e in line.events)
    if not ops:
        raise ValueError(f"{path}: no XLA Ops on /device:TPU:{device}")
    tr = DeviceTrace(sorted(ops), sorted(modules), sorted(host))
    # the harness starts the profiler, then opens the window, then runs
    # work: no device op of the trace can start before the window opens.
    # Where one seems to, the device clock lags; shift it forward so the
    # first op starts with the window (a lower bound on the lag).
    win = tr.window()
    first = min([tr.ops[0][0]] + [m[0] for m in tr.modules[:1]])
    lag = max(0.0, win[0] - first) if win else 0.0
    if lag:
        tr.ops = [(a + lag, b + lag, n) for a, b, n in tr.ops]
        tr.modules = [(a + lag, b + lag, n) for a, b, n in tr.modules]
    return tr


def clip(events: Iterable[Tuple[float, float, str]],
         window: Interval) -> List[Tuple[float, float, str]]:
    lo, hi = window
    return [(max(a, lo), min(b, hi), n) for a, b, n in events
            if b > lo and a < hi]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Interval]:
    """Sorted, merged intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(tr: DeviceTrace, window: Interval) -> float:
    """Seconds of the window in which some operation ran on the device."""
    return sum(b - a for a, b in union(
        (a, b) for a, b, _ in clip(tr.ops, window)))


def module_seconds(tr: DeviceTrace, window: Interval) -> Dict[str, float]:
    """Device seconds per program, within the window."""
    out: Dict[str, float] = defaultdict(float)
    for a, b, n in clip(tr.modules, window):
        out[n] += b - a
    return dict(out)


def module_runs(tr: DeviceTrace, window: Interval, name: str) -> int:
    return sum(1 for a, b, n in tr.modules if n == name
               and window[0] <= 0.5 * (a + b) < window[1])


def op_seconds(tr: DeviceTrace, window: Interval,
               prefix: str) -> Tuple[float, int]:
    """(device seconds, calls) of the ops whose name starts with ``prefix``
    (a kernel's custom call), within the window."""
    hits = [(a, b) for a, b, n in clip(tr.ops, window)
            if n.startswith(prefix)]
    return sum(b - a for a, b in hits), len(hits)


def top_ops(tr: DeviceTrace, window: Interval,
            k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` op families that took most device time; ``while`` and
    ``conditional``, which hold other ops, are left out."""
    out: Dict[str, float] = defaultdict(float)
    for a, b, n in clip(tr.ops, window):
        if op_family(n) not in CONTAINERS:
            out[op_family(n)] += b - a
    return sorted(out.items(), key=lambda kv: -kv[1])[:k]


def idle_gaps(tr: DeviceTrace, window: Interval, k: int = 10,
              min_gap_s: float = 1e-4) -> List[Tuple[str, float]]:
    """Idle device seconds grouped by what the host was doing: each gap of
    at least ``min_gap_s`` is charged to the innermost host span holding
    its midpoint (``idle`` where none does); the ``k`` largest groups."""
    busy = union((a, b) for a, b, _ in clip(tr.ops, window))
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    out: Dict[str, float] = defaultdict(float)
    host = sorted(s for s in tr.host if s[2] != "bench.window")
    active: List[Tuple[float, float, str]] = []
    i = 0
    for a, b in zip(edges[0::2], edges[1::2]):     # gaps in time order
        if b - a < min_gap_s:
            continue
        mid = 0.5 * (a + b)
        while i < len(host) and host[i][0] <= mid:
            active.append(host[i])
            i += 1
        active = [s for s in active if s[1] > mid]
        holding = [(hb - ha, n) for ha, hb, n in active]
        out[min(holding)[1] if holding else "idle"] += b - a
    return sorted(out.items(), key=lambda kv: -kv[1])[:k]
