"""Bench-artifact schema validation (the ``bench`` uniqcheck pass).

BENCH_engine.json is a committed artifact other tooling consumes (the
README serving table, regression eyeballing).
A bench refresh that silently drops the latency distribution — the
TTFT/ITL/queue-wait percentiles the serving story is argued from —
must fail CI, not be discovered a PR later.  Purely structural: values
are checked for presence and type, never for speed (perf gating would
make CI hardware-dependent).
"""

from __future__ import annotations

import json
import os
from typing import List, Tuple

from repro.analysis.findings import Finding

DEFAULT_BENCH_PATH = "BENCH_engine.json"

# every microbench row: identity + the two throughput numbers
_ROW_FIELDS = ("name", "tok_s", "us_per_call")
# every kernel-sweep row (benchmarks/kernel_bench.py): identity, the
# timing pair, and the execution mode (compiled / ref / interpret) —
# consumers must be able to tell a TPU number from a CPU shape check
_KERNEL_FIELDS = ("name", "us_per_call", "gflops", "mode")
# every latency-sweep row: the full percentile set (p50/p95/p99 each)
_SWEEP_SECTIONS = ("shared_prefix_sweep", "multiturn_sweep", "kv_sweep")
_SWEEP_FIELDS = tuple(
    f"{metric}_p{q}_s"
    for metric in ("ttft", "itl", "queue_wait") for q in (50, 95, 99)
) + ("tok_s", "submitted", "completed")


def _num(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def run_bench_check(path: str = DEFAULT_BENCH_PATH) \
        -> Tuple[List[Finding], dict]:
    findings: List[Finding] = []
    info = {"bench_path": path, "bench_rows": 0, "bench_sweep_rows": 0}
    if not os.path.exists(path):
        findings.append(Finding(
            rule="BENCH-SCHEMA", path=path, detail="missing",
            message="bench artifact not found (regenerate with "
                    "benchmarks/engine_bench.py)"))
        return findings, info
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError) as e:
        findings.append(Finding(
            rule="BENCH-SCHEMA", path=path, detail="unparseable",
            message=f"bench artifact is not valid JSON: {e}"))
        return findings, info

    def missing(section: str, ident: str, field: str, why: str) -> None:
        findings.append(Finding(
            rule="BENCH-SCHEMA", path=path,
            detail=f"{section}[{ident}].{field}",
            message=f"{section} row {ident!r}: field {field!r} {why}"))

    rows = doc.get("rows")
    if not isinstance(rows, list) or not rows:
        findings.append(Finding(
            rule="BENCH-SCHEMA", path=path, detail="rows",
            message="top-level 'rows' must be a non-empty list"))
        rows = []
    for i, row in enumerate(rows):
        ident = str(row.get("name", i)) if isinstance(row, dict) else str(i)
        if not isinstance(row, dict):
            missing("rows", ident, "-", "row is not an object")
            continue
        info["bench_rows"] += 1
        for field in _ROW_FIELDS:
            if field not in row:
                missing("rows", ident, field, "is missing")
            elif field != "name" and not _num(row[field]):
                missing("rows", ident, field, "is not numeric")
    kernels = doc.get("kernels")
    if not isinstance(kernels, list) or not kernels:
        findings.append(Finding(
            rule="BENCH-SCHEMA", path=path, detail="kernels",
            message="'kernels' section missing or empty (regenerate with "
                    "benchmarks/kernel_bench.py)"))
        kernels = []
    for i, row in enumerate(kernels):
        ident = str(row.get("name", i)) if isinstance(row, dict) else str(i)
        if not isinstance(row, dict):
            missing("kernels", ident, "-", "row is not an object")
            continue
        info["bench_kernel_rows"] = info.get("bench_kernel_rows", 0) + 1
        for field in _KERNEL_FIELDS:
            if field not in row:
                missing("kernels", ident, field, "is missing")
            elif field not in ("name", "mode") and not _num(row[field]):
                missing("kernels", ident, field, "is not numeric")
    for section in _SWEEP_SECTIONS:
        sweep = doc.get(section)
        if sweep is None:
            findings.append(Finding(
                rule="BENCH-SCHEMA", path=path, detail=section,
                message=f"latency sweep section {section!r} is missing"))
            continue
        for i, row in enumerate(sweep if isinstance(sweep, list) else []):
            ident = str(i)
            if not isinstance(row, dict):
                missing(section, ident, "-", "row is not an object")
                continue
            info["bench_sweep_rows"] += 1
            for field in _SWEEP_FIELDS:
                if field not in row:
                    missing(section, ident, field, "is missing")
                elif not _num(row[field]):
                    missing(section, ident, field, "is not numeric")
    return findings, info
