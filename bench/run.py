#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration (``bench/configs/<config>.json``) and its traffic
mix (``bench/traffic/<mix>.json``, with ``<mix>.py`` where the mix brings a
generator of its own) are found by name from ``BENCHMARK.json``; the per-layer
metrics' readers are ``bench/layers/<metric>.py``.  The configuration file
names its plain reference (``reference``) and the counts of its work
(``work``), each a Python file of the checkout, and may set fields of the
program's ArchConfig (``arch_fields``).  Nothing here names a cell or an
architecture.

A run, in one process that holds the chip:

  set-up   device check (a TPU, as many chips as the cell asks for, a device
           kind with published peaks), the served weights built from the
           seed on the device (``launch/serve.py``'s build), the engine,
           one warm request burst per batch shape the traffic can reach, then
           the traffic's lead: the window opens once the load is steady
  window   ``--seconds`` of traffic through ``Engine.submit`` / ``step``,
           timed by the host clock around each step; with ``--trace 1`` the
           profiler records it (its Python tracer off), and the per-layer
           metrics are read instead of the end-to-end ones
  drain    turns due in the window get ``MAX_DRAIN_S`` of engine steps to
           give their first token, from the window's end, or in a traced
           run from the end of the profiler's teardown; one with none by
           the drain's end is ``failed``
  check    the program's state freed, a sample of finished requests drawn
           from the seed is run through the plain reference the
           configuration names and its widest served-token gap is held to
           the configuration's limit

The last line of stdout is one JSON object; the numbers compared are also
the last lines of stderr.  On anything but a TPU it exits nonzero before
printing a result.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import heapq  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import traffic, work  # noqa: E402

MAX_DRAIN_S = 60.0          # engine time a turn due in the window may take


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- the cell ---------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    mix: dict
    generate: object        # sessions(mix, seed, vocab, n) of the mix
    end_to_end: List[dict]
    per_layer: List[dict]
    reference: types.ModuleType     # the configuration's ``reference``
    work: types.ModuleType          # the configuration's ``work``


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = wl[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())

    def mine(m):
        return m.get("workloads") is None or name in m["workloads"]
    mixes = root / "bench" / "traffic"
    return Cell(w, config, traffic.load(w["traffic"], mixes),
                traffic.generator(w["traffic"], mixes),
                [m for m in spec["end_to_end"] if mine(m)],
                [m for m in spec["per_layer"] if mine(m)],
                config_module(config, "reference", root),
                config_module(config, "work", root))


def load_file(path: pathlib.Path, key: str) -> types.ModuleType:
    """The Python file ``path``, run as module ``key``."""
    spec = importlib.util.spec_from_file_location(key, path)
    mod = sys.modules[key] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_reader(metric: str, directory: pathlib.Path = BENCH / "layers"):
    """``read(run) -> float | None`` of ``<directory>/<metric>.py``."""
    return load_file(directory / f"{metric}.py",
                     f"bench_layer_{metric.replace('.', '_')}").read


# the modules a configuration file names, by key, and what each provides:
#   reference  gaps(config, seed, seqs, control=False) -> max_gap, mean_gap,
#              tokens, per_seq, and with ``control`` the control's readings
#   work       weight_bytes(config) of the served tree; decode_step(config,
#              contexts) -> (flops, bytes) of one decode step
CONFIG_MODULES = {"reference": ("gaps",),
                  "work": ("weight_bytes", "decode_step")}


def config_module(config: dict, key: str,
                  root: pathlib.Path = ROOT) -> types.ModuleType:
    """The module that the configuration file names under ``key``, a path
    from the checkout's ``root``; a file that is missing or lacks one of
    ``CONFIG_MODULES[key]`` stops the run, naming it."""
    rel = config.get(key)
    where = f"configuration {config.get('name')!r}"
    if not isinstance(rel, str):
        raise SystemExit(f"{where} names no {key!r} file")
    path = (root / rel).resolve()
    if not path.is_relative_to(root.resolve()) or not path.is_file():
        raise SystemExit(f"{where}: {key} {rel} is not a file of the "
                         f"checkout {root}")
    mod = load_file(path, f"bench_{key}_{re.sub(r'[^0-9A-Za-z]', '_', rel)}")
    missing = [f for f in CONFIG_MODULES[key]
               if not callable(getattr(mod, f, None))]
    if missing:
        raise SystemExit(f"{where}: {key} {rel} has no {', '.join(missing)}")
    return mod


def require_chips(chips: int):
    """The devices, if they are TPUs with published peaks; else exit."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench/run.py needs a TPU; JAX's first device is "
                         f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips; JAX sees "
                         f"{len(devs)}")
    try:
        work.peaks(devs[0].device_kind)
    except KeyError as e:
        raise SystemExit(str(e))
    return devs[:chips]


class CompileLog:
    """Host times of every program compiled or loaded from the cache."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda e, s, **k: self._on(e))
        jax.monitoring.register_event_listener(lambda e, **k: self._on(e))

    def _on(self, event: str) -> None:
        if event in self.EVENTS:
            self.times.append(time.perf_counter())

    def between(self, a: float, b: float) -> int:
        return sum(1 for t in self.times if a <= t <= b)


# -- the engine -------------------------------------------------------------

def serve_args(config: dict, seed: int, annotate: bool):
    """``launch/serve.py``'s arguments for this configuration."""
    from repro.launch import serve
    s = config["serving"]
    argv = ["--arch", s["arch"], "--engine", "--w-bits", str(s["w_bits"]),
            "--w-dist", s["w_dist"], "--kv-bits", str(s["kv_bits"]),
            "--seed", str(seed), "--max-slots", str(s["max_slots"]),
            "--max-len", str(s["max_len"]),
            "--page-size", str(s["page_size"]),
            "--pool-bytes", str(s["pool_bytes"]),
            "--prefill-chunk", str(s["prefill_chunk"])]
    if s["prefix_cache"]:
        argv.append("--prefix-cache")
    if annotate:
        argv.append("--profile-annotations")
    return serve.build_parser().parse_args(argv)


def _hashable(v):
    return tuple(map(_hashable, v)) if isinstance(v, list) else v


def model_config(config: dict):
    """The program's ArchConfig of the architecture named in ``serving``,
    with every size the file holds (the file is the configuration as run):
    the ten keys of the table below, then the fields the file's optional
    ``arch_fields`` sets by their ArchConfig names (experts, layer kinds,
    state sizes: what the table does not hold).  A JSON list there becomes
    a tuple, so the config stays hashable as a static argument."""
    from repro.configs import base as cb
    sizes = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
             "d_ff": "intermediate_size", "n_heads": "num_attention_heads",
             "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
             "vocab": "vocab_size", "rope_theta": "rope_theta",
             "norm_eps": "rms_norm_eps",
             "tie_embeddings": "tie_word_embeddings"}
    fields = config.get("arch_fields", {})
    known = {f.name for f in dataclasses.fields(cb.ArchConfig)}
    for k in fields:
        if k not in known:
            raise SystemExit(f"configuration {config.get('name')!r}: "
                             f"arch_fields sets {k!r}, which ArchConfig "
                             f"has no field of")
        if k in sizes:
            raise SystemExit(f"configuration {config.get('name')!r}: "
                             f"arch_fields sets {k!r}, which the file "
                             f"holds as {sizes[k]!r}")
    cfg = dataclasses.replace(cb.get(config["serving"]["arch"]),
                              **{a: config[b] for a, b in sizes.items()})
    return dataclasses.replace(
        cfg, **{k: _hashable(v) for k, v in fields.items()})


def weights_seed(seed: int) -> int:
    """The seed of the weights: the run's seed folded into 31 bits."""
    return seed % (2 ** 31 - 1)


def build(config: dict, seed: int, counts: types.ModuleType,
          annotate: bool = False):
    """The engine with its served weights; ``counts`` is the module the
    configuration names under ``work``."""
    import jax
    from repro.launch import serve
    from repro.serve.engine import Engine
    cfg = model_config(config)
    sargs = serve_args(config, weights_seed(seed), annotate)
    params = jax.block_until_ready(serve.build_engine_params(cfg, sargs))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"[build] {cfg.n_layers} layers W{config['serving']['w_bits']}: "
        f"{n_bytes} B served ({config['work']} counts "
        f"{counts.weight_bytes(config)})")
    return Engine(params, cfg, serve.SERVE_OPTS, serve.engine_config(sargs))


def warm(eng, config: dict, mix: dict, seed: int) -> int:
    """Compile and run, outside the window, every batch shape this cell's
    traffic reaches: a burst of k one-chunk prompts for each power of two
    k up to ``max_slots`` reaches chunk batch k and its decode bucket; a
    prompt served twice reaches the copy-on-write of a shared page, which
    any prefix hit that ends inside a page needs (even a chance one-token
    hit on unshared traffic)."""
    from repro.serve.engine import Request, SamplingParams
    s = config["serving"]
    rng = np.random.default_rng([seed, 1])
    chunk = s["prefill_chunk"] * s["page_size"]
    uid, k = -1, 1
    while True:
        reqs = []
        for _ in range(k):
            reqs.append(Request(uid, rng.integers(
                0, config["vocab_size"], chunk).astype(np.int32),
                SamplingParams(max_new_tokens=2)))
            uid -= 1
        eng.generate(reqs)
        if k >= s["max_slots"]:
            break
        k = min(2 * k, s["max_slots"])
    if s["prefix_cache"]:
        # the second admission hits all but the last token, which lands
        # mid-page in a page the cache shares
        prompt = rng.integers(0, config["vocab_size"], chunk
                              + s["page_size"] // 2 + 1).astype(np.int32)
        for _ in range(2):
            eng.generate([Request(uid, prompt.copy(),
                                  SamplingParams(max_new_tokens=2))])
            uid -= 1
    eng.flush_prefix_cache()
    eng.reset_stats()
    return -uid - 1


# -- the traffic ------------------------------------------------------------

@dataclasses.dataclass
class Live:
    """A submitted turn as the client sees it."""
    session: traffic.Session
    turn: int
    due: float
    n_prompt: int
    prompt: np.ndarray
    asked: int
    seen: int = 0
    first: Optional[float] = None
    last: Optional[float] = None


class Driver:
    """Offers the mix to the engine and records what each client sees."""

    def __init__(self, eng, config: dict, mix: dict, seed: int, generate):
        self.eng, self.mix = eng, mix
        self.vocab = config["vocab_size"]
        self.closed = mix["arrival"]["kind"] == "closed"
        clients = mix["arrival"].get("clients", 0)
        self.clients = config["serving"]["max_slots"] \
            if clients == "max_slots" else int(clients)
        self.rng = np.random.default_rng([seed, 2])
        self.pool = generate(mix, seed, self.vocab, 4096)
        self.next_session = 0
        self.history: Dict[int, np.ndarray] = {}
        self.pending: list = []         # heap of (due, order, sid, turn)
        self.order = 0
        self.live: Dict[int, Live] = {}
        self.uid = 0
        self.t0 = 0.0
        # what the clients saw: tokens per step, by the step's end time
        self.steps: List[tuple] = []    # (end time, tokens emitted)
        self.gaps: List[tuple] = []     # (time of the later token, gap s)
        self.first_tokens: List[tuple] = []   # (due, first token time)
        self.decode_ctx: List[tuple] = []     # (step end, [contexts])
        self.done: List[tuple] = []     # (Live, RequestOutput, end time)
        self.initial: set = set()       # closed loop: the clients' first
        self.failed: List[str] = []

    def _take_session(self) -> traffic.Session:
        s = self.pool[self.next_session % len(self.pool)]
        self.next_session += 1
        return s

    def _schedule(self, due: float, sess: traffic.Session, turn: int):
        heapq.heappush(self.pending, (due, self.order, sess, turn))
        self.order += 1

    def start(self) -> None:
        self.t0 = time.perf_counter()
        if self.closed:
            shares = traffic.fixed_order().permutation(
                (np.arange(self.clients) + 0.5) / self.clients)
            for c in range(self.clients):
                sess = self._take_session()
                if self.mix.get("steady_start"):
                    sess = traffic.steady_start(sess, float(shares[c]),
                                                self.rng, self.vocab)
                self._schedule(self.t0, sess, 0)
        else:
            for sess in self.pool:
                self._schedule(self.t0 + sess.arrival_s, sess, 0)

    def submit_due(self, now: float) -> None:
        from repro.serve.engine import Request, SamplingParams
        while self.pending and self.pending[0][0] <= now:
            due, _, sess, j = heapq.heappop(self.pending)
            turn = sess.turns[j]
            hist = self.history.get(sess.sid) if j else None
            if j == 0:
                hist = sess.prefix if sess.prefix is not None else \
                    np.zeros(0, np.int32)
            prompt = np.concatenate([hist, turn.new_tokens])
            self.uid += 1
            req = Request(self.uid, prompt, SamplingParams(
                max_new_tokens=turn.output, seed=self.uid), arrival_time=due)
            self.live[self.uid] = Live(sess, j, due, prompt.size, prompt,
                                       turn.output)
            if self.closed and self.uid <= self.clients:
                self.initial.add(self.uid)
            try:
                self.eng.submit(req)
            except ValueError as e:
                self.failed.append(f"uid {self.uid}: refused: {e}")

    def step(self) -> float:
        """One engine step; returns its end time."""
        fin = self.eng.step()
        t1 = time.perf_counter()
        counts = {seq.request.uid: (len(seq.generated), seq)
                  for seq in self.eng.scheduler.running().values()}
        for o in fin:
            counts[o.uid] = (len(o.token_ids), None)
        n_tok, ctx = 0, []
        for uid, (n, seq) in counts.items():
            st = self.live.get(uid)
            if st is None or n <= st.seen:
                continue
            new = n - st.seen
            n_tok += new
            if st.seen == 0:
                st.first = t1
                self.first_tokens.append((st.due, t1))
                self.gaps.extend((t1, 0.0) for _ in range(new - 1))
                decoded = new - 1
            else:
                self.gaps.append((t1, t1 - st.last))
                self.gaps.extend((t1, 0.0) for _ in range(new - 1))
                decoded = new
            if decoded:
                ctx.append(st.n_prompt + n - 1)
            st.seen, st.last = n, t1
        self.steps.append((t1, n_tok))
        self.decode_ctx.append((t1, ctx))
        for o in fin:
            self._complete(o, t1)
        return t1

    def _complete(self, out, t1: float) -> None:
        st = self.live.pop(out.uid)
        if len(out.token_ids) != st.asked:
            self.failed.append(f"uid {out.uid}: {len(out.token_ids)} tokens "
                               f"for {st.asked} asked")
        self.done.append((st, out, t1))
        sess, j = st.session, st.turn
        self.history[sess.sid] = np.concatenate(
            [st.prompt, np.asarray(out.token_ids, np.int32)])
        if j + 1 < len(sess.turns):
            self._schedule(t1 + sess.turns[j + 1].think_s, sess, j + 1)
        else:
            self.history.pop(sess.sid, None)
            if self.closed:
                self._schedule(t1, self._take_session(), 0)

    def idle_until_due(self) -> None:
        if self.pending:
            time.sleep(min(1e-3, max(0.0, self.pending[0][0]
                                     - time.perf_counter())))

    def first_tokens_pending(self, a: float, b: float) -> int:
        return sum(1 for st in self.live.values()
                   if a <= st.due < b and st.first is None) + sum(
            1 for due, _, _, _ in self.pending if a <= due < b)

    def all_clients_started(self) -> bool:
        return len(self.initial) == self.clients and all(
            uid not in self.live or self.live[uid].first is not None
            for uid in self.initial)


# -- the window -------------------------------------------------------------

@dataclasses.dataclass
class Window:
    start: float
    end: float
    decode_steps: int
    counters: Dict[str, int]
    compiles: int
    drain: Tuple[float, float] = (0.0, 0.0)     # the drain's start, end


def counters(eng) -> Dict[str, int]:
    """The engine's own counters (exact sums and counts)."""
    reg = eng.telemetry.registry
    st = eng.stats()
    return {"decode_steps": eng.n_decode_steps,
            "prefill_tokens": eng.n_prefill_tokens,
            "prompt_tokens": eng.n_prompt_tokens,
            "prefill_calls": eng.n_prefill_calls,
            "tokens_decoded": reg.counter("tokens_decoded").value,
            "cache_hit_tokens": st["cache_hit_tokens"],
            "preemptions": st["preemptions"],
            "waiting": eng.scheduler.n_waiting}


def run_traffic(drv: Driver, mix: dict, seconds: float, compile_log,
                trace_dir: Optional[str]):
    """Lead, window, and the drain that lets turns due in the window
    answer.  The drain runs the engine for at most ``MAX_DRAIN_S`` from
    its start: the window's end, or in a traced run the end of the
    profiler's teardown, which is the harness's time and not the engine's.
    Returns the Window."""
    import jax
    drv.start()
    lead = mix["lead"]
    while True:                                         # the lead
        now = time.perf_counter()
        drv.submit_due(now)
        if not drv.eng.has_work:
            drv.idle_until_due()
            continue
        t1 = drv.step()
        if lead["kind"] == "first_tokens" and drv.all_clients_started():
            break
        if lead["kind"] == "seconds" and t1 - drv.t0 >= lead["s"]:
            break
    ws = t1
    c0 = counters(drv.eng)
    if trace_dir:
        # the host tracer keeps every TraceAnnotation (the engine.* and
        # bench.* spans); the Python tracer, which records every host frame,
        # is off: it added host time to each step and swelled the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    annot = jax.profiler.TraceAnnotation("bench.window")
    annot.__enter__()
    while True:                                         # the window
        now = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.submit"):
            drv.submit_due(now)
        if not drv.eng.has_work:
            with jax.profiler.TraceAnnotation("bench.wait"):
                drv.idle_until_due()
            if time.perf_counter() >= ws + seconds:
                t1 = time.perf_counter()
                break
            continue
        with jax.profiler.TraceAnnotation("bench.step"):
            t1 = drv.step()
        if t1 >= ws + seconds:
            break
    we = t1
    annot.__exit__(None, None, None)
    if trace_dir:
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"[trace] stop_trace {time.perf_counter() - t_stop:.2f}s")
    c1 = counters(drv.eng)
    drain_start = time.perf_counter() if trace_dir else we
    win = Window(ws, we, c1["decode_steps"] - c0["decode_steps"],
                 {k: c1[k] - c0[k] for k in c1},
                 compile_log.between(ws, we))
    waiting = drv.first_tokens_pending(ws, we)
    while drv.first_tokens_pending(ws, we) \
            and time.perf_counter() < drain_start + MAX_DRAIN_S:  # the drain
        drv.submit_due(time.perf_counter())
        if drv.eng.has_work:
            drv.step()
        else:
            drv.idle_until_due()
    win.drain = (drain_start, time.perf_counter())
    latest = max((t for due, t in drv.first_tokens if ws <= due < we),
                 default=we)
    log(f"[drain] {win.drain[0] - we:+.3f}s to {win.drain[1] - we:+.3f}s "
        f"after the window's end: {waiting} turns due in the window waited "
        f"for a first token, {drv.first_tokens_pending(ws, we)} still do; "
        f"the latest first token came {latest - we:+.3f}s after the end")
    return win


def percentile(xs, q: float) -> float:
    """Percentile over every sample (no histogram buckets)."""
    return float(np.percentile(np.asarray(xs, np.float64), q))


def end_to_end(drv: Driver, win: Window) -> Dict[str, float]:
    span = win.end - win.start
    inside = [n for t, n in drv.steps if win.start < t <= win.end]
    gaps = [g for t, g in drv.gaps if win.start < t <= win.end]
    out = {"out_tok_s": sum(inside) / span,
           "tpot_ms": 1e3 * span / max(win.decode_steps, 1),
           "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps else None}
    ttft = [t - due for due, t in drv.first_tokens
            if win.start <= due < win.end]
    out["ttft_p90_ms"] = 1e3 * percentile(ttft, 90) if ttft else None
    log(f"[window] {span:.3f}s: {sum(inside)} tokens, {win.decode_steps} "
        f"decode steps, {len(gaps)} gaps, {len(ttft)} first tokens due in "
        f"it; {win.compiles} programs compiled or loaded; counters "
        f"{win.counters}")
    if ttft:
        log(f"[window] TTFT p50 {1e3 * percentile(ttft, 50):.1f}ms "
            f"p90 {out['ttft_p90_ms']:.1f}ms max {1e3 * max(ttft):.1f}ms")
    return out


# -- the check --------------------------------------------------------------

def check_sample(drv: Driver, k: int, seed: int):
    """``k`` of the requests the run finished (lead, window and drain ran
    the same programs), the longest served one among them."""
    done = drv.done
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i][1].token_ids))
    rng = np.random.default_rng([seed, 3])
    rest = [i for i in rng.permutation(len(done)) if i != longest]
    pick = [longest] + rest[:k - 1]
    return [(np.concatenate([done[i][0].prompt, np.asarray(
        done[i][1].token_ids, np.int32)]), done[i][0].n_prompt)
        for i in pick]


# -- main -------------------------------------------------------------------

def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="hold the reference's lower-precision control "
                         "(check.control) to the limit in the program's "
                         "place (not a benchmark run)")
    return ap.parse_args(argv)


def main(argv=None, require=require_chips,
         root: pathlib.Path = ROOT) -> dict:
    """One run; ``require`` is the device check and ``root`` the checkout
    whose ``BENCHMARK.json`` names the cell (tests replace both)."""
    args = parse(argv)
    cell = load_cell(args.workload, root)
    devs = require(cell.workload["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    compile_log = CompileLog()
    log(f"[device] {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {enable_compile_cache()}")
    config, mix = cell.config, cell.mix
    control = bool(args.control)
    eng = build(config, args.seed, cell.work, annotate=bool(args.trace))
    n_warm = warm(eng, config, mix, args.seed)
    drv = Driver(eng, config, mix, args.seed, cell.generate)
    t_setup = time.perf_counter()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    win = run_traffic(drv, mix, args.seconds, compile_log, trace_dir)
    setup_s = win.start - _T_START
    log(f"[setup] {setup_s:.3f}s: build and warm-up ({n_warm} warm "
        f"requests) {t_setup - _T_START:.3f}s, lead "
        f"{win.start - t_setup:.3f}s")

    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
              "device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind, "count": len(devs)}}
    due = [st for st, _, _ in drv.done if win.start <= st.due < win.end] + \
        [st for st in drv.live.values() if win.start <= st.due < win.end]
    result["attempted"] = len(due)
    unanswered = {uid: st for uid, st in drv.live.items()
                  if win.start <= st.due < win.end and st.first is None}
    result["failed"] = len(drv.failed) + len(unanswered)
    for f in drv.failed[:5]:
        log(f"[failed] {f}")
    for uid, st in unanswered.items():
        log(f"[failed] uid {uid}: no first token; due {st.due - win.end:+.3f}s"
            f", drain {win.drain[0] - win.end:+.3f}s to "
            f"{win.drain[1] - win.end:+.3f}s, from the window's end")
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if args.trace:
        from bench import trace
        tr = trace.load(trace.xplane_path(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        w = tr.window()
        reading = types.SimpleNamespace(
            trace=tr, window=w, window_s=w[1] - w[0], drv=drv, win=win,
            spans=list(eng.telemetry.tracer.spans()), config=config,
            work=cell.work, peaks=work.peaks(devs[0].device_kind))
        for m in cell.per_layer:
            v = layer_reader(m["name"], root / "bench" / "layers")(reading)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": units[m["name"]]}
        busy = trace.busy_seconds(tr, w)
        result["device"].update(busy_s=busy, window_s=w[1] - w[0])
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in trace.top_ops(tr, w)],
            "idle_gaps": [[n, s] for n, s in trace.idle_gaps(tr, w)]}
        del tr, reading
    else:
        e2e = end_to_end(drv, win)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": units[m["name"]]}
    result["device"]["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)

    # the check: free the program's state, then run the reference
    chk = config["check"]
    seqs = check_sample(drv, mix["check_requests"], args.seed)
    del eng, drv.eng
    gc.collect()
    t_ref = time.perf_counter()
    got = cell.reference.gaps(config, weights_seed(args.seed), seqs,
                              control=control)
    number = chk["number"]
    log(f"[check] reference {config['reference']} over {len(seqs)} "
        f"requests, {got['tokens']} served tokens, in "
        f"{time.perf_counter() - t_ref:.1f}s; per request widest gap "
        f"{['%.4f' % g for g in got['per_seq']]}; widest "
        f"{got['max_gap']!r}, mean {got['mean_gap']!r}")
    value = got[number]
    if control:             # the control is put in the program's place
        log(f"[control] per precision {got['control']!r}")
        value = got["control"][chk["control"]][number]
    result["correct"] = bool(seqs) and got["tokens"] > 0 and \
        value <= chk["limit"] and result["failed"] == 0 and \
        result["attempted"] > 0
    result["check"] = {number: {"value": value, "limit": chk["limit"]},
                       "failed": {"value": result["failed"], "limit": 0}}
    log(f"[check] {number} {value!r} (limit {chk['limit']}); failed "
        f"{result['failed']} (limit 0); attempted {result['attempted']}; "
        f"correct {result['correct']}")
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
