"""The one traffic generator; each mix is ``bench/traffic/<mix>.json``.

A mix describes sessions.  A session is a list of turns; each turn appends
new tokens to the session's history (the first turn after an optional shared
system prompt) and asks for a number of output tokens, served greedily.  The
next turn's prompt is the whole history, served tokens included, so later
turns can hit the prefix cache.  Sessions arrive in one of two ways:

  closed   ``clients`` clients; each starts its next session as soon as its
           last one has completed (a fixed-concurrency batch job)
  poisson  sessions arrive at ``rate_per_s`` (an open loop of users)

A mix that needs another arrival kind brings ``bench/traffic/<mix>.py`` beside
its ``.json``, with a ``sessions(mix, seed, vocab, n)`` of its own (which may
call this module's and set each session's ``arrival_s``); ``generator`` finds
it by the mix's name.

Sizes are not drawn independently: each size list is the same set of
quantiles of its distribution, cut into blocks of ``block`` values that each
hold the whole set in one fixed order, so every seed offers the same sizes in
the same order and any run of ``block`` consecutive draws has the same sizes.
The seed draws every token id.

Keys of a mix file (all lengths in tokens, times in seconds):

  arrival        {"kind": "closed", "clients": C} | {"kind": "poisson",
                 "rate_per_s": r}
  system_prompt  null | {"count": n, "tokens": t, "zipf_s": s}: one of n
                 shared prompts, the k-th chosen with weight 1 / k**s
  turns          {"min": a, "max": b}: turns per session, uniform
  first_tokens   lognormal {"median", "sigma", "min", "max"}: own tokens of
                 the first turn
  turn_tokens    lognormal: tokens a later turn appends (user or tool)
  output         lognormal: output tokens per turn
  think_s        mean of the exponential pause before a later turn (0: none)
  max_context    a session ends before a turn would pass this length
  steady_start   closed loop only: each client's first session starts with
                 a uniform share of its output already in its prompt
  block          stratification block (see above)
  lead           {"kind": "first_tokens"}: the window opens once every
                 client has its first token; {"kind": "seconds", "s": t}:
                 it opens t seconds after the first arrival
  check_requests finished requests whose tokens the reference checks
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import statistics
import sys
from typing import Callable, List, Optional

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"
_N01 = statistics.NormalDist()


def load(name: str, directory: pathlib.Path = TRAFFIC_DIR) -> dict:
    """The parameters of mix ``name`` (``<directory>/<name>.json``)."""
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def generator(name: str, directory: pathlib.Path = TRAFFIC_DIR) -> Callable:
    """``sessions(mix, seed, vocab, n)`` of mix ``name``: that of
    ``<directory>/<name>.py`` where the mix brings one, else this module's."""
    path = directory / f"{name}.py"
    if not path.is_file():
        return sessions
    key = f"bench_traffic_{name.replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(key, path)
    mod = sys.modules[key] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.sessions


def fixed_order() -> np.random.Generator:
    """The one order of every block, the same for every seed."""
    return np.random.default_rng(0)


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a clipped lognormal, as ints."""
    q = (np.arange(n) + 0.5) / n
    z = np.array([_N01.inv_cdf(float(x)) for x in q])
    v = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def exponential_quantiles(mean: float, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return -mean * np.log1p(-q)


def stratified(values: np.ndarray, count: int,
               rng: np.random.Generator) -> np.ndarray:
    """``count`` draws: blocks that each permute all of ``values``
    (``rng`` fixes the permutations)."""
    blocks = -(-count // len(values))
    return np.concatenate([rng.permutation(values)
                           for _ in range(blocks)])[:count]


@dataclasses.dataclass
class Turn:
    new_tokens: np.ndarray        # appended to the history before the turn
    output: int                   # tokens asked for
    think_s: float = 0.0          # pause after the previous turn completed


@dataclasses.dataclass
class Session:
    sid: int
    prefix: Optional[np.ndarray]  # shared system prompt, or None
    turns: List[Turn]
    arrival_s: Optional[float]    # poisson: offset from the first arrival


def sessions(mix: dict, seed: int, vocab: int, n: int) -> List[Session]:
    """The first ``n`` sessions of ``mix`` under ``seed`` (less any whose
    first turn would not fit ``max_context``)."""
    rng = np.random.default_rng(seed)
    order = fixed_order()
    block = int(mix["block"])
    arrival = mix["arrival"]
    closed = arrival["kind"] == "closed"
    if not closed and arrival["kind"] != "poisson":
        raise ValueError(f"arrival kind {arrival['kind']!r} needs a "
                         f"generator of its own, bench/traffic/<mix>.py")
    sp = mix.get("system_prompt")
    prefixes, which = [], np.zeros(n, np.int64)
    if sp:
        prefixes = [rng.integers(0, vocab, sp["tokens"]).astype(np.int32)
                    for _ in range(sp["count"])]
        w = 1.0 / np.arange(1, sp["count"] + 1) ** sp["zipf_s"]
        cdf = np.cumsum(w / w.sum())
        q = (np.arange(block) + 0.5) / block
        which = stratified(np.searchsorted(cdf, q), n, order)
    lo, hi = mix["turns"]["min"], mix["turns"]["max"]
    n_turns = stratified(lo + (np.arange(block) * (hi - lo + 1)) // block,
                         n, order)
    total_turns = int(n_turns.sum())
    first = stratified(lognormal_quantiles(mix["first_tokens"], block), n,
                       order)
    later = stratified(lognormal_quantiles(mix["turn_tokens"], block),
                       total_turns, order)
    outs = stratified(lognormal_quantiles(mix["output"], block),
                      total_turns, order)
    think = stratified(exponential_quantiles(mix["think_s"], block),
                       total_turns, order) if mix["think_s"] else \
        np.zeros(total_turns)
    arrivals = None
    if not closed:
        gaps = stratified(exponential_quantiles(
            1.0 / arrival["rate_per_s"], block), n, order)
        arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    out, t = [], 0
    cap = mix["max_context"]
    for i in range(n):
        prefix = prefixes[which[i]] if sp else None
        length = 0 if prefix is None else prefix.size
        turns = []
        for j in range(int(n_turns[i])):
            add = int(first[i] if j == 0 else later[t])
            if length + add + int(outs[t]) > cap:
                break
            turns.append(Turn(rng.integers(0, vocab, add).astype(np.int32),
                              int(outs[t]), 0.0 if j == 0 else
                              float(think[t])))
            length += add + int(outs[t])
            t += 1
        t += int(n_turns[i]) - len(turns)
        if turns:
            out.append(Session(i, prefix, turns,
                               None if closed else float(arrivals[i])))
    return out


def steady_start(session: Session, share: float,
                 rng: np.random.Generator, vocab: int) -> Session:
    """``session`` with ``share`` of its first turn's output already in its
    prompt (a client caught mid-answer when the window opens)."""
    turn = session.turns[0]
    done = min(int(round(share * turn.output)), turn.output - 1)
    extra = rng.integers(0, vocab, done).astype(np.int32)
    first = Turn(np.concatenate([turn.new_tokens, extra]),
                 turn.output - done, turn.think_s)
    return dataclasses.replace(session, turns=[first] + session.turns[1:])
