"""The one traffic generator: a mix is a data file, this reads it.

A mix (`bench/traffic/<name>.json`) is parameters only. Its load is made of
sessions; a session is one or more turns, and a single request is a
session of one turn. The file gives:

``arrivals``  how sessions start. ``{"process": "poisson", "rate_per_s": r}``
              is an open loop: exponential gaps at r sessions a second,
              optionally in ``"bursts": {"period_s": p, "on_s": a}``, which
              puts the same mean rate into the first a seconds of every p
              (r * p / a while on, nothing while off).
              ``{"process": "backlog", "requests": n, "queue_factor": q}``
              is an offline job: a queue of n sessions, kept at q times the
              round width waiting, that never runs dry.
``classes``   a list of request classes, each with its ``share`` of the
              sessions: ``user_len`` (a turn's new text; the whole prompt of
              a single request), ``output_len``, and optionally
              ``system_prompts`` (a few shared prefixes with Zipf
              popularity, which a running server holds in its prefix
              cache), ``turns`` (lo, hi) and ``think_s`` (mean). A turn's
              prompt is the whole history (the earlier prompts and the
              replies the server actually gave) plus its new text, and the
              next turn is due one think time after the reply ends.
``tokens``    optional ``{"topics": k, "zipf_s": s}``: each session's token
              ids are Zipf-distributed inside one of k equal slices of the
              vocabulary (skewed text, which skews MoE routing the way real
              topics do); without it ids are uniform.

plus ``context_cap`` (the model's context), ``lead_s`` (traffic before the
window) and ``drain_cap_s`` (how long requests due in the window may run on
after it). A mix with long and short requests in one queue is two classes;
bursty chat is a sessions class with ``bursts``: new mixes are new files.

Sizes are stratified: a run with N draws of a distribution takes its N
quantiles at (i + 0.5) / N, so every seed offers the same sizes. ``order``
says in what order: ``"seeded"`` (the default) lets the seed choose it;
``"balanced"`` gives every seed one fixed low-discrepancy order (a Kronecker
sequence per kind of draw), in which any stretch of the queue holds sizes
from across the whole distribution. A window that serves only the head of a
long queue, as a backlog's does, needs the balanced order to see the same
work on every seed; the seed then draws the token ids, topics and weights.
"""
from __future__ import annotations

import dataclasses
import json
import math
import statistics
from pathlib import Path
from typing import List, Optional

import numpy as np

MIX_DIR = Path(__file__).resolve().parent / "traffic"
PROCESSES = ("poisson", "backlog")
ORDERS = ("seeded", "balanced")
# one irrational step per kind of draw: user text, output, think time, arrival
_STEPS = (0.6180339887498949, 0.41421356237309515, 0.7320508075688772,
          0.2360679774997898)
_NORMAL = statistics.NormalDist()


def load_mix(name: str, mix_dir: Path = MIX_DIR) -> dict:
    path = Path(mix_dir) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    if mix.get("arrivals", {}).get("process") not in PROCESSES:
        raise ValueError(f"mix {name!r}: arrivals.process must be one of {PROCESSES}")
    if not mix.get("classes"):
        raise ValueError(f"mix {name!r}: no request classes")
    if mix.get("order", "seeded") not in ORDERS:
        raise ValueError(f"mix {name!r}: order must be one of {ORDERS}")
    return mix


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def balanced_order(n: int, kind: int) -> np.ndarray:
    """Position i of the queue takes the rank of frac((i + 1) * step) among
    the n: every stretch of the queue is spread over the whole range."""
    frac = np.mod((np.arange(n) + 1) * _STEPS[kind], 1.0)
    return np.argsort(np.argsort(frac, kind="stable"), kind="stable")


def arrange(x: np.ndarray, rng: np.random.Generator,
            kind: Optional[int] = None) -> np.ndarray:
    """Ascending stratified draws in the order a run offers them: the seed's
    (``kind`` None) or the balanced order of that kind of draw."""
    return rng.permutation(x) if kind is None else x[balanced_order(len(x), kind)]


def lognormal_lengths(spec: dict, n: int, rng: np.random.Generator,
                      kind: Optional[int] = None) -> np.ndarray:
    """n integer lengths: stratified lognormal (median, sigma) clipped to
    [lo, hi], in the order `arrange` gives."""
    z = np.array([_NORMAL.inv_cdf(q) for q in _quantiles(n)])
    x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    x = np.clip(x, spec["lo"], spec["hi"]).astype(np.int64)
    return arrange(x, rng, kind)


def exponential_draws(mean: float, n: int, rng: np.random.Generator,
                      kind: Optional[int] = None) -> np.ndarray:
    return arrange(-mean * np.log1p(-_quantiles(n)), rng, kind)


def stratified_choice(weights, n: int, rng: np.random.Generator) -> np.ndarray:
    """n category indices whose counts follow `weights` as closely as whole
    numbers allow (largest remainders), in an order the rng chooses."""
    w = np.asarray(weights, float) / np.sum(weights)
    counts = np.floor(w * n).astype(int)
    rest = np.argsort(-(w * n - counts), kind="stable")[: n - counts.sum()]
    counts[rest] += 1
    return rng.permutation(np.repeat(np.arange(len(w)), counts))


def zipf_weights(n: int, s: float) -> np.ndarray:
    return 1.0 / np.arange(1, n + 1) ** s


def poisson_starts(arr: dict, n: int, rng: np.random.Generator,
                   kind: Optional[int] = None) -> np.ndarray:
    """n session start times at `rate_per_s` on average; with `bursts` the
    arrivals fall only in the on-part of each period, at the rate that
    keeps the mean."""
    rate = arr["rate_per_s"]
    b = arr.get("bursts")
    period, on = (b["period_s"], b["on_s"]) if b else (1.0, 1.0)
    active = np.cumsum(exponential_draws(on / (rate * period), n, rng, kind))
    return np.floor(active / on) * period + np.mod(active, on)


class TokenSource:
    """Token ids for prompts: uniform, or Zipf inside a topic slice."""

    def __init__(self, mix: dict, vocab: int, rng: np.random.Generator):
        self.rng = rng
        self.vocab = vocab
        spec = mix.get("tokens", {})
        self.topics = int(spec.get("topics", 0))
        if self.topics:
            self.width = vocab // self.topics
            p = zipf_weights(self.width, spec["zipf_s"])
            self.p = p / p.sum()

    def draw(self, n: int, topic: int = 0) -> List[int]:
        if not self.topics:
            return self.rng.integers(0, self.vocab, n).tolist()
        ranks = self.rng.choice(self.width, size=n, p=self.p)
        return (topic * self.width + ranks).tolist()


@dataclasses.dataclass
class Turn:
    user: List[int]
    out_len: int
    think_s: float     # after this turn's reply, before the next turn


@dataclasses.dataclass
class Session:
    start_s: Optional[float]   # from the start of traffic; None in a backlog
    system: Optional[int]      # index into Traffic.system_prompts
    turns: List[Turn]
    cls: int = 0

    def prompt(self, system_prompts) -> List[int]:
        """The first turn's prompt."""
        head = system_prompts[self.system] if self.system is not None else []
        return head + self.turns[0].user


@dataclasses.dataclass
class Traffic:
    process: str
    sessions: List[Session] = dataclasses.field(default_factory=list)
    system_prompts: List[List[int]] = dataclasses.field(default_factory=list)


def build(mix: dict, seed: int, vocab: int, horizon_s: float) -> Traffic:
    """The traffic of one run: the sessions that start in the first
    `horizon_s` seconds of traffic (poisson), or the whole queue (backlog)."""
    rng = np.random.default_rng(int(seed))
    balanced = mix.get("order", "seeded") == "balanced"

    def kind(k: int) -> Optional[int]:
        return k if balanced else None

    toks = TokenSource(mix, vocab, rng)
    cap = mix["context_cap"]
    arr = mix["arrivals"]
    if arr["process"] == "backlog":
        n = int(arr["requests"])
        starts = [None] * n
    else:
        n = max(1, math.ceil(arr["rate_per_s"] * horizon_s))
        starts = poisson_starts(arr, n, rng, kind(3)).tolist()
    classes = mix["classes"]
    which_cls = stratified_choice([c.get("share", 1.0) for c in classes], n, rng)
    topic = stratified_choice(np.ones(max(toks.topics, 1)), n, rng)
    out = Traffic(arr["process"])
    sessions: List[Optional[Session]] = [None] * n
    for ci, c in enumerate(classes):
        idx = np.flatnonzero(which_cls == ci)
        m = len(idx)
        sys_ids = [None] * m
        sp = c.get("system_prompts")
        if sp:
            lens = np.rint(np.linspace(sp["lo"], sp["hi"], sp["count"])).astype(int)
            base = len(out.system_prompts)
            out.system_prompts += [toks.draw(int(k)) for k in rng.permutation(lens)]
            sys_ids = (base + stratified_choice(
                zipf_weights(sp["count"], sp["zipf_s"]), m, rng)).tolist()
        nt = c.get("turns", {"lo": 1, "hi": 1})
        lo, hi = nt["lo"], nt["hi"]
        n_turns = stratified_choice(np.ones(hi - lo + 1), m, rng) + lo
        t = int(n_turns.sum())
        ulen = lognormal_lengths(c["user_len"], t, rng, kind(0))
        olen = lognormal_lengths(c["output_len"], t, rng, kind(1))
        think = (exponential_draws(c["think_s"]["mean"], t, rng, kind(2))
                 if "think_s" in c else np.zeros(t))
        j = 0
        for i, k, s in zip(idx, n_turns, sys_ids):
            head = len(out.system_prompts[s]) if s is not None else 0
            turns = []
            for q in range(int(k)):
                u, o = int(ulen[j]), int(olen[j])
                if q == 0:   # the first prompt leaves room for one token
                    u = min(u, cap - 1 - head)
                    o = min(o, cap - head - u)
                turns.append(Turn(toks.draw(u, int(topic[i])), o, float(think[j])))
                j += 1
            sessions[i] = Session(starts[i], s, turns, ci)
    out.sessions = sessions
    return out
