"""The one traffic generator: a mix file of parameters -> the run's requests.

A mix is a JSON file beside this one (``traffic/<mix>.json``). Its keys:

* ``loop``: ``"open"`` (sessions arrive as a Poisson process at the cell's
  ``sessions_per_s``) or ``"closed"`` (``clients`` callers, each sending its
  next request when the last one finished);
* ``prompt`` / ``output``: length distributions (see :func:`quantile`);
* ``turns``: ``{"min", "max"}``, uniform whole turns per session (open loop);
* ``paid_share``: share of sessions on the paid (SECDED) tier;
* ``sessions`` (closed loop, optional): ``{"pick": "zipf", "theta",
  "working_set": f}`` reuses N sessions, N sized so that their KV at their
  mean context is ``f`` times the pool's device capacity; without it every
  request is a one-turn session of its own.

Every seed gets the same *set* of sizes and gaps: each quantity is a block
of stratified quantiles of its distribution, shuffled. An open loop draws
its k sessions as one block of k quantiles, shuffled by the fixed
:data:`SCHEDULE_SEED`, so every run offers the same sessions at the same
times and the run's seed draws only the token ids; a closed loop shuffles
its blocks of :data:`BLOCK` by the run's seed, and any whole number of
blocks holds the same multiset.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np

#: Stratification block: quantiles (i + 0.5) / BLOCK of a distribution.
BLOCK = 32
#: Seed of the open loop's schedule (arrival gaps, sizes, tiers): the same
#: for every run, so that runs differ by the system under test and not by
#: a burst of arrivals that one seed's order happens to make.
SCHEDULE_SEED = 0


def quantile(dist: dict, q: float) -> int:
    """Inverse CDF of a length distribution at ``q`` in (0, 1).

    ``{"dist": "lognormal", "median", "sigma", "min", "max", "round_up"}``,
    ``{"dist": "uniform", "min", "max"}`` (whole numbers, both ends in) or
    ``{"dist": "choice", "values": [...]}`` (equally likely).
    """
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(q))
        x = min(max(x, dist["min"]), dist["max"])
        step = dist.get("round_up", 1)
        return int(math.ceil(x / step) * step)
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        return int(lo + min(int(q * (hi - lo + 1)), hi - lo))
    if kind == "choice":
        vals = dist["values"]
        return int(vals[min(int(q * len(vals)), len(vals) - 1)])
    raise ValueError(f"unknown distribution {kind!r}")


def support(dist: dict) -> list[int]:
    """Every value :func:`quantile` can return on the stratified grid of a
    closed loop (blocks of :data:`BLOCK`)."""
    return sorted({quantile(dist, (i + 0.5) / BLOCK) for i in range(BLOCK)})


def strata(dist: dict, n: int, rng: np.random.Generator,
           block: int = BLOCK) -> np.ndarray:
    """``n`` values: whole blocks of ``block`` stratified quantiles, each
    block shuffled by ``rng`` (the last block cut to length)."""
    grid = np.asarray([quantile(dist, (i + 0.5) / block)
                       for i in range(block)], np.int64)
    blocks = [rng.permutation(grid) for _ in range(-(-n // block))]
    return np.concatenate(blocks)[:n] if blocks else grid[:0]


def exp_grid(rate: float, block: int) -> np.ndarray:
    """Stratified quantiles of the exponential gap at ``rate``."""
    q = (np.arange(block) + 0.5) / block
    return -np.log1p(-q) / rate


def exp_gaps(rate: float, n: int, rng: np.random.Generator,
             block: int = BLOCK) -> np.ndarray:
    """``n`` Poisson inter-arrival gaps (s): stratified exponential
    quantiles, shuffled in blocks like :func:`strata`."""
    grid = exp_grid(rate, block)
    blocks = [rng.permutation(grid) for _ in range(-(-n // block))]
    return np.concatenate(blocks)[:n] if blocks else grid[:0]


@dataclass
class Session:
    sid: str
    tier: str
    prompt: np.ndarray
    turns: list[int] = field(default_factory=list)   # max_new per turn
    arrival_s: float = 0.0   # open loop: first turn's scheduled arrival


def prompt_tokens(rng: np.random.Generator, n: int, vocab: int
                  ) -> np.ndarray:
    return rng.integers(0, vocab, n).astype(np.int32)


def open_loop(mix: dict, rate: float, seconds: float, vocab: int,
              rng: np.random.Generator) -> list[Session]:
    """Sessions due in ``[0, seconds)`` at ``rate`` sessions/s: the same
    gaps, prompts, turns, outputs and tiers for every run (each one block
    of stratified quantiles, shuffled by :data:`SCHEDULE_SEED`); ``rng``
    draws the prompts' token ids."""
    order = np.random.default_rng(SCHEDULE_SEED)
    k = max(1, int(math.ceil(rate * seconds)))
    while k > 1 and exp_grid(rate, k).sum() >= seconds:
        k -= 1      # the last arrival (the gaps' fixed sum) in the window
    gaps = exp_gaps(rate, k, order, block=k)
    prompts = strata(mix["prompt"], k, order, block=k)
    tdist = {"dist": "uniform", **mix["turns"]}
    turns = strata(tdist, k, order, block=k)
    n_out = int(turns.sum())
    outs = strata(mix["output"], n_out, order, block=n_out)
    paid = np.zeros(k, bool)
    paid[:int(round(mix.get("paid_share", 0.0) * k))] = True
    paid = order.permutation(paid)
    sessions, t, o = [], 0.0, 0
    for i in range(k):
        t += float(gaps[i])
        if t >= seconds:
            break
        s = Session(f"c{i}", "paid" if paid[i] else "batch",
                    prompt_tokens(rng, int(prompts[i]), vocab), arrival_s=t)
        for _ in range(int(turns[i])):
            s.turns.append(int(outs[o]))
            o += 1
        sessions.append(s)
    return sessions


class ClosedLoop:
    """The request sequence of a closed loop: :meth:`next` hands a caller
    its next request as ``(session id, tier, prompt, max_new, fresh)``.
    ``fresh`` means a new session (the engine prefills ``prompt``); else
    the request is the next turn of a parked session."""

    def __init__(self, mix: dict, vocab: int, rng: np.random.Generator,
                 max_len: int, n_sessions: int = 0):
        self.mix, self.vocab, self.rng = mix, vocab, rng
        self.max_len = max_len
        self.n_sessions = n_sessions
        self._prompts: list[int] = []
        self._outs: list[int] = []
        self._paid: list[bool] = []
        self._made = 0
        # zipf sessions: rank -> [sid, tier, prompt, context length]
        self.slots: list[list] = []
        self.retired: list[str] = []
        if n_sessions:
            theta = mix["sessions"]["theta"]
            w = 1.0 / np.arange(1, n_sessions + 1) ** theta
            self._p = w / w.sum()
            self._rank = rng.permutation(n_sessions)   # popularity -> slot
            for _ in range(n_sessions):
                self.slots.append(self._fresh())

    def _draw(self, buf: list, dist: dict) -> int:
        if not buf:
            buf.extend(strata(dist, BLOCK, self.rng).tolist())
        return int(buf.pop())

    def _draw_paid(self) -> bool:
        if not self._paid:
            share = self.mix.get("paid_share", 0.0)
            blk = np.zeros(BLOCK, bool)
            blk[:int(round(share * BLOCK))] = True
            self._paid.extend(self.rng.permutation(blk).tolist())
        return bool(self._paid.pop())

    def _fresh(self) -> list:
        sid = f"q{self._made}"
        self._made += 1
        p = self._draw(self._prompts, self.mix["prompt"])
        tier = "paid" if self._draw_paid() else "batch"
        return [sid, tier, prompt_tokens(self.rng, p, self.vocab), 0]

    def next(self, busy: set[str]) -> tuple[str, str, np.ndarray, int, bool]:
        m = self._draw(self._outs, self.mix["output"])
        if not self.n_sessions:
            sid, tier, prompt, _ = self._fresh()
            return sid, tier, prompt, m, True
        while True:
            slot = int(self._rank[self.rng.choice(self.n_sessions,
                                                  p=self._p)])
            if self.slots[slot][0] not in busy:
                break
        s = self.slots[slot]
        ctx = s[3]
        # a turn that would not fit the block table starts a new session
        # in this slot: the old one is retired (the caller closes it)
        if ctx and ctx - 1 + m > self.max_len:
            self.retired.append(s[0])
            s = self.slots[slot] = self._fresh()
            ctx = 0
        fresh = ctx == 0
        s[3] = (len(s[2]) if fresh else ctx) + m
        return s[0], s[1], s[2], m, fresh


def mean_context(mix: dict, max_len: int) -> float:
    """Time-mean context (tokens) of a session that takes turns until its
    table is full, with the mix's stratified prompts and outputs; the
    sizing rule of the closed loop's ``working_set``."""
    prompts = [quantile(mix["prompt"], (i + 0.5) / BLOCK)
               for i in range(BLOCK)]
    outs = [quantile(mix["output"], (i + 0.5) / BLOCK) for i in range(BLOCK)]
    total = weight = 0.0
    for i, p in enumerate(prompts):
        ctx, j = p, i
        while True:
            m = outs[j % BLOCK]
            j += 7
            if ctx - 1 + m > max_len:
                break
            total += (ctx + m / 2) * m
            weight += m
            ctx += m
    return total / weight
