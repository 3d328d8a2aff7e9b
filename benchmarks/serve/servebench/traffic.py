"""Traffic schedules, made from a mix file and ``--seed`` alone.

A mix file (``traffic/<mix>.json``) holds parameters only: the loop
(open or closed), the arrival rate or the client count, and the length
distributions.  This one generator reads every mix.

Every seed gets the same multiset of sizes and of arrival gaps: each
distribution is sampled at stratified quantiles ``(i + 0.5) / n``.  In
an open loop every request of the schedule is due in the window, and
the seed shuffles the order and draws the token ids.  A closed loop's
window takes only the first few requests of each client, so their
order decides the window's work: there every seed gets the same order,
and draws only the token ids.  So runs with different seeds do the same
amount of work, and the spread between seeds is the system's, not the
dice's.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

_STD_NORMAL = NormalDist()


def bucket(n: int) -> int:
    """Cache positions a prompt of ``n`` tokens occupies in the engine:
    it left-pads every prompt to a power of two, at least 8."""
    b = 8
    while b < n:
        b *= 2
    return b


def rng_for(seed: int, *path: int) -> np.random.Generator:
    """A generator keyed by the seed and a path of integers (session,
    turn, ...): the same request gets the same tokens whatever order the
    run reaches it in."""
    return np.random.default_rng([int(seed) & (2**64 - 1), *path])


def _quantile(spec: Dict, u: float) -> float:
    dist = spec["dist"]
    if dist == "lognormal":
        return spec["median"] * math.exp(spec["sigma"] * _STD_NORMAL.inv_cdf(u))
    if dist == "uniform":
        return spec["min"] + u * (spec["max"] - spec["min"] + 1)
    if dist == "exponential":
        return -math.log1p(-u) * spec["mean"]
    raise ValueError(f"unknown distribution {dist!r}")


def deck(spec: Dict, n: int, rng: np.random.Generator,
         integer: bool = True) -> np.ndarray:
    """``n`` stratified draws of ``spec``, clipped to its ``min``/``max``
    and shuffled by ``rng``."""
    vals = np.array([_quantile(spec, (i + 0.5) / n) for i in range(n)])
    if integer:
        vals = np.floor(vals) if spec["dist"] == "uniform" else np.round(vals)
    lo, hi = spec.get("min"), spec.get("max")
    if lo is not None or hi is not None:
        vals = np.clip(vals, lo, hi)
    vals = vals.astype(np.int64) if integer else vals
    return rng.permutation(vals)


@dataclasses.dataclass(frozen=True)
class Turn:
    user_len: int           # new prompt tokens this turn adds
    answer_len: int         # tokens the request asks for (no EOS)


@dataclasses.dataclass(frozen=True)
class Session:
    """One user: its turns are sent one after another, each carrying
    the whole history (system prompt, earlier turns and answers)."""
    index: int
    client: int
    arrival_s: float        # open loop: offset from the window's start
    turns: List[Turn]


@dataclasses.dataclass
class Schedule:
    mix: Dict
    seed: int
    vocab: int
    sessions: List[Session]
    loop: str               # "open" | "closed"
    clients: int
    system: np.ndarray      # shared system prompt (may be empty)

    def user_tokens(self, session: int, turn: int, n: int) -> np.ndarray:
        """The ``n`` user tokens of one turn: ids in ``[1, vocab)``
        (0 is the engine's padding id)."""
        return rng_for(self.seed, 1, session, turn).integers(
            1, self.vocab, n, dtype=np.int64).astype(np.int32)

    def turn_gap_s(self) -> float:
        return float(self.mix.get("turn_gap_s", 0.0))


def _turn_counts(mix: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    spec = mix.get("turns", {"dist": "uniform", "min": 1, "max": 1})
    return deck(spec, n, rng)


def make_schedule(mix: Dict, seed: int, vocab: int, seconds: float,
                  per_client: int = 256) -> Schedule:
    """The whole schedule of one run.

    Open loop: ``round(rate * seconds)`` sessions, Poisson arrivals from
    a stratified deck of exponential gaps.  Closed loop: ``clients``
    clients, each with ``per_client`` requests of its own that it sends
    one after another."""
    rng = rng_for(seed if mix["loop"] == "open" else 0, 0)
    system = rng_for(seed, 2).integers(
        1, vocab, int(mix.get("system_prompt_tokens", 0)),
        dtype=np.int64).astype(np.int32)
    loop = mix["loop"]
    if loop == "open":
        n = max(1, round(mix["rate_per_s"] * seconds))
        gaps = deck({"dist": "exponential", "mean": 1.0 / mix["rate_per_s"]},
                    n, rng, integer=False)
        arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        clients = int(mix["clients"])
    elif loop == "closed":
        clients = int(mix["clients"])
        n = clients * per_client
        arrivals = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {loop!r}")
    n_turns = _turn_counts(mix, n, rng)
    total = int(n_turns.sum())
    users = deck(mix["prompt"], total, rng)
    answers = deck(mix["output"], total, rng)
    sessions, k = [], 0
    for i in range(n):
        turns = [Turn(int(users[k + t]), int(answers[k + t]))
                 for t in range(int(n_turns[i]))]
        k += len(turns)
        # Closed loop: client c owns sessions c, c + C, c + 2C, ... in
        # that order, so which requests a client sends never depends on
        # timing, only how many of them it gets to.
        sessions.append(Session(i, i % clients, float(arrivals[i]), turns))
    return Schedule(mix, seed, vocab, sessions, loop, clients, system)


def prompt_len(sched: Schedule, s: Session, turn: int) -> int:
    """Prompt tokens of turn ``turn``: system prompt, every user turn up
    to this one, and every earlier answer."""
    return (len(sched.system) + sum(t.user_len for t in s.turns[:turn + 1])
            + sum(t.answer_len for t in s.turns[:turn]))


def largest_footprint(sched: Schedule) -> int:
    """The most cache positions any request of the schedule needs: its
    bucketed prompt plus its output."""
    return max(bucket(prompt_len(sched, s, t)) + s.turns[t].answer_len
               for s in sched.sessions for t in range(len(s.turns)))


def check_fits(sched: Schedule, max_len: int, max_batch: int,
               pool_positions: Optional[int] = None) -> None:
    """Raise unless every request fits ``max_len`` (the engine refuses
    one that does not) and ``max_batch`` of the largest fit the pool at
    once (the engine rejects an admission the pool cannot hold)."""
    worst = largest_footprint(sched)
    if worst > max_len:
        raise ValueError(f"a request needs {worst} positions > max_len "
                         f"{max_len}")
    if pool_positions is not None and max_batch * worst > pool_positions:
        raise ValueError(f"{max_batch} requests of {worst} positions do not "
                         f"fit a pool of {pool_positions}")
