"""Sessions of turns: the general traffic generator.

A traffic mix (``bench/traffic/<mix>.json``) gives the parameters; this
module turns them, a seed and a window length into a plan of sessions.
Each session picks a shared system prompt (or none), then runs a number
of turns: turn ``k``'s prompt is the system prompt, every earlier user
message and served answer, and a new user message; its answer has a
fixed length (``max_new_tokens``).  A session ends at its last turn or
before the turn that would pass ``engine.max_len``.

Keys of a mix:

- ``session_rate``: sessions arrive as a Poisson process at this rate
  per second, independent of service (an open loop);
- ``system_prompts``, ``system_len``, ``zipf_s``: how many shared system
  prompts, their length, and the Zipf exponent of their popularity (0
  prompts: no shared prefix);
- ``turns``: ``[min, max]`` turns per session, uniform;
- ``user`` and ``answer``: lognormal lengths, ``{"median", "sigma",
  "min", "max"}``;
- ``think_s``: mean of the exponential pause between an answer's end and
  the next turn being due (0: at once);
- ``engine``: ``{"slots", "max_len"}``, the engine the mix is served by;
- ``shape_seed``: the seed of the sizes and arrivals.  A run's ``--seed``
  draws only the token ids, so every seed serves the same sessions at the
  same times: the seed changes the data, never the work.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class Turn:
    user: np.ndarray    # the new user message's token ids
    answer: int         # max_new_tokens
    think_s: float      # pause before this turn is due (after the last answer)


@dataclass
class Session:
    index: int                    # position in the shape stream
    system: int                   # system prompt id, -1 for none
    turns: list = field(default_factory=list)
    arrival: float = 0.0          # seconds after the window opens


@dataclass
class Plan:
    sessions: list                # by arrival
    system_prompts: list          # token id arrays


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def session_shapes(mix: dict, n: int) -> list:
    """The first ``n`` sessions of the mix's shape stream, as
    ``(system, [(user_len, answer_len, think_s), ...])``; a function of
    ``shape_seed`` alone."""
    rng = np.random.default_rng(mix["shape_seed"])
    n_sys = mix["system_prompts"]
    sys_len = mix["system_len"] if n_sys else 0
    max_len = mix["engine"]["max_len"]
    lo, hi = mix["turns"]
    out = []
    for _ in range(n):
        system = (int(rng.choice(n_sys, p=zipf_weights(n_sys, mix["zipf_s"])))
                  if n_sys else -1)
        k = int(rng.integers(lo, hi + 1))
        users = _lognormal(rng, mix["user"], k)
        answers = _lognormal(rng, mix["answer"], k)
        thinks = (rng.exponential(mix["think_s"], k) if mix["think_s"]
                  else np.zeros(k))
        turns, context = [], sys_len
        for u, a, t in zip(users, answers, thinks):
            if context + u + a > max_len:
                break
            turns.append((int(u), int(a), float(t)))
            context += u + a
        out.append((system, turns))
    return out


def arrivals(mix: dict, seconds: float) -> np.ndarray:
    """Arrival times within ``[0, seconds)``; the gaps are a
    fixed stream scaled by the rate."""
    rng = np.random.default_rng([mix["shape_seed"], 1])
    rate = mix["session_rate"]
    times, t = [], 0.0
    while True:
        t += rng.exponential(1.0) / rate
        if t >= seconds:
            return np.asarray(times)
        times.append(t)


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> Plan:
    """The plan of one run."""
    times = arrivals(mix, seconds)
    shapes = session_shapes(mix, len(times))
    rng = np.random.default_rng([seed, 3])
    systems = [rng.integers(0, vocab, mix["system_len"]).astype(np.int32)
               for _ in range(mix["system_prompts"])]
    sessions = []
    for i, (system, turns) in enumerate(shapes):
        s = Session(index=i, system=system, arrival=float(times[i]))
        for u, a, t in turns:
            s.turns.append(Turn(user=rng.integers(0, vocab, u).astype(
                np.int32), answer=a, think_s=t))
        sessions.append(s)
    return Plan(sessions=sessions, system_prompts=systems)
