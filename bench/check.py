"""The comparison that decides a run's ``correct``.

Every answer the window completed is held to the configuration's
guarantees.  The cheap ones are checked on every answer: it came back, it
is guaranteed and not degraded.  The values are checked on a sample drawn
from the seed (``Sampler``), once the window has closed: for each sampled
answer the configuration's plain NumPy reference evaluates the QoI on the
served fields and on the full-precision fields, and

    err_over_bound = max |QoI(served) - QoI(original)| / reported bound
    bound_over_tau = reported bound / (tau * range of QoI(served))

are compared with the configuration's limits (both 1: the certificate and
the requested tolerance).  ``tau * range`` is the absolute tolerance as the
retrieval loop defines it.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

PER_STRATUM = 4     # answers sampled per (QoI, tau)


@dataclass
class Answer:
    """One request of the window, as the client saw it."""
    client: int
    session: str
    qoi: str
    tau: float
    submitted: float            # s after the window opened
    done: float                 # s after the window opened
    latency_s: float = 0.0      # the server's own handle time
    bytes_moved: int = 0
    bound: float = float("nan")
    guaranteed: bool = False
    degraded: bool = False
    error: Optional[str] = None

    @property
    def wait_s(self) -> float:
        return self.done - self.submitted

    @property
    def certified(self) -> bool:
        return self.error is None and self.guaranteed and not self.degraded


class Sampler:
    """Stratified reservoir of answers whose values are kept for the
    check: up to ``per_stratum`` per (QoI, tau), drawn from the seed.
    Thread-safe; ``read`` is called only for answers that are kept."""

    def __init__(self, seed: int, strata: Sequence[Tuple[str, float]],
                 per_stratum: int = PER_STRATUM):
        self._k = per_stratum
        self._mu = threading.Lock()
        self._rng = {s: np.random.default_rng([int(seed), i])
                     for i, s in enumerate(strata)}
        self._seen: Dict[Tuple[str, float], int] = {}
        self.kept: Dict[Tuple[str, float], List] = {s: [] for s in strata}

    def offer(self, answer: Answer, read: Callable[[], Dict[str, np.ndarray]]
              ) -> None:
        key = (answer.qoi, answer.tau)
        with self._mu:
            i = self._seen.get(key, 0)
            self._seen[key] = i + 1
            slot = i if i < self._k else int(self._rng[key].integers(i + 1))
        if slot >= self._k:
            return
        values = read()
        with self._mu:
            kept = self.kept[key]
            if slot < len(kept):
                kept[slot] = (answer, values)
            else:
                kept.append((answer, values))

    def samples(self) -> List[Tuple[Answer, Dict[str, np.ndarray]]]:
        return [s for kept in self.kept.values() for s in kept]


def compare(samples, answers: Sequence[Answer], reference, fields,
            limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """Every number compared, each with its limit (``max`` or ``min``)."""
    truth: Dict[str, np.ndarray] = {}
    err_over_bound, bound_over_tau = 0.0, 0.0
    for a, values in samples:
        if a.qoi not in truth:
            truth[a.qoi] = reference.qoi(a.qoi, fields)
        served = reference.qoi(a.qoi, values)
        true_err = float(np.max(np.abs(served - truth[a.qoi])))
        rng = float(np.max(served) - np.min(served))
        tau_abs = a.tau * (rng if rng > 0 else 1.0)
        if a.bound > 0:
            err_over_bound = max(err_over_bound, true_err / a.bound)
        elif true_err > 0 or not np.isfinite(a.bound):
            err_over_bound = float("inf")
        bound_over_tau = max(bound_over_tau, a.bound / tau_abs)
    return {
        "err_over_bound": {"value": err_over_bound,
                           "max": float(limits["err_over_bound"])},
        "bound_over_tau": {"value": bound_over_tau,
                           "max": float(limits["bound_over_tau"])},
        "uncertified": {"value": sum(1 for a in answers
                                     if a.error is None and not a.certified),
                        "max": 0},
        "unanswered": {"value": sum(1 for a in answers if a.error is not None),
                       "max": 0},
        "checked": {"value": len(samples), "min": 1},
    }


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    for c in checks.values():
        v = c["value"]
        if not np.isfinite(v):
            return False
        if "max" in c and v > c["max"]:
            return False
        if "min" in c and v < c["min"]:
            return False
    return True


def describe(checks: Dict[str, Dict[str, float]]) -> List[str]:
    """One line per number compared, with its limit."""
    out = []
    for name, c in checks.items():
        op, lim = ("<=", c["max"]) if "max" in c else (">=", c["min"])
        out.append(f"check {name} {c['value']!r} {op} {lim!r}")
    return out
