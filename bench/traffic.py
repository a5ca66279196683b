"""The closed-loop traffic generator that every mix under ``traffic/`` feeds.

A mix is a JSON file of parameters:

* ``clients``: closed-loop clients; each waits for its answer before it
  sends the next request.
* ``taus``: the tolerances one session asks in turn, loosest first; after
  the last the client opens a fresh session.
* ``qoi_order`` and ``qoi_zipf_s``: session QoIs follow a Zipf law with
  exponent ``qoi_zipf_s`` over ``qoi_order`` (most popular first; null
  means the configuration's own order).  The law is met by a fixed
  low-discrepancy sequence, not by sampling: every run of it holds each
  QoI in nearly its share.
* ``stagger``: client ``c`` starts its first session at tau index
  ``c * len(taus) // clients``, so the clients are out of step and do not
  all ask the same thing at once.

The clients deal the QoI sequence between them: client ``c``'s ``k``-th
session takes place ``c + k * clients`` of it, so the sessions the clients
have open at any time are a contiguous run of the sequence and hold each
QoI in nearly its share.  Nothing here depends on the seed: every run of a
mix sends the same requests, whatever its seed, and the seed changes only
the fields (and which answers are checked).  A window that holds part of a
Zipf cycle therefore holds the same part in every run.
"""
from __future__ import annotations

import itertools
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Iterator, List, Sequence, Tuple

import numpy as np

CYCLE = 64          # sessions in one period of a client's QoI sequence


@dataclass(frozen=True)
class Session:
    name: str                   # the sticky session (server client key)
    qoi: str
    taus: Tuple[float, ...]


def load(path: Path) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") != "closed":
        raise ValueError(f"{path}: only closed-loop mixes are generated")
    if int(mix["clients"]) < 1 or not mix["taus"]:
        raise ValueError(f"{path}: needs clients >= 1 and some taus")
    return mix


def zipf_sequence(n: int, s: float, length: int = CYCLE) -> List[int]:
    """Ranks 0..n-1 in an order whose every prefix holds rank ``r`` in
    nearly its Zipf share ``(r+1)^-s / sum``: at each step the rank that is
    furthest behind its share goes next (ties to the more popular)."""
    w = np.array([(r + 1.0) ** -float(s) for r in range(n)])
    p = w / w.sum()
    counts = np.zeros(n)
    seq = []
    for i in range(length):
        r = int(np.argmax(p * (i + 1) - counts))
        counts[r] += 1
        seq.append(r)
    return seq


def client_sessions(mix: dict, qois: Sequence[str]) -> List[Iterator[Session]]:
    """One endless session iterator per client of ``mix``."""
    order = [q for q in (mix.get("qoi_order") or qois) if q in qois]
    if not order:
        raise ValueError("the mix names none of the configuration's QoIs")
    taus = tuple(float(t) for t in mix["taus"])
    n = int(mix["clients"])
    seq = zipf_sequence(len(order), float(mix.get("qoi_zipf_s", 0.0)))

    def sessions(c: int) -> Iterator[Session]:
        start = first_tau(mix, c)
        for k in itertools.count():
            q = order[seq[(c + k * n) % len(seq)]]
            yield Session(f"c{c}.s{k}", q, taus[start if k == 0 else 0:])
    return [sessions(c) for c in range(n)]


def first_tau(mix: dict, c: int) -> int:
    """The tau index at which client ``c`` starts its first session."""
    if not mix.get("stagger"):
        return 0
    return c * len(mix["taus"]) // int(mix["clients"])
