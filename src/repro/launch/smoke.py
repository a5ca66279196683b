"""The served retrieval path, driven once and checked against NumPy.

``serve_and_check`` generates seeded Hurricane-like velocity fields,
refactors them into a fresh store archive through ``RetrievalServer``
(refactor -> save -> ``open_archive``), and serves staggered, tightening
VTOT requests from a few sticky clients through ``RetrievalServer.submit``
— worker pool, coalescer and a shared ``DecodeBatcher`` included.  Client
``k`` starts its tau ladder ``k`` waves late (and stops ``k`` steps short),
so concurrent requests sit at different decode states and their device
work can share batched dispatches.  Every answer is held to the certified-retrieval contract
against a float64 NumPy VTOT on the full-precision fields:

    true error <= certified bound <= tau_abs,  guaranteed, not degraded

with the values read back from the client's sticky session
(``RetrievalSession.current``) and ``tau_abs`` = tau x the range of the
reconstructed VTOT, as the retrieval loop defines it.  ``chip_smoke.py``
runs this at Hurricane ISABEL scale on one TPU; the tests run it at a tiny
shape on the CPU.
"""
from __future__ import annotations

import os
import threading
import time
from concurrent.futures import as_completed
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from repro.data.synthetic import nyx_like_fields
from repro.launch.serve import Request, RetrievalServer

TAUS: Tuple[float, ...] = (1e-2, 1e-4, 1e-6)
VELOCITY = ("Vx", "Vy", "Vz")
SEED = 42
CLIENTS = ("client0", "client1")
BATCH_WINDOW_MS = 20.0
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


@dataclass
class Answer:
    """One served request and its check against the NumPy reference."""
    client: str
    tau: float
    latency_s: float          # server-side handle time
    client_s: float           # submit -> result, as the client waits
    bytes_moved: int
    true_error: float
    bound: float              # certified max VTOT error bound
    tau_abs: float
    guaranteed: bool
    degraded: bool

    @property
    def ok(self) -> bool:
        return (self.guaranteed and not self.degraded
                and self.true_error <= self.bound <= self.tau_abs)


@dataclass
class SmokeReport:
    generate_s: float
    refactor_s: float         # refactor + save + open, server build
    compile_s: float          # trace + lower + backend compile, summed
    answers: List[Answer] = field(default_factory=list)
    batch_stats: Dict[str, float] = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return bool(self.answers) and all(a.ok for a in self.answers)


class _CompileClock:
    """Sums JAX's compile-phase durations while registered (thread-safe:
    workers compile concurrently)."""

    def __init__(self):
        self._mu = threading.Lock()
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            with self._mu:
                self.seconds += duration

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self)


def vtot(fields: Dict[str, np.ndarray]) -> np.ndarray:
    """Plain float64 VTOT = sqrt(Vx² + Vy² + Vz²)."""
    vx, vy, vz = (np.asarray(fields[v], np.float64) for v in VELOCITY)
    return np.sqrt(vx * vx + vy * vy + vz * vz)


def schedule(clients: Sequence[str],
             taus: Sequence[float]) -> List[List[Tuple[str, float]]]:
    """Waves of concurrent (client, tau) requests: client k starts k waves
    late and tightens through the first ``len(taus) - k`` tolerances, so
    every wave after the first overlaps two decode states and the run
    ends with the first client's tightest answer."""
    return [[(c, taus[w - k]) for k, c in enumerate(clients)
             if 0 <= w - k < len(taus) - k]
            for w in range(len(taus))]


def serve_and_check(shape: Tuple[int, int, int], workdir: str,
                    log: Callable[[str], None] = print) -> SmokeReport:
    """Serve the tau ladder from a store archive refactored into
    ``workdir`` (which must not hold one already) and check every answer;
    see the module docstring."""
    with _CompileClock() as clock:
        t0 = time.perf_counter()
        fields = nyx_like_fields(shape=tuple(shape), seed=SEED)
        ref = vtot(fields)
        generate_s = time.perf_counter() - t0
        store = os.path.join(workdir, "velocity.prs")
        if os.path.exists(store):
            raise FileExistsError(f"{store} exists: the smoke refactors "
                                  f"into a fresh directory")
        server = RetrievalServer(fields, store_path=store,
                                 workers=len(CLIENTS),
                                 decode_batch_ms=BATCH_WINDOW_MS)
        report = SmokeReport(generate_s=generate_s,
                             refactor_s=server.refactor_s, compile_s=0.0)
        log(f"[smoke] {len(VELOCITY)} fields {tuple(shape)} generated in "
            f"{generate_s:.3f}s; refactored into "
            f"{server.archive.total_nbytes} B in {server.refactor_s:.3f}s")
        try:
            for wave in schedule(CLIENTS, TAUS):
                report.answers += _serve_wave(server, wave, ref, log)
            if server.decode_batcher is not None:
                report.batch_stats = server.decode_batcher.stats.as_dict()
        finally:
            server.close()
    report.compile_s = clock.seconds
    return report


def _serve_wave(server: RetrievalServer, wave, ref: np.ndarray,
                log: Callable[[str], None]) -> List[Answer]:
    """Submit one wave concurrently, then check each answer."""
    futures, submitted = [], []
    for client, tau in wave:
        submitted.append(time.perf_counter())
        futures.append(server.submit(Request(client=client, qois=["VTOT"],
                                             tau=tau)))
    index = {fut: i for i, fut in enumerate(futures)}
    done: Dict[int, float] = {}
    for fut in as_completed(futures):          # stamp in completion order
        done[index[fut]] = time.perf_counter() - submitted[index[fut]]
    answers = []
    for i, ((client, tau), fut) in enumerate(zip(wave, futures)):
        out = fut.result()
        session = server.sessions[client]
        q = vtot({v: session.current(v)[0] for v in VELOCITY})
        rng = float(np.max(q) - np.min(q))
        a = Answer(client=client, tau=tau, latency_s=out["latency_s"],
                   client_s=done[i],
                   bytes_moved=int(out["bytes_moved"]),
                   true_error=float(np.max(np.abs(q - ref))),
                   bound=float(out["est_errors"]["VTOT"]),
                   tau_abs=tau * (rng if rng > 0 else 1.0),
                   guaranteed=bool(out["guaranteed"]),
                   degraded=bool(out["degraded"]))
        log(f"[smoke] {client} tau={tau:.0e} latency={a.latency_s:.3f}s "
            f"client={a.client_s:.3f}s moved={a.bytes_moved}B "
            f"true_err={a.true_error:.6e} bound={a.bound:.6e} "
            f"tau_abs={a.tau_abs:.6e} guaranteed={a.guaranteed} "
            f"degraded={a.degraded} ok={a.ok}")
        answers.append(a)
    return answers
