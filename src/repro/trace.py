"""Spans and counters of the served path, on the profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``.  While a
profiler runs it is a host event in the profiler's own trace, on the same
clock as the device planes, so each idle gap of the device can be put
down to what the host was doing.  With no profiler running it records
nothing and costs about half a microsecond.  Spans nest by thread: the
enclosing span on the same thread is a span's parent.  Only the request
span (and the batcher's flush, once per window) carries metadata; the
``client`` and ``seq`` of a request's span identify the request whose
work its child spans cover.

The span names, one table for the code and for PERF.md:

==============================  =========================================
``repro.request``               one request on its worker thread
``repro.retrieval.reconstruct`` one variable's progressive refresh
``repro.retrieval.estimate``    QoI estimate: upload, dispatch, bounds
``repro.store.read``            store read + crc32c check (not cache hits)
``repro.codec.inflate``         host entropy decode of fetched planes
``repro.batch.window``          waiting for a decode batch to fill / drain
``repro.batch.flush``           the batcher's drain, stack and dispatch
``repro.reader.refresh``        contribution rebuild + fixed-order sum
``repro.device.wait``           waiting for device work before a D2H copy
``repro.transfer.d2h``          the device-to-host copy itself
==============================  =========================================

Counters follow the serve plane's pattern, one lock-guarded stats object
per subsystem merged by ``RetrievalServer.metrics()``: ``TransferStats``
counts bytes across the host-device boundary, and ``compiles()`` is the
process's count of backend compiles (a ``jax.monitoring`` listener).
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

REQUEST = "repro.request"
RECONSTRUCT = "repro.retrieval.reconstruct"
ESTIMATE = "repro.retrieval.estimate"
STORE_READ = "repro.store.read"
INFLATE = "repro.codec.inflate"
BATCH_WINDOW = "repro.batch.window"
BATCH_FLUSH = "repro.batch.flush"
READER_REFRESH = "repro.reader.refresh"
DEVICE_WAIT = "repro.device.wait"
TRANSFER_D2H = "repro.transfer.d2h"
SPANS = (REQUEST, RECONSTRUCT, ESTIMATE, STORE_READ, INFLATE, BATCH_WINDOW,
         BATCH_FLUSH, READER_REFRESH, DEVICE_WAIT, TRANSFER_D2H)

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def span(name: str, **meta) -> TraceAnnotation:
    """A host span named ``name`` (one of ``SPANS``) for a ``with``
    block; ``meta`` becomes the event's arguments in the trace."""
    return TraceAnnotation(name, **meta)


@dataclass(eq=False)      # an identity, so SessionOptions stays hashable
class TransferStats:
    """Bytes across the host-device boundary on the retrieval path:
    every device-to-host copy made through ``to_host``, and every host
    array handed to the device (counted by the caller with ``note_h2d``)."""
    d2h_bytes: int = 0
    h2d_bytes: int = 0
    _mu: threading.Lock = field(default_factory=threading.Lock,
                                repr=False, compare=False)

    def note(self, d2h: int = 0, h2d: int = 0) -> None:
        with self._mu:
            self.d2h_bytes += int(d2h)
            self.h2d_bytes += int(h2d)

    def as_dict(self) -> Dict[str, float]:
        with self._mu:
            return {"d2h_bytes_total": float(self.d2h_bytes),
                    "h2d_bytes_total": float(self.h2d_bytes)}


def note_h2d(xfer: Optional[TransferStats], *arrays) -> None:
    """Count the host arrays about to be handed to the device."""
    if xfer is not None:
        xfer.note(h2d=sum(int(a.nbytes) for a in arrays))


def to_host(x, xfer: Optional[TransferStats] = None) -> np.ndarray:
    """``np.asarray`` of the device array ``x``: the wait for the work
    that makes it under ``repro.device.wait``, the copy alone under
    ``repro.transfer.d2h``, its bytes counted in ``xfer``.  The wait adds
    no work: ``np.asarray`` blocks until ``x`` is ready either way."""
    with span(DEVICE_WAIT):
        jax.block_until_ready(x)
    with span(TRANSFER_D2H):
        out = np.asarray(x)
    if xfer is not None:
        xfer.note(d2h=out.nbytes)
    return out


class CompileStats:
    """Backend compiles (or loads from the persistent compilation cache)
    and their seconds, from JAX's compile-duration events."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            with self._mu:
                self.count += 1
                self.seconds += duration

    def snapshot(self) -> Tuple[int, float]:
        with self._mu:
            return self.count, self.seconds


_compiles: Optional[CompileStats] = None
_compiles_mu = threading.Lock()


def compiles() -> CompileStats:
    """The process's compile counter; its ``jax.monitoring`` listener is
    installed on the first call, and counts from then on."""
    global _compiles
    with _compiles_mu:
        if _compiles is None:
            _compiles = CompileStats()
            jax.monitoring.register_event_duration_secs_listener(_compiles)
        return _compiles
