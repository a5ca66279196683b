"""Concurrent multi-tenant serve plane.

The paper's deployment shape (Fig. 1) is many analysis clients pulling
on-demand-precision reconstructions from ONE progressive archive.  This
package turns ``repro.launch.serve`` from a sequential for-loop into a
real service:

  * :mod:`repro.serve.pool`     — bounded worker pool with per-session
    locking, load shedding (503 + Retry-After past the high-water mark)
    and handle-latency histograms.
  * :mod:`repro.serve.coalesce` — cross-session request coalescing: N
    clients tightening the same variable to the same eps from the same
    decode state share one fetch + one recompose; the result is fanned
    out to every waiter (bit-identical by the plane-count invariant).
  * :mod:`repro.serve.batch`    — cross-session decode batching: one
    vmapped fused decode + recompose dispatch per serve tick covering
    every reader's newly fetched planes, with a per-reader fallback for
    stragglers whose shape matches nobody.
  * :mod:`repro.serve.budget`   — server-level pooled contribution
    budget replacing the per-variable ``contrib_budget_bytes``: readers
    borrow/return field-sized leases against one pool so the hottest
    variables win.
  * :mod:`repro.serve.metrics`  — plaintext counter dump + log-bucketed
    latency histogram backing the ``/health`` and ``/metrics`` endpoints
    on :mod:`repro.store.httpd`.

Everything here is pure stdlib + numpy; the decode/recompose layers are
untouched except for the borrow/adopt hooks in ``core/refactor.py``.
"""
from repro.serve.batch import BatcherStats, DecodeBatcher
from repro.serve.budget import ContribBudgetPool, PoolStats
from repro.serve.coalesce import CoalesceStats, ReconstructCoalescer
from repro.serve.metrics import LatencyHistogram, render_metrics
from repro.serve.pool import ServePlane, ServerOverloadedError

__all__ = [
    "BatcherStats",
    "DecodeBatcher",
    "ContribBudgetPool",
    "PoolStats",
    "CoalesceStats",
    "ReconstructCoalescer",
    "LatencyHistogram",
    "render_metrics",
    "ServePlane",
    "ServerOverloadedError",
]
