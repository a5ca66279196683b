"""Concurrent serve-plane bench: 64 simulated clients against one archive
behind a modelled network link (RemoteByteStore — real per-request latency,
shared-link wire time), sequential for-loop vs worker pool + coalescing.

What these rows watch across PRs:

  * ``serve/seq/clients=64`` — the pre-serve-plane shape: one thread
    handles the client stream in arrival order; every request's link
    round-trips and recompose serialize end to end.
  * ``serve/pool/clients=64/workers=8`` — the serve plane: per-client
    sessions run on 8 workers (round-trips of distinct requests overlap)
    and concurrent duplicate tightens coalesce into one fetch + one
    recompose fanned out to the waiters.  ``speedup`` is sequential wall
    over pooled wall and must hold >= 2x — the tentpole claim; the derived
    string also carries coalesce hits vs leader flights.
  * ``serve/tail/clients=64/workers=8`` — tail amplification under
    concurrency: us_per_call is the pooled p99 handle latency, derived
    ``tail`` = p99/p50.  Queueing convoys (a lost per-session lock, an
    accidental global serialization) show up here before they show in the
    mean.
  * ``serve/batched_tick/clients=64/workers=8`` — the same 64 requests
    submitted round-robin across (var, eps) groups (the mixed-tenant tick
    shape) through the full stack: pool + coalescer + a shared
    ``DecodeBatcher``, every group on the fused device-decode path.
    Distinct flights run on distinct workers and their fused decode
    flushes / device recomposes merge into vmapped dispatches per batching
    window.  ``dispatch_ratio`` (decode items per device dispatch, from
    BatcherStats) must hold >= 2 — the batching claim — with wall time
    still well under the sequential baseline (``speedup_vs_seq``).

Both modes run the SAME request schedule and per-client sticky sessions;
the workload mixes duplicate (var, eps) tightens across clients — the
multi-tenant dashboard shape coalescing exists for — with per-client
unique work.  Reconstruction results are asserted bit-identical between
the two modes before any row is emitted (the plane-count invariant: same
final fetched-plane counts => same bytes).
"""
from __future__ import annotations

import json
import threading
import time

import numpy as np

from repro.core.refactor import refactor_variables
from repro.data.synthetic import ge_like_fields
from repro.options import SessionOptions
from repro.serve import DecodeBatcher, ReconstructCoalescer, ServePlane
from repro.store import MemoryByteStore, RemoteByteStore, SegmentCache
from repro.store.container import StoreArchive, build_sharded_container

N_CLIENTS = 64
WORKERS = 8
LATENCY_S = 2e-4              # LAN round-trip per request (propagation)
BANDWIDTH_BPS = 400e6         # shared-link wire rate, FIFO
EPS_LADDER = (1e-3, 1e-6)
BATCH_WINDOW_MS = 30.0        # decode-batching window for the batched row


def _schedule(variables):
    """64 clients -> one (client, var, eps) request each, bursty: identical
    (var, eps) pairs arrive back-to-back — the dashboard-refresh shape
    (many tenants tightening the same hot variable at once) that
    cross-session coalescing exists for — while distinct pairs fill the
    other worker slots (the pool overlaps their round-trips)."""
    reqs = []
    for i in range(N_CLIENTS):
        var = variables[i % len(variables)]
        eps = EPS_LADDER[(i // len(variables)) % len(EPS_LADDER)]
        reqs.append((f"c{i:02d}", var, eps))
    reqs.sort(key=lambda r: (r[1], r[2]))
    return reqs


def _interleave(reqs, width=3):
    """Round-robin the schedule across (var, eps) groups, ``width`` requests
    per group per cycle: nearby requests hit DISTINCT reconstructions, so
    the worker pool runs several different flights at once — the
    mixed-tenant tick shape the decode batcher exists for (a fully bursty
    order leaves the batcher nothing to merge: the coalescer collapses the
    duplicates and its few leaders barely overlap).  ``width`` > 1 keeps a
    duplicate adjacent to its leader so coalescing still collapses most
    repeat work."""
    groups = {}
    for r in reqs:
        groups.setdefault((r[1], r[2]), []).append(r)
    out, queues = [], list(groups.values())
    while len(out) < len(reqs):
        for q in queues:
            out.extend(q[:width])
            del q[:width]
    return out


class _MiniServer:
    """The serve-plane stack minus the CLI: one StoreArchive over the modelled link
    model, a cross-session SegmentCache, sticky per-client sessions, and —
    in pooled mode — a ServePlane plus cross-session coalescer."""

    def __init__(self, manifest, payload, workers=None, coalesce=False,
                 decode_batcher=None):
        self.remote = RemoteByteStore(MemoryByteStore(payload),
                                      latency_s=LATENCY_S,
                                      bandwidth_bps=BANDWIDTH_BPS)
        self.cache = SegmentCache(max_bytes=256 << 20)
        self.archive = StoreArchive(manifest, self.remote,
                                    prefetch_workers=2, cache=self.cache)
        self.coalescer = ReconstructCoalescer() if coalesce else None
        self.decode_batcher = decode_batcher
        self.sessions = {}
        self._mu = threading.Lock()
        self.results = {}
        self.plane = None
        if workers is not None:
            self.plane = ServePlane(self.handle, workers=workers,
                                    queue_depth=4 * N_CLIENTS,
                                    session_key=lambda r: r[0])

    def handle(self, req):
        client, var, eps = req
        with self._mu:
            session = self.sessions.get(client)
            if session is None:
                session = self.archive.open(SessionOptions(
                    decode_batcher=self.decode_batcher))
                session.coalescer = self.coalescer
                self.sessions[client] = session
        data, achieved = session.reconstruct(var, eps)
        self.results[req] = data
        return achieved

    def close(self):
        if self.plane is not None:
            self.plane.shutdown(wait=True)
        self.archive.close()


def _quantiles(latencies_s):
    lat = np.sort(np.asarray(latencies_s))
    return (float(np.percentile(lat, 50)) * 1e3,
            float(np.percentile(lat, 99)) * 1e3)


def run():
    fields = ge_like_fields(n=1 << 15, seed=0)
    arch = refactor_variables(fields, method="hb")
    manifest, payloads = build_sharded_container(arch, shard_by="single")
    manifest = json.loads(json.dumps(manifest))
    payload = payloads[""]
    variables = sorted(fields)
    reqs = _schedule(variables)

    # untimed warmup: reader jit + codec dispatch, off the link model, so
    # the sequential row isn't charged for first-touch compilation.  A
    # fresh session per rung matches the clients' one-shot fetch shapes
    # (each timed client jumps straight to its eps from a cold state).
    warm = StoreArchive(manifest, MemoryByteStore(payload),
                        prefetch_workers=2)
    try:
        for eps in EPS_LADDER:
            s = warm.open()
            for v in variables:
                s.reconstruct(v, eps)
    finally:
        warm.close()

    # sequential baseline: one thread, arrival order
    seq = _MiniServer(manifest, payload)
    try:
        lat = []
        t0 = time.perf_counter()
        for req in reqs:
            r0 = time.perf_counter()
            seq.handle(req)
            lat.append(time.perf_counter() - r0)
        seq_wall = time.perf_counter() - t0
        seq_p50, seq_p99 = _quantiles(lat)
        seq_bytes = seq.remote.stats.bytes_moved
        seq_results = dict(seq.results)
    finally:
        seq.close()

    # pooled: same schedule through the serve plane, coalescing on
    pool = _MiniServer(manifest, payload, workers=WORKERS, coalesce=True)
    try:
        t0 = time.perf_counter()
        futures = [pool.plane.submit(req) for req in reqs]
        for fut in futures:
            fut.result()
        pool_wall = time.perf_counter() - t0
        pm = pool.plane.metrics()
        cm = pool.coalescer.metrics()
        pool_bytes = pool.remote.stats.bytes_moved
        for req in reqs:        # bit-identity: concurrency must not show
            np.testing.assert_array_equal(pool.results[req],
                                          seq_results[req])
    finally:
        pool.close()

    # batched tick: the interleaved schedule through pool + coalescer +
    # shared DecodeBatcher merging concurrent flights' device work.  One
    # untimed pass first compiles the vmapped batch graphs (batch sizes
    # are padded to powers of two, so the timed pass reuses them even when
    # bucket compositions differ).
    # every group rides the batcher here (not just the >= FUSED_MIN_COUNT
    # ones "auto" picks): a reader's small same-shape levels stack into one
    # vmapped dispatch alongside its neighbours' — the per-tick dispatch
    # collapse the row exists to measure
    from repro.kernels import ops
    bat_reqs = _interleave(reqs)
    prev_path = ops.set_decode_path("fused")
    try:
        for timed_pass in (False, True):
            bat = DecodeBatcher(window_ms=BATCH_WINDOW_MS)
            srv = _MiniServer(manifest, payload, workers=WORKERS,
                              coalesce=True, decode_batcher=bat)
            try:
                t0 = time.perf_counter()
                futures = [srv.plane.submit(req) for req in bat_reqs]
                for fut in futures:
                    fut.result()
                bat_wall = time.perf_counter() - t0
                bs = bat.stats.as_dict()
                for req in reqs:    # bit-identity: batching must not show
                    np.testing.assert_array_equal(srv.results[req],
                                                  seq_results[req])
            finally:
                srv.close()
    finally:
        ops.set_decode_path(prev_path)
    decode_ratio = (bs["decode_items"] / bs["decode_dispatches"]
                    if bs["decode_dispatches"] else 0.0)

    speedup = seq_wall / pool_wall
    p50, p99 = pm["latency_p50_ms"], pm["latency_p99_ms"]
    tail = p99 / p50 if p50 > 0 else float("inf")
    return [
        (f"serve/seq/clients={N_CLIENTS}", seq_wall * 1e6,
         f"p50={seq_p50:.1f}ms;p99={seq_p99:.1f}ms;"
         f"wire_bytes={seq_bytes}"),
        (f"serve/pool/clients={N_CLIENTS}/workers={WORKERS}",
         pool_wall * 1e6,
         f"speedup={speedup:.2f}x;p50={p50:.1f}ms;p99={p99:.1f}ms;"
         f"coalesce_hits={cm['hits_total']:.0f};"
         f"flights={cm['leaders_total']:.0f};"
         f"wire_bytes={pool_bytes}"),
        (f"serve/tail/clients={N_CLIENTS}/workers={WORKERS}", p99 * 1e3,
         f"tail={tail:.2f};p50={p50:.1f}ms;p99={p99:.1f}ms;"
         f"shed={pm['shed_total']:.0f}"),
        (f"serve/batched_tick/clients={N_CLIENTS}/workers={WORKERS}",
         bat_wall * 1e6,
         f"speedup_vs_seq={seq_wall / bat_wall:.2f}x;"
         f"dispatch_ratio={decode_ratio:.2f};"
         f"decode_items={bs['decode_items']:.0f};"
         f"decode_dispatches={bs['decode_dispatches']:.0f};"
         f"recompose_items={bs['recompose_items']:.0f};"
         f"recompose_dispatches={bs['recompose_dispatches']:.0f};"
         f"window_ms={BATCH_WINDOW_MS:g}"),
    ]


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
