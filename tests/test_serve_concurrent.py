"""Concurrent multi-tenant serve plane: worker pool + per-session locks +
load shedding (repro.serve.pool), cross-session request coalescing
(repro.serve.coalesce), the server-wide pooled contribution budget
(repro.serve.budget), thread-safety of the shared stats sinks and the
SegmentCache, idempotent archive creation, and the /health + /metrics +
ETag surface of repro.store.httpd.

The load-bearing contracts:

  * coalesced duplicate tighten requests perform at most ONE store fetch
    per shared segment, and every concurrent result is bit-identical to a
    sequential single-client retrieval at the same tolerance;
  * pooled-budget denials/reclaims only ever cost recompute — never
    correctness — and every lease is returned on session close;
  * shared mutable stats (FetchStats/ContribStats) and the SegmentCache
    lose no updates under thread races, and cache floors hold while
    archives race;
  * two servers booting on the same missing --store path refactor once
    and never publish a half-written container.
"""
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.core.refactor import ContribStats, refactor_variables
from repro.data.synthetic import ge_like_fields
from repro.launch.serve import Request, RetrievalServer, ensure_archive
from repro.options import OpenOptions, SessionOptions
from repro.serve import (ContribBudgetPool, LatencyHistogram,
                         ReconstructCoalescer, ServePlane,
                         ServerOverloadedError, render_metrics)
from repro.store import (MemoryByteStore, SegmentCache, memory_store_archive,
                         open_archive, save_archive)
from repro.store.bytestore import HTTPByteStore
from repro.store.fetcher import FetchStats
from repro.store.httpd import StoreHTTPServer


def _vel_fields(n=1 << 10, seed=0):
    fields = ge_like_fields(n=n, seed=seed)
    return {k: fields[k] for k in ("Vx", "Vy", "Vz")}


@pytest.fixture(scope="module")
def vel():
    return _vel_fields()


@pytest.fixture(scope="module")
def hb_archive(vel):
    return refactor_variables(vel, method="hb")


class _GatedStore(MemoryByteStore):
    """A ByteStore whose reads can be blocked on demand — pins a leader
    flight inside its first fetch so waiters deterministically join it.
    The gate starts open (archive/session setup reads pass through)."""

    def __init__(self, data: bytes):
        super().__init__(data)
        self.gate = threading.Event()
        self.gate.set()

    def read(self, offset: int, length: int) -> bytes:
        if not self.gate.wait(30):
            raise TimeoutError("gated store never released")
        return super().read(offset, length)


# ------------------------------------------------------------- coalescing --


def test_coalesced_duplicates_fetch_each_segment_once(vel, hb_archive):
    """N concurrent identical tighten requests: one leader flight, N-1
    adoptions, and the store sees EXACTLY the reads a single session
    would issue — at most one fetch per shared segment."""
    n_dup, var, eps = 5, "Vx", 1e-5
    # baseline: the store reads one session alone needs (prediction off so
    # the count is deterministic)
    with memory_store_archive(hb_archive) as sa:
        s = sa.open(SessionOptions(prefetch_depth=0))
        s.reconstruct(var, eps)
        baseline_reads = sa.fetcher.stats.store_reads

    from repro.store.container import build_sharded_container, StoreArchive
    manifest, payloads = build_sharded_container(hb_archive,
                                                 shard_by="single")
    manifest = json.loads(json.dumps(manifest))
    store = _GatedStore(payloads[""])
    # the shared cache is what makes waiter advances byte-free: the
    # leader's fetch populates it, waiters hit it instead of the store
    sa = StoreArchive(manifest, store, prefetch_workers=2,
                      cache=SegmentCache())
    coal = ReconstructCoalescer()
    sessions = []
    for _ in range(n_dup):
        s = sa.open(SessionOptions(prefetch_depth=0))
        s.coalescer = coal
        sessions.append(s)
    store.gate.clear()          # now pin the leader's first fetch
    results, errors = [None] * n_dup, []

    def worker(i):
        try:
            results[i] = sessions[i].reconstruct(var, eps)
        except BaseException as exc:   # noqa: BLE001 - surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_dup)]
    threads[0].start()
    # leader is pinned inside its first store read; wait for its flight
    deadline = time.monotonic() + 30
    while coal.metrics()["inflight"] < 1:
        assert time.monotonic() < deadline, "leader flight never appeared"
        time.sleep(0.002)
    for t in threads[1:]:
        t.start()
    while coal.stats.hits < n_dup - 1:   # all waiters joined the flight
        assert time.monotonic() < deadline, "waiters never joined"
        time.sleep(0.002)
    store.gate.set()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors
    assert coal.stats.leaders == 1
    assert coal.stats.adoptions == n_dup - 1
    assert coal.stats.fallbacks == 0
    # <= 1 store fetch per shared segment: the waiters' advances were all
    # cache hits, so the store saw only the single-session read count
    assert sa.fetcher.stats.store_reads == baseline_reads
    ref, ref_bound = results[0]
    for data, bound in results[1:]:
        assert np.array_equal(ref, data)
        assert bound == ref_bound
    sa.close()


def test_concurrent_results_bit_identical_to_sequential(vel, hb_archive):
    """16 clients (mixed vars/eps, duplicates included) through the worker
    pool + coalescer reconstruct exactly what fresh sequential
    single-client sessions produce."""
    ladder = (1e-2, 1e-6)
    reqs = [(f"c{i}", v, eps) for i, (v, eps) in enumerate(
        (v, e) for e in ladder for v in sorted(vel) for _ in range(3))]
    with memory_store_archive(hb_archive,
                              OpenOptions(cache=SegmentCache())) as sa:
        coal = ReconstructCoalescer()
        sessions = {}
        mu = threading.Lock()

        def handle(req):
            client, var, eps = req
            with mu:
                s = sessions.get(client)
                if s is None:
                    s = sa.open()
                    s.coalescer = coal
                    sessions[client] = s
            return s.reconstruct(var, eps)

        with ServePlane(handle, workers=6, queue_depth=64,
                        session_key=lambda r: r[0]) as plane:
            futs = [plane.submit(r) for r in reqs]
            got = [f.result() for f in futs]

    seq = hb_archive.open()
    for (client, var, eps), (data, bound) in zip(reqs, got):
        want, want_bound = seq.reconstruct(var, eps)
        assert np.array_equal(want, data), (client, var, eps)
        assert want_bound == bound


def test_coalescer_falls_back_without_serve_hooks(hb_archive):
    """Readers lacking the serve hooks (no state_signature/adopt) still
    work through a coalescer-attached session — counted uncoalescable."""
    coal = ReconstructCoalescer()
    session = hb_archive.open()
    session.coalescer = coal
    reader = session.readers["Vx"]
    # simulate a legacy reader: hide the hooks behind a wrapper
    class _Legacy:
        def __init__(self, inner):
            self._inner = inner

        def request(self, eps):
            return self._inner.request(eps)
    session.readers["Vx"] = _Legacy(reader)
    data, bound = session.reconstruct("Vx", 1e-3)
    assert coal.stats.uncoalescable == 1
    want, _ = hb_archive.open().reconstruct("Vx", 1e-3)
    assert np.array_equal(want, data)


# ---------------------------------------------------- pool + load shedding --


def test_load_shedding_past_high_water():
    gate = threading.Event()
    plane = ServePlane(lambda req: gate.wait(10), workers=1, queue_depth=2)
    try:
        f1 = plane.submit("a")
        f2 = plane.submit("b")
        with pytest.raises(ServerOverloadedError) as ei:
            plane.submit("c")
        assert ei.value.retry_after_s >= 1.0
        assert ei.value.pending == 2 and ei.value.queue_depth == 2
        health = plane.health()
        assert health["ok"] is False and health["retry_after_s"] >= 1.0
        gate.set()
        assert f1.result(10) and f2.result(10)
        m = plane.metrics()
        assert m["shed_total"] == 1 and m["requests_total"] == 2
        assert m["errors_total"] == 0
        assert plane.health()["ok"] is True
    finally:
        plane.shutdown()


def test_per_session_serialization_and_cross_session_parallelism():
    """Same-session requests must serialize; different sessions overlap."""
    active = {"n": 0, "max": 0, "overlap_same": False}
    mu = threading.Lock()

    def handler(req):
        session, _ = req
        with mu:
            active["n"] += 1
            active["max"] = max(active["max"], active["n"])
            active.setdefault(session, 0)
            active[session] += 1
            if active[session] > 1:
                active["overlap_same"] = True
        time.sleep(0.02)
        with mu:
            active["n"] -= 1
            active[session] -= 1

    with ServePlane(handler, workers=4, queue_depth=64,
                    session_key=lambda r: r[0]) as plane:
        futs = [plane.submit((f"s{j % 2}", j)) for j in range(8)]
        for f in futs:
            f.result(10)
    assert not active["overlap_same"], \
        "two requests of one session ran concurrently"
    assert active["max"] >= 2, "distinct sessions never overlapped"


def test_plane_rejects_after_shutdown_and_counts_errors():
    plane = ServePlane(lambda req: 1 / 0, workers=1, queue_depth=4)
    fut = plane.submit("x")
    with pytest.raises(ZeroDivisionError):
        fut.result(10)
    assert plane.metrics()["errors_total"] == 1
    plane.shutdown()
    with pytest.raises(RuntimeError):
        plane.submit("y")


def test_latency_histogram_quantiles_and_render():
    h = LatencyHistogram()
    for ms in (1, 1, 1, 1, 2, 2, 5, 5, 20, 400):
        h.observe(ms / 1e3)
    snap = h.snapshot()
    assert snap["count"] == 10
    assert 0.5 <= snap["p50_ms"] <= 3.0
    assert snap["p99_ms"] >= 100
    assert snap["max_ms"] >= 400
    text = render_metrics({"b_total": 2.0, "a_total": 1.0})
    assert text.splitlines() == ["a_total 1", "b_total 2"]


# ------------------------------------------------------ pooled contribution --


class _Owner:
    """Stand-in for a pooled bitplane reader: slot dict + the pool's
    deposit/clear callback."""

    def __init__(self):
        self.slots = {}

    def _pool_set_contrib(self, slot, value):
        if value is None:
            self.slots.pop(slot, None)
        else:
            self.slots[slot] = value


def test_pool_grant_touch_release_accounting():
    pool = ContribBudgetPool(total_bytes=100)
    a = _Owner()
    assert pool.retain(a, slot=0, level=0, nbytes=60, value="x")
    assert a.slots[0] == "x" and pool.holds(a, 0)
    assert pool.borrowed_bytes == 60
    assert pool.retain(a, slot=0, level=0, nbytes=60, value="x2")  # touch
    assert a.slots[0] == "x2" and pool.borrowed_bytes == 60
    assert pool.stats.touches == 1 and pool.stats.grants == 1
    pool.release(a, 0)
    assert not pool.holds(a, 0) and pool.borrowed_bytes == 0
    assert 0 not in a.slots
    # oversize request: denied outright
    assert not pool.retain(a, slot=1, level=0, nbytes=101, value="y")
    assert pool.stats.denials == 1


def test_pool_reclaims_strictly_worse_scored_leases():
    pool = ContribBudgetPool(total_bytes=100, depth_weight=4.0)
    coarse, fine = _Owner(), _Owner()
    # two coarse (deep-level) holdings fill the pool
    assert pool.retain(coarse, slot=5, level=5, nbytes=50, value="c5")
    assert pool.retain(coarse, slot=6, level=6, nbytes=50, value="c6")
    # a fine-level request reclaims them (worse depth-weighted scores)
    assert pool.retain(fine, slot=0, level=0, nbytes=80, value="f0")
    assert fine.slots[0] == "f0"
    assert not pool.holds(coarse, 6) and 6 not in coarse.slots
    assert pool.stats.reclaims >= 1
    assert pool.borrowed_bytes <= 100


def test_pool_grant_reclaims_multiple_victims_atomically():
    """A fresh request may reclaim SEVERAL strictly-worse-scored leases in
    one shot; every victim's slot is cleared under the pool lock."""
    pool = ContribBudgetPool(total_bytes=100, depth_weight=0.0)
    a, b, c = _Owner(), _Owner(), _Owner()
    assert pool.retain(a, slot=0, level=0, nbytes=40, value="a")
    assert pool.retain(b, slot=0, level=0, nbytes=60, value="b")
    # needs both resident leases (strictly staler ticks) reclaimed
    assert pool.retain(c, slot=0, level=0, nbytes=95, value="c")
    assert c.slots[0] == "c"
    assert not pool.holds(a, 0) and not pool.holds(b, 0)
    assert a.slots == {} and b.slots == {}
    assert pool.borrowed_bytes == 95
    assert pool.stats.reclaims == 2


def test_pool_denial_never_partially_evicts():
    """When even reclaiming every worse-scored lease cannot make room, the
    pool denies WITHOUT evicting anyone — a denied request must not churn
    other readers' caches."""
    pool = ContribBudgetPool(total_bytes=100, depth_weight=10.0)
    owners = [_Owner() for _ in range(2)]
    assert pool.retain(owners[0], slot=0, level=0, nbytes=50, value="a")
    assert pool.retain(owners[1], slot=0, level=0, nbytes=50, value="b")
    # a deep-level requester scores BELOW both fine-level residents:
    # no strictly-worse victims exist, so it is denied outright
    deep = _Owner()
    assert not pool.retain(deep, slot=0, level=9, nbytes=50, value="c")
    assert pool.holds(owners[0], 0) and pool.holds(owners[1], 0)
    assert owners[0].slots[0] == "a" and owners[1].slots[0] == "b"
    assert deep.slots == {}
    assert pool.stats.denials == 1
    assert pool.stats.reclaims == 0


def test_pooled_budget_bit_identical_and_released_on_close(vel, hb_archive):
    """A tiny shared pool forces spills/reclaims across sessions, yet every
    reconstruction matches the unbounded reader bit for bit; closing the
    sessions returns every lease."""
    unbounded = hb_archive.open()
    pool = ContribBudgetPool(total_bytes=64 << 10, depth_weight=4.0)
    with memory_store_archive(hb_archive) as sa:
        s1 = sa.open(SessionOptions.pooled(pool))
        s2 = sa.open(SessionOptions.pooled(pool))
        for eps in (1e-2, 1e-4, 1e-6):
            for v in sorted(vel):
                want, want_bound = unbounded.reconstruct(v, eps)
                for s in (s1, s2):
                    got, bound = s.reconstruct(v, eps)
                    assert np.array_equal(want, got), (v, eps)
                    assert bound == want_bound
                assert pool.borrowed_bytes <= pool.total_bytes
        st = sa.fetcher.stats
        assert st.contrib_spills + pool.stats.grants > 0
        s1.close()
        s2.close()
    assert pool.borrowed_bytes == 0
    assert pool.metrics()["leases"] == 0


# ------------------------------------------------- shared stats thread-safety --


@pytest.mark.parametrize("stats_cls", [FetchStats, ContribStats])
def test_contrib_stats_hammer_loses_no_updates(stats_cls):
    """The shared contrib sink (one FetchStats per fetcher serves EVERY
    session's readers) under 8 threads of racing read-modify-write: totals
    must be exact, not approximately right."""
    st = stats_cls()
    n_threads, n_ops = 8, 2000
    start = threading.Barrier(n_threads)

    def worker():
        start.wait()
        for _ in range(n_ops):
            st.contrib_note(delta_bytes=3, spills=1, recomputes=1)
            st.contrib_note(delta_bytes=-1)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    resident, peak, spills, recomputes = st.contrib_snapshot()
    assert resident == n_threads * n_ops * 2
    assert spills == n_threads * n_ops
    assert recomputes == n_threads * n_ops
    assert peak >= resident


def test_one_fetcher_many_threads_bit_identical(vel, hb_archive):
    """Many sessions hammering ONE fetcher (the --store serving shape:
    shared FetchStats sink, shared cache) from concurrent threads — every
    result bit-identical, accounting self-consistent."""
    with memory_store_archive(hb_archive,
                              OpenOptions(cache=SegmentCache())) as sa:
        want = {(v, e): hb_archive.open().reconstruct(v, e)
                for v in sorted(vel) for e in (1e-3, 1e-6)}
        errors = []

        def worker(seed):
            rng = np.random.default_rng(seed)
            s = sa.open()
            names = sorted(vel)
            rng.shuffle(names)
            # per session, eps tightens monotonically (progressive-session
            # semantics: a looser re-request returns the current state)
            for e in (1e-3, 1e-6):
                for v in names:
                    got, bound = s.reconstruct(v, e)
                    ref, ref_bound = want[(v, e)]
                    if not np.array_equal(ref, got) or bound != ref_bound:
                        errors.append((v, e))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        st = sa.fetcher.stats
        resident, peak, _, _ = st.contrib_snapshot()
        assert peak >= resident >= 0


# ------------------------------------------------------ cache thread-safety --


def test_segment_cache_threaded_stress_accounting_balances():
    """Seeded multi-threaded put/get storm: no lost inserts (every put is
    either resident, evicted, or admission-skipped), byte accounting
    balances exactly, and the global bound holds."""
    for admission in (False, True):
        cache = SegmentCache(max_bytes=64_000, depth_weight=8.0,
                             admission_control=admission)
        n_threads, n_ops = 8, 400
        start = threading.Barrier(n_threads)

        def worker(tid, cache=cache):
            rng = np.random.default_rng(1000 + tid)
            start.wait()
            for i in range(n_ops):
                key = (tid, i)                      # unique -> no re-puts
                size = int(rng.integers(100, 1500))
                depth = int(rng.integers(0, 12))
                arch = ("A", "B")[int(rng.integers(0, 2))]
                cache.put(key, bytes(size), depth=depth, archive=arch)
                cache.get((int(rng.integers(0, n_threads)),
                           int(rng.integers(0, n_ops))))

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st = cache.stats
        puts = n_threads * n_ops
        assert st.insertions + st.admission_skips == puts
        assert st.insertions - st.evictions == len(cache)
        assert cache.nbytes <= 64_000
        with cache._lock:
            by_hand = sum(len(e.data) for e in cache._entries.values())
            assert by_hand == cache._nbytes
            for name in list(cache._archives):
                per_arch = sum(len(e.data)
                               for e in cache._entries.values()
                               if e.archive == name)
                assert per_arch == cache._archives[name].nbytes
        if not admission:
            assert st.admission_skips == 0


def test_cache_floor_holds_under_racing_archives():
    """Archive A is filled to its floor, then threads hammer archive B:
    external pressure must never take A below archive_floor_bytes."""
    floor = 8_000
    cache = SegmentCache(max_bytes=32_000, depth_weight=0.0,
                         archive_floor_bytes=floor)
    for i in range(10):                      # 10 KiB resident for A
        cache.put(("A", i), bytes(1_000), depth=0, archive="A")
    assert cache.archive_nbytes("A") >= floor
    start = threading.Barrier(4)

    def worker(tid):
        start.wait()
        for i in range(300):
            cache.put(("B", tid, i), bytes(900), depth=0, archive="B")

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert cache.archive_nbytes("A") >= floor
    assert cache.nbytes <= 32_000


def test_admission_control_skips_colder_than_resident():
    """Under pressure a deep-LSB newcomer is refused instead of evicting
    the hot MSB working set (single-threaded semantics check)."""
    cache = SegmentCache(max_bytes=3_000, depth_weight=100.0,
                         admission_control=True)
    for i in range(3):
        cache.put(("msb", i), bytes(1_000), depth=0)
        cache.get(("msb", i))
    cache.put(("lsb", 0), bytes(1_000), depth=40)
    assert cache.stats.admission_skips == 1
    assert ("lsb", 0) not in cache and len(cache) == 3
    # a hot-depth insert still displaces normally
    cache.put(("msb", 99), bytes(1_000), depth=0)
    assert ("msb", 99) in cache
    assert cache.stats.evictions >= 1
    # re-putting a resident key is a refresh, never admission-checked
    cache.put(("msb", 99), bytes(1_000), depth=0)
    assert ("msb", 99) in cache


# -------------------------------------------------- idempotent archive boot --


def test_ensure_archive_races_refactor_exactly_once(tmp_path):
    """Six racing boots on one missing store path: the refactor runs once,
    exactly one caller reports having created, and the published container
    opens clean (no lock/tmp debris)."""
    vel = _vel_fields(n=1 << 8)
    path = str(tmp_path / "ge.prs")
    calls = []
    mu = threading.Lock()

    def builder():
        with mu:
            calls.append(1)
        return refactor_variables(vel, method="hb")

    created = []
    start = threading.Barrier(6)

    def worker():
        start.wait()
        created.append(ensure_archive(path, builder))

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(calls) == 1
    assert created.count(True) == 1 and created.count(False) == 5
    assert not os.path.exists(path + ".lock")
    assert not any(f.startswith("ge.prs.tmp")
                   for f in os.listdir(tmp_path))
    with open_archive(path) as sa:
        data, bound = sa.open().reconstruct("Vx", 1e-3)
        want, _ = refactor_variables(vel, method="hb") \
            .open().reconstruct("Vx", 1e-3)
        assert np.array_equal(want, data)


def test_ensure_archive_existing_and_stale_lock(tmp_path):
    vel = _vel_fields(n=1 << 8)
    path = str(tmp_path / "ge.prs")
    # existing container: no builder call, returns False
    save_archive(refactor_variables(vel, method="hb"), path)
    assert ensure_archive(path, builder=lambda: pytest.fail(
        "builder must not run for an existing container")) is False
    # stale lock from a crashed creator: broken and creation proceeds
    path2 = str(tmp_path / "ge2.prs")
    lock = path2 + ".lock"
    with open(lock, "w") as fh:
        fh.write("999999\n")
    os.utime(lock, (time.time() - 3600, time.time() - 3600))
    assert ensure_archive(path2,
                          lambda: refactor_variables(vel, method="hb"),
                          stale_lock_s=60.0) is True
    assert os.path.exists(path2) and not os.path.exists(lock)
    # a LIVE lock makes waiters time out rather than corrupt
    path3 = str(tmp_path / "ge3.prs")
    with open(path3 + ".lock", "w") as fh:
        fh.write("1\n")
    with pytest.raises(TimeoutError):
        ensure_archive(path3, builder=lambda: pytest.fail("must not build"),
                       wait_timeout_s=0.2, poll_s=0.02)
    os.unlink(path3 + ".lock")


# ------------------------------------------------- /health /metrics + ETag --


def _get(url, headers=None, method="GET"):
    req = urllib.request.Request(url, headers=headers or {}, method=method)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def test_health_and_metrics_endpoints_under_concurrency(tmp_path):
    """Tier-1 smoke: boot a concurrent RetrievalServer over a real store
    path, expose /health + /metrics over repro.store.httpd, and drive 8
    concurrent clients — endpoints answer throughout, counters land."""
    fields = ge_like_fields(n=1 << 10, seed=0)
    path = str(tmp_path / "ge.prs")
    server = RetrievalServer(fields, method="hb", store_path=path,
                             workers=4, queue_depth=32,
                             contrib_pool_bytes=1 << 20,
                             cache_admission=True)
    httpd = StoreHTTPServer(path, metrics_source=server.metrics,
                            health_source=server.health).start()
    try:
        status, _, body = _get(httpd.url_for("health"))
        assert status == 200 and body == b"ok\n"
        results, errors = [], []

        def client(i):
            try:
                results.append(server.handle(
                    Request(client=f"c{i}", qois=["T"], tau=1e-2)))
            except BaseException as exc:   # noqa: BLE001 - surfaced below
                errors.append(exc)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        status, _, _ = _get(httpd.url_for("health"))
        assert status in (200, 503)        # alive while under load
        for t in threads:
            t.join()
        assert not errors, errors
        assert len(results) == 8
        assert all(r["guaranteed"] for r in results)
        status, headers, body = _get(httpd.url_for("metrics"))
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        metrics = {}
        for line in body.decode().splitlines():
            name, value = line.rsplit(" ", 1)
            metrics[name] = float(value)
        assert metrics["serve_requests_total"] == 8.0
        assert metrics["serve_shed_total"] == 0.0
        assert metrics["serve_latency_count"] == 8.0
        assert metrics["serve_latency_p99_ms"] >= \
            metrics["serve_latency_p50_ms"] > 0
        for key in ("serve_workers", "coalesce_leaders_total",
                    "pool_total_bytes", "cache_hits_total",
                    "fetch_store_reads_total", "contrib_peak_bytes"):
            assert key in metrics, key
        # names are unique and sorted (parseable plaintext contract)
        names = [ln.rsplit(" ", 1)[0] for ln in body.decode().splitlines()]
        assert names == sorted(names) and len(names) == len(set(names))
    finally:
        httpd.stop()
        server.close()


def test_httpd_etag_conditional_get_and_head(vel, hb_archive, tmp_path):
    path = str(tmp_path / "a.prs")
    save_archive(hb_archive, path)
    with StoreHTTPServer(path) as srv:
        status, headers, body = _get(srv.url)
        assert status == 200 and len(body) == os.path.getsize(path)
        etag = headers["ETag"]
        assert etag.startswith('"') and etag.endswith('"')
        # HEAD: same validator, no body
        status, headers, head_body = _get(srv.url, method="HEAD")
        assert status == 200 and head_body == b""
        assert headers["ETag"] == etag
        assert int(headers["Content-Length"]) == os.path.getsize(path)
        # conditional GET: matching validator -> 304, nothing re-sent
        for match in (etag, f'W/{etag}', f'"zzz", {etag}', "*"):
            status, headers, body = _get(srv.url,
                                         {"If-None-Match": match})
            assert status == 304 and body == b"", match
            assert headers["ETag"] == etag
        assert srv.stats["not_modified"] == 4
        # stale validator -> full 200
        status, _, body = _get(srv.url, {"If-None-Match": '"0-0"'})
        assert status == 200 and len(body) == os.path.getsize(path)
        # ranged reads still carry the validator
        status, headers, _ = _get(srv.url, {"Range": "bytes=0-15"})
        assert status == 206 and headers["ETag"] == etag


def test_http_bytestore_revalidates_with_if_none_match(vel, hb_archive,
                                                       tmp_path):
    path = str(tmp_path / "a.prs")
    save_archive(hb_archive, path)
    with StoreHTTPServer(path) as srv:
        with HTTPByteStore(srv.url) as hs:
            first = hs.read_all()
            assert hs.stats.not_modified == 0
            moved = hs.stats.bytes_moved
            again = hs.read_all()          # revalidation: 304, cached body
            assert again == first
            assert hs.stats.not_modified == 1
            assert hs.stats.bytes_moved == moved   # no body re-transfer
            # rewrite -> new ETag -> fresh body (never a stale mix)
            with open(path, "rb") as fh:
                data = fh.read()
            with open(path, "wb") as fh:
                fh.write(data + b"x")
            os.utime(path, (time.time() + 2, time.time() + 2))
            fresh = hs.read_all()
            assert fresh == data + b"x"
            assert hs.stats.not_modified == 1
        assert srv.stats["not_modified"] == 1


# --------------------------------------------------- batched decode ticks --


def test_batched_tick_bit_identical_to_per_reader(vel):
    """N concurrent sessions flushing their fused decodes through ONE
    shared DecodeBatcher (the batched serve tick) reconstruct exactly what
    per-reader dispatches produce — including a straggler variable whose
    unique shape matches no bucket and must take the fallback path — and
    the batcher's counters prove both routes actually ran."""
    from repro.kernels import ops
    from repro.serve import DecodeBatcher

    prev = ops.set_decode_path("fused")
    try:
        fields = dict(vel)                          # Vx/Vy/Vz, same shape
        rng = np.random.default_rng(3)
        # 2x the element count of every other variable: its finest-level
        # group has a word width (W=32) nothing else has, so its decode
        # flush is a guaranteed singleton bucket -> per-reader fallback
        fields["Wodd"] = rng.standard_normal(1 << 11)
        archive = refactor_variables(fields, method="hb")
        eps = 1e-6
        reqs = [("c0", ("Vx", "Vy", "Vz")), ("c1", ("Vx", "Vy", "Vz")),
                ("c2", ("Vx", "Vy", "Vz")), ("c3", ("Wodd",))]
        bat = DecodeBatcher(window_ms=50.0)
        barrier = threading.Barrier(len(reqs))
        with memory_store_archive(archive) as sa:
            sessions = {c: sa.open(SessionOptions(prefetch_depth=0,
                                                  decode_batcher=bat))
                        for c, _ in reqs}

            def handle(req):
                client, names = req
                barrier.wait(10)        # align: one tick, every session
                return [sessions[client].reconstruct(v, eps)
                        for v in names]

            with ServePlane(handle, workers=len(reqs), queue_depth=16,
                            session_key=lambda r: r[0]) as plane:
                futs = [plane.submit(r) for r in reqs]
                got = {r[0]: f.result(120) for r, f in zip(reqs, futs)}
        st = bat.stats.as_dict()
        assert st["decode_batched"] >= 2       # same-shape groups coalesced
        # the straggler's unique-shape groups fell back to solo dispatches
        assert st["decode_items"] > st["decode_batched"]
        assert st["decode_dispatches"] < st["decode_items"]
        # per-reader reference: fresh fused sessions WITHOUT a batcher issue
        # one dispatch per group flush; results must match bit-for-bit
        for client, names in reqs:
            ref = archive.open()
            for (data, bound), v in zip(got[client], names):
                want, want_bound = ref.reconstruct(v, eps)
                assert np.array_equal(want.view(np.uint64),
                                      data.view(np.uint64)), (client, v)
                assert want_bound == bound
    finally:
        ops.set_decode_path(prev)


def test_server_metrics_report_the_batcher_once(tmp_path):
    """/metrics carries the shared decode batcher's counters once, under
    the ``batch_*`` names, and the pool adds no second copy."""
    from repro.kernels import ops

    prev = ops.set_decode_path("fused")
    try:
        server = RetrievalServer(_vel_fields(), method="hb",
                                 store_path=str(tmp_path / "v.prs"),
                                 workers=2, decode_batch_ms=5.0)
        try:
            futs = [server.submit(Request(client=f"c{i}", qois=["VTOT"],
                                          tau=1e-3)) for i in range(2)]
            assert all(f.result(120)["guaranteed"] for f in futs)
            m = server.metrics()
            st = server.decode_batcher.stats.as_dict()
        finally:
            server.close()
    finally:
        ops.set_decode_path(prev)
    assert m["batch_decode_items"] == st["decode_items"] > 0
    assert not [k for k in m if k.startswith("serve_batch_")]


def test_batcher_straggler_shapes_dispatch_solo():
    """Deterministic fallback accounting: two concurrent submissions with
    unmatchable shapes produce two solo dispatches and zero batched items;
    two with equal shapes produce one vmapped dispatch covering both."""
    from repro.bitplane.encoder import (encode_level, inflate_planes,
                                        sign_plane_bytes)
    from repro.serve import DecodeBatcher

    def job(bat, lbp, k, out, i):
        words, shifts = inflate_planes(lbp.count, lbp.nbits,
                                       lbp.planes[:k], 0)
        sb = sign_plane_bytes(lbp.count, lbp.signs)
        scale = np.float64(2.0) ** (lbp.exponent - lbp.nbits)
        t = bat.submit_decode(words, shifts, None, sb, scale, lbp.count)
        out[i] = np.asarray(t.result()[1])

    rng = np.random.default_rng(5)
    small = encode_level(rng.standard_normal(40))
    big = encode_level(rng.standard_normal(400))
    for pair, want_batched, want_dispatches in (
            ((small, big), 0, 2),        # straggler shapes: solo fallbacks
            ((big, big), 2, 1)):         # equal shapes: one vmapped call
        bat = DecodeBatcher(window_ms=25.0)
        out = [None, None]
        threads = [threading.Thread(target=job,
                                    args=(bat, lbp, 17, out, i))
                   for i, lbp in enumerate(pair)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        st = bat.stats.as_dict()
        assert st["decode_batched"] == want_batched
        assert st["decode_dispatches"] == want_dispatches
        for lbp, vals in zip(pair, out):
            from repro.bitplane.encoder import decode_magnitudes, \
                decode_values
            want = decode_values(lbp, decode_magnitudes(lbp, 17))
            assert np.array_equal(want.view(np.uint64),
                                  vals.view(np.uint64))


def test_batcher_splits_buckets_past_max_batch_bytes():
    """A bucket whose vmapped dispatch would exceed the batcher's device
    budget splits into power-of-two chunks — here 3 same-shape items under
    a budget that fits two become one batched pair plus one solo dispatch —
    and every item still decodes bit-identically."""
    from repro.bitplane.encoder import (decode_magnitudes, decode_values,
                                        encode_level, inflate_planes,
                                        sign_plane_bytes)
    from repro.serve import DecodeBatcher
    from repro.serve.batch import _item_bytes

    rng = np.random.default_rng(8)
    lbps = [encode_level(rng.standard_normal(400)) for _ in range(3)]

    def submit(bat, lbp):
        words, shifts = inflate_planes(lbp.count, lbp.nbits,
                                       lbp.planes[:17], 0)
        return bat.submit_decode(words, shifts, None,
                                 sign_plane_bytes(lbp.count, lbp.signs),
                                 np.float64(2.0) ** (lbp.exponent - lbp.nbits),
                                 lbp.count)

    probe = DecodeBatcher(window_ms=0.0)
    item = _item_bytes(submit(probe, lbps[0]))
    bat = DecodeBatcher(window_ms=0.0)
    bat.max_batch_bytes = 2 * item + 1
    tickets = [submit(bat, lbp) for lbp in lbps]
    assert bat.flush() == 2
    st = bat.stats.as_dict()
    assert st["decode_items"] == 3 and st["decode_batched"] == 2
    for lbp, t in zip(lbps, tickets):
        want = decode_values(lbp, decode_magnitudes(lbp, 17))
        got = np.asarray(t.result()[1])
        assert np.array_equal(want.view(np.uint64), got.view(np.uint64))
