"""Cross-session decode batching: one fused dispatch per serve-plane tick.

With the fused device decode (kernels/ops.decode_values_fused) each reader
still pays one jit dispatch per group flush.  Under the concurrent serve
plane many readers flush at the same moment — the coalescer already merges
*identical* requests, but distinct sessions tightening distinct variables
each dispatch alone.  ``DecodeBatcher`` closes that gap:

  * readers ``submit_decode`` / ``submit_recompose`` work items and block
    on ``Ticket.result()``;
  * the FIRST waiter sleeps one batching window (``window_ms``) and then
    drains everything pending, bucketing by dispatch shape —
    ``("decode", P_pad, W)`` for plane flushes and
    ``("recompose", shape, levels, start, n_vals, is_ip)`` for
    contributions (hb and `ip` items recompose through different graphs,
    so they never share a bucket; an ip item's quantum is a traced operand
    and does not split buckets);
  * buckets with >= 2 items go through ONE vmapped dispatch
    (``ops.decode_values_fused_batch`` / ``scatter_recompose_from_batch``);
    singletons — stragglers whose shape matched nobody — fall back to the
    ordinary per-reader dispatch inside the same drain.

vmap adds a leading batch axis and nothing else: every slice runs the same
elementwise graph as a solo dispatch, so batched results are bit-identical
to per-reader results (the conformance suite and
``tests/test_serve_concurrent.py`` pin this).

Decode is a pure function of (plane words, state), so the scheme needs no
rollback path: if a waiter's window expires without anyone flushing it, it
simply flushes itself — worst case the batch is smaller, never wrong.
The batcher is shared across sessions (it lives on the server and rides
into readers via ``SessionOptions.decode_batcher``); all entry points are
thread-safe.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.kernels import ops
from repro.trace import BATCH_FLUSH, BATCH_WINDOW, span


@dataclass
class BatcherStats:
    """Dispatch accounting — the serve bench's ``dispatch_ratio`` (items per
    dispatch) comes straight from these counters."""
    decode_items: int = 0
    decode_dispatches: int = 0
    decode_batched: int = 0        # items that rode a vmapped dispatch
    recompose_items: int = 0
    recompose_dispatches: int = 0
    recompose_batched: int = 0
    flushes: int = 0
    _mu: threading.Lock = field(default_factory=threading.Lock,
                                repr=False, compare=False)

    def as_dict(self) -> Dict[str, float]:
        with self._mu:
            items = self.decode_items + self.recompose_items
            disp = self.decode_dispatches + self.recompose_dispatches
            return {
                "decode_items": float(self.decode_items),
                "decode_dispatches": float(self.decode_dispatches),
                "decode_batched": float(self.decode_batched),
                "recompose_items": float(self.recompose_items),
                "recompose_dispatches": float(self.recompose_dispatches),
                "recompose_batched": float(self.recompose_batched),
                "flushes": float(self.flushes),
                "dispatch_ratio": float(items) / disp if disp else 0.0,
            }


class Ticket:
    """One submitted work item; ``result()`` blocks until a flush ran it."""

    def __init__(self, batcher: "DecodeBatcher", kind: str, key: Tuple,
                 payload: Tuple):
        self._batcher = batcher
        self.kind = kind
        self.key = key
        self.payload = payload
        self._done = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None

    def _finish(self, result=None, error: Optional[BaseException] = None):
        self._result = result
        self._error = error
        self._done.set()

    def result(self):
        # first waiter gives the window a chance to fill, then drains the
        # whole pending set itself; later waiters usually find _done set
        with span(BATCH_WINDOW):
            done = self._done.wait(self._batcher.window_s)
        if not done:
            self._batcher.flush()
            with span(BATCH_WINDOW):      # another thread's flush of it
                self._done.wait()
        if self._error is not None:
            raise self._error
        return self._result


class DecodeBatcher:
    """Shape-bucketed batching front for fused decode + device recompose."""

    def __init__(self, window_ms: float = 2.0,
                 batch_recompose: bool = True, plane_slots: int = 64):
        self.window_s = max(0.0, float(window_ms)) / 1e3
        self.batch_recompose = bool(batch_recompose)
        # decode items are padded to this many plane slots (host-side, zero
        # no-op planes) so every same-width group lands in ONE bucket and
        # the vmapped graph set stays tiny; archives with more planes than
        # this keep their natural power-of-two padded length
        self.plane_slots = int(plane_slots)
        # device working set one vmapped dispatch may take: a third of the
        # device's memory; buckets past it split into power-of-two chunks
        # (None where the backend reports no limit: the CPU)
        self.max_batch_bytes = _device_batch_budget()
        self.stats = BatcherStats()
        self._mu = threading.Lock()
        self._pending: List[Ticket] = []

    # -- submission -------------------------------------------------------
    def submit_decode(self, words: np.ndarray, shifts: np.ndarray, state,
                      sign_bytes: np.ndarray, scale: float,
                      count: int) -> Ticket:
        """Queue one group flush.  Arguments mirror
        ``ops.decode_values_fused``; padding to the bucketable full-word,
        uniform-plane-slot layout happens here so the key is exact — items
        with different fetched-plane counts still merge (``plane_slots``
        pads the shorter ones with zero planes, exact no-ops)."""
        w, sh, st, sb = ops.prepare_fused_decode(words, shifts, state,
                                                 sign_bytes, count,
                                                 self.plane_slots)
        key = ("decode", w.shape[0], w.shape[1])
        t = Ticket(self, "decode", key, (w, sh, st, sb, scale, count))
        with self._mu:
            self._pending.append(t)
        return t

    def submit_recompose(self, vals, shape: Tuple[int, ...], levels: int,
                         start: int, quantum: Optional[float] = None,
                         idx=None) -> Ticket:
        """Queue one contribution placement+recompose
        (``transform.hierarchical.scatter_recompose_from``).  A non-None
        ``quantum`` routes through the `ip` variant
        (``scatter_recompose_ip_from``), which also takes the group's node
        indices ``idx`` for its tail — the quantum itself is a traced
        operand, so ip items with different quanta still share a bucket;
        only the hb/ip graph split keys the bucket."""
        key = ("recompose", tuple(shape), int(levels), int(start),
               int(len(vals)), quantum is not None)
        t = Ticket(self, "recompose", key,
                   (idx, vals, tuple(shape), int(levels), int(start),
                    quantum))
        with self._mu:
            self._pending.append(t)
        return t

    # -- draining ---------------------------------------------------------
    def flush(self) -> int:
        """Drain everything pending in shape buckets.  Returns the number
        of device dispatches issued.  Safe to call from any thread at any
        time (decode is pure; an extra flush only shrinks batches)."""
        with self._mu:
            batch, self._pending = self._pending, []
        if not batch:
            return 0
        buckets: Dict[Tuple, List[Ticket]] = {}
        for t in batch:
            buckets.setdefault(t.key, []).append(t)
        dispatches = 0
        with span(BATCH_FLUSH, items=len(batch)):
            for key, bucket in buckets.items():
                for tickets in self._chunks(bucket):
                    try:
                        if key[0] == "decode":
                            dispatches += self._run_decode(tickets)
                        else:
                            dispatches += self._run_recompose(tickets)
                    except BaseException as e:   # propagate to every waiter
                        for t in tickets:
                            t._finish(error=e)
        with self.stats._mu:
            self.stats.flushes += 1
        return dispatches

    def _chunks(self, tickets: List[Ticket]) -> List[List[Ticket]]:
        """Split one bucket so each vmapped dispatch — padded to a power
        of two — stays within ``max_batch_bytes`` (at least one item)."""
        if self.max_batch_bytes is None or len(tickets) < 2:
            return [tickets]
        fit = max(1, self.max_batch_bytes // _item_bytes(tickets[0]))
        size = 1 << (fit.bit_length() - 1)          # largest pow2 <= fit
        return [tickets[i:i + size] for i in range(0, len(tickets), size)]

    @staticmethod
    def _pad_pow2(items: List) -> List:
        """Repeat the last item up to the next power-of-two batch size, so
        vmapped graphs compile for O(log B) distinct batch shapes instead
        of one per observed bucket size (padding lanes are computed and
        discarded — decode is pure, so they cost a little device work and
        change nothing)."""
        b = 1
        while b < len(items):
            b <<= 1
        return items + [items[-1]] * (b - len(items))

    def _run_decode(self, tickets: List[Ticket]) -> int:
        import jax.numpy as jnp
        n = len(tickets)
        with self.stats._mu:
            self.stats.decode_items += n
            self.stats.decode_dispatches += 1
            if n > 1:
                self.stats.decode_batched += n
        if n == 1:
            w, sh, st, sb, scale, count = tickets[0].payload
            mag, vals = ops._decode_fused(w, sh, st, sb, jnp.float64(scale))
            tickets[0]._finish((mag, vals[:count]))
            return 1
        padded = self._pad_pow2(tickets)
        stack = lambda i: jnp.stack([t.payload[i] for t in padded])
        scales = jnp.asarray([t.payload[4] for t in padded],
                             dtype=jnp.float64)
        mag_b, vals_b = ops._decode_fused_batch(stack(0), stack(1), stack(2),
                                                stack(3), scales)
        for i, t in enumerate(tickets):
            t._finish((mag_b[i], vals_b[i][: t.payload[5]]))
        return 1

    def _run_recompose(self, tickets: List[Ticket]) -> int:
        import jax.numpy as jnp

        from repro.transform.hierarchical import (
            scatter_recompose_from, scatter_recompose_from_batch,
            scatter_recompose_ip_from, scatter_recompose_ip_from_batch)
        n = len(tickets)
        batched = n > 1 and self.batch_recompose
        with self.stats._mu:
            self.stats.recompose_items += n
            self.stats.recompose_dispatches += 1 if batched else n
            if batched:
                self.stats.recompose_batched += n
        if not batched:
            for t in tickets:
                idx, vals, shape, levels, start, quantum = t.payload
                if quantum is None:
                    t._finish(scatter_recompose_from(jnp.asarray(vals),
                                                     shape, levels, start))
                else:
                    t._finish(scatter_recompose_ip_from(
                        jnp.asarray(idx), jnp.asarray(vals), shape, levels,
                        start, jnp.float64(quantum)))
            return n
        _, _, shape, levels, start, quantum = tickets[0].payload
        padded = self._pad_pow2(tickets)
        vals_b = jnp.stack([jnp.asarray(t.payload[1]) for t in padded])
        if quantum is None:
            out = scatter_recompose_from_batch(vals_b, shape, levels, start)
        else:
            idx_b = jnp.stack([jnp.asarray(t.payload[0]) for t in padded])
            q_b = jnp.asarray([t.payload[5] for t in padded],
                              dtype=jnp.float64)
            out = scatter_recompose_ip_from_batch(idx_b, vals_b, shape,
                                                  levels, start, q_b)
        for i, t in enumerate(tickets):
            t._finish(out[i])
        return 1


def _device_batch_budget() -> Optional[int]:
    """A third of the default device's memory, or None where the backend
    reports no limit (the CPU)."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return int(limit) // 3 if limit else None


def _item_bytes(t: Ticket) -> int:
    """Device bytes one item adds to a vmapped dispatch — arguments,
    results and temporaries, bounding what the v5e compiler's memory
    analysis counts at archival sizes (tests/test_tpu_compile.py): a decode
    item holds its plane words plus at most eight magnitude-length uint64
    arrays (state in and out, values, per-plane bits); a recompose item
    its value vector (an `ip` item its index vector too) plus at most six
    field-sized f64 arrays
    (the device pads the field's minor dimensions to its tiling)."""
    if t.kind == "decode":
        w, _, st, sb = t.payload[:4]
        return int(w.nbytes + sb.nbytes + 8 * st.nbytes)
    idx, vals, shape = t.payload[:3]
    idx_nbytes = 0 if idx is None else idx.nbytes
    return int(idx_nbytes + vals.nbytes + 6 * 8 * int(np.prod(shape)))
