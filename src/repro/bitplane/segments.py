"""Progressive segment streams: incremental per-level plane retrieval state.

A LevelStream owns the *decode state* of one coefficient group and tracks how
many planes have been "moved" so far — retrieval cost is charged once per
plane, and recomposition is incremental (newly arrived planes OR into the
magnitude state), matching Definition 1's progressive-compressor contract.

The stream no longer holds the encoded planes themselves: it pulls them
through a ``PlaneSource`` — either an in-memory `LevelBitplanes` wrapper or a
store-backed source that fetches checksum-verified segments through a
`SegmentFetcher` (repro.store).  ``prefetch_to_eps`` forwards a *hint* to the
source: a store-backed source issues background fetches for the planes an
upcoming request will need, so transport overlaps the QoI estimator round
(the in-memory source ignores it).  Decoded results are bit-identical across
sources and across any fetch schedule ending at the same plane counts.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Union

import numpy as np

from repro.bitplane.encoder import (
    LevelBitplanes,
    PlaneGroupMeta,
    accumulate_planes,
    inflate_planes,
    plane_bound,
    planes_needed,
    sign_plane_bytes,
    values_from_planes,
)
from repro.kernels import ops
from repro.trace import INFLATE, note_h2d, span, to_host


class _Ready:
    """Trivial ticket for a decode dispatched inline (no batcher)."""

    def __init__(self, res):
        self._res = res

    def result(self):
        return self._res


@dataclass
class PlaneSegment:
    level: int
    plane: int
    nbytes: int


class PlaneSource:
    """Access to one coefficient group's encoded segments.

    ``meta`` is always resident; payload bytes are produced on demand by
    ``planes``/``signs``.  ``prefetch`` is a non-binding hint that the given
    plane range (plus the sign segment, if plane 0 is included) will be
    requested soon.
    """

    meta: PlaneGroupMeta

    def planes(self, start: int, stop: int) -> Sequence[bytes]:
        raise NotImplementedError

    def planes_available(self, start: int, stop: int):
        """Deliverable prefix of planes [start, stop): ``(buffers, error)``,
        with ``error`` None only when every plane arrived.  A bitplane
        prefix is useful exactly as far as it is contiguous, so a source
        that can fail partially (store-backed) overrides this to return
        what it got; the default is all-or-nothing via ``planes``."""
        try:
            return list(self.planes(start, stop)), None
        except Exception as e:
            return [], e

    def signs(self) -> bytes:
        raise NotImplementedError

    def prefetch(self, start: int, stop: int, certain: bool = True) -> None:
        """Hint that planes [start, stop) will be requested; ``certain=False``
        marks a speculative prediction the reader may never follow up on."""
        pass


class InMemoryPlaneSource(PlaneSource):
    """The classic path: planes live in a `LevelBitplanes` in RAM."""

    def __init__(self, lbp: LevelBitplanes):
        self.lbp = lbp
        self.meta = lbp.meta()

    def planes(self, start: int, stop: int) -> Sequence[bytes]:
        return self.lbp.planes[start:stop]

    def signs(self) -> bytes:
        return self.lbp.signs


class LevelStream:
    """Progressive reader state over one group's PlaneSource."""

    def __init__(self, source: Union[PlaneSource, LevelBitplanes],
                 batcher=None, xfer_stats=None):
        if isinstance(source, LevelBitplanes):
            source = InMemoryPlaneSource(source)
        self.source = source
        self.meta = source.meta
        self.batcher = batcher        # serve.DecodeBatcher or None
        self.xfer_stats = xfer_stats  # trace.TransferStats or None
        self.fetched = 0
        self.bytes_fetched = 0
        # degraded mode: deepest reachable plane count once a segment of
        # this group proved permanently unavailable (None = fully available)
        self.pinned: Optional[int] = None
        self.pin_error: Optional[BaseException] = None
        # _mag is dual-representation: host (count,) uint64 on the host
        # path, or a device-resident full-word-length (W*32,) uint64 array
        # on the fused path (keeps jit cache keys count-independent and the
        # state on device across incremental flushes)
        self._mag = None
        self._signs: Optional[bytes] = None
        self._sign_bytes: Optional[np.ndarray] = None
        self._values: Optional[np.ndarray] = None
        self._values_dev = None
        # fused path defers decode: newly fetched planes pile up here and
        # flush in ONE jit dispatch at the next values()/values_device()
        self._pending_words: list = []
        self._pending_shifts: list = []

    def _pin(self, k: int, err: BaseException) -> None:
        self.pinned = k
        self.pin_error = err

    def fetch_to_planes(self, k: int) -> int:
        """Retrieve planes up to k (MSB-first). Returns newly moved bytes.

        A permanently unavailable segment does not raise: the stream *pins*
        at the deepest contiguous plane prefix it could decode — its bound
        (computed from actually-decoded planes) stays valid, just wider
        than requested — and records the cause in ``pin_error``."""
        meta = self.meta
        k = int(np.clip(k, 0, meta.nbits))
        if self.pinned is not None:
            k = min(k, self.pinned)
        if meta.exponent is None or k <= self.fetched:
            return 0
        if self.fetched == 0 and self._signs is None:
            try:
                self._signs = self.source.signs()
            except Exception as e:       # no signs -> no usable plane 0
                self._pin(0, e)
                return 0
        blobs, err = self.source.planes_available(self.fetched, k)
        got = self.fetched + len(blobs)
        # signs ride with the first plane: their bytes are charged when a
        # plane actually lands, keeping healthy-path accounting unchanged
        new_bytes = sum(meta.plane_sizes[self.fetched:got])
        if self.fetched == 0 and got > 0:
            new_bytes += meta.sign_size
        if blobs:
            if ops.use_fused_decode(meta.count):
                # defer: inflate now (cheap, host) but leave the bit-OR +
                # sign + scale to one fused device dispatch at flush time;
                # byte accounting above is already settled, so deferral
                # never changes FetchStats
                with span(INFLATE):
                    words, shifts = inflate_planes(meta.count, meta.nbits,
                                                   blobs, self.fetched)
                self._pending_words.append(words)
                self._pending_shifts.append(shifts)
            else:
                state = self._host_mag()
                with span(INFLATE):
                    self._mag = accumulate_planes(meta.count, meta.nbits,
                                                  blobs, self.fetched,
                                                  state=state)
            self.fetched = got
            self.bytes_fetched += new_bytes
            self._values = None
            self._values_dev = None
        if err is not None:
            self._pin(self.fetched, err)
        return new_bytes if blobs else 0

    def fetch_to_eps(self, eps: float) -> int:
        return self.fetch_to_planes(planes_needed(self.meta, eps))

    def prefetch_to_planes(self, k: int, certain: bool = True) -> None:
        """Hint the source that planes up to ``k`` will be requested; a
        store-backed source starts moving planes [fetched, k) in the
        background.  Never changes decode state or byte accounting."""
        meta = self.meta
        if meta.exponent is None:
            return
        k = int(np.clip(k, 0, meta.nbits))
        if self.pinned is not None:
            k = min(k, self.pinned)    # never speculate past the pin
        if k > self.fetched:
            self.source.prefetch(self.fetched, k, certain=certain)

    def prefetch_to_eps(self, eps: float, certain: bool = True) -> None:
        """Plane-count hint derived from an upcoming ``eps`` request."""
        self.prefetch_to_planes(planes_needed(self.meta, eps),
                                certain=certain)

    def _host_mag(self) -> Optional[np.ndarray]:
        """Normalize the magnitude state to host (count,) uint64, folding any
        deferred planes through the host unpack (integer-exact, so the value
        is independent of which path folds them)."""
        count = self.meta.count
        mag = self._mag
        if mag is not None and (not isinstance(mag, np.ndarray)
                                or mag.shape != (count,)):
            mag = np.asarray(mag)[:count].copy()
        for words, shifts in zip(self._pending_words, self._pending_shifts):
            if mag is None:
                mag = np.zeros(count, dtype=np.uint64)
            mag |= ops.unpack_bitplanes(words, shifts, count)
        self._pending_words.clear()
        self._pending_shifts.clear()
        self._mag = mag
        return mag

    def _decoded_signs(self) -> np.ndarray:
        if self._sign_bytes is None:
            self._sign_bytes = sign_plane_bytes(self.meta.count, self._signs)
        return self._sign_bytes

    def flush_submit(self):
        """Phase 1 of the fused flush: hand the deferred planes to the
        decode batcher (or dispatch inline when there is none).  Returns an
        opaque ticket for ``flush_collect``, or None when nothing is
        pending.  Split in two so a caller draining many streams can submit
        them all before collecting — one batched dispatch instead of one
        per stream."""
        if not self._pending_words:
            return None
        meta = self.meta
        words = np.concatenate(self._pending_words, axis=0)
        shifts = np.concatenate(self._pending_shifts)
        self._pending_words.clear()
        self._pending_shifts.clear()
        scale = np.float64(2.0) ** (meta.exponent - meta.nbits)
        sb = self._decoded_signs()
        # the payload as handed over, before the decode pads its planes
        note_h2d(self.xfer_stats, words, shifts, sb,
                 *([self._mag] if isinstance(self._mag, np.ndarray) else []))
        if self.batcher is not None:
            return self.batcher.submit_decode(words, shifts, self._mag, sb,
                                              scale, meta.count)
        return _Ready(ops.decode_values_fused(words, shifts, self._mag, sb,
                                              scale, meta.count))

    def flush_collect(self, ticket) -> None:
        """Phase 2: adopt the fused decode result (device magnitude state +
        device values)."""
        if ticket is None:
            return
        mag, vals = ticket.result()
        self._mag = mag
        self._values_dev = vals

    def _flush(self) -> None:
        self.flush_collect(self.flush_submit())

    def values_device(self):
        """Device-resident float64 values when the fused path produced them
        (None otherwise) — lets the reader feed ``scatter_recompose_from``
        without a host round-trip."""
        if self.fetched == 0:
            return None
        self._flush()
        return self._values_dev

    def values(self) -> np.ndarray:
        if self._values is None:
            if self.fetched == 0:
                self._values = np.zeros(self.meta.count, dtype=np.float64)
            else:
                self._flush()
                if self._values_dev is not None:
                    self._values = to_host(self._values_dev,
                                           self.xfer_stats)
                else:
                    self._values = values_from_planes(
                        self.meta.count, self.meta.exponent, self.meta.nbits,
                        self._host_mag(), self._signs)
        return self._values

    @property
    def bound(self) -> float:
        return plane_bound(self.meta, self.fetched)

    def reset(self) -> None:
        self.fetched = 0
        self.bytes_fetched = 0
        self.pinned = None            # a re-read may find the blob healed
        self.pin_error = None
        self._mag = None
        self._signs = None
        self._sign_bytes = None
        self._values = None
        self._values_dev = None
        self._pending_words.clear()
        self._pending_shifts.clear()
