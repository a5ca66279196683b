"""Algorithm 1: GENERAL DATA REFACTOR — variables -> progressive archives.

Supported progressive representations (paper §V-B):
  * "hb"         PMGARD-HB: hierarchical-basis multilevel + bitplanes (paper's
                 preferred method — tight Σ_l e_l bound)
  * "ob"         PMGARD (orthogonal basis): + L² projection, loose bound
  * "ip"         interpolation-predicted: closed-loop residuals against the
                 decoder's truncated reconstruction; max_g e_g bound once
                 every group reaches its recorded prediction depth (see
                 transform/hierarchical.py `ip` section)
  * "psz3"       multi-snapshot SZ3-like ladder
  * "psz3_delta" residual-ladder SZ3-like

Every representation satisfies Definition 1: refactor into segments, then
reconstruct from a prefix with a *guaranteed, reported* L-inf bound. The
retrieval session gives a uniform interface to the QoI-preserved retrieval
loop (core/retrieval.py).

Incremental recomposition (§Perf, HB linearity)
-----------------------------------------------
``recompose_hb`` is linear, and a coefficient field supported on levels
<= l is untouched by the recompose steps coarser than l.  The HB reader
therefore represents the reconstruction as the fixed-order sum of
*per-level contribution fields*

    x̂ = Σ_{l = L..0}  recompose_hb_from(scatter(values_l), start=l)

and caches each contribution keyed by the level's fetched-plane count.
When a retrieval iteration moves planes of only a few levels, only those
levels' contributions are recomputed (a partial recompose from level l
down — for the finest level a pure scatter, no interpolation at all)
instead of re-running the full multilevel recompose on every iteration.
Because each contribution is a pure function of that level's decoded
values, and the codec's integer arithmetic makes decoded values depend
only on the final plane counts, *any* fetch schedule ending in the same
plane counts yields a bit-identical reconstruction — asserted against
from-scratch sessions in tests/test_incremental_recompose.py.

Bounded contribution cache (memory-budgeted retrieval)
------------------------------------------------------
Unbounded, the contribution cache holds one full-grid f64 field per
coefficient group — (L+1)·n·8 bytes per variable — which becomes the
server's scaling wall long before the segment bytes do.  Passing
``contrib_budget_bytes`` to ``open_reader`` / ``RetrievalSession`` /
``Archive.open`` caps the *retained* cache: the reader keeps at most
``budget // (n·8)`` contribution fields resident, finest levels first
(level 0 is the hottest — size-weighted budgets give it the most planes
in flight, and its rebuild skips every interpolation step but the last),
and spills the coarsest fields.  A spilled contribution is transparently
rebuilt through ``recompose_hb_from`` on the next refresh that needs it;
because contributions are pure functions of decoded values and the
summation order is fixed (coarse -> fine), a bounded reader reconstructs
*bit-identically* to an unbounded one at every requested eps — a zero
budget simply degrades to recompute-always.  The refresh streams the sum
(compute one contribution, add, then retain or drop it), so transient
working memory is two fields regardless of budget.  Spill/recompute/
residency counters land in ``ContribStats`` — store-backed readers share
their fetcher's ``FetchStats``, which carries the same fields (see
repro.store.fetcher).
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.bitplane.encoder import (
    LevelBitplanes,
    decode_prefix,
    encode_level,
    plane_bound,
    planes_needed,
)
from repro.bitplane.segments import InMemoryPlaneSource, LevelStream
from repro.compressors.snapshots import (
    DeltaSnapshotArchive,
    SnapshotArchive,
    default_snapshot_eps,
)
from repro.core.masks import OutlierMask, build_zero_velocity_mask
from repro.options import SessionOptions, _from_legacy
from repro.trace import READER_REFRESH, RECONSTRUCT, note_h2d, span, to_host
from repro.transform.hierarchical import (
    decompose_hb,
    grid_levels,
    ip_error_bound,
    level_map,
    pad_to_grid,
    recompose_hb,
    recompose_hb_from,
    scatter_recompose_from,
    scatter_recompose_ip_from,
    trunc_to_quantum,
    unpad,
)
from repro.transform.orthogonal import decompose_ob, ob_kappa, recompose_ob

METHODS = ("hb", "ob", "ip", "psz3", "psz3_delta")


def _pred_planes(meta) -> int:
    """Recorded `ip` prediction depth of a group; archives written before
    the field existed (or non-ip groups) default to full depth — the
    truncation becomes the identity and the contribution degenerates to
    the plain HB form."""
    return meta.pred_planes if meta.pred_planes is not None else meta.nbits


def _resolve_session_options(options: Optional[SessionOptions],
                             legacy: dict, where: str) -> SessionOptions:
    """Shared shim: an explicit SessionOptions wins; loose legacy kwargs
    build one through the once-warning deprecation path; neither means the
    defaults.  Mixing the two spellings is a hard error — silently merging
    them would make the options object lie about what the session uses."""
    if legacy:
        if options is not None:
            raise TypeError(f"{where}: pass either a SessionOptions object "
                            f"or legacy keyword arguments, not both")
        return _from_legacy(SessionOptions, legacy, where)
    return options if options is not None else SessionOptions()


@dataclass(frozen=True)
class VarAvailability:
    """Availability report for one variable of a degraded session.

    ``floor`` is the tightest L-inf bound the variable can still certify
    from the segments that *are* deliverable (for a healthy variable: the
    codec's own floor at full plane depth).  ``pinned`` marks variables the
    retrieval loop must stop tightening — requesting a smaller eps cannot
    move more bytes.  ``detail`` carries the first underlying cause
    (human-readable, for the serve-plane degradation report)."""
    pinned: bool
    floor: float
    detail: str = ""


@dataclass
class ContribStats:
    """Contribution-cache accounting for one (or more) bitplane readers.

    Field names deliberately match the ``contrib_*`` counters on
    ``repro.store.fetcher.FetchStats`` so a store-backed reader can bump its
    fetcher's stats object directly and a server sees one aggregate:

      * ``contrib_resident_bytes`` — contribution fields currently retained.
      * ``contrib_peak_bytes``     — high-water mark of the above (the
        RSS-proxy the memory-bound bench tracks; transient working fields
        during a refresh are not counted — they are bounded by two fields).
      * ``contrib_spills``         — contribution fields computed for a
        refresh and then dropped instead of retained (budget pressure);
        each may have to be rebuilt by a later refresh.
      * ``contrib_recomputes``     — budget-induced rebuilds: refreshes of a
        level whose plane count had NOT moved (an unbounded reader would
        have served it from cache).

    A sink is often SHARED — store-backed readers across every concurrent
    session of one archive aggregate into their fetcher's FetchStats — so
    all mutation funnels through ``contrib_note`` (one lock-guarded
    read-modify-write; the peak update must see its own delta, which bare
    ``+=`` from racing threads cannot guarantee).
    """
    contrib_resident_bytes: int = 0
    contrib_peak_bytes: int = 0
    contrib_spills: int = 0
    contrib_recomputes: int = 0

    def __post_init__(self) -> None:
        self._mu = threading.Lock()

    def contrib_note(self, delta_bytes: int = 0, spills: int = 0,
                     recomputes: int = 0) -> None:
        """Atomically apply a residency delta / spill / recompute event."""
        with self._mu:
            self.contrib_resident_bytes += delta_bytes
            if self.contrib_resident_bytes > self.contrib_peak_bytes:
                self.contrib_peak_bytes = self.contrib_resident_bytes
            self.contrib_spills += spills
            self.contrib_recomputes += recomputes

    def contrib_snapshot(self) -> Tuple[int, int, int, int]:
        with self._mu:
            return (self.contrib_resident_bytes, self.contrib_peak_bytes,
                    self.contrib_spills, self.contrib_recomputes)

    def merge(self, other) -> "ContribStats":
        """Accumulate another carrier of the ``contrib_*`` counters
        (another ContribStats, or a store fetcher's FetchStats)."""
        snap = other.contrib_snapshot() if hasattr(other, "contrib_snapshot") \
            else (other.contrib_resident_bytes, other.contrib_peak_bytes,
                  other.contrib_spills, other.contrib_recomputes)
        with self._mu:
            self.contrib_resident_bytes += snap[0]
            self.contrib_peak_bytes += snap[1]
            self.contrib_spills += snap[2]
            self.contrib_recomputes += snap[3]
        return self


# ---------------------------------------------------------------------------
# Per-variable archives
# ---------------------------------------------------------------------------


@dataclass
class BitplaneVarArchive:
    """PMGARD-HB/OB: per-level bitplane groups over the multilevel transform."""
    method: str                    # "hb" | "ob" | "ip"
    orig_shape: Tuple[int, ...]
    padded_shape: Tuple[int, ...]
    levels: int
    groups: List[LevelBitplanes]   # detail levels 0..L-1, then base (index L)
    group_indices: List[np.ndarray]

    @property
    def total_nbytes(self) -> int:
        return sum(g.total_nbytes for g in self.groups)

    def plane_sources(self) -> List[InMemoryPlaneSource]:
        """One PlaneSource per coefficient group — the uniform segment-access
        surface shared with store-backed variables (repro.store)."""
        return [InMemoryPlaneSource(g) for g in self.groups]

    def open_reader(self, options: Optional[SessionOptions] = None,
                    **legacy) -> "_BitplaneVarReader":
        opts = _resolve_session_options(options, legacy,
                                        "BitplaneVarArchive.open_reader")
        return _BitplaneVarReader(
            self, contrib_budget_bytes=opts.contrib_budget_bytes,
            contrib_pool=opts.contrib_pool,
            decode_batcher=opts.decode_batcher,
            xfer_stats=opts.xfer_stats)


@dataclass
class SnapshotVarArchive:
    archive: object                # SnapshotArchive | DeltaSnapshotArchive

    @property
    def total_nbytes(self) -> int:
        return self.archive.total_nbytes

    def open_reader(self, options: Optional[SessionOptions] = None,
                    **legacy) -> "_SnapshotVarReader":
        # snapshot readers hold at most one decoded field; the contribution
        # budget/pool is a bitplane-reader concept and is accepted (and
        # validated) for interface uniformity only
        _resolve_session_options(options, legacy,
                                 "SnapshotVarArchive.open_reader")
        return _SnapshotVarReader(self)


@dataclass
class Archive:
    """Refactored multi-precision segments + metadata for all variables."""
    method: str
    variables: Dict[str, object]
    masks: Dict[str, OutlierMask]
    ranges: Dict[str, float]
    shapes: Dict[str, Tuple[int, ...]]

    @property
    def total_nbytes(self) -> int:
        n = sum(v.total_nbytes for v in self.variables.values())
        n += sum(m.nbytes for m in self.masks.values())
        return n

    def open(self, options: Optional[SessionOptions] = None,
             **legacy) -> "RetrievalSession":
        opts = _resolve_session_options(options, legacy, "Archive.open")
        return RetrievalSession(self, opts)

    def n_elements(self, name: str) -> int:
        return int(np.prod(self.shapes[name]))


def refactor_variables(fields: Dict[str, np.ndarray],
                       method: str = "hb",
                       nbits: int = 48,
                       max_levels: int = 32,
                       snapshot_eps: Optional[Sequence[float]] = None,
                       n_snapshots: int = 10,
                       mask_zero_velocity: bool = True) -> Archive:
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; expected one of {METHODS}")
    masks = build_zero_velocity_mask(fields) if mask_zero_velocity else {}
    variables: Dict[str, object] = {}
    ranges: Dict[str, float] = {}
    shapes: Dict[str, Tuple[int, ...]] = {}
    for name, data in fields.items():
        data = np.asarray(data, dtype=np.float64)
        shapes[name] = data.shape
        rng = float(np.max(data) - np.min(data))
        ranges[name] = rng if rng > 0 else 1.0
        if method in ("hb", "ob", "ip"):
            variables[name] = _build_bitplane_var(data, method, nbits, max_levels)
        else:
            ladder = list(snapshot_eps) if snapshot_eps is not None else \
                default_snapshot_eps(ranges[name], n=n_snapshots)
            if method == "psz3":
                variables[name] = SnapshotVarArchive(
                    SnapshotArchive.build(data, ladder))
            else:
                variables[name] = SnapshotVarArchive(
                    DeltaSnapshotArchive.build(data, ladder))
    return Archive(method=method, variables=variables, masks=masks,
                   ranges=ranges, shapes=shapes)


def _build_bitplane_var(data: np.ndarray, method: str, nbits: int,
                        max_levels: int) -> BitplaneVarArchive:
    padded, orig_shape = pad_to_grid(data)
    levels = grid_levels(padded.shape, max_levels)
    if method == "ip":
        groups, indices = _encode_ip_groups(padded, levels, nbits)
    else:
        transform = decompose_hb if method == "hb" else decompose_ob
        coeffs = np.asarray(transform(padded, levels))
        lmap = level_map(padded.shape, levels).ravel()
        flat = coeffs.ravel()
        groups, indices = [], []
        for l in range(levels + 1):      # details 0..L-1, base = L
            idx = np.flatnonzero(lmap == l)
            groups.append(encode_level(flat[idx], nbits=nbits))
            indices.append(idx)
    return BitplaneVarArchive(method=method, orig_shape=orig_shape,
                              padded_shape=padded.shape, levels=levels,
                              groups=groups, group_indices=indices)


def _encode_ip_groups(padded: np.ndarray, levels: int,
                      nbits: int) -> Tuple[List[LevelBitplanes],
                                           List[np.ndarray]]:
    """Closed-loop interpolation-predicted encoding (method "ip").

    Groups are encoded base-first: each group's coefficients are the
    residual of the original nodal values against the running sum of the
    coarser groups' *decoder* contributions — the exact fixed-order sum
    ``_refresh_hb_incremental`` replays (same prefix decode, same jit'd
    scatter+recompose, same f64 accumulation order), so in the matched
    regime (every group fetched to at least its recorded ``pred_planes``)
    the decoder's prediction reproduces the encoder's bit-for-bit and the
    error bound composes as max_g e_g instead of Σ_g e_g.  Computing the
    prediction any other way (e.g. one joint recompose of the truncated
    coefficient field) drifts from the decoder by ulps, which for fine
    groups — whose residual exponents sit far below the field scale —
    can exceed the codec's 2^{E_g-nbits} slack and break the certified
    bound.

    ``pred_planes`` per group is chosen against a single absolute
    truncation target θ = amax_min / (2·(levels+1)) (amax_min = smallest
    nonzero per-group HB surplus scale): kp = ceil(E_g - log2 θ), so every
    group's prediction truncation error is <= θ and the total mismatch
    budget across the ladder stays below amax_min/2 — residuals keep the
    open-loop surplus scale, and the matched regime becomes reachable
    right where the finest level starts being resolved (mid bitrates)."""
    import jax.numpy as jnp
    shape = padded.shape
    lmap = level_map(shape, levels).ravel()
    indices = [np.flatnonzero(lmap == l) for l in range(levels + 1)]
    hb = np.asarray(decompose_hb(padded, levels)).ravel()
    amaxes = [float(np.max(np.abs(hb[idx]))) if idx.size else 0.0
              for idx in indices]
    nonzero = [a for a in amaxes if a > 0.0]
    theta = min(nonzero) / (2.0 * (levels + 1)) if nonzero else 0.0
    x_flat = padded.ravel()
    total = np.zeros(shape, dtype=np.float64)
    groups: List[LevelBitplanes] = [None] * (levels + 1)
    for l in range(levels, -1, -1):      # base first — prediction order
        idx = indices[l]
        resid = x_flat[idx] - total.ravel()[idx]
        lbp = encode_level(resid, nbits=nbits)
        if lbp.exponent is not None:
            kp = nbits
            if theta > 0.0:
                kp = int(np.clip(int(np.ceil(lbp.exponent - np.log2(theta))),
                                 0, nbits))
            lbp.pred_planes = kp
            if l > 0 and kp > 0:
                u = decode_prefix(lbp, kp)
                q = 2.0 ** (lbp.exponent - kp)
                c = scatter_recompose_ip_from(
                    jnp.asarray(idx), jnp.asarray(u), shape, levels,
                    min(l, levels - 1), q)
                total += np.asarray(c)
        groups[l] = lbp
    return groups, indices


# ---------------------------------------------------------------------------
# Retrieval session (uniform progressive-reader interface)
# ---------------------------------------------------------------------------


class _BitplaneVarReader:
    """Progressive reader over a bitplane variable — in-memory
    `BitplaneVarArchive` or store-backed `repro.store.StoreBitplaneVar`
    (same surface: method/shapes/levels/groups/group_indices/plane_sources);
    planes arrive through each group's PlaneSource.

    ``contrib_budget_bytes`` bounds the retained HB contribution cache (see
    module docstring): None keeps every level resident (the classic path);
    any other value keeps the ``budget // field_nbytes`` finest levels and
    spills the rest, rebuilding them on demand — bit-identical outputs at
    any budget, including zero.  ``contrib_stats`` is an optional external
    sink carrying the ``contrib_*`` counters (store-backed readers pass
    their fetcher's FetchStats so several readers aggregate into one view).

    ``contrib_pool`` replaces the static cap with a server-wide
    :class:`repro.serve.budget.ContribBudgetPool`: retention becomes a
    borrow against one shared pool (hottest variables win), and slot
    mutation moves under the pool's lock so cross-session reclaim is
    race-free.  Spill/recompute semantics — and bit-identical outputs —
    are unchanged; only WHICH levels stay resident becomes dynamic.

    ``xfer_stats`` (a :class:`repro.trace.TransferStats`) counts the bytes
    the reader and its streams move between host and device.
    """

    def __init__(self, var, contrib_budget_bytes: Optional[int] = None,
                 contrib_stats=None, contrib_pool=None, decode_batcher=None,
                 xfer_stats=None):
        self.var = var
        self._batcher = decode_batcher
        self._xfer = xfer_stats
        self.streams = [LevelStream(src, batcher=decode_batcher,
                                    xfer_stats=xfer_stats)
                        for src in var.plane_sources()]
        self._idx_dev: Dict[int, object] = {}   # device group_indices cache
        self._recon: Optional[np.ndarray] = None
        self._dirty = True
        # HB incremental recomposition state (see module docstring): one
        # cached contribution field per coefficient group, keyed by the
        # fetched-plane count it was computed at (-1 = never computed).
        ngroups = var.levels + 1
        self._contribs: List[Optional[np.ndarray]] = [None] * ngroups
        self._contrib_fetched: List[int] = [-1] * ngroups
        self._field_nbytes = int(np.prod(var.padded_shape)) * 8
        self.contrib_stats = contrib_stats if contrib_stats is not None \
            else ContribStats()
        self._pool = contrib_pool
        if contrib_pool is not None:
            self._resident_cap = ngroups    # the pool arbitrates dynamically
        elif contrib_budget_bytes is None:
            self._resident_cap = ngroups
        else:
            self._resident_cap = min(
                ngroups, max(0, int(contrib_budget_bytes)) //
                self._field_nbytes)

    @property
    def contrib_resident_levels(self) -> List[int]:
        """Levels whose contribution field is currently retained."""
        return [l for l, c in enumerate(self._contribs) if c is not None]

    def _note_resident(self, delta_fields: int) -> None:
        self.contrib_stats.contrib_note(
            delta_bytes=delta_fields * self._field_nbytes)

    def _pool_set_contrib(self, slot: int, value) -> None:
        """Slot mutation for POOLED readers — called only by the pool, under
        the pool's lock (deposit on retain, clear on reclaim/release), so a
        refresh on one session and a reclaim driven by another can never
        interleave half-way.  Residency accounting moves with the slot."""
        had = self._contribs[slot] is not None
        self._contribs[slot] = value
        has = value is not None
        if has and not had:
            self._note_resident(+1)
        elif had and not has:
            self._note_resident(-1)

    def reconstruct_at_resolution(self, coarsen: int,
                                  eps: float) -> Tuple[np.ndarray, float]:
        """Progression in RESOLUTION (paper §II): reconstruct the 2^coarsen-
        strided sub-grid by fetching only the coarser level groups — detail
        levels 0..coarsen-1 (the finest) are never moved. Returns the
        coarse field (strided shape) and its achieved L-inf bound relative
        to the true coarse-grid values."""
        if self.var.method not in ("hb", "ip"):
            # OB's L² corrections mix finer details into coarse nodal
            # values, so a truncated reconstruction is not the nodal
            # sub-grid — HB's level independence (which `ip` inherits: a
            # group's contribution never touches coarser nodes) is what
            # enables this.
            raise ValueError("resolution progression requires method='hb' "
                             "or method='ip'")
        levels = self.var.levels
        coarsen = int(np.clip(coarsen, 0, levels))
        active = list(range(coarsen, levels + 1))   # coarser details + base
        targets = self._plane_targets(eps)
        for l in active:
            if self.streams[l].fetch_to_planes(targets[l]):
                self._dirty = True
        if self.var.method == "ip":
            # `ip` semantics are defined by the fixed-order contribution
            # sum (a joint recompose of truncated coefficients drifts by
            # ulps from what the encoder's residuals were closed against)
            rec = np.zeros(self.var.padded_shape, dtype=np.float64)
            for l in range(levels, coarsen - 1, -1):
                rec += self._compute_contrib(l)
        else:
            flat = np.zeros(int(np.prod(self.var.padded_shape)),
                            dtype=np.float64)
            for l in active:
                flat[self.var.group_indices[l]] = self.streams[l].values()
            rec = np.asarray(recompose_hb(
                flat.reshape(self.var.padded_shape), levels))
        full = unpad(rec, self.var.orig_shape)
        coarse = full[tuple(slice(None, None, 1 << coarsen)
                            for _ in self.var.orig_shape)]
        # bound on the sub-grid: HB/ip coarse nodes never receive finer-
        # level contributions, so only the active groups' bounds apply
        if self.var.method == "ip":
            mism = self._ip_mismatches([s.fetched for s in self.streams])
            achieved = ip_error_bound([self.streams[l].bound for l in active],
                                      [mism[l] for l in active])
        else:
            achieved = float(np.sum([self.streams[l].bound for l in active]))
        return coarse, achieved

    @property
    def bytes_fetched(self) -> int:
        return sum(s.bytes_fetched for s in self.streams)

    def _budgets(self, eps: float) -> List[float]:
        """Split the variable's L-inf budget across coefficient groups so the
        method's composition bound meets eps.

        The split is *size-weighted* (§Perf): minimising total plane bits
        Σ_l n_l·(E_l − log2 e_l) subject to Σ_l e_l <= eps gives
        e_l ∝ n_l — the finest level (half the elements) deserves ~half the
        budget; the equal split overspends ~log2(L/2) planes on it.
        OB additionally divides detail budgets by (1+κ) per its bound."""
        counts = np.asarray([g.count for g in self.var.groups], dtype=float)
        weights = counts / counts.sum()
        if self.var.method in ("hb", "ip"):
            return [eps * w for w in weights]
        kappa = ob_kappa(len(self.var.padded_shape))
        out = [eps * w / (1.0 + kappa) for w in weights[:-1]]
        return out + [eps * weights[-1]]

    def _ip_quantum(self, l: int) -> float:
        """Group ``l``'s prediction quantum 2^{E-kp} (0.0 for an all-zero
        group — no truncation)."""
        m = self.streams[l].meta
        if m.exponent is None:
            return 0.0
        return 2.0 ** (m.exponent - _pred_planes(m))

    def _ip_mismatches(self, depths: List[int]) -> List[float]:
        """Per-group prediction mismatch δ_g at the given plane depths:
        how far the decoder's truncated contribution can sit from the one
        the encoder closed its residuals against (0 once the depth reaches
        the recorded ``pred_planes``)."""
        out = []
        for s, k in zip(self.streams, depths):
            m = s.meta
            kp = _pred_planes(m)
            if m.exponent is None or k >= kp:
                out.append(0.0)
            else:
                out.append(2.0 ** (m.exponent - k) - 2.0 ** (m.exponent - kp))
        return out

    def _plane_targets(self, eps: float) -> List[int]:
        """Per-group plane targets for a request at ``eps`` — a pure
        function of (eps, static group metadata), never of fetch state, so
        coalesced sessions compute identical targets.  hb/ob: exactly the
        size-weighted eps split (``planes_needed`` per budget).  ip picks
        the cheaper of two sound plans by predicted from-zero bytes:

          A. the hb-style split — bound Σ_g e_g <= eps without ever
             reaching the prediction depths (shallow requests);
          B. matched — every group to max(pred_planes, planes_needed(eps)),
             where the bound collapses to max_g e_g <= eps (the mid/deep-
             bitrate win).
        """
        metas = [s.meta for s in self.streams]
        ka = [planes_needed(m, b)
              for m, b in zip(metas, self._budgets(eps))]
        if self.var.method != "ip":
            return ka
        kb = [max(_pred_planes(m), planes_needed(m, eps))
              if m.exponent is not None else 0 for m in metas]

        def cost(ks):
            return sum(sum(m.plane_sizes[:k]) + (m.sign_size if k else 0)
                       for m, k in zip(metas, ks))

        return kb if cost(kb) <= cost(ka) else ka

    def achieved_bound(self) -> float:
        bounds = [s.bound for s in self.streams]
        if self.var.method == "hb":
            return float(np.sum(bounds))
        if self.var.method == "ip":
            return ip_error_bound(
                bounds, self._ip_mismatches([s.fetched
                                             for s in self.streams]))
        kappa = ob_kappa(len(self.var.padded_shape))
        return float((1.0 + kappa) * np.sum(bounds[:-1]) + bounds[-1])

    @property
    def is_degraded(self) -> bool:
        """True once any coefficient group pinned at a partial plane prefix
        (a segment of it is permanently unavailable this session)."""
        return any(s.pinned is not None for s in self.streams)

    def availability_floor(self) -> float:
        """Tightest bound certifiable from the deliverable plane prefixes:
        each group contributes its bound at the deepest reachable plane
        (the pin for degraded groups, full depth otherwise), composed
        exactly like ``achieved_bound``."""
        depths = [s.pinned if s.pinned is not None else s.meta.nbits
                  for s in self.streams]
        bounds = [plane_bound(s.meta, d)
                  for s, d in zip(self.streams, depths)]
        if self.var.method == "hb":
            return float(np.sum(bounds))
        if self.var.method == "ip":
            return ip_error_bound(bounds, self._ip_mismatches(depths))
        kappa = ob_kappa(len(self.var.padded_shape))
        return float((1.0 + kappa) * np.sum(bounds[:-1]) + bounds[-1])

    def availability(self) -> VarAvailability:
        detail = ""
        if self.is_degraded:
            errs = [s.pin_error for s in self.streams
                    if s.pin_error is not None]
            detail = str(errs[0]) if errs else ""
        return VarAvailability(pinned=self.is_degraded,
                               floor=self.availability_floor(),
                               detail=detail)

    def request(self, eps: float) -> Tuple[np.ndarray, float]:
        for s, k in zip(self.streams, self._plane_targets(eps)):
            if s.fetch_to_planes(k):
                self._dirty = True
        return self.current()

    def current(self) -> Tuple[np.ndarray, float]:
        """Reconstruction at the present plane counts and its certified
        bound — fetches nothing."""
        if self.var.method in ("hb", "ip"):
            with span(READER_REFRESH):
                self._refresh_hb_incremental()
        else:
            self._refresh_full()
        return self._recon, self.achieved_bound()

    def prefetch_eps(self, eps: float, certain: bool = True) -> None:
        """Hint that a request at ``eps`` is coming: split the budget exactly
        as ``request`` will and forward per-group plane ranges to the
        sources.  Store-backed sources start background fetches; in-memory
        sources ignore it.  No decode state or byte accounting changes.
        ``certain=False`` (a speculative prediction) is byte-safe here —
        plane fetches are monotone prefixes, so a too-shallow prediction is
        always a subset of whatever is eventually consumed — but the flag is
        forwarded so the fetcher knows which cache entries it may evict."""
        for s, k in zip(self.streams, self._plane_targets(eps)):
            s.prefetch_to_planes(k, certain=certain)

    def _group_idx_dev(self, l: int):
        idx = self._idx_dev.get(l)
        if idx is None:
            import jax.numpy as jnp
            note_h2d(self._xfer, self.var.group_indices[l])
            idx = self._idx_dev[l] = jnp.asarray(self.var.group_indices[l])
        return idx

    def _contrib_submit(self, l: int):
        """Phase 1 of a contribution rebuild: route the placement+recompose to
        the device when the stream holds device-resident decoded values
        (fused path), queueing on the shared DecodeBatcher when one is
        attached so same-shape rebuilds across readers merge into one
        vmapped dispatch.  Returns an opaque handle for
        ``_contrib_collect``."""
        shape, levels = self.var.padded_shape, self.var.levels
        start = min(l, levels - 1)       # base group (index L) needs all steps
        vals_dev = self.streams[l].values_device()
        if vals_dev is None:
            return ("host", None)
        if self.var.method == "ip":
            idx = self._group_idx_dev(l)
            q = self._ip_quantum(l)
            if self._batcher is not None:
                return ("ticket", self._batcher.submit_recompose(
                    vals_dev, shape, levels, start, quantum=q, idx=idx))
            return ("array", scatter_recompose_ip_from(idx, vals_dev, shape,
                                                       levels, start, q))
        if self._batcher is not None:
            return ("ticket", self._batcher.submit_recompose(
                vals_dev, shape, levels, start))
        return ("array", scatter_recompose_from(vals_dev, shape, levels,
                                                start))

    def _contrib_collect(self, l: int, handle) -> np.ndarray:
        kind, h = handle
        if kind == "ticket":
            return to_host(h.result(), self._xfer)
        if kind == "array":
            return to_host(h, self._xfer)
        # host route: scatter on host, partial recompose on device — bit-
        # identical to the device route's placement + recompose (pinned by
        # tests/test_decode_conformance.py, tests/test_recompose_reference.py)
        shape, levels = self.var.padded_shape, self.var.levels
        idx = self.var.group_indices[l]
        vals = self.streams[l].values()
        start = min(l, levels - 1)
        flat = np.zeros(int(np.prod(shape)), dtype=np.float64)
        if self.var.method == "ip":
            # truncated part seeds the finer groups' prediction; the tail
            # rides back in at this group's own nodes — the host mirror of
            # ``scatter_recompose_ip_from``
            t = trunc_to_quantum(vals, self._ip_quantum(l))
            flat[idx] = t
            note_h2d(self._xfer, flat)
            out = np.array(to_host(recompose_hb_from(flat.reshape(shape),
                                                     levels, start),
                                   self._xfer))
            out.ravel()[idx] += vals - t
            return out
        flat[idx] = vals
        note_h2d(self._xfer, flat)
        return to_host(recompose_hb_from(flat.reshape(shape), levels, start),
                       self._xfer)

    def _compute_contrib(self, l: int) -> np.ndarray:
        """Contribution of group ``l``: its decoded values scattered onto the
        padded grid, partially recomposed from its own level down.  A pure
        function of the level's decoded values — bitwise reproducible."""
        return self._contrib_collect(l, self._contrib_submit(l))

    def _refresh_hb_incremental(self) -> None:
        """HB linearity: recompute only the per-level contributions whose
        plane counts moved (partial recompose from that level down), then
        re-sum in a fixed coarse->fine order.  The `ip` method rides the
        same machinery — its contribution adds a truncation before the
        recompose and a tail after (see ``_contrib_collect``), but remains
        a pure function of the group's decoded values, and the fixed
        summation order here is exactly what its encoder closed the
        residual loop against.  Contributions are pure
        functions of each level's decoded values, so any fetch schedule
        ending at the same plane counts reconstructs bit-identically.

        Under a contribution budget the sum is *streamed*: each level's
        field is produced (from cache, or rebuilt if spilled/moved), added
        into the running total in the same fixed order, then retained only
        if the level sits inside the resident set — the finest
        ``_resident_cap`` levels.  The streamed path performs the exact same
        additions in the exact same order as the unbounded path, so outputs
        are bit-identical at any budget."""
        levels = self.var.levels
        stale = [self._contrib_fetched[l] != self.streams[l].fetched
                 for l in range(levels + 1)]
        # the early-out keys on plane counts, not residency: a repeat request
        # at an already-satisfied eps serves the cached reconstruction even
        # at budget 0 (where no contribution is ever retained)
        if not any(stale) and self._recon is not None:
            return
        st = self.contrib_stats
        # phase 1: flush every stream's deferred fused decode, submitting
        # them all before collecting so a shared DecodeBatcher can merge
        # this reader's flushes — and concurrent sessions' — into one
        # vmapped dispatch per shape bucket
        flushes = [(s, s.flush_submit()) for s in self.streams]
        for s, t in flushes:
            s.flush_collect(t)
        # phase 2: same submit-then-collect for the contribution rebuilds
        # this refresh needs (collection happens inside the fixed-order sum)
        pending = {}
        for l in range(levels, -1, -1):
            if self._contribs[l] is None or stale[l]:
                pending[l] = self._contrib_submit(l)
        total = np.zeros(self.var.padded_shape, dtype=np.float64)
        for l in range(levels, -1, -1):       # fixed summation order
            c = self._contribs[l]
            if l in pending:
                if c is None and not stale[l]:
                    # planes did not move — an unbounded reader would have a
                    # cached field here; this rebuild is pure budget cost
                    st.contrib_note(recomputes=1)
                c = self._contrib_collect(l, pending[l])
                self._contrib_fetched[l] = self.streams[l].fetched
            total += c
            if self._pool is not None:
                # pooled retention: borrow a field-sized lease against the
                # server-wide pool.  The pool deposits into the slot under
                # its own lock (reclaiming colder holdings of ANY session
                # first); a denial means this field is hot enough to keep
                # only at someone hotter's expense — spill it instead.
                if not self._pool.retain(self, slot=l, level=l,
                                         nbytes=self._field_nbytes, value=c):
                    st.contrib_note(spills=1)
            # resident policy: keep the finest levels (low l), spill coarse
            elif l < self._resident_cap:
                if self._contribs[l] is None:
                    self._note_resident(+1)
                self._contribs[l] = c
            else:
                # computed for this refresh, dropped instead of retained —
                # the next refresh that finds this level stale-free will
                # charge a contrib_recompute to rebuild it
                if self._contribs[l] is not None:   # defensive: cap is static
                    self._note_resident(-1)
                    self._contribs[l] = None
                st.contrib_note(spills=1)
        self._recon = unpad(total, self.var.orig_shape)
        self._dirty = False

    def _refresh_full(self) -> None:
        """OB path: the L² corrections couple levels, so reconstruction is
        from-scratch whenever any stream moved (cached otherwise)."""
        if self._dirty or self._recon is None:
            flat = np.zeros(int(np.prod(self.var.padded_shape)), dtype=np.float64)
            for s, idx in zip(self.streams, self.var.group_indices):
                flat[idx] = s.values()
            recompose = recompose_hb if self.var.method == "hb" else recompose_ob
            rec = np.asarray(recompose(flat.reshape(self.var.padded_shape),
                                       self.var.levels))
            self._recon = unpad(rec, self.var.orig_shape)
            self._dirty = False

    # -- serve-plane hooks (repro.serve.coalesce / budget) -------------------

    def state_signature(self) -> Tuple[int, ...]:
        """Decode state as the tuple of per-group fetched-plane counts.
        Decoded values — and hence the reconstruction — are a pure function
        of this signature (the invariant tests/test_incremental_recompose.py
        asserts), which is what makes cross-session coalescing sound: two
        readers with equal signatures reconstruct bit-identically."""
        return tuple(s.fetched for s in self.streams)

    def advance_to(self, eps: float) -> bool:
        """Move every stream exactly as ``request(eps)`` would WITHOUT
        recomposing — the coalescer's waiter path (the leader's fetch made
        these planes cache-hot).  Returns True if any stream moved."""
        moved = False
        for s, k in zip(self.streams, self._plane_targets(eps)):
            if s.fetch_to_planes(k):
                moved = True
                self._dirty = True
        return moved

    def adopt_reconstruction(self, recon: np.ndarray) -> None:
        """Install an externally computed reconstruction for the CURRENT
        decode state (coalescing fan-out).  Contribution slots whose plane
        counts moved since they were cached are dropped — serving them from
        a later refresh would desynchronize cache and decode state; the
        slots that did not move stay valid (pure functions of unchanged
        values)."""
        for l in range(self.var.levels + 1):
            if self._contrib_fetched[l] != self.streams[l].fetched:
                if self._contribs[l] is not None:
                    if self._pool is not None:
                        self._pool.release(self, l)   # clears slot + counts
                    else:
                        self._note_resident(-1)
                        self._contribs[l] = None
                self._contrib_fetched[l] = self.streams[l].fetched
        self._recon = recon
        self._dirty = False

    def close(self) -> None:
        """Return pooled leases (the serve plane closes sessions; a reader
        without a pool has nothing to give back)."""
        if self._pool is not None:
            self._pool.release_owner(self)


class _SnapshotVarReader:
    def __init__(self, var: SnapshotVarArchive):
        self.reader = var.archive.open()

    @property
    def bytes_fetched(self) -> int:
        return self.reader.bytes_fetched

    def request(self, eps: float) -> Tuple[np.ndarray, float]:
        return self.reader.request(eps)


class RetrievalSession:
    """Progressive, stateful reader over all variables of an Archive (the
    in-memory `Archive` or a store-backed `repro.store.StoreArchive` — every
    variable builds its own reader via ``open_reader``).

    Session policy comes from a :class:`repro.options.SessionOptions`
    (prefetch depth, per-variable contribution budget, shared contribution
    pool — see its docstring); the pre-v4 loose kwargs still work through
    the once-warning deprecation shim.  ``coalescer`` (assignable after
    construction) routes ``reconstruct`` through cross-session
    single-flight."""

    def __init__(self, archive, options: Optional[SessionOptions] = None,
                 **legacy):
        opts = _resolve_session_options(options, legacy, "RetrievalSession")
        self.archive = archive
        self.options = opts
        self.contrib_budget_bytes = opts.contrib_budget_bytes
        self.contrib_pool = opts.contrib_pool
        self.xfer_stats = opts.xfer_stats
        self.coalescer = None
        self.readers: Dict[str, object] = {}
        self._mask_charged: Dict[str, bool] = {}
        for name, var in archive.variables.items():
            self.readers[name] = var.open_reader(opts)
            self._mask_charged[name] = False
        self._mask_bytes = 0
        # How many reassign_eb reduction steps ahead the retrieval loop may
        # hint to the fetcher (depth 1 is always a prefix of the next
        # round's fetch, so nothing speculative is ever wasted).
        self.prefetch_depth = opts.prefetch_depth

    @property
    def bytes_retrieved(self) -> int:
        return sum(r.bytes_fetched for r in self.readers.values()) \
            + self._mask_bytes

    def contrib_stats(self) -> ContribStats:
        """Aggregate contribution-cache counters over this session's bitplane
        readers.  Distinct sink objects are summed once — store-backed
        readers all share their fetcher's FetchStats, so the aggregate never
        double-counts (note that shared sink also carries other sessions of
        the same archive)."""
        agg = ContribStats()
        seen = set()
        for r in self.readers.values():
            st = getattr(r, "contrib_stats", None)
            if st is not None and id(st) not in seen:
                seen.add(id(st))
                agg.merge(st)
        return agg

    def availability(self) -> Dict[str, VarAvailability]:
        """Per-variable availability for variables pinned by missing
        segments — empty on a healthy session.  The retrieval loop uses the
        reported floors to stop tightening pinned variables (see
        core/retrieval.py); the serve plane prints them."""
        out: Dict[str, VarAvailability] = {}
        for name, r in self.readers.items():
            get = getattr(r, "availability", None)
            if get is not None:
                a = get()
                if a.pinned:
                    out[name] = a
        return out

    @property
    def degraded(self) -> bool:
        return bool(self.availability())

    def reader(self, name: str):
        """The per-variable reader, opening one lazily for variables that
        appeared AFTER this session did (live archives: a journal replay on
        ``refresh()`` can add timeseries variables to an open archive)."""
        r = self.readers.get(name)
        if r is None:
            var = self.archive.variables.get(name)
            if var is None:
                refresh = getattr(self.archive, "refresh", None)
                if refresh is not None:
                    refresh()          # maybe it was journaled since open
                var = self.archive.variables.get(name)
            if var is None:
                raise KeyError(name)
            r = var.open_reader(self.options)
            self.readers[name] = r
            self._mask_charged.setdefault(name, False)
        return r

    def follow(self, name: str) -> "FollowStream":
        """Follow-mode view over a live timeseries variable: ``poll()``
        surfaces newly appended timesteps (refreshing the archive's journal
        first), ``read(t)`` decodes them — without reopening anything, and
        bit-identical to a one-shot session over the same data."""
        return FollowStream(self, name)

    def prefetch(self, name: str, eps: float, certain: bool = True) -> None:
        """Non-binding hint that ``reconstruct(name, eps)`` is coming —
        forwarded to readers that support background segment fetch
        (store-backed bitplane and snapshot readers); a no-op otherwise.
        ``certain=False`` marks a *predicted* eps the retrieval loop may
        overshoot; readers whose fetch granularity is not prefix-monotone
        (independent psz3 snapshots) skip those to avoid moving bytes that
        are never consumed."""
        reader = self.readers.get(name)
        prefetch = getattr(reader, "prefetch_eps", None)
        if prefetch is not None:
            prefetch(eps, certain=certain)

    def reconstruct(self, name: str, eps: float) -> Tuple[np.ndarray, float]:
        """Reconstruct variable to L-inf bound <= eps; returns the data (with
        outlier-masked points exact) and the achieved bound.  With a
        ``coalescer`` attached (serve plane), concurrent duplicate requests
        across sessions collapse into one fetch + recompose — bit-identical
        results by the plane-count invariant."""
        with span(RECONSTRUCT):
            if self.coalescer is not None:
                data, achieved = self.coalescer.reconstruct(self, name, eps)
            else:
                data, achieved = self.reader(name).request(eps)
            mask = self.archive.masks.get(name)
            if mask is not None:
                if not self._mask_charged[name]:
                    self._mask_bytes += mask.nbytes
                    self._mask_charged[name] = True
                data = mask.apply(data)
        return data, achieved

    def current(self, name: str) -> Tuple[np.ndarray, float]:
        """The variable as this session's last ``reconstruct`` left it, with
        its certified bound — moves no bytes (bitplane archives only)."""
        data, achieved = self.readers[name].current()
        mask = self.archive.masks.get(name)
        return (data if mask is None else mask.apply(data)), achieved

    def reconstruct_at_resolution(self, name: str, coarsen: int,
                                  eps: float) -> Tuple[np.ndarray, float]:
        """Progression in resolution (paper §II): the 2^coarsen-strided
        sub-grid with an L-inf guarantee, moving only coarse-level segments
        (hb/ip bitplane archives only)."""
        reader = self.readers[name]
        if not isinstance(reader, _BitplaneVarReader):
            raise ValueError("resolution progression requires a bitplane "
                             "(hb/ip) archive")
        data, achieved = reader.reconstruct_at_resolution(coarsen, eps)
        return data, achieved

    def eb_array(self, name: str, achieved: float) -> np.ndarray:
        """Per-point error-bound array: achieved everywhere, 0 at exact
        (masked) points."""
        eb = np.full(self.archive.shapes[name], achieved, dtype=np.float64)
        mask = self.archive.masks.get(name)
        if mask is not None:
            eb[mask.mask] = 0.0
        return eb

    def close(self) -> None:
        """Release per-reader resources (pooled contribution leases).  The
        serve plane calls this when it retires a sticky session; in-memory
        sessions without a pool have nothing to release."""
        for r in self.readers.values():
            close = getattr(r, "close", None)
            if close is not None:
                close()

    def bitrate(self, names: Optional[Sequence[str]] = None) -> float:
        """Bits per element over the referenced variables (paper §III-C)."""
        names = list(names) if names is not None else list(self.readers)
        n_elems = sum(self.archive.n_elements(n) for n in names)
        rbytes = sum(self.readers[n].bytes_fetched for n in names) \
            + self._mask_bytes
        return 8.0 * rbytes / max(n_elems, 1)


class FollowStream:
    """Live view over one timeseries variable of an open session.

    ``poll()`` refreshes the archive's journal and returns the timestep
    indices that became visible since the previous poll (never re-reporting
    one); ``read(t)`` decodes any retained timestep through the session's
    chain-caching reader, so walking the stream in order pays exactly one
    delta decode per step — the property that makes a followed session
    bit- AND byte-identical to a one-shot session over the same timesteps.
    """

    def __init__(self, session: RetrievalSession, name: str):
        reader = session.reader(name)
        var = getattr(reader, "var", None)
        if var is None or not hasattr(var, "timesteps"):
            raise ValueError(f"variable {name!r} is not a timeseries — "
                             f"follow() needs a journaled (v4) live archive")
        self.session = session
        self.name = name
        self._reader = reader
        self._var = var
        # report everything already visible on the first poll
        self._next_t = var.base_t

    @property
    def latest(self) -> Optional[int]:
        """Newest visible timestep index (None before the first append)."""
        return self._var.latest_t

    def poll(self) -> List[int]:
        """Refresh the journal; return newly visible timestep indices."""
        refresh = getattr(self.session.archive, "refresh", None)
        if refresh is not None:
            refresh()
        latest = self._var.latest_t
        if latest is None:
            return []
        start = max(self._next_t, self._var.base_t)
        if start > latest:
            return []
        self._next_t = latest + 1
        return list(range(start, latest + 1))

    def read(self, t: int) -> Tuple[np.ndarray, float]:
        """Decode timestep ``t``; returns ``(data, certified bound)``."""
        return self._reader.read(t)
