"""Algorithms 2-4: QoI-preserved progressive data retrieval.

The loop iteratively refines the reconstruction until the *estimated* QoI
error bounds (Section IV theory — no ground truth needed) drop below the
requested tolerances:

  1. assign_eb (Alg 3): initial per-variable bounds from the requested
     relative QoI tolerances and the variables' value ranges.
  2. reconstruct every involved variable to its current bound (progressive —
     only new segments move).
  3. estimate each QoI's error upper bound on the reconstruction; done when
     all max bounds <= τ_abs.
  4. reassign_eb (Alg 4): at the worst point of the worst QoI, tighten the
     involved variables' bounds by c=1.5 until the *point* estimate clears
     the tolerance, then loop.

τ is relative to the QoI's value range (paper §III-C); the range is taken
from the current reconstruction and refreshed every round (ground truth is
unattainable mid-retrieval).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qoi import Expr
from repro.core.refactor import VarAvailability
from repro.trace import (ESTIMATE, REASSIGN, TransferStats, note_h2d,
                         span, to_host)

REDUCTION_FACTOR = 1.5          # c in Alg 4
MIN_REL_EPS = 2.0 ** -60        # full-fidelity floor
LADDER_STEPS = 200              # max Alg-4 tightening steps per iteration


@dataclass
class QoIRequest:
    name: str
    expr: Expr
    tau_rel: float


@dataclass
class IterationLog:
    iteration: int
    eps: Dict[str, float]
    est_errors: Dict[str, float]
    tau_abs: Dict[str, float]
    bytes_retrieved: int


@dataclass
class RetrievalResult:
    values: Dict[str, np.ndarray]
    achieved_eb: Dict[str, float]
    est_errors: Dict[str, float]
    tau_abs: Dict[str, float]
    bytes_retrieved: int
    bitrate: float
    iterations: List[IterationLog]
    converged: bool
    # certified degraded mode: True when any variable was availability-
    # pinned (permanently missing segments).  ``est_errors`` remain valid
    # upper bounds — computed from what actually decoded — they just may
    # exceed ``tau_abs``; ``availability`` reports the pinned variables.
    degraded: bool = False
    availability: Dict[str, VarAvailability] = field(default_factory=dict)


def assign_eb(requests: Sequence[QoIRequest],
              ranges: Dict[str, float]) -> Dict[str, float]:
    """Algorithm 3: per-variable initial bound = min relative tolerance among
    the QoIs involving the variable, times the variable's range."""
    eps: Dict[str, float] = {}
    for req in requests:
        for v in req.expr.variables():
            rel = min(1.0, req.tau_rel)
            eps[v] = min(eps.get(v, 1.0), rel)
    return {v: e * ranges[v] for v, e in eps.items()}


_JIT_CACHE: Dict[tuple, "jax.stages.Wrapped"] = {}


def _estimate(expr: Expr, values: Dict[str, np.ndarray],
              ebs: Dict[str, np.ndarray],
              xfer: Optional[TransferStats] = None
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Jit-compiled (value, bound) evaluation, cached per (expr, shapes) —
    eager dispatch of the estimator graph dominated retrieval wall time
    (§Perf: ~2x end-to-end on the GE pipeline).  Its device program is
    named ``jit__qoi_estimate``."""
    with span(ESTIMATE):
        names = tuple(sorted(values))
        shapes = tuple(np.shape(values[k]) for k in names)
        key = (expr, names, shapes)   # Expr nodes hash structurally
        fn = _JIT_CACHE.get(key)
        if fn is None:
            def _qoi_estimate(vals, eb):
                return expr.eval(vals, eb)
            fn = _JIT_CACHE[key] = jax.jit(_qoi_estimate)
        note_h2d(xfer, *(values[k] for k in names), *(ebs[k] for k in names))
        val, bound = fn({k: jnp.asarray(values[k]) for k in names},
                        {k: jnp.asarray(ebs[k]) for k in names})
        return to_host(val, xfer), to_host(bound, xfer)


def retrieve_qoi_controlled(session,
                            requests: Sequence[QoIRequest],
                            max_iters: int = 100,
                            reduction: float = REDUCTION_FACTOR,
                            verbose: bool = False) -> RetrievalResult:
    """Algorithm 2 main loop over a RetrievalSession."""
    ranges = session.archive.ranges
    xfer = getattr(session, "xfer_stats", None)
    needed = sorted(set().union(*[r.expr.variables() for r in requests]))
    for v in needed:
        if v not in session.readers:
            raise KeyError(f"QoI references unknown variable {v!r}")
    eps = assign_eb(requests, ranges)
    floors = {v: MIN_REL_EPS * ranges[v] for v in needed}
    prefetch = getattr(session, "prefetch", None)
    # Certain hints already forwarded, keyed by their eps: reassign only
    # tightens the involved variables, so re-hinting an unchanged variable
    # every round is pure reader/fetcher-lock churn (it resolves to planes
    # the session has already consumed) — worth skipping now that hints may
    # cross a real wire's submission path.  Speculative (certain=False)
    # predictions stay unconditional: their eps varies per round.
    hinted: Dict[str, float] = {}

    def hint(v: str, e: float) -> None:
        if prefetch is not None and hinted.get(v) != e:
            prefetch(v, e)
            hinted[v] = e
    logs: List[IterationLog] = []
    values: Dict[str, np.ndarray] = {}
    eb_arrays: Dict[str, np.ndarray] = {}
    achieved: Dict[str, float] = {}
    pinned_vars: set = set()       # availability-pinned (degraded) variables
    converged = False

    for it in range(max_iters):
        # -- progressive reconstruction at current bounds (lines 9-11).
        # Hint every variable's fetch up front: the store fetcher starts
        # moving later variables' segments while earlier variables decode.
        for v in needed:
            hint(v, eps[v])
        for v in needed:
            data, ach = session.reconstruct(v, eps[v])
            values[v] = data
            achieved[v] = ach
            eb_arrays[v] = session.eb_array(v, ach)

        # -- availability-pinned variables (certified degraded mode): a
        # variable whose segments are permanently unavailable cannot be
        # tightened past its achievable floor — raise its ladder floor so
        # reassign_eb freezes it there instead of re-requesting the same
        # missing planes forever (the frozen/at_floor machinery below then
        # guarantees termination exactly as for codec floors).
        get_avail = getattr(session, "availability", None)
        if get_avail is not None:
            for v, a in get_avail().items():
                if v in floors and np.isfinite(a.floor):
                    floors[v] = max(floors[v], a.floor)
                    pinned_vars.add(v)

        # -- QoI error estimation (lines 12-24)
        est_errors: Dict[str, float] = {}
        tau_abs: Dict[str, float] = {}
        worst: Optional[Tuple[str, int, float]] = None  # (qoi, flat idx, excess)
        bounds_cache: Dict[str, np.ndarray] = {}
        for req in requests:
            val, bound = _estimate(req.expr, values, eb_arrays, xfer)
            rng = float(np.max(val) - np.min(val))
            t_abs = req.tau_rel * (rng if rng > 0 else 1.0)
            max_err = float(np.max(bound))
            est_errors[req.name] = max_err
            tau_abs[req.name] = t_abs
            bounds_cache[req.name] = bound
            if max_err > t_abs:
                idx = int(np.argmax(bound))
                excess = max_err / t_abs if np.isfinite(max_err) else np.inf
                if worst is None or excess > worst[2]:
                    worst = (req.name, idx, excess)

        logs.append(IterationLog(iteration=it, eps=dict(eps),
                                 est_errors=dict(est_errors),
                                 tau_abs=dict(tau_abs),
                                 bytes_retrieved=session.bytes_retrieved))
        if verbose:
            print(f"[retrieve] iter={it} bytes={session.bytes_retrieved} "
                  f"est={ {k: f'{v:.3e}' for k, v in est_errors.items()} }")

        if worst is None:
            converged = True
            break

        # -- reassign_eb (Alg 4): tighten on the worst point
        with span(REASSIGN):
            qname, idx, _ = worst
            req = next(r for r in requests if r.name == qname)
            involved = sorted(req.expr.variables())
            pt_vals = {v: values[v].ravel()[idx] for v in involved}
            # a pinned variable's bound cannot drop below what it achieved —
            # seeding its ladder with the (unreachable) requested eps would
            # predict tightenings the reconstruct pass can never deliver and
            # spin the reassign loop until max_iters
            pt_ebs = {v: achieved[v] if v in pinned_vars
                      else min(achieved[v], eps[v]) for v in involved}
            # honour exact (masked) points
            for v in involved:
                pt_ebs[v] = float(eb_arrays[v].ravel()[idx]) if \
                    eb_arrays[v].ravel()[idx] == 0.0 else pt_ebs[v]
            # Evaluate the whole geometric eps-ladder of candidate bound
            # states in ONE batched _estimate call (§Perf) — the legacy loop
            # dispatched up to LADDER_STEPS sequential scalar-jit
            # evaluations.  State t is exactly what t reduction rounds of
            # the sequential loop produce (cumulative division, per-variable
            # floor clamp, frozen once at or below the floor — masked points
            # enter at 0 and stay there).
            ladders: Dict[str, np.ndarray] = {}
            for v in involved:
                lad = np.empty(LADDER_STEPS + 1, dtype=np.float64)
                cur = pt_ebs[v]
                lad[0] = cur
                for t in range(1, LADDER_STEPS + 1):
                    if cur > floors[v]:
                        cur = max(cur / reduction, floors[v])
                    lad[t] = cur
                ladders[v] = lad
            # -- async segment prefetch: reassign always lands at ladder
            # state t_star >= 1 (state 0 is the current, still-violating
            # bound), so the planes for ladder[depth=1] are a guaranteed
            # prefix of the next round's fetch.  Hand these predicted
            # next-eps to the fetcher NOW so store-backed sessions move
            # segments in the background while the batched ladder estimate
            # below (and the next estimator round) run.  Depths > 1 hide
            # more latency but may speculate past t_star.
            depth = int(np.clip(getattr(session, "prefetch_depth", 1),
                                1, LADDER_STEPS))
            if prefetch is not None:
                for v in involved:
                    predicted = float(ladders[v][depth])
                    if predicted > 0.0:
                        prefetch(v, min(eps[v], predicted), certain=False)
            _, pb = _estimate(
                req.expr,
                {v: np.full(LADDER_STEPS, pt_vals[v]) for v in involved},
                {v: ladders[v][:LADDER_STEPS] for v in involved}, xfer)
            ok = np.asarray(pb) <= tau_abs[qname]
            progressable = np.zeros(LADDER_STEPS, dtype=bool)
            for v in involved:
                progressable |= ladders[v][:LADDER_STEPS] > floors[v]
            frozen = ~progressable
            at_floor = False
            if ok.any():
                t_star = int(np.argmax(ok))       # first state meeting tau
            elif frozen.any():
                t_star = int(np.argmax(frozen))   # sequential loop stops here
                at_floor = True
            else:
                t_star = LADDER_STEPS             # exhausted, not converged
            pt_ebs = {v: float(ladders[v][t_star]) for v in involved}
            for v in involved:
                eps[v] = min(eps[v], pt_ebs[v]) if pt_ebs[v] > 0 else eps[v]
            # -- the landing state is now exact: prefetch the full
            # next-round plane set so transport overlaps the remaining
            # bookkeeping and the per-variable decode/recompose of the next
            # reconstruct pass.
            for v in involved:
                hint(v, eps[v])
        if at_floor:
            # full fidelity reached and still unbounded -> retrieve all and stop
            for v in involved:
                eps[v] = floors[v]
            for v in needed:
                data, ach = session.reconstruct(v, eps[v])
                values[v], achieved[v] = data, ach
                eb_arrays[v] = session.eb_array(v, ach)
            break

    bitrate = session.bitrate(needed)
    get_avail = getattr(session, "availability", None)
    availability = get_avail() if get_avail is not None else {}
    return RetrievalResult(values=values, achieved_eb=achieved,
                           est_errors=est_errors, tau_abs=tau_abs,
                           bytes_retrieved=session.bytes_retrieved,
                           bitrate=bitrate, iterations=logs,
                           converged=converged,
                           degraded=bool(availability),
                           availability=availability)
