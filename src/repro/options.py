"""Unified open/session option objects for the public archive API.

Before manifest v4 the opener surface had grown three parallel kwarg
sprawls: ``open_archive`` took seven transport knobs, ``StoreArchive.open``
three session knobs, and every variable archive's ``open_reader`` its own
divergent pair.  This module collapses them into two frozen dataclasses —
:class:`OpenOptions` (how an archive is *opened*: transport, verification,
caching, fault tolerance) and :class:`SessionOptions` (how one session
*reads*: prefetch depth, contribution budget/pool) — with
``multi_tenant_config()``-style presets for the common deployments.

The old kwargs keep working through a deprecation shim that warns ONCE per
call-site pattern (:class:`ReproDeprecationWarning`); the test suite turns
the warning into an error (see pytest.ini), so no first-party module can
quietly regress onto the legacy spelling.

This module deliberately imports nothing from ``repro.store`` or
``repro.core`` — both shim layers import it, so it must sit below them.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, fields, replace
from typing import Any, Callable, Optional

__all__ = [
    "OpenOptions",
    "SessionOptions",
    "ReproDeprecationWarning",
    "warn_deprecated_once",
]


class ReproDeprecationWarning(DeprecationWarning):
    """A deprecated repro API spelling (legacy kwargs, shimmed signatures).

    Subclasses DeprecationWarning so standard tooling recognises it, but
    has its own type so the test suite can escalate exactly these to
    errors without fighting third-party deprecation noise."""


_warned: set = set()


def warn_deprecated_once(key: str, message: str, stacklevel: int = 3) -> None:
    """Emit ``message`` as a ReproDeprecationWarning the FIRST time ``key``
    is seen this process; later identical call sites stay silent.  A serve
    loop calling a shimmed API per-request must not flood stderr."""
    if key in _warned:
        return
    _warned.add(key)
    warnings.warn(message, ReproDeprecationWarning, stacklevel=stacklevel)


def _reset_deprecation_warnings() -> None:
    """Test hook: make every deprecation warn again (compat tests assert
    both the warning AND the warn-once suppression)."""
    _warned.clear()


def _from_legacy(cls, legacy: dict, where: str):
    """Build an options object from legacy kwargs, warning once.  Unknown
    names raise TypeError exactly like a real signature mismatch would."""
    valid = {f.name for f in fields(cls)}
    unknown = set(legacy) - valid
    if unknown:
        raise TypeError(f"{where}: unexpected keyword argument(s) "
                        f"{sorted(unknown)}")
    warn_deprecated_once(
        f"{where}:{','.join(sorted(legacy))}",
        f"{where}: passing {sorted(legacy)} as loose keyword arguments is "
        f"deprecated; pass {cls.__name__}(...) instead",
    )
    return cls(**legacy)


@dataclass(frozen=True)
class OpenOptions:
    """How an archive container is opened (transport + integrity layer).

    Fields mirror the archive-wide knobs that used to sprawl across
    ``open_archive``'s signature:

      * ``prefetch_workers`` — background segment-fetch threads (0 disables
        async prefetch);
      * ``verify`` — crc32c-check every delivered segment (disable only for
        forensics on a known-damaged container);
      * ``blob_resolver`` — override blob-name -> ByteStore lookup so shards
        can mix backends;
      * ``cache`` — cross-session ``SegmentCache``;
      * ``archive_id`` — cache budget-group override (default: manifest
        hash);
      * ``retry_policy`` / ``quarantine`` — fault-tolerance layer
        (``repro.store.retry``); None enables the hardened defaults;
      * ``follow`` — replay the manifest v4 journal on open and allow
        ``StoreArchive.refresh()`` to tail it afterwards (live archives);
        False pins the session to the base manifest.
    """
    prefetch_workers: int = 2
    verify: bool = True
    blob_resolver: Optional[Callable[[str], Any]] = None
    cache: Optional[Any] = None
    archive_id: Optional[str] = None
    retry_policy: Optional[Any] = None
    quarantine: Optional[Any] = None
    follow: bool = True

    @classmethod
    def default(cls) -> "OpenOptions":
        """Single-client defaults: verified reads, light prefetch."""
        return cls()

    @classmethod
    def multi_tenant(cls, cache, retry_policy=None,
                     quarantine=None) -> "OpenOptions":
        """Serve-plane preset: a shared cross-session cache plus the
        hardened retry/quarantine defaults (None keeps them enabled)."""
        return cls(cache=cache, retry_policy=retry_policy,
                   quarantine=quarantine)

    @classmethod
    def unverified(cls) -> "OpenOptions":
        """Forensics preset: skip crc32c so a damaged container can still
        be inspected; never publishes bytes to a shared cache."""
        return cls(verify=False)

    def with_(self, **changes) -> "OpenOptions":
        return replace(self, **changes)


@dataclass(frozen=True)
class SessionOptions:
    """How one retrieval session reads (per-session memory/prefetch policy).

      * ``prefetch_depth`` — how many ``reassign_eb`` reduction steps ahead
        the retrieval loop may hint to the fetcher;
      * ``contrib_budget_bytes`` — per-variable cap on each bitplane
        reader's retained contribution cache (None = unbounded; bit
        -identical outputs at any budget);
      * ``contrib_pool`` — server-wide
        :class:`repro.serve.budget.ContribBudgetPool` replacing the static
        cap (takes precedence when both are set);
      * ``decode_batcher`` — shared :class:`repro.serve.batch.DecodeBatcher`
        merging this session's fused decode / recompose dispatches with
        every other session's into one vmapped device call per serve tick
        (None = per-reader dispatch; results are bit-identical either way);
      * ``xfer_stats`` — :class:`repro.trace.TransferStats` counting the
        bytes this session moves between host and device (None = not
        counted).
    """
    prefetch_depth: int = 1
    contrib_budget_bytes: Optional[int] = None
    contrib_pool: Optional[Any] = None
    decode_batcher: Optional[Any] = None
    xfer_stats: Optional[Any] = None

    @classmethod
    def default(cls) -> "SessionOptions":
        return cls()

    @classmethod
    def memory_bounded(cls, budget_bytes: int) -> "SessionOptions":
        """Cap each variable's resident recompose state; spilled levels are
        rebuilt on demand (outputs stay bit-identical)."""
        return cls(contrib_budget_bytes=int(budget_bytes))

    @classmethod
    def pooled(cls, pool) -> "SessionOptions":
        """Serve-plane preset: retention borrows from one shared pool."""
        return cls(contrib_pool=pool)

    def with_(self, **changes) -> "SessionOptions":
        return replace(self, **changes)
