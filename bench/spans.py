"""Host spans around the calls into each layer of the served path.

The program has no spans of its own yet, so in a traced run the harness
wraps these functions in ``jax.profiler.TraceAnnotation``s named
``bench.<layer>``; the trace reduction then names each idle gap of the
device by the innermost of them.  Nothing is wrapped in an untraced run,
whose numbers are the end-to-end metrics.

The wrapped functions are the program's own, some of them private, and a
later change to the program may rename or remove one.  A function that is
not there is skipped and named as absent, never an error: its idle time
then falls to the next span out.
"""
from __future__ import annotations

import contextlib
import functools
import importlib
from typing import Iterator, List

# (module, class or None, function, span)
LAYER_SPANS = (
    ("repro.serve.pool", "ServePlane", "_run", "bench.serve"),
    ("repro.launch.serve", None, "retrieve_qoi_controlled", "bench.retrieve"),
    ("repro.core.retrieval", None, "_estimate", "bench.estimate"),
    ("repro.core.refactor", "RetrievalSession", "reconstruct",
     "bench.reconstruct"),
    ("repro.core.refactor", "RetrievalSession", "eb_array", "bench.eb_array"),
    ("repro.core.refactor", "_BitplaneVarReader", "_refresh_hb_incremental",
     "bench.recompose_sum"),
    ("repro.core.refactor", "_BitplaneVarReader", "_contrib_collect",
     "bench.contrib_to_host"),
    ("repro.bitplane.segments", "LevelStream", "fetch_to_planes",
     "bench.fetch_decode_host"),
    ("repro.bitplane.segments", "LevelStream", "flush_collect",
     "bench.decode_wait"),
    ("repro.serve.batch", "DecodeBatcher", "flush", "bench.batch_flush"),
)


def _annotated(fn, span: str):
    from jax.profiler import TraceAnnotation

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with TraceAnnotation(span):
            return fn(*args, **kwargs)
    return wrapper


def _find(mod_name: str, cls_name, fn_name: str):
    """The object that holds ``fn_name`` and the function, or None."""
    try:
        owner = importlib.import_module(mod_name)
    except ImportError:
        return None
    if cls_name is not None:
        owner = getattr(owner, cls_name, None)
    if owner is None or not callable(vars(owner).get(fn_name)):
        return None
    return owner, vars(owner)[fn_name]


@contextlib.contextmanager
def layer_spans() -> Iterator[List[str]]:
    """Wrap every ``LAYER_SPANS`` function there is while the block runs;
    the block gets the list of spans whose function is absent."""
    undo, absent = [], []
    try:
        for mod_name, cls_name, fn_name, span in LAYER_SPANS:
            found = _find(mod_name, cls_name, fn_name)
            if found is None:
                absent.append(span)
                continue
            owner, original = found
            setattr(owner, fn_name, _annotated(original, span))
            undo.append((owner, fn_name, original))
        yield absent
    finally:
        for owner, fn_name, original in reversed(undo):
            setattr(owner, fn_name, original)
