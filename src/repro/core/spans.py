"""Program spans on the served path, on the profiler's clock.

Off by default: :func:`span` then returns one shared null context manager
and records nothing, so a span costs one module-global read and one call.

:func:`enable` turns recording on for the process. Each span then

* enters a ``jax.profiler.TraceAnnotation`` of its name: while a profiler
  trace runs, the span lands on the host plane, on the clock of the
  device plane, so that an idle gap of the device can be put down to the
  program span the host was in;
* appends a :class:`Record` to a bounded ring per name. Times come from
  ``time.monotonic()``, the clock of the scheduler's wall-clock mode
  (``submit`` arrivals, ``DispatchRecord.started``).

A record names its parent, the span open around it on the same thread, and
an id: the request or batch the work belongs to, passed to :func:`span` or
given to the open span by :func:`set_id` once it is known. A span opened
without an id takes its parent's. The served path names a batch by the id
of its first request (DESIGN.md §12).
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Deque, Dict, List, NamedTuple, Optional

import numpy as np
from jax.profiler import TraceAnnotation

RING = 1 << 15          # records kept per name; the oldest are dropped


class Record(NamedTuple):
    start: float        # time.monotonic() seconds
    end: float
    parent: Optional[str]
    id: Optional[int]


_NULL = contextlib.nullcontext()
_on = False
_rings: Dict[str, Deque[Record]] = {}
_local = threading.local()


def _stack() -> List["_Span"]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class _Span:
    __slots__ = ("name", "id", "parent", "start", "annotation")

    def __init__(self, name: str, id: Optional[int]):
        self.name = name
        self.id = id

    def __enter__(self) -> "_Span":
        stack = _stack()
        outer = stack[-1] if stack else None
        self.parent = None if outer is None else outer.name
        if self.id is None and outer is not None:
            self.id = outer.id
        stack.append(self)
        self.annotation = TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        end = time.monotonic()
        self.annotation.__exit__(*exc)
        _stack().pop()
        ring = _rings.get(self.name)
        if ring is None:
            ring = _rings.setdefault(self.name,
                                     collections.deque(maxlen=RING))
        ring.append(Record(self.start, end, self.parent, self.id))
        return False


def span(name: str, id: Optional[int] = None):
    """A context manager around one piece of work: recorded when spans
    are on, the shared null context when they are off."""
    if not _on:
        return _NULL
    return _Span(name, id)


def set_id(id: int) -> None:
    """Give the innermost open span on this thread its id; spans opened
    inside it after this call inherit it."""
    if _on:
        stack = _stack()
        if stack:
            stack[-1].id = id


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def enabled() -> bool:
    return _on


def reset() -> None:
    """Drop every record."""
    _rings.clear()


def records() -> Dict[str, List[Record]]:
    """Name -> its records, oldest first."""
    return {name: list(ring) for name, ring in list(_rings.items())}


def summary() -> Dict[str, Dict[str, float]]:
    """Name -> count, and total, p50, p95 and max duration in seconds."""
    out = {}
    for name, recs in sorted(records().items()):
        d = np.array([r.end - r.start for r in recs])
        out[name] = {"count": len(d), "total_s": float(d.sum()),
                     "p50_s": float(np.percentile(d, 50)),
                     "p95_s": float(np.percentile(d, 95)),
                     "max_s": float(d.max())}
    return out
