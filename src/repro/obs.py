"""Host-clock spans kept in memory: the program's own record of where a
step's time goes.

``span(name, **attrs)`` is a context manager. It records ``Span(name, t0,
t1, parent, attrs)`` on ``time.perf_counter`` and enters
``jax.profiler.TraceAnnotation(name)``, so that a profiler trace, when one
is running, carries the same span on the device's clock. Parents come
from a per-thread stack of open spans.

Rule for names: a span around an asynchronous JAX call ends in
``.dispatch`` and measures the enqueue only; a span around a sync (a
device array turned into a NumPy array or a Python number) ends in
``.wait`` and is the only kind that measures the device from the host.
No ``.dispatch`` span is ever device time.

Closed spans go to a ring of ``RING`` entries. A root span that is among
the ``KEEP`` longest seen so far is also kept with all the spans under it,
after the ring has overwritten them: the flight recorder that holds a
stall of a long-running server (``longest()``). Two sources record spans
of their own under whatever span is open: garbage collection (``gc``,
from ``gc.callbacks``) and every backend compile (``compile``, from JAX's
monitoring events; it ends when the event arrives and lasts as long as
the event says).

``enabled`` switches it all: off, ``span`` records nothing and opens no
annotation. It is on by default; a span costs a few microseconds.
"""
from __future__ import annotations

import collections
import gc
import heapq
import itertools
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

from jax import monitoring
from jax.profiler import TraceAnnotation

__all__ = ["Span", "span", "record", "spans", "holds_since", "longest",
           "reset", "enabled", "device_waits", "RING", "KEEP"]

RING = 65536
KEEP = 8
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

enabled = True


class _Local(threading.local):
    def __init__(self):
        self.stack: List[Span] = []       # open spans, innermost last
        self.gc_t0: Optional[float] = None


_ids = itertools.count(1)
_local = _Local()
_lock = threading.RLock()       # a gc span may close inside _close
_ring: collections.deque = collections.deque(maxlen=RING)
_longest: List[Tuple[float, int, "Span", List["Span"]]] = []   # min-heap
_lost = float("-inf")           # close time of the last span overwritten


class Span:
    """One timed interval. ``parent`` is the id of the enclosing span
    (None for a root); ``attrs`` may be filled while the span is open."""
    __slots__ = ("name", "t0", "t1", "parent", "attrs", "id", "_ann",
                 "_kids")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.t0 = self.t1 = 0.0
        self.parent: Optional[int] = None
        self.id = 0
        self._ann = None
        self._kids: Optional[List[Span]] = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __repr__(self):
        return (f"Span({self.name!r}, {self.seconds * 1e3:.3f} ms, "
                f"parent={self.parent}, {self.attrs})")

    def __enter__(self) -> "Span":
        if not enabled:
            return self
        stack = _local.stack
        self.id = next(_ids)
        if stack:
            self.parent = stack[-1].id
        else:
            self._kids = []
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._ann is None:
            return False
        self.t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self._ann = None
        stack = _local.stack
        if stack and stack[-1] is self:
            stack.pop()
        _close(self, stack)
        return False


def _close(sp: Span, stack: List[Span]):
    global _lost
    if len(_ring) == _ring.maxlen:
        _lost = _ring[0].t1
    _ring.append(sp)
    if sp._kids is None:                  # not a root: file under its root
        if stack and stack[0]._kids is not None \
                and len(stack[0]._kids) < RING:
            stack[0]._kids.append(sp)
        return
    kids, sp._kids = sp._kids, None
    with _lock:
        item = (sp.seconds, sp.id, sp, kids)
        if len(_longest) < KEEP:
            heapq.heappush(_longest, item)
        elif item[0] > _longest[0][0]:
            heapq.heapreplace(_longest, item)


def span(name: str, **attrs) -> Span:
    """``with span("serve.step", tick=3) as sp: ...``; see the module
    docstring."""
    return Span(name, attrs)


def record(name: str, t0: float, t1: float, **attrs) -> Optional[Span]:
    """A span that has already ended, filed under the span open now."""
    if not enabled:
        return None
    sp = Span(name, attrs)
    sp.id, sp.t0, sp.t1 = next(_ids), t0, t1
    stack = _local.stack
    if stack:
        sp.parent = stack[-1].id
    else:
        sp._kids = []
    _close(sp, stack)
    return sp


def spans(t0: float = float("-inf"), t1: float = float("inf"),
          name: Optional[str] = None) -> List[Span]:
    """Closed spans still in the ring that lie inside ``[t0, t1]``, in the
    order they closed; only those called ``name`` if given."""
    return [s for s in list(_ring) if t0 <= s.t0 and s.t1 <= t1
            and (name is None or s.name == name)]


def holds_since(t: float) -> bool:
    """Whether the ring still holds every span that closed after ``t``
    (the last one it overwrote closed at or before ``t``)."""
    return _lost <= t


def longest() -> List[Tuple[Span, List[Span]]]:
    """The ``KEEP`` longest root spans seen since the last ``reset``, the
    longest first, each with every span recorded under it."""
    with _lock:
        kept = sorted(_longest, key=lambda it: (-it[0], it[1]))
    return [(root, kids) for _, _, root, kids in kept]


def reset():
    """Forget every span."""
    global _lost
    with _lock:
        _lost = float("-inf")
        _ring.clear()
        _longest.clear()


def device_waits(all_spans: Iterable[Span],
                 name: str) -> List[Tuple[Span, float]]:
    """Each span called ``name``, with the seconds of the ``.wait`` spans
    under it (at any depth; a ``.wait`` inside another counts once)."""
    all_spans = list(all_spans)
    by_id: Dict[int, Span] = {s.id: s for s in all_spans}
    waits = {s.id: 0.0 for s in all_spans if s.name == name}
    for s in all_spans:
        if not s.name.endswith(".wait"):
            continue
        p, outer = s.parent, None
        while p is not None and p in by_id:
            up = by_id[p]
            if up.name.endswith(".wait"):
                outer = up
            if p in waits:
                if outer is None:
                    waits[p] += s.seconds
                break
            p = up.parent
    return [(by_id[i], w) for i, w in waits.items()]


def _on_gc(phase: str, info: dict):
    if phase == "start":
        _local.gc_t0 = time.perf_counter()
    elif phase == "stop":
        t0 = _local.gc_t0
        if t0 is not None:
            _local.gc_t0 = None
            record("gc", t0, time.perf_counter(),
                   generation=info.get("generation"),
                   collected=info.get("collected"))


def _on_duration(event: str, seconds: float, **_kw):
    if event == COMPILE_EVENT:
        t1 = time.perf_counter()
        record("compile", t1 - seconds, t1)


gc.callbacks.append(_on_gc)
monitoring.register_event_duration_secs_listener(_on_duration)
