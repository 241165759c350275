"""Lock-minimal per-worker ring-buffer tracer.

Every thread that emits events owns a private ``_Ring`` — a fixed-size
circular buffer reached through ``threading.local`` — so the record
path takes NO lock: one ``perf_counter`` read, one tuple build, one
list-slot store. The tracer's global lock is touched only when a
thread registers its ring (once per thread) and at collection time.
When a ring fills, new events overwrite the oldest (drop-oldest); the
``dropped`` counter keeps the loss honest.

The disabled fast path is structural, not a flag check inside the
tracer: instrumentation sites hold ``tracer = None`` and open
``region(tracer, name)``, which is one ``is None`` test returning a
shared null context — an untraced run builds no ring, no event and no
profiler annotation. A constructed ``Tracer`` is always live.

Spans are recorded as *complete* events at span end (Chrome trace
``ph="X"``). The usual form is a region::

    with region(tr, "flush", cat="flush") as args:
        ...                      # args: a dict (None when tr is None)

which stamps start and end around the block. ``tracer.span(name, t0)``
records a span whose start ``t0 = tracer.now()`` was taken on another
code path, or whose recording is decided only at its end. One ring
append per span means per-lane append order is span *end* order —
sorting by start time (ties: longer first) reconstructs the nesting,
which is how the exporter's time-in-state accounting works.

Every region also opens a ``jax.profiler.TraceAnnotation`` named
``"repro:" + name`` on the same thread: under ``jax.profiler.trace``
the program's spans land in the profile's host plane on the profiler's
own clock, beside the device operations (one Perfetto/TensorBoard view
of host steps and device work). With no profiler session active the
annotation records nothing.

Lanes map onto Chrome trace (pid, tid): ``pid`` is the host rank
(cluster mode gives every host its own process row in Perfetto) and
``tid`` is a per-ring serial; ``set_lane`` names the calling thread's
lane ("worker-3", "dispatcher-0", "driver", ...) and pins its sort
position.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Tuple

__all__ = ["Tracer", "TraceEvent", "region"]

# prefix of the profiler annotation every region opens
PROFILER_PREFIX = "repro:"


class TraceEvent(NamedTuple):
    """One collected event, flattened with its lane identity.

    ``ts``/``dur`` are seconds relative to the tracer epoch; the
    Chrome exporter converts to µs. ``ph`` follows the trace-event
    format: "X" complete span, "C" counter.
    """

    ph: str
    name: str
    cat: str
    ts: float
    dur: float
    args: Optional[Dict[str, Any]]
    pid: int
    tid: int
    lane: str


class _Ring:
    """Single-writer circular event buffer (one owner thread)."""

    __slots__ = ("cap", "buf", "idx", "n", "tid", "name", "pid", "sort")

    def __init__(self, cap: int, tid: int, name: str, pid: int = 0,
                 sort: Optional[int] = None):
        self.cap = cap
        self.buf: List[Optional[tuple]] = [None] * cap
        self.idx = 0        # next write slot
        self.n = 0          # total events ever appended
        self.tid = tid
        self.name = name
        self.pid = pid
        self.sort = sort

    def append(self, ev: tuple) -> None:
        i = self.idx
        self.buf[i] = ev
        self.idx = 0 if i + 1 == self.cap else i + 1
        self.n += 1

    @property
    def dropped(self) -> int:
        return max(0, self.n - self.cap)

    def snapshot(self) -> List[tuple]:
        """Events in append order, oldest first (last ``cap`` kept).
        The oldest event sits at the write slot once the ring has
        wrapped; before that the slots from it on are still empty."""
        i = self.idx
        return [e for e in self.buf[i:] + self.buf[:i] if e is not None]


class _Region:
    """One span around a ``with`` block (see ``region``)."""

    __slots__ = ("_tr", "_name", "_cat", "_args", "_t0", "_ann")

    def __init__(self, tr: "Tracer", name: str, cat: str,
                 args: Optional[Dict[str, Any]]):
        self._tr = tr
        self._name = name
        self._cat = cat
        self._args = {} if args is None else args
        self._ann = None

    def __enter__(self) -> Dict[str, Any]:
        self._ann = self._tr._annotation(PROFILER_PREFIX + self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self._args

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        tr = self._tr
        tr._ring().append(("X", self._name, self._cat, self._t0 - tr._epoch,
                           t1 - self._t0, self._args or None))
        self._ann.__exit__(None, None, None)
        return False


class _NullRegion:
    """The shared region of an untraced site: records nothing."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


NULL_REGION = _NullRegion()


def region(tracer: Optional["Tracer"], name: str, cat: str = "span",
           args: Optional[Dict[str, Any]] = None):
    """A context manager recording one complete span around its block
    on the calling thread's ring, inside the profiler annotation
    ``"repro:" + name``; ``as`` binds the span's args dict (``args``,
    or a fresh one), which the block may fill. When ``tracer`` is None
    (tracing off) it is the shared null region, whose ``as`` value is
    None, so a site fills args only under ``if a is not None``."""
    if tracer is None:
        return NULL_REGION
    return _Region(tracer, name, cat, args)


class Tracer:
    """Collects span and counter events into per-thread rings.

    Record paths (``region(tracer, ...)``/``span``/``counter``) are
    safe from any thread and lock-free after the thread's first event. Collection
    (``events()``/``rings()``) merges all rings preserving each lane's
    internal order; it is meant to run at quiescence (after
    ``mine()``/``refresh()`` returns) but tolerates concurrent writers
    — a torn read can at worst miss or duplicate boundary events, never
    corrupt collected tuples.
    """

    def __init__(self, ring_size: int = 65536):
        if ring_size < 8:
            raise ValueError("ring_size must be >= 8")
        from jax.profiler import TraceAnnotation
        self.ring_size = int(ring_size)
        self._annotation = TraceAnnotation
        self._epoch = time.perf_counter()
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._rings: List[_Ring] = []
        self._next_tid = 1

    # ---- record path -------------------------------------------------

    def now(self) -> float:
        return time.perf_counter()

    def _new_ring(self, name: str, pid: int = 0,
                  sort: Optional[int] = None) -> _Ring:
        with self._lock:
            r = _Ring(self.ring_size, self._next_tid, name, pid, sort)
            self._next_tid += 1
            self._rings.append(r)
        self._tls.ring = r
        return r

    def _ring(self) -> _Ring:
        r = getattr(self._tls, "ring", None)
        if r is None:
            r = self._new_ring(threading.current_thread().name)
        return r

    def set_lane(self, name: str, sort_index: Optional[int] = None,
                 pid: int = 0) -> None:
        """Name the calling thread's lane (idempotent, renames in place)."""
        r = getattr(self._tls, "ring", None)
        if r is None:
            self._new_ring(name, pid, sort_index)
        else:
            r.name, r.pid = name, pid
            if sort_index is not None:
                r.sort = sort_index

    def span(self, name: str, t0: float, cat: str = "span",
             args: Optional[Dict[str, Any]] = None) -> None:
        """Record a complete span that started at ``t0 = tracer.now()``
        — for a start and end on different code paths, or a span kept
        only if its end decides so. Not mirrored into the profiler."""
        t1 = time.perf_counter()
        self._ring().append(("X", name, cat, t0 - self._epoch, t1 - t0, args))

    def counter(self, name: str, values: Dict[str, Any]) -> None:
        """Record a counter sample (Perfetto draws these as tracks)."""
        ts = time.perf_counter() - self._epoch
        self._ring().append(("C", name, "counter", ts, 0.0, dict(values)))

    # ---- collection --------------------------------------------------

    def rings(self) -> List[_Ring]:
        with self._lock:
            rs = list(self._rings)
        rs.sort(key=lambda r: (r.pid, r.sort if r.sort is not None else 1 << 30,
                               r.tid))
        return rs

    def events(self) -> List[TraceEvent]:
        """All events, lane by lane, per-lane append order preserved."""
        out: List[TraceEvent] = []
        for r in self.rings():
            for ph, name, cat, ts, dur, args in r.snapshot():
                out.append(TraceEvent(ph, name, cat, ts, dur, args,
                                      r.pid, r.tid, r.name))
        return out

    def dropped(self) -> int:
        return sum(r.dropped for r in self.rings())

    def lanes(self) -> List[Tuple[int, int, str]]:
        """(pid, tid, name) per registered lane, display order."""
        return [(r.pid, r.tid, r.name) for r in self.rings()]

    def lane_names(self) -> List[str]:
        return [r.name for r in self.rings()]
