"""Program spans and counters on the profiler's clock.

``span(name, **counts)`` marks a stretch of work on the calling thread;
``count(name, n)`` adds to the innermost span the thread has open. Tracing
is on exactly while a JAX profiler trace is being collected
(``jax.profiler.TraceAnnotation.is_enabled()``): there is no switch. With
it off a span costs that one check and records nothing.

With it on, each span is a ``jax.profiler.TraceAnnotation``, so it lands
in the ``.xplane.pb`` on the host thread that ran it, beside the device's
operations, carrying its ``span_id`` and, at its end, its counts. Each
span also appends one :class:`Record` to a bounded in-memory buffer
(``records()``), kept from the span's start, with ``end_ns`` None while
it is open. Record times are ``time.perf_counter_ns()``; the profiler's
host events start at a fixed offset from that clock, so pairing any
record with its xplane event (by ``span_id``) places every record against
the device's operations.

A span's parent is the innermost span open on the same thread, unless it
is given one: work that a span causes on another thread (an I/O task it
submitted) names that span as its parent, so the spans of one checkpoint
save share one root. ``record()`` adds a closed record after the fact,
without an annotation (a profiler event cannot be written late).

JAX is imported lazily, and only once the process has imported it: a
process without JAX has no profiler, so tracing is off there.
"""
from __future__ import annotations

import contextlib
import itertools
import sys
import threading
import time

#: Records kept before further ones are dropped (and counted in
#: ``dropped()``).
CAPACITY = 1 << 16

_annotation = None           # jax.profiler.TraceAnnotation, once imported
_local = threading.local()   # .stack: the thread's open spans, innermost last
_ids = itertools.count(1)
_guard = threading.Lock()    # the buffer and the drop count
_buffer: list = []
_dropped = 0
_OFF = contextlib.nullcontext()


def enabled() -> bool:
    """True while a JAX profiler trace is being collected."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation.is_enabled()


class Record:
    """One span: ``thread`` is the native thread id, times are
    ``perf_counter_ns``, ``cpu_ns`` is the thread's CPU time over the span
    (None for a record added after the fact)."""

    __slots__ = ("name", "id", "parent", "thread", "start_ns", "end_ns",
                 "cpu_ns", "counts", "_cpu0", "_ta")

    def __init__(self, name, parent, counts, start_ns=None, end_ns=None):
        self.name = name
        self.id = next(_ids)
        self.parent = parent
        self.thread = threading.get_native_id()
        self.start_ns = start_ns
        self.end_ns = end_ns
        self.cpu_ns = None
        self.counts = counts

    @property
    def open(self) -> bool:
        return self.end_ns is None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    @property
    def off_cpu_s(self) -> float:
        """Seconds of the span's wall time the thread was off the CPU."""
        return (self.end_ns - self.start_ns - self.cpu_ns) / 1e9

    def __enter__(self):
        stack = _stack()
        if self.parent is None and stack:
            self.parent = stack[-1].id
        stack.append(self)
        self._ta = _annotation(self.name, span_id=self.id)
        self._ta.__enter__()
        self._cpu0 = time.thread_time_ns()
        self.start_ns = time.perf_counter_ns()
        _keep(self)
        return self

    def __exit__(self, *exc):
        self.end_ns = time.perf_counter_ns()
        self.cpu_ns = time.thread_time_ns() - self._cpu0
        _stack().pop()
        if self.counts:
            self._ta.set_metadata(**self.counts)
        self._ta.__exit__(*exc)
        self._ta = None
        return False

    def __repr__(self):
        return (f"<Record {self.name}#{self.id} parent={self.parent} "
                f"{'open' if self.open else f'{self.seconds:.6f}s'} "
                f"{self.counts}>")


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _keep(rec: Record) -> None:
    global _dropped
    with _guard:
        if len(_buffer) < CAPACITY:
            _buffer.append(rec)
        else:
            _dropped += 1


def span(name: str, parent: int | None = None, **counts):
    """A context manager: a traced span while a profiler trace is being
    collected, else a no-op. ``parent`` names the span that caused this
    work on another thread; by default it is the thread's innermost open
    span."""
    if not enabled():
        return _OFF
    return Record(name, parent, counts)


def count(name: str, n) -> None:
    """Add ``n`` to count ``name`` of the calling thread's innermost open
    span; nothing when no span is open."""
    stack = getattr(_local, "stack", None)
    if stack:
        counts = stack[-1].counts
        counts[name] = counts.get(name, 0) + n


def tag(name: str, value) -> None:
    """Set ``name`` on the calling thread's innermost open span (a value
    known only once the span has started)."""
    stack = getattr(_local, "stack", None)
    if stack:
        stack[-1].counts[name] = value


def current() -> int | None:
    """The id of the calling thread's innermost open span, or None."""
    stack = getattr(_local, "stack", None)
    return stack[-1].id if stack else None


def record(name: str, start_ns: int, end_ns: int, parent: int | None,
           **counts) -> None:
    """Keep a closed record of work measured elsewhere (no annotation)."""
    _keep(Record(name, parent, counts, start_ns, end_ns))


class _TimedLock:
    __slots__ = ("lock",)

    def __init__(self, lock):
        self.lock = lock

    def __enter__(self):
        if not self.lock.acquire(blocking=False):
            t0 = time.perf_counter_ns()
            self.lock.acquire()
            count("lock_wait_ns", time.perf_counter_ns() - t0)
        return self.lock

    def __exit__(self, *exc):
        self.lock.release()
        return False


def locked(lock, traced: bool | None = None):
    """``with locked(lock):`` takes ``lock``. While tracing is on, an
    acquire that has to block is timed and charged as ``lock_wait_ns`` to
    the waiting thread's innermost open span. ``traced`` passes on an
    ``enabled()`` the caller already made."""
    if traced is None:
        traced = enabled()
    return _TimedLock(lock) if traced else lock


def records() -> list:
    """The records kept so far, in the order their spans started."""
    with _guard:
        return list(_buffer)


def dropped() -> int:
    """Records not kept because the buffer was full."""
    return _dropped


def clear() -> None:
    global _dropped
    with _guard:
        _buffer.clear()
        _dropped = 0


def closed(recs, name: str):
    """The records named ``name``, or ``name:<detail>`` (``io.queued:`` and
    ``io.run:`` carry the task's signature), or None when any of them is
    still open."""
    out = [r for r in recs if r.name.split(":", 1)[0] == name]
    return None if any(r.open for r in out) else out


def subtree(recs, root_ids) -> list:
    """The records under the spans ``root_ids`` (those included)."""
    ids, out = set(root_ids), []
    for r in recs:                  # parents start before their children
        if r.id in ids or r.parent in ids:
            ids.add(r.id)
            out.append(r)
    return out
