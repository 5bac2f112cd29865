"""Host spans at the program's layer boundaries, on the profiler's clock.

``span(name)`` marks a stretch of host work with a
``jax.profiler.TraceAnnotation``, so it shows in a profiler trace beside
the device's operations, and times it.  Once :func:`record` has switched
the recorder on, each span that ends is also kept in memory as
``(name, start_ns, end_ns, parent)`` until :func:`drain` hands the list
over.  Whether or not the recorder is on, every span that ends adds its
duration to its name's total (:func:`totals`), and the program notes a
few sizes of what it built with :func:`gauge` (``repro.stored_slots``:
the value slots one apply of the operator last built streams): a reader
that comes after the work, such as a benchmark's per-layer metric, finds
them there.  Off (the default), a span costs its annotation, two clock
reads, one flag test and one addition.

Timestamps are ``time.time_ns()``: the wall clock on which the profiler
stamps its own events (a trace stores them from its
``profile_start_time``), so a kept span lines up with the same span in
a trace.

Device work is named with ``jax.named_scope`` under the same ``repro.``
prefix (``repro.gather_rhs``, ``repro.kernel``, ``repro.unpermute``,
``repro.halo``); those names live in the compiled program's metadata,
not here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time

import jax

__all__ = ["Span", "span", "record", "drain", "settled", "totals", "gauge",
           "gauges", "reset"]

_recording = False
_kept: list = []
_totals: dict = {}             # span name -> nanoseconds, over every span
_gauges: dict = {}             # name -> the value noted last
_kept_lock = threading.Lock()
_open = threading.local()      # per thread: the spans entered, innermost last


@dataclasses.dataclass
class Span:
    """One span as it runs; ``seconds`` is valid once it has ended."""

    name: str
    start_ns: int
    parent: str | None
    end_ns: int = 0

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def record(on: bool = True) -> None:
    """Switch keeping spans in memory on (or off)."""
    global _recording
    _recording = on


def settled(tree):
    """``tree``, waited for on the device while the recorder is on, so
    that a kept span around its making ends when its arrays are there;
    off, ``tree`` at once, with no host sync."""
    return jax.block_until_ready(tree) if _recording else tree


def drain() -> list:
    """The spans kept since the last drain, in the order they ended, as
    ``(name, start_ns, end_ns, parent)``; the record is emptied."""
    global _kept
    with _kept_lock:
        out, _kept = _kept, []
    return out


def totals() -> dict:
    """Seconds per span name, summed over every span that has ended
    since the process started (or :func:`reset`), recorder on or off."""
    with _kept_lock:
        return {k: v / 1e9 for k, v in _totals.items()}


def gauge(name: str, value) -> None:
    """Note ``value`` under ``name``; a later note replaces it."""
    _gauges[name] = value


def gauges() -> dict:
    """The values noted last, by name."""
    return dict(_gauges)


def reset() -> None:
    """Forget the totals, the gauges and the kept spans."""
    global _kept
    with _kept_lock:
        _kept = []
        _totals.clear()
    _gauges.clear()


@contextlib.contextmanager
def span(name: str):
    """Mark and time the body; yields its :class:`Span`."""
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    s = Span(name, time.time_ns(), stack[-1].name if stack else None)
    stack.append(s)
    try:
        with jax.profiler.TraceAnnotation(name):
            yield s
    finally:
        s.end_ns = time.time_ns()
        stack.pop()
        with _kept_lock:
            _totals[name] = _totals.get(name, 0) + s.end_ns - s.start_ns
            if _recording:
                _kept.append((s.name, s.start_ns, s.end_ns, s.parent))
