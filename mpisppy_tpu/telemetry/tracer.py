"""Span tracing over monotonic clocks into a lock-free ring buffer, and
onto the JAX profiler's timeline.

Design constraints (doc/src/telemetry.md):

  * recording a span must be cheap enough for per-iteration hot paths:
    one `time.monotonic_ns()` call and one profiler annotation on enter,
    one slot assignment on exit — no locks, no allocation beyond the
    annotation and the record tuple;
  * the buffer is bounded: a preallocated slot list indexed by an
    `itertools.count()` sequence (atomic under the GIL, so concurrent
    spoke threads never tear or lose the index), with old records
    overwritten once the capacity wraps;
  * timestamps are `CLOCK_MONOTONIC` nanoseconds — system-wide on
    Linux, so traces recorded by separate spoke PROCESSES merge onto
    one consistent timeline with the hub's (export.merge_traces);
  * every span is also a `jax.profiler.TraceAnnotation` named
    "sppy.<name>" (its args as the annotation's arguments), so under
    `jax.profiler.start_trace` it lands on the profiler's host plane, on
    the clock of the device ops;
  * imports nothing of jax but that one class, on the first span: an
    annotation cannot sync the device, so a tracer call can never
    introduce a device sync into the jitted path (tests/test_telemetry.py
    guards this structurally).

Records are tuples (kind first):
    ("X", name, pid, tid, ts_us, dur_us, args)   complete span
    ("i", name, pid, tid, ts_us, args)           instant event
    ("C", name, pid, ts_us, values)              counter sample

`pid` here is the Chrome-trace ROW id: the real os.getpid() for the
main track, synthetic per-track ids for in-process spokes (each spoke
renders as its own process row even when it shares the hub's process).
"""

from __future__ import annotations

import itertools
import threading
import time
import os


class _Span:
    """Context manager recording one complete ("X") event on exit, inside
    a profiler annotation "sppy.<name>"."""

    __slots__ = ("_tracer", "_name", "_pid", "_args", "_t0", "_ann")

    def __init__(self, tracer, name, pid, args):
        self._tracer = tracer
        self._name = name
        self._pid = pid
        self._args = args

    def __enter__(self):
        from jax.profiler import TraceAnnotation
        self._ann = TraceAnnotation("sppy." + self._name,
                                    **(self._args or {}))
        self._ann.__enter__()
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.monotonic_ns()
        self._ann.__exit__(exc_type, exc, tb)
        self._tracer._append(
            ("X", self._name, self._pid, threading.get_native_id(),
             self._t0 // 1000, (t1 - self._t0) // 1000, self._args))
        return False


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


NULL_SPAN = _NullSpan()


class _TrackScope:
    """Thread-local track push/pop (so spans recorded inside a spoke's
    step land on that spoke's row without threading a track argument
    through every call site)."""

    __slots__ = ("_tracer", "_label")

    def __init__(self, tracer, label):
        self._tracer = tracer
        self._label = label

    def __enter__(self):
        tls = self._tracer._tls
        stack = getattr(tls, "stack", None)
        if stack is None:
            stack = tls.stack = []
        stack.append(self._label)
        return self

    def __exit__(self, exc_type, exc, tb):
        self._tracer._tls.stack.pop()
        return False


class Tracer:
    """Lock-free bounded trace recorder (see module docstring)."""

    enabled = True

    def __init__(self, capacity=65536, main_label="hub"):
        self.capacity = max(int(capacity), 16)
        self._slots = [None] * self.capacity
        self._seq = itertools.count()    # C-level atomic increment
        self._pid = os.getpid()
        self.main_label = main_label
        # track label -> synthetic row pid; insertion-ordered so the
        # merged trace shows spokes in wiring order
        self._tracks = {}
        self._tracks_lock = threading.Lock()
        self._tls = threading.local()

    def set_main_label(self, label):
        self.main_label = label

    # -- track (row) management ------------------------------------------
    def track(self, label):
        """Scope: spans/events recorded inside land on `label`'s row.
        label=None is the main (hub) row."""
        return _TrackScope(self, label)

    def _track_pid(self, track):
        if track is None:
            stack = getattr(self._tls, "stack", None)
            track = stack[-1] if stack else None
        if track is None:
            return self._pid
        pid = self._tracks.get(track)
        if pid is None:
            with self._tracks_lock:
                pid = self._tracks.setdefault(
                    track, self._pid * 1000 + 1 + len(self._tracks))
        return pid

    # -- recording --------------------------------------------------------
    def _append(self, rec):
        i = next(self._seq)
        self._slots[i % self.capacity] = (i, rec)

    def span(self, name, track=None, args=None):
        return _Span(self, name, self._track_pid(track), args)

    def instant(self, name, track=None, args=None):
        self._append(("i", name, self._track_pid(track),
                      threading.get_native_id(),
                      time.monotonic_ns() // 1000, args))

    def counter(self, name, values, track=None):
        """Chrome counter sample ("C"): values is {series: number}."""
        self._append(("C", name, self._track_pid(track),
                      time.monotonic_ns() // 1000, dict(values)))

    # -- drain ------------------------------------------------------------
    def records(self):
        """Snapshot of retained records in emission order."""
        live = [s for s in self._slots if s is not None]
        live.sort(key=lambda t: t[0])
        return [rec for _, rec in live]

    @property
    def emitted(self):
        live = [s[0] for s in self._slots if s is not None]
        return max(live) + 1 if live else 0

    @property
    def dropped(self):
        """Records overwritten after the ring wrapped."""
        return max(0, self.emitted - self.capacity)


class NullTracer:
    """Disabled-mode stand-in: every operation is a no-op and span()
    returns the shared NULL_SPAN (no allocation on the hot path)."""

    enabled = False
    capacity = 0
    main_label = "off"
    _tracks = {}
    _pid = 0

    def set_main_label(self, label):
        pass

    def track(self, label):
        return NULL_SPAN

    def span(self, name, track=None, args=None):
        return NULL_SPAN

    def instant(self, name, track=None, args=None):
        pass

    def counter(self, name, values, track=None):
        pass

    def records(self):
        return []

    emitted = 0
    dropped = 0


NULL_TRACER = NullTracer()
