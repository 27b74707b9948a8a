"""Spans and counters of the stream replay path (``Engine.replay_stream``).

Every chunk of a stream passes through the same phases, each a
:func:`span` named ``engine.<phase>``, in this order and never
overlapping:

============  ==============================================================
``source``    taking the next item from the caller's iterator, or slicing
              the dense array (the end-of-stream pull is one more)
``put``       unwrapping the item and ``Request.of``: the host-to-device
              copy of keys, sizes and costs and the eager broadcasts
``init``      chunk 0 only: ``policy.init(K)`` and the lane broadcast
``dispatch``  the ``_replay_chunk`` call: its asynchronous launch, plus
              trace and compile on a jit-cache miss
``wait``      ``block_until_ready`` on the chunk's totals: the host waiting
              on the device
``fetch``     the device-to-host copies of the totals and observables
``sum``       the 64-bit host sums
============  ==============================================================

A span does two things.  Under ``jax.profiler.trace`` it is a host event
named ``engine.<phase>`` with the chunk's index as its ``chunk`` stat, on
the profiler's clock beside the device ops.  Always, its
``perf_counter_ns`` duration is added to the chunk's row of the call's
:class:`StreamRecord`.

Counters, per chunk: ``compiles`` and ``compile_s`` from one
``jax.monitoring`` time-span listener, registered when this module is
imported.  It takes ``/jax/core/compile/backend_compile_duration`` (once
for each executable compiled or loaded from the persistent cache: a
compile) and ``/jax/core/compile/jaxpr_trace_duration`` (tracing: time, no
compile; a trace nested inside another is dropped from ``compile_s``, its
time being inside the outer one), and credits each event to the chunk
whose span is open on that thread; events outside any span are not
counted.  The call also keeps a tally of compiles and seconds (inclusive)
by the compiled or traced function's name.  ``h2d_bytes`` is what ``put``
copied to the device.

A record keeps the newest :data:`RING` chunks' rows and 64-bit totals of
the whole call, so its memory does not grow with the stream.
:func:`last_stream` returns the newest finished call's.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import NamedTuple

import jax
import numpy as np

SPANS = ("source", "put", "init", "dispatch", "wait", "fetch", "sum")
COLUMNS = (("chunk",) + tuple(s + "_s" for s in SPANS)
           + ("compiles", "compile_s", "h2d_bytes"))
RING = 1 << 14      # rows kept per call: 2^14 x 11 float64 = 1.4 MB

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"

_COL = {f"engine.{s}": COLUMNS.index(s + "_s") for s in SPANS}
_COMPILES = COLUMNS.index("compiles")
_COMPILE_NS = COLUMNS.index("compile_s")
_H2D = COLUMNS.index("h2d_bytes")
# times are kept in integer ns and shown in seconds
_SECONDS = np.array([1e-9 if c.endswith("_s") else 1.0 for c in COLUMNS])

_local = threading.local()     # .rec: the record of this thread's open call
_last: "StreamRecord | None" = None


class StreamTrace(NamedTuple):
    """One finished ``replay_stream`` call, as :func:`last_stream` gives it.

    ``rows`` maps each of :data:`COLUMNS` to the newest ``min(chunks,
    RING)`` chunks' values, in chunk order (times in seconds);
    ``totals`` holds the same columns summed over the whole call (the
    end-of-stream pull included, ``chunk`` left out); ``compiled`` maps a
    compiled or traced function's name to ``(compiles, seconds)``.
    ``rank_row`` is the rank-row lowering the chunk program traced,
    ``(lowering, lane_block)`` with lowering ``"dense"``, ``"tiled"`` or
    ``"jnp"`` (see :func:`note_rank_row`), or ``None`` for a policy with
    no rank row.
    """

    rows: dict
    totals: dict
    compiled: dict
    chunks: int
    wall_s: float
    rank_row: tuple | None = None


class StreamRecord:
    """Per-chunk rows of one call in a ring of :data:`RING` rows, and
    totals."""

    def __init__(self):
        self._ring = np.zeros((RING, len(COLUMNS)), np.float64)
        self._row = [0] * len(COLUMNS)
        self._totals = [0] * len(COLUMNS)
        self._compiled: dict = {}
        self._traces: list = []    # (start, ns) of the open row's traces
        self.chunks = 0
        self.wall_ns = 0
        self.open = False          # a span of this record is open
        self.rank_row = None       # (lowering, lane block), set on trace

    def add_h2d(self, nbytes: int) -> None:
        self._row[_H2D] += nbytes

    def add_compile(self, fun_name: str, compiled: bool, start: float,
                    end: float) -> None:
        ns = round((end - start) * 1e9)
        if not compiled:
            # events arrive as they end, so the traces nested in this one
            # are the newest ones that started after it
            while self._traces and self._traces[-1][0] >= start:
                self._row[_COMPILE_NS] -= self._traces.pop()[1]
            self._traces.append((start, ns))
        self._row[_COMPILES] += compiled
        self._row[_COMPILE_NS] += ns
        n, s = self._compiled.get(fun_name, (0, 0.0))
        self._compiled[fun_name] = (n + compiled, s + (end - start))

    def end_chunk(self, chunk: int) -> None:
        """File the open row as chunk ``chunk``'s and start the next."""
        row, self._row = self._row, [0] * len(COLUMNS)
        self._traces.clear()
        row[0] = chunk
        self._ring[self.chunks % len(self._ring)] = row
        self._totals = [t + v for t, v in zip(self._totals, row)]
        self.chunks += 1

    def finish(self, wall_ns: int) -> None:
        """Add the unfiled row (the end-of-stream pull) to the totals."""
        self._row[0] = 0
        self._totals = [t + v for t, v in zip(self._totals, self._row)]
        self._row = [0] * len(COLUMNS)
        self.wall_ns = wall_ns

    def view(self) -> StreamTrace:
        n = min(self.chunks, len(self._ring))
        order = np.arange(self.chunks - n, self.chunks) % len(self._ring)
        rows = self._ring[order] * _SECONDS
        totals = {c: (t * 1e-9 if c.endswith("_s") else t)
                  for c, t in zip(COLUMNS[1:], self._totals[1:])}
        return StreamTrace(rows={c: rows[:, j] for j, c in enumerate(COLUMNS)},
                           totals=totals, compiled=dict(self._compiled),
                           chunks=self.chunks, wall_s=self.wall_ns * 1e-9,
                           rank_row=self.rank_row)


class span:
    """``with span("engine.put", i):`` — a profiler span named ``name`` with
    stat ``chunk=i``, whose duration is added to the open chunk row of
    this thread's :func:`stream` record (if one is open)."""

    __slots__ = ("_ann", "_rec", "_col", "_t0")

    def __init__(self, name: str, chunk: int):
        self._col = _COL[name]
        self._ann = jax.profiler.TraceAnnotation(name, chunk=chunk)
        self._rec = getattr(_local, "rec", None)

    def __enter__(self):
        self._ann.__enter__()
        if self._rec is not None:
            self._rec.open = True
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self._t0
        if self._rec is not None:
            self._rec.open = False
            self._rec._row[self._col] += dt
        self._ann.__exit__(*exc)


@contextlib.contextmanager
def stream():
    """Open a record for one ``replay_stream`` call on this thread; on a
    normal exit it becomes :func:`last_stream`'s."""
    global _last
    rec = StreamRecord()
    prev = getattr(_local, "rec", None)
    _local.rec = rec
    t0 = time.perf_counter_ns()
    try:
        yield rec
    finally:
        _local.rec = prev
    rec.finish(time.perf_counter_ns() - t0)
    _last = rec


def note_rank_row(lowering: str, lane_block: int) -> None:
    """Called while ``rank_step`` is traced: the rank-row ``lowering``
    (``"dense"``, ``"tiled"`` or ``"jnp"``) and lanes per grid step
    (``lane_block``; 0 for jnp).  It lands in this thread's open
    :func:`stream` record, so it costs nothing per chunk."""
    rec = getattr(_local, "rec", None)
    if rec is not None:
        rec.rank_row = (lowering, int(lane_block))


def last_stream() -> StreamTrace | None:
    """The newest finished ``replay_stream`` call's record, or ``None``."""
    rec = _last
    return None if rec is None else rec.view()


def _on_span(event: str, start: float, end: float, fun_name: str = "?",
             **_) -> None:
    if event != COMPILE_EVENT and event != TRACE_EVENT:
        return
    rec = getattr(_local, "rec", None)
    if rec is not None and rec.open:
        rec.add_compile(fun_name, event == COMPILE_EVENT, start, end)


jax.monitoring.register_event_time_span_listener(_on_span)
