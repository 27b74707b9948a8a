"""The iterator the engine pulls its chunks from: warm-up, then the window.

``Engine.replay_stream`` syncs each chunk's totals to the host before it
asks for the next chunk, so the time between two ``next()`` calls is one
chunk's whole cost, host to host.  This wrapper records those times:

* the first ``warmup`` chunks are set-up (they compile the chunk program,
  or load it from the cache);
* the window starts at the next ``next()``, yields whole chunks only, and
  ends at the first ``next()`` after ``seconds``;
* every ``next()`` of the window is timed, and so is the feed inside it
  (the source producing its chunk), as a ``feed`` span in the profiler's
  trace as well.

With a ``Tracer``, the profiler records window chunks ``[skip, skip+n)``.
"""
from __future__ import annotations

import time

import jax


class Tracer:
    """Starts the profiler at window chunk ``skip`` and stops it ``n``
    chunks later (or when the window ends first)."""

    def __init__(self, log_dir: str, skip: int, n: int):
        self.log_dir, self.skip, self.n = log_dir, skip, n
        self.span = None
        self.chunks = range(0)

    def at_chunk(self, i: int) -> None:
        if i == self.skip:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("traced_window")
            self.span.__enter__()
        elif i == self.skip + self.n:
            self.stop(i)

    def stop(self, i: int) -> None:
        if self.span is None:
            return
        self.span.__exit__(None, None, None)
        self.span = None
        jax.profiler.stop_trace()
        self.chunks = range(self.skip, i)


class Window:
    def __init__(self, source, warmup: int, seconds: float,
                 tracer: Tracer | None = None):
        self.source, self.warmup, self.seconds = source, warmup, seconds
        self.tracer = tracer
        self.fed = 0               # chunks handed out, warm-up included
        self.calls: list = []      # host time of each window next()
        self.feed_s: list = []     # time the source took, per window chunk
        self.stop = None           # host time of the next() that ended it

    def __iter__(self):
        return self

    def __next__(self):
        now = time.perf_counter()
        if self.fed >= self.warmup:
            if self.calls and now - self.calls[0] >= self.seconds:
                self.stop = now
                if self.tracer is not None:
                    self.tracer.stop(len(self.calls))
                raise StopIteration
            self.calls.append(now)
            if self.tracer is not None:
                self.tracer.at_chunk(len(self.calls) - 1)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("feed"):
            item = self.source.next()
        if self.fed >= self.warmup:
            self.feed_s.append(time.perf_counter() - t0)
        self.fed += 1
        return item

    @property
    def start(self) -> float:
        return self.calls[0]

    @property
    def chunk_s(self) -> list:
        """Host time of each window chunk, ``next()`` to ``next()``."""
        ends = self.calls[1:] + [self.stop]
        return [b - a for a, b in zip(self.calls, ends)]
