"""Requests replayed in the window, all lanes, per second of the window's
wall time (first ``next()`` of the window to the return of
``replay_stream``)."""


def read(ctx):
    return ctx["window_requests"] / ctx["window_s"]
