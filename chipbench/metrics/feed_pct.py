"""Share of the traced window the host spent in the harness's chunk source
(the ``feed`` spans: slicing the ring, or reading and decoding the trace
file)."""


def read(ctx):
    tr = ctx["traced"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * tr["feed_s"] / tr["window_s"]
