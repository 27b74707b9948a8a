"""95th percentile, over all of the window's chunks, of the host time
between successive ``next()`` calls: one chunk's whole cost, host to
host."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx["chunk_s"], 95)) * 1e3
