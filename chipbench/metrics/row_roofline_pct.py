"""Share of the HBM roofline that the rank-row step reaches.

The work counted is what the rank-row algorithm needs, whatever implements
it: each request reads and writes the ranks its lane's policy works on,
``lane_pad(k)`` of them for an active size ``k`` (padded to the chip's 128
lanes), at ``rank_bytes`` each (the configuration's key width).  Not the
row the program allocates (DAC reserves ``K * growth`` ranks), and not the
passes of any one kernel: a change that touches fewer bytes reads as a
gain, and one that works on the active ranks alone stays under 100%.

``k`` per request comes from the plain reference (its ``ranks``), on the
lanes the correctness check replays, averaged over them and summed over
the traced chunks.  That least time at the chip's peak HBM bandwidth
(``chipbench/peaks.json``), over the device busy time of the traced
window.  Nothing to read (no ``rank_bytes`` in the configuration, no
ranks from the reference, no traced chunk): no value.
"""
import numpy as np


def read(ctx):
    tr, ranks = ctx["traced"], ctx["ranks"]
    width = ctx["config"].get("rank_bytes")
    if tr is None or not tr["chunks"] or not ranks or width is None:
        return None
    per_lane = np.mean([sum(r[c] for c in tr["chunks"])
                        for r in ranks.values()])
    need_bytes = ctx["lanes"] * per_lane * width * 2
    need_s = need_bytes / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * need_s / tr["busy_s"]
