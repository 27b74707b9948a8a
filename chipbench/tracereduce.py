"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Everything is taken over all device operations in the traced window, not
by name, so that renaming a jitted function or a kernel moves nothing:

* ``window_s``: the host span ``traced_window`` that the harness opens
  after the profiler starts and closes before it stops;
* ``busy_s``: the union of the intervals in which an operation ran on the
  device, inside the window, averaged over the devices that ran any (a
  trace in which none ran is an error, not a zero);
* ``ops``: the device operations that started inside the window, per
  chip: averaged over the devices that ran any, as ``busy_s`` is, so that
  a step on four chips reads what one chip ran (one chip: their count);
* ``device_ops``: the ten operation names that took the most device time;
* ``idle_gaps``: the idle time inside the window, by what the host was
  doing: ``feed`` (the harness's chunk source), ``in_program`` (a device
  program was running, between its operations), ``replay_stream``
  (anything else: the engine's host loop, transfers, syncs).
"""
from __future__ import annotations

import collections

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge ``[n, 2]`` intervals (start, end) into disjoint ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    stops = ends[np.r_[idx[1:] - 1, len(iv) - 1]]
    return np.stack([starts, stops], axis=1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _inside(points: np.ndarray, spans: np.ndarray) -> np.ndarray:
    """Which points fall inside one of the disjoint sorted ``spans``."""
    if len(spans) == 0:
        return np.zeros(len(points), bool)
    j = np.searchsorted(spans[:, 0], points, side="right") - 1
    ok = j >= 0
    out = np.zeros(len(points), bool)
    out[ok] = points[ok] < spans[j[ok], 1]
    return out


def _events(line):
    names, iv = [], []
    for ev in line.events:
        names.append(ev.name)
        iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    return names, np.asarray(iv, np.float64).reshape(-1, 2)


def load(path):
    """Planes of interest: host spans by name, and per device its op and
    module events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    host = collections.defaultdict(list)
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ("traced_window", "feed", "replay_stream"):
                        host[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            names, ops = _events(lines[OPS_LINE])
            mods = (_events(lines[MODULES_LINE])[1] if MODULES_LINE in lines
                    else np.zeros((0, 2)))
            devices.append({"plane": plane.name, "names": names, "ops": ops,
                            "modules": mods})
    return {k: np.asarray(v, np.float64) for k, v in host.items()}, devices


def reduce_trace(path) -> dict:
    return reduce_planes(*load(path), where=str(path))


def reduce_planes(host: dict, devices: list, where: str = "trace") -> dict:
    """The numbers above from :func:`load`'s host spans and devices."""
    if "traced_window" not in host or len(host["traced_window"]) != 1:
        raise ValueError(f"{where}: no single traced_window span")
    lo, hi = host["traced_window"][0]
    feed = _union(_clip(host.get("feed", np.zeros((0, 2))), lo, hi))
    busy, n_ops = [], 0
    by_name = collections.Counter()
    gaps = collections.Counter()
    for dev in devices:
        ops = dev["ops"]
        keep = (ops[:, 0] >= lo) & (ops[:, 0] < hi)
        if not keep.any():
            continue
        n_ops += int(keep.sum())
        for name, (s, e) in zip(np.asarray(dev["names"], object)[keep],
                                ops[keep]):
            by_name[name] += (min(e, hi) - s) * 1e-9
        u = _union(_clip(ops, lo, hi))
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
        # idle gaps: the complement of the union inside [lo, hi]
        edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
        edges = edges[edges[:, 1] > edges[:, 0]]
        mid = edges.mean(axis=1)
        length = (edges[:, 1] - edges[:, 0]) * 1e-9
        in_prog = _inside(mid, _union(_clip(dev["modules"], lo, hi)))
        in_feed = _inside(mid, feed) & ~in_prog
        gaps["in_program"] += float(length[in_prog].sum())
        gaps["feed"] += float(length[in_feed].sum())
        gaps["replay_stream"] += float(length[~in_prog & ~in_feed].sum())
    if not busy:
        raise ValueError(
            f"{where}: no device operation inside traced_window "
            f"[{lo:.0f}, {hi:.0f}] ns; device planes with an {OPS_LINE!r} "
            f"line and their op spans: "
            f"{[(d['plane'], d['ops'][:, 0].min(), d['ops'][:, 1].max()) for d in devices if len(d['ops'])]}")
    n_dev = len(busy)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_dev,
        "devices": len(busy),
        "ops": n_ops / n_dev,
        "feed_s": float((feed[:, 1] - feed[:, 0]).sum()) * 1e-9,
        "device_ops": [[n, t / n_dev] for n, t in by_name.most_common(10)],
        "idle_gaps": [[n, t / n_dev] for n, t in gaps.most_common(10)],
    }
