"""``Engine.replay_stream``'s spans and counters (``repro.core.tracing``):
the ``engine.*`` spans in a profiler trace, the in-memory record that
``last_stream()`` gives, the compile counter against the jit cache, the
ring's bound, and totals unchanged by the instrumentation."""
import glob

import jax
import numpy as np
import pytest

from repro.analysis.retrace import cache_entries
from repro.core import Engine, Metrics, Request, make_policy, tracing
from repro.core.simulator import _replay_chunk
from repro.data.traces import zipf_trace

ENGINE = Engine()
PHASES = ("source", "put", "dispatch", "wait", "fetch", "sum")
LOOP = [c + "_s" for c in tracing.SPANS]


def lanes(B, T, seed):
    return np.stack([zipf_trace(N=64, T=T, alpha=1.0, seed=seed + b)
                     for b in range(B)]).astype(np.int32)


def chunked(keys, chunk):
    return (keys[:, lo:lo + chunk] for lo in range(0, keys.shape[1], chunk))


def host_events(path):
    """``(name, start, end, chunk)`` of the host events named ``engine.*``
    or ``call``, in start order."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine.") or ev.name == "call":
                    stats = dict(ev.stats)
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                stats.get("chunk")))
    return sorted(out, key=lambda e: e[1])


@pytest.mark.parametrize("source", ["iterator", "dense"])
def test_profiler_trace_has_each_span_per_chunk_in_order(source, tmp_path):
    keys = lanes(3, 100, seed=1)
    arg, kw = ((chunked(keys, 32), {}) if source == "iterator"
               else (keys, {"chunk": 32}))
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("call"):
            ENGINE.replay_stream("dac", arg, 12, **kw)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = host_events(path)
    (_, lo, hi, _), = [e for e in events if e[0] == "call"]
    spans = [e for e in events if e[0] != "call"]
    n = 4                                    # 32, 32, 32, 4
    want = []
    for i in range(n):
        phases = PHASES[:2] + ("init",) * (i == 0) + PHASES[2:]
        want += [(f"engine.{p}", i) for p in phases]
    want.append(("engine.source", n))        # the pull that ends the stream
    assert [(name, chunk) for name, _, _, chunk in spans] == want
    for (_, _, end, _), (_, start, _, _) in zip(spans, spans[1:]):
        assert end <= start                  # no two spans overlap
    assert all(lo <= s and e <= hi for _, s, e, _ in spans)


def test_last_stream_has_one_row_per_chunk_within_the_wall_time():
    keys = lanes(2, 150, seed=3)
    sizes = np.full_like(keys, 7)
    ENGINE.replay_stream("dac", keys, 12, sizes=sizes, chunk=40)
    rec = tracing.last_stream()
    assert rec.chunks == 4
    np.testing.assert_array_equal(rec.rows["chunk"], np.arange(4))
    assert set(rec.rows) == set(tracing.COLUMNS)
    assert rec.rows["init_s"][0] > 0 and not rec.rows["init_s"][1:].any()
    for c in LOOP:
        if c != "init_s":
            assert (rec.rows[c] > 0).all(), c
    spent = sum(rec.totals[c] for c in LOOP)
    assert 0 < spent <= rec.wall_s
    # the totals are the rows plus the end-of-stream pull (source only)
    for c in tracing.COLUMNS[1:]:
        extra = rec.totals[c] - rec.rows[c].sum()
        if c == "source_s":
            assert extra > 0
        else:
            assert extra == pytest.approx(0, abs=1e-12), c
    # int32 keys, sizes; one float32 scalar cost: 4 B each
    np.testing.assert_array_equal(rec.rows["h2d_bytes"],
                                  [4 * (2 * 2 * t + 1)
                                   for t in (40, 40, 40, 30)])


def test_compiles_agree_with_the_jit_cache():
    """Compiles fall in chunk 0 and in the chunk of the remainder shape, the
    two chunks in which ``_replay_chunk``'s jit cache grows."""
    keys = lanes(3, 100, seed=5)
    before = []

    def items():
        for c in chunked(keys, 32):
            before.append(cache_entries(_replay_chunk))
            yield c
        before.append(cache_entries(_replay_chunk))

    ENGINE.replay_stream("dac", items(), 41)    # a K no other test uses
    rec = tracing.last_stream()
    growth = np.diff(before)
    assert growth.tolist() == [1, 0, 0, 1]
    assert ((rec.rows["compiles"] > 0) == (growth > 0)).all()
    assert (rec.rows["compile_s"] > 0).tolist() == (growth > 0).tolist()
    assert rec.compiled["jit(_replay_chunk)"][0] == growth.sum()
    assert rec.totals["compiles"] == rec.rows["compiles"].sum()
    # a warm repeat compiles nothing
    ENGINE.replay_stream("dac", chunked(keys, 32), 41)
    assert tracing.last_stream().totals["compiles"] == 0


def test_compile_events_count_only_inside_a_span():
    with tracing.stream() as rec:
        jax.jit(lambda x: x * 3 + 1)(np.arange(5))       # outside a span
        with tracing.span("engine.dispatch", 0):
            jax.jit(lambda x: x * 5 - 2)(np.arange(7))
        rec.end_chunk(0)
    out = tracing.last_stream()
    assert out.rows["compiles"].tolist() == [1]
    assert out.totals["compiles"] == 1
    assert 0 < out.rows["compile_s"][0] <= out.rows["dispatch_s"][0]


def test_ring_keeps_the_newest_rows_in_bounded_memory(monkeypatch):
    assert tracing.RING * len(tracing.COLUMNS) * 8 < 2 << 20
    monkeypatch.setattr(tracing, "RING", 4)
    keys = lanes(1, 100, seed=7)
    ENGINE.replay_stream("lru", keys, 8, chunk=10)
    rec = tracing.last_stream()
    assert rec.chunks == 10
    np.testing.assert_array_equal(rec.rows["chunk"], [6, 7, 8, 9])
    assert tracing._last._ring.shape == (4, len(tracing.COLUMNS))
    assert rec.totals["h2d_bytes"] == 10 * 4 * (10 + 2)


def replay_unchanged(policy, items, K, observe):
    """The stream loop as it was before the spans: per chunk, dispatch and
    sum the fetched totals in 64-bit."""
    policy = make_policy(policy)
    state = totals = obs_sums = None
    T = 0
    for reqs in items:
        if state is None:
            lead = reqs.key.shape[:-1]
            state = jax.tree_util.tree_map(
                lambda x: np.broadcast_to(x, lead + x.shape).copy(),
                policy.init(K))
            totals = np.zeros((6,) + lead, np.float64)
        state, m, obs = _replay_chunk(policy, state, reqs, False, observe)
        totals += np.stack([np.asarray(f, dtype=np.float64) for f in m])
        T += reqs.key.shape[-1]
        if obs is not None:
            part = {k: np.asarray(v, np.float64).sum(axis=-1)
                    for k, v in obs.items()}
            obs_sums = part if obs_sums is None else {
                k: obs_sums[k] + part[k] for k in part}
    return totals, {k: v / T for k, v in obs_sums.items()}


@pytest.mark.parametrize("source", ["iterator", "dense"])
def test_totals_are_bit_identical_to_the_unchanged_loop(source):
    keys = lanes(3, 250, seed=11)
    rng = np.random.default_rng(11)
    sizes = rng.integers(1, 5000, keys.shape).astype(np.int32)
    costs = rng.random(keys.shape).astype(np.float32)
    pieces = [(keys[:, lo:lo + 64], sizes[:, lo:lo + 64],
               costs[:, lo:lo + 64]) for lo in range(0, 250, 64)]
    if source == "iterator":
        got = ENGINE.replay_stream("dac", iter(pieces), 16, observe=True)
    else:
        got = ENGINE.replay_stream("dac", keys, 16, sizes=sizes, costs=costs,
                                   chunk=64, observe=True)
    totals, obs = replay_unchanged(
        "dac", [Request.of(*p) for p in pieces], 16, observe=True)
    want = Metrics(*totals)
    for name in Metrics._fields:
        np.testing.assert_array_equal(getattr(got.metrics, name),
                                      getattr(want, name), err_msg=name)
    for k in obs:
        np.testing.assert_array_equal(got.obs[k], obs[k], err_msg=k)
