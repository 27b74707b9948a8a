"""The benchmark's copies agree with the originals they were taken from."""
import numpy as np
import pytest

from chipbench import generator
from chipbench.reference import oracle_general
from chipbench.reference.dac import DAC


@pytest.mark.parametrize("K,alpha,n_ids", [(4, 0.8, 64), (16, 1.2, 256),
                                           (32, 0.2, 4096), (8, 1.4, 32)])
def test_dac_matches_repro_oracle(K, alpha, n_ids):
    from repro.core.oracle import OracleDynamicAdaptiveClimb
    rng = generator.rng_for(K, 0)
    keys = generator.zipf_keys(rng, generator.zipf_cdf(n_ids, alpha), 6000)
    mine, theirs = DAC(K), OracleDynamicAdaptiveClimb(K)
    got = [mine.step(int(k)) for k in keys]
    want = [theirs.step(int(k)) for k in keys]
    assert got == want
    assert mine.k == theirs.k


def test_dac_resizes_both_ways():
    """The traces above must drive both of Algorithm 2's resize paths."""
    seen = set()
    for keys in ([0, 1] * 200, list(range(400))):
        cache = DAC(8)
        for k in keys:
            cache.step(k)
            seen.add(cache.k)
    assert min(seen) < 8 < max(seen)


def test_oracle_general_round_trip(tmp_path):
    from repro.data import ingest
    rng = generator.rng_for(7, 0)
    raw = rng.integers(0, 2**64 - 1, size=50, dtype=np.uint64)
    keys = raw[generator.zipf_keys(rng, generator.zipf_cdf(50, 0.9), 3000)]
    sizes = generator.object_sizes(rng, 3000, 16.0, 1.5)
    path = tmp_path / "t.oracleGeneral.bin"
    generator.write_oracle_general(path, keys, sizes)
    ids, got_sizes = oracle_general.read(path, 3000)
    tr = ingest.load_trace(str(path))
    np.testing.assert_array_equal(ids, tr.keys)
    np.testing.assert_array_equal(got_sizes, tr.sizes)
    np.testing.assert_array_equal(generator.next_access(keys),
                                  ingest._next_access(keys))


def test_zipf_and_sizes_match_repro():
    from repro.data import traces
    cdf = generator.zipf_cdf(1000, 0.99)
    np.testing.assert_allclose(np.diff(cdf, prepend=0),
                               traces._zipf_pmf(1000, 0.99), rtol=1e-9)
    a = generator.object_sizes(np.random.default_rng(3), 500, 16.0, 1.5)
    np.testing.assert_array_equal(a, traces.object_sizes(500, seed=3))


def test_ranks_sum_lane_pad_of_the_active_size_per_request():
    """The reference's ``ranks`` per chunk equal ``lane_pad(k)`` summed over
    the chunk's requests, with ``k`` the size each request was decided at."""
    from chipbench.reference import dac
    config = {"K": 8, "policy_params": {"eps": 0.5, "growth": 4, "k_min": 2}}
    keys = [0, 1] * 150 + list(range(300)) + [3] * 200
    lane, probe = dac.Lane(config), dac.DAC(8)
    want = []
    for lo in range(0, len(keys), 64):
        total = 0
        for k in keys[lo:lo + 64]:
            total += dac.lane_pad(probe.k)
            probe.step(k)
        want.append(total)
        lane.chunk(np.asarray(keys[lo:lo + 64]))
    assert lane.ranks == want
    assert len(lane.cache.k_log) > 2       # the trace resized the cache
