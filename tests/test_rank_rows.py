"""Lane-padded rank rows + the tiled and dense policy-step kernels.

Proves the padding invariants the refactor rests on: ``find`` / ``promote``
/ ``demote`` / ``rank_step`` are equivalent on padded and tight rows, and
the tiled Pallas kernel (forced down to 128-lane tiles so multi-tile
carries actually fire) is bit-identical to the jnp oracle at awkward K —
non-multiples of 128, single-element rows, K larger than one tile —
including ``wipe_from`` boundaries and fully-``EMPTY`` rows.  The dense
kernel (whole rows in ``(bl, W)`` lane blocks, updated in place) is held
to the same oracle at lane counts that do and do not fill its blocks, and
the engine's default lowering is checked per backend.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import Engine, make_policy, tracing
from repro.core.policy import (EMPTY, LANE, Request, demote, find, lane_pad,
                               padded_row, pallas_mode, promote, rank_step)
from repro.core.simulator import _replay_chunk
from repro.kernels import policy_step
from repro.kernels.policy_step import dense_lane_block, fused_policy_step
from repro.tier import CacheTier, replay_tier

K_GRID = [1, 7, 127, 128, 129, 1000]


def climb_plan(hit, i, scalars):
    """CLIMB with a traced length scalar — the simplest full-contract plan
    (promote-by-one on hit, replace bottom on miss, wipe = none)."""
    (n,) = scalars
    src = jnp.where(hit, i, n - 1)
    t = jnp.where(hit, jnp.maximum(i - 1, 0), n - 1)
    return src, t, n, (n,)


def _tight_row(K, k, rng):
    """A tight [K] row with k distinct resident keys, rest EMPTY."""
    row = np.full(K, -1, np.int32)
    row[:k] = rng.choice(5 * K + 8, size=k, replace=False).astype(np.int32)
    return jnp.asarray(row)


# --- padded vs tight primitive equivalence ----------------------------------

@pytest.mark.parametrize("K", [1, 7, 127, 128, 129])
def test_find_promote_demote_padded_equivalence(K):
    rng = np.random.default_rng(K + 1)
    k = int(rng.integers(1, K + 1))
    tight = _tight_row(K, k, rng)
    W = lane_pad(K)
    padded = jnp.concatenate([tight, jnp.full((W - K,), EMPTY, jnp.int32)])

    present = tight[int(rng.integers(0, k))]
    absent = jnp.int32(5 * K + 9)
    for key in (present, absent):
        ht, it = find(tight, key)
        hp, ip = find(padded, key)
        assert bool(ht) == bool(hp)
        if bool(ht):
            assert int(it) == int(ip)

    i = int(rng.integers(0, k))
    t = int(rng.integers(0, i + 1))
    np.testing.assert_array_equal(
        np.asarray(promote(padded, i, t, jnp.int32(777))[:K]),
        np.asarray(promote(tight, i, t, jnp.int32(777))))
    d = int(rng.integers(i, k))
    np.testing.assert_array_equal(
        np.asarray(demote(padded, i, d, tight[i])[:K]),
        np.asarray(demote(tight, i, d, tight[i])))
    # padding untouched by either primitive
    assert np.all(np.asarray(promote(padded, i, t, jnp.int32(777))[K:]) == -1)
    assert np.all(np.asarray(demote(padded, i, d, tight[i])[K:]) == -1)


# --- tiled kernel vs jnp oracle at edge K -----------------------------------

@pytest.mark.parametrize("K", K_GRID)
def test_fused_step_parity_edge_K(K):
    """128-lane forced tiles: K=1000 runs 8 tiles, so the cross-tile argmin,
    boundary carry, and evicted-extraction paths all fire."""
    rng = np.random.default_rng(K)
    n = jnp.int32(K)
    cache_j = cache_p = padded_row(K)

    @jax.jit
    def jstep(c, key):
        return rank_step(c, key, (n,), climb_plan)

    @jax.jit
    def pstep(c, key):
        return fused_policy_step(c, key, (n,), climb_plan,
                                 interpret=True, tile=LANE)

    for step in range(100):
        key = jnp.int32(rng.integers(0, max(2 * K, 4)))
        cache_j, _, hit_j, ev_j = jstep(cache_j, key)
        cache_p, _, hit_p, ev_p = pstep(cache_p, key)
        assert bool(hit_j) == bool(hit_p), step
        assert int(ev_j) == int(ev_p), step
        np.testing.assert_array_equal(np.asarray(cache_j),
                                      np.asarray(cache_p))
    # padding invariant held throughout
    assert np.all(np.asarray(cache_p)[K:] == -1)


@pytest.mark.parametrize("K", [1, 7, 127, 129])
def test_fused_step_pads_tight_rows_internally(K):
    """Direct calls with tight (non-padded) rows — e.g. the rank_step
    doctest — pad internally and slice back, bit-identical to the oracle."""
    rng = np.random.default_rng(K + 7)
    n = jnp.int32(K)
    cache_j = cache_p = jnp.full((K,), EMPTY, jnp.int32)
    for step in range(60):
        key = jnp.int32(rng.integers(0, max(2 * K, 4)))
        cache_j, _, hit_j, _ = rank_step(cache_j, key, (n,), climb_plan)
        cache_p, _, hit_p, _ = fused_policy_step(
            cache_p, key, (n,), climb_plan, interpret=True, tile=LANE)
        assert cache_p.shape == (K,)
        assert bool(hit_j) == bool(hit_p), step
        np.testing.assert_array_equal(np.asarray(cache_j),
                                      np.asarray(cache_p))


# --- wipe_from boundaries and empty rows ------------------------------------

@pytest.mark.parametrize("wipe", [0, 1, 64, 127, 128, 200, 255, 256])
def test_wipe_from_boundaries(wipe):
    """Wipes landing on/off tile edges of a 2-tile row (W=256, tile=128),
    including wipe=0 (clears the freshly inserted key too) and wipe=W."""
    W = 256
    cache = jnp.arange(W, dtype=jnp.int32)

    def plan(hit, i, scalars):
        return jnp.int32(W - 1), jnp.int32(0), jnp.int32(wipe), ()

    ref = rank_step(cache, jnp.int32(999), (), plan)
    got = fused_policy_step(cache, jnp.int32(999), (), plan,
                            interpret=True, tile=LANE)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))
    assert bool(got[2]) == bool(ref[2]) and int(got[3]) == int(ref[3])
    assert np.all(np.asarray(got[0])[wipe:] == -1)


@pytest.mark.parametrize("K", [4, 128, 300])
@pytest.mark.parametrize("key", [5, -1])
def test_full_empty_row(K, key):
    """A fully-EMPTY row: a miss inserts at the bottom rank; searching for
    EMPTY itself (-1) 'hits' at rank 0 in both lowerings alike."""
    cache = padded_row(K)
    n = jnp.int32(K)
    ref = rank_step(cache, jnp.int32(key), (n,), climb_plan)
    got = fused_policy_step(cache, jnp.int32(key), (n,), climb_plan,
                            interpret=True, tile=LANE)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))
    assert bool(got[2]) == bool(ref[2]) and int(got[3]) == int(ref[3])


# --- composition: scan + vmap over multi-tile rows --------------------------

def test_scan_vmap_multitile_parity():
    K = 300                                  # W = 384 -> 3 forced tiles
    B, T = 3, 120
    rng = np.random.default_rng(42)
    keys = jnp.asarray(rng.integers(0, 2 * K, size=(B, T)).astype(np.int32))
    n = jnp.int32(K)

    def run(step_fn):
        def one(lane_keys):
            def body(c, key):
                c, _, hit, _ = step_fn(c, key)
                return c, hit
            return jax.lax.scan(body, padded_row(K), lane_keys)
        return jax.jit(jax.vmap(one))(keys)

    cj, hj = run(lambda c, key: rank_step(c, key, (n,), climb_plan))
    cp, hp = run(lambda c, key: fused_policy_step(
        c, key, (n,), climb_plan, interpret=True, tile=LANE))
    np.testing.assert_array_equal(np.asarray(hp), np.asarray(hj))
    np.testing.assert_array_equal(np.asarray(cp), np.asarray(cj))


def test_compiled_config_lowers_for_tpu():
    """The interpret=False (Mosaic) configuration cannot execute on CPU,
    but it must *lower*: cross-platform export for TPU proves the kernel
    is Mosaic-legal, scan+vmap included (tools/check_lowering.py runs the
    fuller sweep; this is the in-suite smoke)."""
    jexport = pytest.importorskip("jax.export")
    K = 300
    n = jnp.int32(K)

    def f(cache, keys):
        def body(c, key):
            c, _, hit, _ = fused_policy_step(c, key, (n,), climb_plan,
                                             interpret=False, tile=LANE)
            return c, hit
        return jax.lax.scan(body, cache, keys)

    exp = jexport.export(jax.jit(f), platforms=["tpu"])(
        jax.ShapeDtypeStruct((lane_pad(K),), jnp.int32),
        jax.ShapeDtypeStruct((16,), jnp.int32))
    assert "tpu" in [p.lower() for p in exp.platforms]


# --- property: random promote/wipe plans on padded rows ---------------------

@settings(max_examples=20, deadline=None)
@given(data=st.data())
def test_random_plan_sequences_parity(data):
    """Arbitrary valid plans (any t <= src < W, any wipe boundary, keys
    including EMPTY) keep the tiled kernel bit-identical to the oracle —
    stronger than policy-shaped sequences."""
    K = data.draw(st.integers(min_value=1, max_value=200))
    W = lane_pad(K)
    cache_j = cache_p = padded_row(K)
    for _ in range(data.draw(st.integers(min_value=1, max_value=6))):
        key = jnp.int32(data.draw(st.integers(min_value=-1,
                                              max_value=2 * K)))
        src = data.draw(st.integers(min_value=0, max_value=K - 1))
        t = data.draw(st.integers(min_value=0, max_value=src))
        wipe = data.draw(st.integers(min_value=0, max_value=W))

        def plan(hit, i, scalars, src=src, t=t, wipe=wipe):
            return jnp.int32(src), jnp.int32(t), jnp.int32(wipe), ()

        rj = rank_step(cache_j, key, (), plan)
        rp = fused_policy_step(cache_p, key, (), plan,
                               interpret=True, tile=LANE)
        np.testing.assert_array_equal(np.asarray(rp[0]), np.asarray(rj[0]))
        assert bool(rp[2]) == bool(rj[2]) and int(rp[3]) == int(rj[3])
        cache_j, cache_p = rj[0], rp[0]


# --- dense kernel: (bl, W) lane blocks, one in-place pass per request -------

def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation in ``jaxpr`` and its sub-jaxprs."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                if hasattr(sub, "eqns"):
                    found += _pallas_calls(sub)
    return found


@pytest.fixture
def dense_only(monkeypatch):
    """Refuse the tiled kernel, so a test can only pass on the dense one,
    and give the dense kernel a budget of ``rows`` lanes of ``W`` ranks:
    8 makes every lane count of 8 or more run several blocks."""
    def tiled(*a, **k):
        raise AssertionError("the tiled kernel ran")
    monkeypatch.setattr(policy_step, "_tiled_call", tiled)

    def budget(W, rows=8):
        monkeypatch.setattr(policy_step, "DENSE_VMEM_BYTES", rows * 16 * W)
    return budget


def _lanes(B, T, K, seed):
    """``[B, T]`` keys, or ``[T]`` for ``B=None`` (the unbatched call)."""
    rng = np.random.default_rng(seed)
    keys = rng.zipf(1.3, size=(B or 1, T)) % (3 * K)
    keys = keys.astype(np.int32)
    return keys[0] if B is None else keys


def _assert_replays_equal(spec, keys, K):
    ref = Engine().replay(spec, keys, K, observe=True, use_pallas=False)
    got = Engine().replay(spec, keys, K, observe=True,
                          use_pallas="interpret")
    np.testing.assert_array_equal(np.asarray(got.info.hit),
                                  np.asarray(ref.info.hit))
    np.testing.assert_array_equal(np.asarray(got.info.evicted_key),
                                  np.asarray(ref.info.evicted_key))
    for name in ref.obs or {}:
        np.testing.assert_array_equal(np.asarray(got.obs[name]),
                                      np.asarray(ref.obs[name]))
    return ref


@pytest.mark.parametrize("B", [None, 1, 5, 8, 13, 24, 1030])
def test_dense_step_parity_lane_counts(dense_only, B):
    """One lane unbatched, fewer lanes than a block (B=1, 5: a block of
    all of them), whole blocks (8, 24 = 3 blocks of 8), a padded last
    block (13 -> 16), and lanes padded to 1,152 whose scalars travel in
    blocks of 128 lanes, each serving 16 grid steps."""
    dense_only(128)
    if B is not None:
        bl = dense_lane_block(B, 128)
        assert bl == (B if B < 8 else 8)
    T = 100 if B == 1030 else 400
    _assert_replays_equal("dac(growth=4)", _lanes(B, T, 32, B or 0), 32)


def _grow_then_shrink(K, B):
    """Distinct keys (misses: jump saturates, k doubles up to kmax), then
    three hot keys (hits: jump and jump' fall, k halves), per lane."""
    rng = np.random.default_rng(K)
    return np.stack([np.concatenate([
        np.arange(12 * K) + 10**5 * b,
        rng.integers(0, 3, 14 * K) + 10**5 * b + 90000])
        for b in range(B)]).astype(np.int32)


@pytest.mark.parametrize("W", [128, 384])
@pytest.mark.parametrize("spec", ["climb", "adaptiveclimb", "dac"])
def test_dense_step_parity_policies(dense_only, spec, W):
    """Every rank policy on the dense kernel at one- and three-vreg rows,
    13 lanes in two blocks; DAC grows to kmax and shrinks back."""
    dense_only(W)
    B = 13
    if spec == "dac":
        K = W // 4                                 # growth 4: kmax = W
        ref = _assert_replays_equal(spec, _grow_then_shrink(K, B), K)
        k = np.asarray(ref.obs["k"])
        assert (k.max(axis=1) == W).all() and (k[:, -1] < W).all()
    else:
        _assert_replays_equal(spec, _lanes(B, 600, W - 20, W), W - 20)


def test_dense_budgeted_tier_under_nested_vmap(dense_only):
    """The tier's budgeted plan (5 control scalars) with a seed axis: the
    tenant vmap takes the dense kernel's lane grid, the seed vmap the
    standard pallas batching rule on top of it."""
    tier = CacheTier("dac", n_tenants=16, budget=16 * LANE)
    dense_only(tier.init()["cache"].shape[-1])   # 16 tenants: 2 blocks
    rng = np.random.default_rng(3)
    keys = (rng.zipf(1.2, size=(2, 400, 16)) % 300).astype(np.int32)
    ref = replay_tier(tier, keys, use_pallas=False)
    got = replay_tier(tier, keys, use_pallas="interpret")
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dense_call_aliases_cache_in_place():
    """The cache operand is aliased to the cache result, in the traced
    ``pallas_call`` and in the module lowered for a TPU, and the grid runs
    over lane blocks."""
    jexport = pytest.importorskip("jax.export")
    B, K = 16, 300

    def step(cache, keys, interpret):
        return jax.vmap(lambda c, k: fused_policy_step(
            c, k, (jnp.int32(K),), climb_plan, interpret=interpret))(
                cache, keys)

    args = (jnp.zeros((B, lane_pad(K)), jnp.int32),
            jnp.zeros((B,), jnp.int32))
    (call,) = _pallas_calls(jax.make_jaxpr(
        lambda c, k: step(c, k, True))(*args).jaxpr)
    assert call.params["input_output_aliases"] == ((1, 0),)
    assert call.params["grid_mapping"].grid == (
        B // dense_lane_block(B, lane_pad(K)),)
    exp = jexport.export(jax.jit(lambda c, k: step(c, k, False)),
                         platforms=["tpu"])(
        *(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args))
    assert "output_operand_alias" in exp.mlir_module()


def _chunk_program(spec, K, B, T):
    """The engine's stream chunk program for ``[B, T]`` chunks, traced
    (not run) under ``use_pallas="auto"``, with the record it leaves."""
    pol = make_policy(spec)
    state = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (B,) + x.shape), pol.init(K))
    reqs = Request.of(jnp.zeros((B, T), jnp.int32))
    with tracing.stream() as rec:
        jaxpr = jax.make_jaxpr(
            lambda s, r: _replay_chunk.__wrapped__(pol, s, r, "auto",
                                                   False))(state, reqs)
    return _pallas_calls(jaxpr.jaxpr), rec.rank_row


def test_engine_default_is_dense_kernel_on_tpu(monkeypatch):
    """``Engine()`` lowers by what it can observe: a backend that reads
    ``tpu`` gives the compiled dense kernel for a row that fits the
    budget, and jnp for one too wide for it; the CPU gives jnp."""
    assert Engine().use_pallas == "auto"
    calls, row = _chunk_program("dac", 1048, 16, 8)
    assert calls == [] and row == ("jnp", 0)               # this CPU
    monkeypatch.delenv(policy_step.INTERPRET_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    (call,), row = _chunk_program("dac", 1048, 16, 8)
    assert call.params["interpret"] is False
    assert row == ("dense", dense_lane_block(16, 4224))
    wide = 1 + policy_step.DENSE_VMEM_BYTES // (4 * 4 * 8 * 4)
    calls, row = _chunk_program("dac", wide, 16, 8)
    assert calls == [] and row == ("jnp", 0)


@pytest.mark.parametrize("mode,want", [
    (None, ("jnp", 0)), ("interpret", ("dense", 3)),
])
def test_stream_record_names_rank_row_lowering(mode, want):
    """``last_stream().rank_row`` reads the lowering of the call's chunk
    program, whether it was traced in this call or came from the jit
    cache; a policy without a rank row leaves it ``None``."""
    keys = _lanes(3, 96, 16, 5)
    for _ in range(2):                       # trace, then jit-cache hit
        Engine().replay_stream("dac", keys, 16, chunk=32, use_pallas=mode)
        assert tracing.last_stream().rank_row == want
    Engine().replay_stream("lru", keys, 16, chunk=32, use_pallas=mode)
    assert tracing.last_stream().rank_row is None
