"""Fused Pallas policy-step kernel + streaming metrics-only replay.

Oracle parity: every rank-based policy must produce bit-identical hit
sequences with the fused kernel on and off (the kernel runs under the
Pallas interpreter on CPU — the same body Mosaic compiles on TPU), and
metrics-only / streaming replays must reproduce the stacked-info totals.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LRU, BLRU, Engine, Request, make_policy
from repro.data.traces import scan_mix_trace, zipf_trace

ENGINE = Engine()

RANK_SPECS = ["climb", "adaptiveclimb", "dynamicadaptiveclimb",
              "dac(eps=0.25,growth=2)"]


def _traces():
    return {
        "zipf": zipf_trace(N=256, T=2500, alpha=0.9, seed=11),
        "scan": scan_mix_trace(N=128, T=2500, alpha=1.0, scan_frac=0.3,
                               scan_len=96, seed=5),
    }


# --- Pallas oracle parity ----------------------------------------------------

@pytest.mark.parametrize("spec", RANK_SPECS)
@pytest.mark.parametrize("kind", ["zipf", "scan"])
def test_pallas_hits_bit_identical(spec, kind):
    trace = _traces()[kind]
    ref = ENGINE.replay(spec, trace, 24, use_pallas=False)
    got = ENGINE.replay(spec, trace, 24, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(got.info.hit),
                                  np.asarray(ref.info.hit))
    assert int(got.metrics.hits) == int(ref.metrics.hits)


@pytest.mark.parametrize("spec", ["adaptiveclimb", "dynamicadaptiveclimb"])
def test_pallas_batched_bit_identical(spec):
    traces = np.stack([zipf_trace(N=96, T=900, alpha=a, seed=s)
                       for s, a in enumerate((0.7, 1.0, 1.2))])
    ref = ENGINE.replay(spec, traces, 16, use_pallas=False)
    got = ENGINE.replay(spec, traces, 16, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(got.info.hit),
                                  np.asarray(ref.info.hit))


def test_engine_level_use_pallas_default():
    trace = zipf_trace(N=128, T=1200, alpha=1.0, seed=2)
    eng = Engine(use_pallas=True)
    ref = ENGINE.replay("dac", trace, 16)
    got = eng.replay("dac", trace, 16)              # engine-level default
    np.testing.assert_array_equal(np.asarray(got.info.hit),
                                  np.asarray(ref.info.hit))
    # per-call override wins over the engine default
    got_off = eng.replay("dac", trace, 16, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got_off.info.hit),
                                  np.asarray(ref.info.hit))


@pytest.mark.parametrize("spec", ["climb", "dynamicadaptiveclimb"])
def test_pallas_interpret_mode_string(spec):
    """The explicit "interpret" mode matches the jnp lowering bit-for-bit
    (True also resolves to interpret on this CPU container, but the string
    pins it regardless of backend)."""
    trace = zipf_trace(N=128, T=1200, alpha=1.0, seed=8)
    ref = ENGINE.replay(spec, trace, 16, use_pallas=False)
    got = ENGINE.replay(spec, trace, 16, use_pallas="interpret")
    np.testing.assert_array_equal(np.asarray(got.info.hit),
                                  np.asarray(ref.info.hit))
    assert int(got.metrics.hits) == int(ref.metrics.hits)


def test_pallas_mode_validation():
    trace = zipf_trace(N=32, T=100, alpha=1.0, seed=0)
    with pytest.raises(ValueError, match="use_pallas"):
        Engine(use_pallas="fast")
    with pytest.raises(ValueError, match="use_pallas"):
        ENGINE.replay("dac", trace, 8, use_pallas="fast")
    with pytest.raises(ValueError, match="use_pallas"):
        ENGINE.replay_stream("dac", trace, 8, use_pallas="maybe")


def test_resolve_interpret_env_override(monkeypatch):
    from repro.kernels.policy_step import INTERPRET_ENV, resolve_interpret
    monkeypatch.setenv(INTERPRET_ENV, "interpret")
    assert resolve_interpret(False) is True       # forced, beats the arg
    monkeypatch.setenv(INTERPRET_ENV, "compiled")
    assert resolve_interpret(True) is False
    monkeypatch.setenv(INTERPRET_ENV, "auto")
    assert resolve_interpret(True) is True        # defers to the arg
    assert resolve_interpret(False) is False
    monkeypatch.setenv(INTERPRET_ENV, "fast")
    with pytest.raises(ValueError, match=INTERPRET_ENV):
        resolve_interpret()
    monkeypatch.delenv(INTERPRET_ENV)
    expect = jax.default_backend() not in ("tpu", "gpu")
    assert resolve_interpret(None) is expect      # per-backend default


@pytest.mark.parametrize("spelling", ["interpret", "1", "true"])
def test_resolve_interpret_refuses_interpreter_on_tpu(monkeypatch, spelling):
    from repro.kernels.policy_step import INTERPRET_ENV, resolve_interpret
    monkeypatch.delenv(INTERPRET_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False       # auto -> compiled on tpu
    monkeypatch.setenv(INTERPRET_ENV, spelling)
    with pytest.raises(RuntimeError, match=INTERPRET_ENV):
        resolve_interpret(None)
    with pytest.raises(RuntimeError, match=INTERPRET_ENV):
        resolve_interpret(False)
    monkeypatch.setenv(INTERPRET_ENV, "compiled")
    assert resolve_interpret(None) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.setenv(INTERPRET_ENV, spelling)
    assert resolve_interpret(None) is True        # CPU unchanged


def test_pallas_flag_is_noop_for_slot_policies():
    trace = zipf_trace(N=128, T=1200, alpha=1.0, seed=6)
    ref = ENGINE.replay("lru", trace, 16)
    got = ENGINE.replay("lru", trace, 16, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(got.info.hit),
                                  np.asarray(ref.info.hit))


# --- metrics-only mode -------------------------------------------------------

@pytest.mark.parametrize("spec", ["lru", "arc", "dynamicadaptiveclimb"])
def test_collect_info_false_matches_stacked_totals(spec):
    trace = zipf_trace(N=256, T=2000, alpha=1.0, seed=7)
    sizes = (1 + (trace % 11)).astype(np.int32)
    full = ENGINE.replay(spec, trace, 24, sizes=sizes)
    lean = ENGINE.replay(spec, trace, 24, sizes=sizes, collect_info=False)
    assert lean.info is None
    assert int(lean.metrics.requests) == int(full.metrics.requests)
    assert int(lean.metrics.hits) == int(full.metrics.hits)
    for f, l in zip(full.metrics, lean.metrics):
        np.testing.assert_allclose(np.asarray(l), np.asarray(f), rtol=1e-6)
    assert lean.miss_ratio == pytest.approx(full.miss_ratio)
    assert lean.byte_miss_ratio == pytest.approx(full.byte_miss_ratio)


def test_collect_info_false_allocates_no_stepinfo():
    """The jitted metrics-only program's output avals contain nothing
    [T]-shaped — the StepInfo stack is truly gone, not just hidden."""
    T = 4096
    out = jax.eval_shape(
        lambda r: ENGINE.replay("lru", r, 16, collect_info=False),
        jax.ShapeDtypeStruct((T,), jnp.int32))
    assert out.info is None
    leaves = jax.tree_util.tree_leaves(out)
    assert leaves and all(T not in leaf.shape for leaf in leaves), \
        [leaf.shape for leaf in leaves]
    # batched: per-lane metrics only, no [B, T] stack
    out = jax.eval_shape(
        lambda r: ENGINE.replay("dac", r, 16, collect_info=False),
        jax.ShapeDtypeStruct((3, T), jnp.int32))
    assert all(T not in leaf.shape for leaf in jax.tree_util.tree_leaves(out))


def test_collect_info_false_still_collects_observables():
    trace = zipf_trace(N=512, T=1500, alpha=0.3, seed=4)
    res = ENGINE.replay("dac(growth=4)", trace, 16, observe=True,
                        collect_info=False)
    assert res.info is None
    ks = np.asarray(res.obs["k"])
    assert ks.shape == (1500,) and ks.min() >= 2


def test_hits_property_errors_without_info():
    trace = zipf_trace(N=64, T=500, alpha=1.0, seed=1)
    res = ENGINE.replay("lru", trace, 8, collect_info=False)
    with pytest.raises(ValueError, match="collect_info"):
        res.hits


# --- streaming replay --------------------------------------------------------

@pytest.mark.parametrize("spec,pallas", [("lru", False), ("sieve", False),
                                         ("dynamicadaptiveclimb", True)])
def test_replay_stream_matches_replay(spec, pallas):
    trace = zipf_trace(N=256, T=5000, alpha=1.0, seed=9)
    full = ENGINE.replay(spec, trace, 24)
    # chunk does not divide T: exercises the remainder program
    stream = ENGINE.replay_stream(spec, trace, 24, chunk=1024,
                                  use_pallas=pallas)
    assert stream.info is None
    assert int(stream.metrics.requests) == 5000
    assert int(stream.metrics.hits) == int(full.metrics.hits)
    assert stream.miss_ratio == pytest.approx(full.miss_ratio)


def test_replay_stream_batched_with_sizes():
    traces = np.stack([zipf_trace(N=96, T=2300, alpha=a, seed=s)
                       for s, a in enumerate((0.8, 1.1))])
    sizes = (1 + (traces % 7)).astype(np.int32)
    full = ENGINE.replay("arc", traces, 16, sizes=sizes)
    stream = ENGINE.replay_stream("arc", traces, 16, sizes=sizes, chunk=512)
    np.testing.assert_array_equal(np.asarray(stream.metrics.hits),
                                  np.asarray(full.metrics.hits))
    np.testing.assert_allclose(stream.byte_miss_ratio, full.byte_miss_ratio,
                               rtol=1e-5)


def test_replay_stream_accepts_request_and_rejects_extras():
    trace = zipf_trace(N=64, T=1000, alpha=1.0, seed=3)
    req = Request.of(trace, sizes=2)
    full = ENGINE.replay("lru", req, 8)
    stream = ENGINE.replay_stream("lru", req, 8, chunk=300)
    assert int(stream.metrics.hits) == int(full.metrics.hits)
    assert stream.metrics.bytes_total == pytest.approx(2000.0)
    with pytest.raises(ValueError, match="inside the Request"):
        ENGINE.replay_stream("lru", req, 8, sizes=3)
    with pytest.raises(ValueError, match="chunk"):
        ENGINE.replay_stream("lru", trace, 8, chunk=0)


# --- counter / timestamp widening -------------------------------------------

def test_lru_timestamps_widen_under_x64():
    st32 = LRU().init(4)
    assert st32["t"].dtype == jnp.int32
    with jax.enable_x64(True):
        st64 = LRU().init(4)
        assert st64["t"].dtype == jnp.int64
        assert st64["last"].dtype == jnp.int64
        assert BLRU().init(4)["t"].dtype == jnp.int64
        # keys stay int32 (they are ids, not counters)
        assert st64["keys"].dtype == jnp.int32
