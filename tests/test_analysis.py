"""The static-analysis subsystem, proven on itself and on fixtures.

Three layers of coverage:

* every AST lint rule demonstrated on a small synthetic bad/good fixture
  pair, plus the waiver machinery (same-line, line-above, stale-waiver
  audit, docstring inertness);
* the jaxpr contract pass over the full policy registry x both Pallas
  modes — and deliberately-broken toy policies that each trip exactly
  the check built to catch them (carry drift, debug callback, unpadded
  row, missing ADAPT_KEYS);
* the retrace auditor caught red-handed by a weak-typed toy step, and
  clean on the real engine; ``tools/repolint.py --lint-only`` exits 0 on
  the repo itself.
"""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (Finding, audit_engine, audit_jit, check_fleet,
                            check_policy, check_tier, lint_source,
                            lint_tree, registry_specs, verify_contracts)
from repro.analysis.contracts import FORBIDDEN_PRIMITIVES
from repro.bench import results
from repro.core import POLICIES
from repro.core.policy import EMPTY, LANE, Policy, Request, padded_row

ROOT = Path(__file__).resolve().parents[1]


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# level 2: AST lint rules on fixtures
# ---------------------------------------------------------------------------

class TestLintRules:
    def test_wallclock_bad(self):
        src = "import time\ndef f():\n    return time.time()\n"
        assert rules_of(lint_source(src, path="m.py")) == ["wallclock"]

    def test_wallclock_datetime(self):
        src = ("import datetime\n"
               "stamp = datetime.datetime.now()\n")
        assert rules_of(lint_source(src, path="m.py")) == ["wallclock"]

    def test_wallclock_good_perf_counter(self):
        src = "import time\nt0 = time.perf_counter()\n"
        assert lint_source(src, path="m.py") == []

    def test_unseeded_rng_legacy_numpy(self):
        src = "import numpy as np\nx = np.random.rand(3)\n"
        assert rules_of(lint_source(src, path="m.py")) == ["unseeded-rng"]

    def test_unseeded_rng_stdlib(self):
        src = "import random\nx = random.choice([1, 2])\n"
        assert rules_of(lint_source(src, path="m.py")) == ["unseeded-rng"]

    def test_unseeded_default_rng(self):
        bad = "import numpy as np\nrng = np.random.default_rng()\n"
        good = "import numpy as np\nrng = np.random.default_rng(17)\n"
        assert rules_of(lint_source(bad, path="m.py")) == ["unseeded-rng"]
        assert lint_source(good, path="m.py") == []

    def test_seeded_generator_draws_pass(self):
        src = ("import numpy as np\nrng = np.random.default_rng(0)\n"
               "x = rng.normal(size=3)\ny = rng.choice([1, 2])\n")
        assert lint_source(src, path="m.py") == []

    def test_jax_random_is_seeded_by_key(self):
        src = ("import jax\n"
               "x = jax.random.normal(jax.random.PRNGKey(0), (3,))\n")
        assert lint_source(src, path="m.py") == []

    def test_schema_literal_bad(self):
        src = f'schema = "{results.SCHEMA_V1}"\n'
        assert rules_of(lint_source(src, path="m.py")) == ["schema-literal"]

    def test_schema_literal_docstring_exempt(self):
        src = f'"""Payloads use ``{results.SCHEMA_V2}`` records."""\n'
        assert lint_source(src, path="m.py") == []

    def test_schema_literal_defining_module_exempt(self):
        src = f'SCHEMA_V1 = "{results.SCHEMA_V1}"\n'
        assert lint_source(src, path="src/repro/bench/results.py") == []
        assert rules_of(lint_source(src, path="other.py")) == [
            "schema-literal"]

    def test_empty_sentinel_bad(self):
        src = "import jax.numpy as jnp\nx = jnp.int32(-1)\n"
        assert rules_of(lint_source(src, path="m.py")) == ["empty-sentinel"]

    def test_empty_sentinel_other_values_pass(self):
        src = ("import jax.numpy as jnp\n"
               "a = jnp.int32(-2)\nb = jnp.int32(0)\nc = jnp.float32(-1)\n")
        assert lint_source(src, path="m.py") == []

    def test_atomic_json_bad(self):
        src = ("import json\ndef save(p, d):\n"
               "    with open(p, 'w') as f:\n        json.dump(d, f)\n")
        assert rules_of(lint_source(src, path="m.py")) == ["atomic-json"]

    def test_atomic_json_writer_body_exempt(self):
        src = ("import json\ndef atomic_write_json(p, d):\n"
               "    with open(p, 'w') as f:\n        json.dump(d, f)\n")
        assert lint_source(src, path="m.py") == []

    def test_json_dumps_passes(self):
        src = "import json\ns = json.dumps({'a': 1})\n"
        assert lint_source(src, path="m.py") == []

    def test_traced_branch_bad(self):
        src = ("import jax.numpy as jnp\ndef f(x):\n"
               "    if jnp.any(x > 0):\n        return 1\n    return 0\n")
        assert rules_of(lint_source(src, path="m.py")) == ["traced-branch"]

    def test_traced_branch_while(self):
        src = ("import jax.numpy as jnp\ndef f(x):\n"
               "    while jnp.sum(x) > 0:\n        x = x - 1\n")
        assert rules_of(lint_source(src, path="m.py")) == ["traced-branch"]

    def test_traced_branch_metadata_ok(self):
        src = ("import jax.numpy as jnp\ndef f(x):\n"
               "    if jnp.dtype(x.dtype) == jnp.dtype(jnp.int32):\n"
               "        return 1\n    return 0\n")
        assert lint_source(src, path="m.py") == []


class TestWaivers:
    BAD = "import time\nt = time.time()"

    def test_same_line_waiver(self):
        src = ("import time\n"
               "t = time.time()  # repolint: waive[wallclock] -- stamp\n")
        assert lint_source(src, path="m.py") == []

    def test_line_above_waiver(self):
        src = ("import time\n"
               "# repolint: waive[wallclock] -- provenance stamp\n"
               "t = time.time()\n")
        assert lint_source(src, path="m.py") == []

    def test_waiver_is_rule_specific(self):
        src = ("import time\n"
               "t = time.time()  # repolint: waive[atomic-json] -- wrong\n")
        assert rules_of(lint_source(src, path="m.py")) == [
            "unused-waiver", "wallclock"]

    def test_stale_waiver_reported(self):
        src = "x = 1  # repolint: waive[wallclock] -- nothing here\n"
        fs = lint_source(src, path="m.py")
        assert rules_of(fs) == ["unused-waiver"]
        assert fs[0].where == "m.py:1"

    def test_waiver_in_docstring_is_inert(self):
        src = ('"""Docs show `# repolint: waive[wallclock]` syntax."""\n'
               "import time\nt = time.time()\n")
        assert rules_of(lint_source(src, path="m.py")) == ["wallclock"]

    def test_multi_rule_waiver(self):
        src = ("import time, json\n"
               "# repolint: waive[wallclock,atomic-json] -- demo\n"
               "t = json.dump({'t': time.time()}, open('x', 'w'))\n")
        assert lint_source(src, path="m.py") == []


def test_repo_lint_is_clean():
    """The repo itself carries no unwaived findings (the CI gate)."""
    assert lint_tree(ROOT) == []


def test_finding_renders():
    f = Finding("wallclock", "a.py:3", "boom")
    assert str(f) == "a.py:3: [wallclock] boom"


# ---------------------------------------------------------------------------
# level 1: jaxpr contracts over the registry
# ---------------------------------------------------------------------------

def test_registry_specs_cover_everything():
    specs = registry_specs()
    assert len(specs) == 2 * len(POLICIES) == 30
    assert all(f"admit({n})" in specs for n in POLICIES)


@pytest.mark.parametrize("use_pallas", [False, "interpret"])
@pytest.mark.parametrize("spec", registry_specs())
def test_policy_contracts(spec, use_pallas):
    assert check_policy(spec, use_pallas=use_pallas) == []


@pytest.mark.parametrize("use_pallas", [False, "interpret"])
@pytest.mark.parametrize("spec", ["dynamicadaptiveclimb",
                                  "admit(dynamicadaptiveclimb)"])
def test_budgeted_contracts(spec, use_pallas):
    assert check_policy(spec, use_pallas=use_pallas, budgeted=True) == []


@pytest.mark.parametrize("use_pallas", [False, "interpret"])
def test_tier_and_fleet_contracts(use_pallas):
    assert check_tier(use_pallas=use_pallas) == []
    assert check_fleet(use_pallas=use_pallas) == []


def test_x64_subpass_is_clean():
    with jax.enable_x64(True):
        for spec in ("dynamicadaptiveclimb", "lru", "hyperbolic", "lhd"):
            assert check_policy(spec) == []


# --- toy policies that each violate exactly one contract -------------------

class _ToyRank(Policy):
    """Minimal well-formed rank-row policy (the control fixture)."""

    name = "toyrank"

    def init(self, K):
        return {"cache": padded_row(K)}

    def step(self, state, req):
        from repro.core.policy import step_info
        cache = state["cache"]
        hit = jnp.any(cache == req.key)
        cache = jnp.where(hit, cache, cache.at[0].set(req.key))
        return {"cache": cache}, step_info(hit, req)


class _CarryDrift(_ToyRank):
    name = "carrydrift"

    def step(self, state, req):
        new, info = super().step(state, req)
        return {"cache": new["cache"].astype(jnp.float32)}, info


class _StructureDrift(_ToyRank):
    name = "structuredrift"

    def step(self, state, req):
        new, info = super().step(state, req)
        return dict(new, extra=jnp.int32(0)), info


class _DebugCallback(_ToyRank):
    name = "debugcallback"

    def step(self, state, req):
        jax.debug.print("key={k}", k=req.key)
        return super().step(state, req)


class _UnpaddedRow(_ToyRank):
    name = "unpaddedrow"

    def init(self, K):
        return {"cache": jnp.full((K,), EMPTY, jnp.int32)}


class _MissingAdaptKeys(_ToyRank):
    name = "missingadapt"
    ADAPT_KEYS = ("jump",)


def test_toy_control_fixture_is_clean():
    assert check_policy(_ToyRank()) == []


def test_carry_aval_drift_caught():
    fs = check_policy(_CarryDrift())
    assert "carry-aval" in rules_of(fs)
    assert any("float32" in f.message for f in fs)


def test_carry_structure_drift_caught():
    assert "carry-structure" in rules_of(check_policy(_StructureDrift()))


def test_forbidden_primitive_caught():
    fs = check_policy(_DebugCallback())
    assert "forbidden-primitive" in rules_of(fs)
    assert any(p in f.message for f in fs for p in FORBIDDEN_PRIMITIVES)


def test_unpadded_row_caught():
    K = 5
    assert LANE % K  # K itself must not be lane-aligned for this fixture
    fs = check_policy(_UnpaddedRow(), K=K)
    assert "row-width" in rules_of(fs)


def test_missing_adapt_keys_caught():
    assert "adapt-keys" in rules_of(check_policy(_MissingAdaptKeys()))


def test_full_verify_contracts_is_clean():
    """The whole CI contract pass (registry x modes, budgeted paths,
    tier/fleet, x64 sub-pass) on the real repo."""
    assert verify_contracts() == []


# ---------------------------------------------------------------------------
# retrace auditor
# ---------------------------------------------------------------------------

def test_audit_jit_clean_on_stable_keys():
    f = jax.jit(lambda x: x * 2)
    fs = audit_jit(f, "toy",
                   prime=[("i32", lambda: f(jnp.int32(1)))],
                   variants=[("same-aval", lambda: f(jnp.int32(9)))],
                   expected=1)
    assert fs == []


def test_audit_jit_catches_weak_typed_call():
    """The classic cache-key bug: a Python scalar where an int32 array
    primed the cache retraces silently — the auditor must see it."""
    f = jax.jit(lambda x: x + 1)
    fs = audit_jit(f, "toy",
                   prime=[("i32", lambda: f(jnp.int32(1)))],
                   variants=[("weak-python-int", lambda: f(1))])
    assert rules_of(fs) == ["retrace"]


def test_audit_jit_expected_count_mismatch():
    f = jax.jit(lambda x: x + 1)
    fs = audit_jit(f, "toy",
                   prime=[("i32", lambda: f(jnp.int32(1)))],
                   variants=[], expected=2)
    assert rules_of(fs) == ["retrace-count"]


def test_engine_retrace_audit_is_clean():
    findings, report = audit_engine()
    assert findings == []
    assert report == {"_replay_single": 4, "_replay_batched": 3,
                      "_replay_chunk": 2}


def test_engine_audit_catches_unstable_policy_key():
    """A policy whose instances compare by identity (no value __eq__)
    retraces on every equal-but-fresh instance — exactly what the
    variant sweep exists to catch."""

    class IdentityPolicy(_ToyRank):
        name = "identitytoy"
        __hash__ = object.__hash__
        __eq__ = object.__eq__

    from repro.core.simulator import Engine, _replay_single
    eng = Engine()
    keys = jnp.arange(8, dtype=jnp.int32) % 3
    fs = audit_jit(
        _replay_single, "engine._replay_single",
        prime=[("a", lambda: eng.replay(IdentityPolicy(), keys, 4))],
        variants=[("fresh equal instance",
                   lambda: eng.replay(IdentityPolicy(), keys, 4))])
    assert rules_of(fs) == ["retrace"]


# ---------------------------------------------------------------------------
# the CLI gate and the schema constants satellite
# ---------------------------------------------------------------------------

def test_repolint_lint_only_exits_clean():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "repolint.py"),
         "--lint-only"], capture_output=True, text=True, cwd=ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "lint: 0 finding(s)" in proc.stdout


def test_schema_constants_are_canonical():
    assert results.SCHEMA_VERSION == results.SCHEMA_V1
    assert results.SCHEMA_VERSIONS == (results.SCHEMA_V1,
                                       results.SCHEMA_V2)
    assert results.SCHEMA_V1.endswith("/v1")
    assert results.SCHEMA_V2.endswith("/v2")
    # the validator accepts exactly the canonical pair
    with pytest.raises(ValueError):
        results.build_payload("x", config={}, records=[],
                              schema=results.SCHEMA_V1 + "x")
