"""Jaxpr-level contract analyzer (level 1 of the static-analysis
subsystem).

Every registry policy — and the tier/fleet ``step_budgeted`` paths built
on them — must satisfy a set of structural contracts that no amount of
replaying a few example traces can prove.  This module traces each
``step`` to its jaxpr (abstractly: no trace is replayed, no kernel run)
and verifies:

``carry-aval`` / ``carry-structure``
    The scan-carry law: the state tree that goes into ``step`` comes out
    with the *identical* avals — same tree structure, shapes, dtypes and
    weak-type flags.  Any drift breaks ``lax.scan`` and silently retraces.
``row-dtype`` / ``row-width`` / ``row-init``
    Rank rows (every ``"cache"`` leaf) are ``int32`` with a lane-padded
    trailing width (``W % LANE == 0``) and start all-``EMPTY``.
``f64-leak``
    No ``float64``/``complex128`` aval anywhere in the traced program
    (under default 32-bit mode, no 64-bit aval at all) — device programs
    must not widen.
``adapt-keys``
    Scalars a policy declares in ``ADAPT_KEYS`` really exist in its state
    tree as ``int32`` leaves (the admission/tier revert-exemption
    contract).
``forbidden-primitive``
    No host-callback / debug primitive (``pure_callback``, ``debug_print``,
    ...) inside the jitted step — they stall the device pipeline.

``verify_contracts`` runs the whole registry (15 policies + their
``admit(...)`` wrappers) under both Pallas settings, the budgeted
DAC/tier/fleet paths, and an x64 sub-pass that re-checks carry stability
when 64-bit mode is ambient.

>>> from repro.analysis import contracts
>>> contracts.check_policy("fifo")
[]
>>> len(contracts.registry_specs())
30
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import tree_util as jtu

from jax.extend import core as jcore

from ..core import POLICIES, make_policy
from ..core.policy import EMPTY, LANE, Request, pallas_mode
from .findings import Finding

__all__ = ["FORBIDDEN_PRIMITIVES", "registry_specs", "check_policy",
           "check_tier", "check_fleet", "verify_contracts"]

# host-callback / debug primitives that must never appear inside a jitted
# step program (they stall the device pipeline and break AOT lowering)
FORBIDDEN_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "outside_call", "host_callback_call",
})


def registry_specs():
    """All registry policies plus their ``admit(...)`` wrappers.

    >>> specs = registry_specs()
    >>> "dynamicadaptiveclimb" in specs and "admit(fifo)" in specs
    True
    """
    names = sorted(POLICIES)
    return tuple(names) + tuple(f"admit({n})" for n in names)


# -- jaxpr walking ------------------------------------------------------

def _as_jaxprs(v):
    if isinstance(v, jcore.ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, jcore.Jaxpr):
        return [v]
    if isinstance(v, (list, tuple)):
        return [j for x in v for j in _as_jaxprs(x)]
    return []


def _iter_jaxprs(jaxpr):
    """Yield ``jaxpr`` and every jaxpr nested in its equations (scan and
    cond bodies, pallas kernels, custom_vmap rules, ...)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in _as_jaxprs(v):
                yield from _iter_jaxprs(sub)


def _scan_program(closed, target, findings):
    """Walk every equation of a traced program for forbidden primitives
    and 64-bit aval leaks."""
    x64 = bool(jax.config.jax_enable_x64)
    bad_dtypes = set()
    bad_prims = set()
    for jaxpr in _iter_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in FORBIDDEN_PRIMITIVES:
                bad_prims.add(eqn.primitive.name)
            for var in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(var, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt is None:
                    continue
                dt = jnp.dtype(dt)
                if dt in (jnp.dtype("float64"), jnp.dtype("complex128")):
                    bad_dtypes.add(str(dt))
                elif not x64 and dt.itemsize == 8:
                    bad_dtypes.add(str(dt))
    for name in sorted(bad_prims):
        findings.append(Finding(
            "forbidden-primitive", target,
            f"primitive {name!r} inside the jitted step program"))
    for dt in sorted(bad_dtypes):
        findings.append(Finding(
            "f64-leak", target,
            f"{dt} aval inside the device program"
            + ("" if x64 else " (x64 is disabled)")))


# -- per-target checks --------------------------------------------------

def _aval_str(a):
    weak = getattr(a, "weak_type", False)
    return f"{a.str_short()}{'~w' if weak else ''}"


def _check_carry(step_fn, state, req, target, findings):
    """Trace ``step_fn(state, req)`` — which must return *only* the new
    state — and verify the scan-carry law plus program-level invariants;
    returns the traced ClosedJaxpr (or None)."""
    in_leaves, in_tree = jtu.tree_flatten(state)
    try:
        closed = jax.make_jaxpr(step_fn)(state, req)
        new_state = jax.eval_shape(step_fn, state, req)
    except Exception as exc:   # a step that won't even trace is a finding
        findings.append(Finding(
            "trace-error", target,
            f"step failed to trace abstractly: {type(exc).__name__}: "
            f"{exc}"))
        return None

    out_tree = jtu.tree_structure(new_state)
    if out_tree != in_tree:
        findings.append(Finding(
            "carry-structure", target,
            f"state tree structure drifts across step: {in_tree} -> "
            f"{out_tree}"))
        return closed

    n = len(in_leaves)
    paths = [jtu.keystr(p)
             for p, _ in jtu.tree_flatten_with_path(state)[0]]
    in_avals, out_avals = closed.in_avals[:n], closed.out_avals[:n]
    for path, a, b in zip(paths, in_avals, out_avals):
        if a != b:
            findings.append(Finding(
                "carry-aval", target,
                f"state leaf {path} drifts across step: "
                f"{_aval_str(a)} -> {_aval_str(b)} (breaks lax.scan)"))
    _scan_program(closed, target, findings)
    return closed


def _check_rows(state, target, findings):
    """Every ``"cache"`` leaf is an int32, lane-padded, all-EMPTY row."""
    for path, leaf in jtu.tree_flatten_with_path(state)[0]:
        if not (path and isinstance(path[-1], jtu.DictKey)
                and path[-1].key == "cache"):
            continue
        where = f"{target}{jtu.keystr(path)}"
        if jnp.dtype(leaf.dtype) != jnp.dtype(jnp.int32):
            findings.append(Finding(
                "row-dtype", where, f"rank row dtype {leaf.dtype}, "
                "expected int32"))
        if leaf.shape[-1] % LANE != 0:
            findings.append(Finding(
                "row-width", where, f"rank row width {leaf.shape[-1]} is "
                f"not a multiple of LANE={LANE}"))
        if not np.all(np.asarray(leaf) == int(EMPTY)):
            findings.append(Finding(
                "row-init", where, "fresh rank row is not all-EMPTY"))


def _check_adapt_keys(pol, state, target, findings):
    """Declared ``ADAPT_KEYS`` exist in the (base) state as int32
    leaves."""
    base, sub = pol, state
    inner = getattr(pol, "base", None)
    if inner is not None and isinstance(state, dict) and "base" in state:
        base, sub = inner, state["base"]
    for key in getattr(base, "ADAPT_KEYS", ()):
        if not (isinstance(sub, dict) and key in sub):
            findings.append(Finding(
                "adapt-keys", target,
                f"declared ADAPT_KEYS entry {key!r} missing from the "
                "state tree"))
            continue
        leaf = sub[key]
        if jnp.dtype(leaf.dtype) != jnp.dtype(jnp.int32):
            findings.append(Finding(
                "adapt-keys", target,
                f"ADAPT_KEYS leaf {key!r} has dtype {leaf.dtype}, "
                "expected int32"))


def _with_cap(state, K):
    """Insert the tier's capacity cap the way ``repro.tier`` does."""
    if isinstance(state, dict) and "base" in state:
        return dict(state, base=dict(state["base"], cap=jnp.int32(K)))
    return dict(state, cap=jnp.int32(K))


def check_policy(spec, K=8, use_pallas=False, budgeted=False):
    """Verify one policy spec's step contracts; returns findings.

    >>> check_policy("dac", use_pallas="interpret")
    []
    >>> check_policy("admit(dac)", budgeted=True)
    []
    """
    pol = make_policy(spec)
    target = (f"{spec}{':budgeted' if budgeted else ''}"
              f"@pallas={use_pallas}")
    findings = []
    state = pol.init(K)
    _check_rows(state, target, findings)
    _check_adapt_keys(pol, state, target, findings)
    if budgeted:
        state = _with_cap(state, K)
        step_fn = lambda st, r: pol.step_budgeted(st, r)[0]
    else:
        step_fn = lambda st, r: pol.step(st, r)[0]
    req = Request.of(jnp.int32(3))
    with pallas_mode(use_pallas):
        _check_carry(step_fn, state, req, target, findings)
    return findings


def check_tier(use_pallas=False, n_tenants=3, budget=6 * LANE):
    """Contract pass over the multi-tenant tier step.

    >>> check_tier()
    []
    """
    from ..tier.tier import CacheTier
    tier = CacheTier("dac", n_tenants=n_tenants, budget=budget)
    target = f"tier(dac,n={n_tenants})@pallas={use_pallas}"
    findings = []
    state = tier.init()
    _check_rows(state, target, findings)
    _check_adapt_keys(tier.policy, state, target, findings)
    req = Request.of(jnp.zeros((n_tenants,), jnp.int32))
    step_fn = lambda st, r: tier.step(st, r)[0]
    with pallas_mode(use_pallas):
        _check_carry(step_fn, state, req, target, findings)
    return findings


def check_fleet(use_pallas=False, n_lanes=4, budget=8 * LANE):
    """Contract pass over the fleet lane-block step.

    >>> check_fleet()
    []
    """
    from ..fleet.fleet import FleetTier, _fleet_step
    tier = FleetTier("dac", n_lanes=n_lanes, budget=budget)
    target = f"fleet(dac,n={n_lanes})@pallas={use_pallas}"
    findings = []
    state = tier.init()
    _check_rows(state, target, findings)
    req = Request.of(jnp.zeros((n_lanes,), jnp.int32))
    step_fn = lambda st, r: _fleet_step(tier, st, r,
                                        jnp.int32(tier.budget))[0]
    with pallas_mode(use_pallas):
        _check_carry(step_fn, state, req, target, findings)
    return findings


def verify_contracts(specs=None, pallas_modes=(False, "interpret"), K=8,
                     include_budgeted=True, include_tier=True,
                     include_x64=True):
    """The full contract pass: registry x Pallas modes, budgeted paths,
    tier/fleet, and an x64 carry-stability sub-pass.  Returns all
    findings (empty = contract-clean)."""
    if specs is None:
        specs = registry_specs()
    findings = []
    for mode in pallas_modes:
        for spec in specs:
            findings += check_policy(spec, K=K, use_pallas=mode)
        if include_budgeted:
            for spec in ("dynamicadaptiveclimb",
                         "admit(dynamicadaptiveclimb)"):
                findings += check_policy(spec, K=K, use_pallas=mode,
                                         budgeted=True)
        if include_tier:
            findings += check_tier(use_pallas=mode)
            findings += check_fleet(use_pallas=mode)
    if include_x64 and not jax.config.jax_enable_x64:
        with jax.enable_x64(True):
            for spec in specs:
                findings += check_policy(spec, K=K, use_pallas=False)
    return findings
