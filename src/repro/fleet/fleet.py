"""Dynamic multi-tenant fleet: tenant lifecycle + auction arbitration +
mesh sharding, one scanned program.

The tier layer (:mod:`repro.tier`) holds N tenants fixed for the whole
replay.  A serving fleet doesn't: tenants arrive, hold a cache for one
session, and leave.  :class:`FleetTier` keeps the tier's fixed-shape
discipline — ``n_lanes`` lane slots allocated up front, every array
``[n_lanes, ...]`` — and moves the lifecycle *inside* the scan via an
``alive`` mask driven by the trace itself: the ``fleet(...)`` trace
family (:func:`repro.data.traces.fleet_trace`) marks an idle lane with
key ``-1``, so an alive-mask edge is an arrival or departure event.

Per scanned step, in order:

1. **departures** — lanes whose key flipped to ``-1``: active size,
   cap and controller scalars zero out, so the departed tenant's slots
   fall back into the free pool by no longer being counted;
2. **admission** — lanes whose key flipped from ``-1``: a fresh tenant
   is granted ``k_min`` plus whatever headroom toward ``k0`` the pool
   covers (cumulative-sum grants, like the greedy arbiter), with
   ``k_min`` *reserved* for every still-idle lane so a full fleet can
   always admit;
3. **policy step** — every lane advances one fused
   ``step_budgeted`` under ``vmap`` (dead lanes run on neutralized
   inputs and their outputs are discarded);
4. **telemetry** — per-lane Metrics, the byte-miss-cost EWMA
   (``utility``), and the SLO penalty histogram update in the carry;
5. **arbitration** — the arbiter prices the next step's capacity caps
   from ``(k, demanding, budget, utility)``; the auction arbiter is the
   one that actually reads ``utility``.

The conservation law generalizes the tier's: at every step
``sum(k) + k_min * n_idle + outstanding_grants <= budget`` — so
``sum(k) <= budget`` holds through any churn pattern (locked by
``tests/test_fleet.py``).

**Mesh sharding**: ``replay_fleet(..., mesh=...)`` splits the lane axis
over a device mesh with ``shard_map``.  Each shard runs the same scanned
program against a per-shard budget split; every ``rebalance`` steps the
shards exchange their committed capacity and utility mass through
``psum`` and the global slack is re-dealt in proportion to utility —
cross-shard capacity trading at O(1) collective cost, scaling the fleet
to thousands of lanes without serializing on one arbiter.

>>> import numpy as np
>>> from repro.data.traces import fleet_trace
>>> keys = fleet_trace(N=64, T=600, n_lanes=4, rate=0.05,
...                    mean_session=150, seed=0)
>>> fl = FleetTier("dac(k_min=4)", n_lanes=4, budget=64, arbiter="auction")
>>> res = replay_fleet(fl, keys, observe=True)
>>> bool(np.asarray(res.obs["k"]).sum(axis=1).max() <= 64)  # conservation
True
>>> res.metrics.hits.shape                                  # per-lane
(4,)
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core import make_policy
from ..core.dynamicadaptiveclimb import DynamicAdaptiveClimb
from ..core.policy import (EMPTY, Request, lane_pad, normalize_pallas_mode,
                           pallas_mode)
from ..core.simulator import Metrics, _count_dtype, _ratio
from ..tier.arbiter import make_arbiter
from . import telemetry

__all__ = ["FleetTier", "FleetResult", "replay_fleet"]


class FleetResult(NamedTuple):
    """Per-lane fleet replay totals plus the SLO telemetry.

    ``metrics`` leaves carry a trailing lane axis (``[N]``, or ``[S, N]``
    seed-batched); idle steps count nothing (``requests`` is each lane's
    *served* request count).  ``avg_k`` is the time-mean active size over
    all T steps (0 while idle), ``alive_frac`` the fraction of steps the
    lane hosted a tenant, ``hist`` the ``[..., N, BINS]`` penalty
    histogram, and ``obs`` is ``{"k": [T, N], "alive": [T, N]}`` under
    ``observe=True`` (else ``None``).
    """

    metrics: Metrics
    avg_k: jax.Array
    alive_frac: jax.Array
    hist: jax.Array
    obs: Any

    # -- per-lane ratios ----------------------------------------------------
    @property
    def hit_ratio(self):
        return _ratio(self.metrics.hits, self.metrics.requests)

    @property
    def miss_ratio(self):
        m = self.metrics
        return _ratio(np.asarray(m.requests) - np.asarray(m.hits),
                      m.requests)

    @property
    def byte_miss_ratio(self):
        return _ratio(self.metrics.bytes_missed, self.metrics.bytes_total)

    @property
    def penalty_ratio(self):
        return _ratio(self.metrics.penalty, self.metrics.cost_total)

    # -- fleet aggregates (sum over the lane axis, then the ratio) ----------
    def _agg(self, num, den):
        return _ratio(np.asarray(num, dtype=np.float64).sum(axis=-1),
                      np.asarray(den, dtype=np.float64).sum(axis=-1))

    @property
    def agg_miss_ratio(self):
        m = self.metrics
        return self._agg(np.asarray(m.requests) - np.asarray(m.hits),
                         m.requests)

    @property
    def agg_byte_miss_ratio(self):
        return self._agg(self.metrics.bytes_missed, self.metrics.bytes_total)

    @property
    def agg_penalty_ratio(self):
        return self._agg(self.metrics.penalty, self.metrics.cost_total)

    # -- SLO telemetry ------------------------------------------------------
    def penalty_quantile(self, q: float):
        """Per-lane penalty quantile (bucket upper edge) — ``[..., N]``."""
        return telemetry.penalty_quantile(self.hist, q)

    def agg_penalty_quantile(self, q: float):
        """Fleet-wide penalty quantile over all served requests."""
        return telemetry.penalty_quantile(
            np.asarray(self.hist, np.float64).sum(axis=-2), q)

    @property
    def jain(self):
        """Jain fairness of mean-occupancy-while-alive across the lanes
        that ever hosted a tenant."""
        af = np.asarray(self.alive_frac, np.float64)
        k = np.asarray(self.avg_k, np.float64)
        occ = np.divide(k, af, out=np.zeros_like(k), where=af > 0)
        return telemetry.jain_index(occ, mask=af > 0)


class FleetTier:
    """Static description of one fleet: policy x n_lanes x budget x
    arbiter.  Hashable (a jit static argument, like ``CacheTier``).

    ``n_lanes`` bounds the *concurrent* tenants (the trace's arrival
    process decides how many are live at once); ``budget`` is the global
    slot pool.  Resizable fleets (DAC) require ``budget >= n_lanes *
    k_min`` so a fully-booked fleet can still hold every tenant at the
    floor — admission reserves that floor for idle lanes.  ``k0`` is the
    admission *target* (granted fully when the pool covers it);
    ``util_decay`` sets the byte-miss-cost EWMA the auction arbiter
    prices by.  Non-resizing policies pair with the static arbiter only,
    exactly like the tier.

    >>> FleetTier("dac(k_min=4)", n_lanes=8, budget=128, arbiter="auction")
    FleetTier(dynamicadaptiveclimb, n_lanes=8, budget=128, arbiter=auction, k0=4, util_decay=0.98)
    """

    def __init__(self, policy="dac", n_lanes: int = 8, budget: int = 256,
                 arbiter="auction", k0: int | None = None,
                 util_decay: float = 0.98):
        self.policy = make_policy(policy)
        self.arbiter = make_arbiter(arbiter)
        self.n_lanes = int(n_lanes)
        self.budget = int(budget)
        self.util_decay = float(util_decay)
        self.resizable = isinstance(self.policy, DynamicAdaptiveClimb)
        if self.n_lanes < 1:
            raise ValueError("n_lanes must be >= 1")
        if self.budget // self.n_lanes < 1:
            raise ValueError(
                f"budget {self.budget} too small for {self.n_lanes} lanes")
        if not self.resizable and self.arbiter.name != "static":
            raise ValueError(
                f"policy {self.policy.name!r} emits no resize signals; only "
                "arbiter('static') is meaningful for it")
        if self.resizable and self.share < self.policy.k_min:
            raise ValueError(
                f"budget {self.budget} cannot float {self.n_lanes} lanes at "
                f"the k_min={self.policy.k_min} floor — admission reserves "
                "k_min per lane so a full fleet never over-commits")
        if k0 is None:
            k0 = (max(self.policy.k_min, self.share // self.policy.growth)
                  if self.resizable else self.share)
        self.k0 = int(k0)
        if self.resizable and not (self.policy.k_min <= self.k0
                                   <= self.budget):
            raise ValueError(
                f"k0 must lie in [k_min={self.policy.k_min}, "
                f"budget={self.budget}], got {self.k0}")

    @property
    def share(self) -> int:
        """The static per-lane partition, ``budget // n_lanes``."""
        return self.budget // self.n_lanes

    @property
    def k_min(self) -> int:
        """Per-lane floor the admission path reserves (0 when the policy
        has no resize floor — non-resizable lanes hold a fixed share)."""
        return self.policy.k_min if self.resizable else 0

    # -- state --------------------------------------------------------------
    def init(self, n_lanes: int | None = None) -> dict:
        """Fresh fleet state for ``n_lanes`` lanes (default: all; the
        sharded path builds one per-shard block).  All lanes start idle:
        ``k = cap = 0``, caches EMPTY, no utility."""
        n = self.n_lanes if n_lanes is None else int(n_lanes)
        if self.resizable:
            p = {
                "cache": jnp.full((n, lane_pad(self.budget)), EMPTY,
                                  jnp.int32),
                "jump": jnp.zeros((n,), jnp.int32),
                "jump2": jnp.zeros((n,), jnp.int32),
                "k": jnp.zeros((n,), jnp.int32),
                "kmax": jnp.full((n,), self.budget, jnp.int32),
                "cap": jnp.zeros((n,), jnp.int32),
            }
        else:
            st = self.policy.init(self.share)
            p = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), st)
        return {"p": p, "alive": jnp.zeros((n,), bool),
                "util": jnp.zeros((n,), jnp.float32)}

    # -- hashability for jit static args ------------------------------------
    def _fields(self):
        return (self.policy, self.arbiter, self.n_lanes, self.budget,
                self.k0, self.util_decay)

    def __hash__(self):
        return hash((type(self).__name__, self._fields()))

    def __eq__(self, other):
        return type(self) is type(other) and self._fields() == other._fields()

    def __repr__(self):
        return (f"FleetTier({self.policy.name}, n_lanes={self.n_lanes}, "
                f"budget={self.budget}, arbiter={self.arbiter.name}, "
                f"k0={self.k0}, util_decay={self.util_decay})")


def _tree_where(mask, a, b):
    """Leaf-wise ``where`` with the [N] mask broadcast over trailing dims."""
    def sel(x, y):
        m = mask.reshape(mask.shape + (1,) * (x.ndim - 1))
        return jnp.where(m, x, y)
    return jax.tree_util.tree_map(sel, a, b)


def _fleet_step(tier: FleetTier, st: dict, req: Request, budget):
    """One fleet step (lifecycle + policy + arbitration) for the lane
    block in ``st``.  ``budget`` is the block's slot budget — the global
    int unsharded, a traced per-shard scalar under ``shard_map``.
    Returns ``(st, (hit, bytes_missed, penalty, k, alive))`` with every
    output masked to live lanes."""
    p, alive_prev, util = st["p"], st["alive"], st["util"]
    alive = req.key >= 0
    arrive = alive & ~alive_prev
    depart = alive_prev & ~alive
    pooled = tier.arbiter.pooled

    if tier.resizable:
        k_min = tier.policy.k_min
        # 1. departures: zero the lane's claim — its slots are now free
        #    simply by not being counted
        k = jnp.where(depart, 0, p["k"])
        cap = jnp.where(depart, 0, p["cap"])
        util = jnp.where(depart | arrive, 0.0, util)

        # 2. admission: k_min guaranteed (reserved for every idle lane),
        #    plus pool headroom toward k0, granted in lane order
        if pooled:
            outstanding = jnp.sum(jnp.where(alive_prev & alive,
                                            jnp.maximum(cap - k, 0), 0))
            reserve = k_min * (jnp.sum(~alive) + jnp.sum(arrive))
            pool = jnp.maximum(
                budget - jnp.sum(k) - reserve - outstanding, 0)
            want = jnp.where(arrive, tier.k0 - k_min, 0)
            before = jnp.cumsum(want) - want
            k_admit = (k_min + jnp.clip(pool - before, 0, want)
                       ).astype(jnp.int32)
        else:
            k_admit = jnp.full_like(k, min(tier.k0, tier.share))
        cache = jnp.where(arrive[:, None], EMPTY, p["cache"])
        jump = jnp.where(arrive, k_admit, jnp.where(depart, 0, p["jump"]))
        jump2 = jnp.where(arrive | depart, 0, p["jump2"])
        k = jnp.where(arrive, k_admit, k)
        cap = jnp.where(arrive, k_admit, cap)

        # 3. step every lane fused; dead lanes run on neutral inputs
        #    (key 0, k floored at k_min) and their outputs are discarded
        safe = {"cache": cache, "jump": jump, "jump2": jump2,
                "k": jnp.maximum(k, k_min), "kmax": p["kmax"], "cap": cap}
        safe_req = Request(key=jnp.where(alive, req.key, 0),
                           size=req.size, cost=req.cost)
        new_p, info = jax.vmap(tier.policy.step_budgeted)(safe, safe_req)
        cache = jnp.where(alive[:, None], new_p["cache"], cache)
        jump = jnp.where(alive, new_p["jump"], jump)
        jump2 = jnp.where(alive, new_p["jump2"], jump2)
        k = jnp.where(alive, new_p["k"], k)
    else:
        # non-resizable: every lane owns the static share; an arrival
        # resets the lane to a fresh policy state
        fresh = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x, (alive.shape[0],) + x.shape).astype(x.dtype),
            tier.policy.init(tier.share))
        pstate = _tree_where(arrive, fresh, p)
        util = jnp.where(depart | arrive, 0.0, util)
        safe_req = Request(key=jnp.where(alive, req.key, 0),
                           size=req.size, cost=req.cost)
        new_p, info = jax.vmap(tier.policy.step)(pstate, safe_req)
        p = _tree_where(alive, new_p, pstate)
        k = jnp.where(alive, tier.share, 0).astype(jnp.int32)

    # 4. telemetry: masked step outputs + the byte-miss-cost EWMA the
    #    auction arbiter prices capacity by
    hit = info.hit & alive
    bm = jnp.where(alive, info.bytes_missed.astype(jnp.float32), 0.0)
    pen = jnp.where(alive, info.penalty, 0.0)
    d = jnp.float32(tier.util_decay)
    util = jnp.where(alive, d * util + (1.0 - d) * pen, util)

    # 5. next step's capacity caps
    if tier.resizable:
        demanding = (jump >= 2 * k) & alive
        if pooled:
            # idle lanes keep their k_min admission reserve out of the
            # arbitrated pool
            budget_eff = budget - tier.policy.k_min * jnp.sum(~alive)
            caps = tier.arbiter(k, demanding, budget_eff, tier.n_lanes,
                                utility=util)
        else:
            caps = tier.arbiter(k, demanding, tier.budget, tier.n_lanes)
        cap = jnp.where(alive, caps, 0).astype(jnp.int32)
        p = {"cache": cache, "jump": jump, "jump2": jump2, "k": k,
             "kmax": p["kmax"], "cap": cap}

    st = {"p": p, "alive": alive, "util": util}
    return st, (hit, bm, pen, k, alive)


def _zero_acc_fleet(n: int) -> Metrics:
    return Metrics(
        requests=jnp.zeros((n,), _count_dtype()),
        hits=jnp.zeros((n,), _count_dtype()),
        bytes_total=jnp.zeros((n,), jnp.float32),
        bytes_missed=jnp.zeros((n,), jnp.float32),
        cost_total=jnp.zeros((n,), jnp.float32),
        penalty=jnp.zeros((n,), jnp.float32),
    )


def _acc_fleet(acc: Metrics, req: Request, hit, bm, pen, alive) -> Metrics:
    """Like the engine's ``_acc_step`` but idle lanes count nothing —
    ``requests`` advances only where a tenant served a request."""
    cd = _count_dtype()
    af = alive.astype(jnp.float32)
    return Metrics(
        requests=acc.requests + alive.astype(cd),
        hits=acc.hits + hit.astype(cd),
        bytes_total=acc.bytes_total + req.size.astype(jnp.float32) * af,
        bytes_missed=acc.bytes_missed + bm,
        cost_total=acc.cost_total + req.cost.astype(jnp.float32) * af,
        penalty=acc.penalty + pen,
    )


def _scan_fleet(tier: FleetTier, reqs: Request, observe: bool) -> FleetResult:
    """Metrics-in-carry scan of one ``[T, N]`` fleet stream."""
    n = reqs.key.shape[1]
    T = reqs.key.shape[0]

    def body(carry, req):
        st, acc, ksum, asum, hist = carry
        st, (hit, bm, pen, k, alive) = _fleet_step(tier, st, req,
                                                   tier.budget)
        acc = _acc_fleet(acc, req, hit, bm, pen, alive)
        hist = hist.at[jnp.arange(n), telemetry.penalty_bucket(pen)].add(
            alive.astype(hist.dtype))
        carry = (st, acc, ksum + k.astype(jnp.float32),
                 asum + alive.astype(jnp.float32), hist)
        return carry, ({"k": k, "alive": alive} if observe else None)

    carry0 = (tier.init(n), _zero_acc_fleet(n),
              jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32),
              jnp.zeros((n, telemetry.BINS), jnp.int32))
    (_, acc, ksum, asum, hist), obs = jax.lax.scan(body, carry0, reqs)
    return FleetResult(metrics=acc, avg_k=ksum / T, alive_frac=asum / T,
                       hist=hist, obs=obs)


@partial(jax.jit, static_argnames=("tier", "observe", "use_pallas"))
def _replay_fleet_single(tier, reqs, observe, use_pallas):
    with pallas_mode(use_pallas):
        return _scan_fleet(tier, reqs, observe)


@partial(jax.jit, static_argnames=("tier", "observe", "use_pallas"))
def _replay_fleet_batched(tier, reqs, observe, use_pallas):
    with pallas_mode(use_pallas):
        return jax.vmap(lambda r: _scan_fleet(tier, r, observe))(reqs)


def _scan_fleet_sharded(tier: FleetTier, reqs: Request, axis: str,
                        n_shards: int, rebalance: int,
                        observe: bool) -> FleetResult:
    """Per-shard scan body (runs inside ``shard_map``): the lane block's
    budget starts at an even split and is re-dealt every ``rebalance``
    steps — each shard publishes its *committed* capacity (claimed slots
    + admission reserve + uncashed grants) and its utility mass through
    ``psum``, and the global slack is split in proportion to utility.
    The collective runs unconditionally every step (SPMD collectives
    cannot sit under a traced branch); the reassignment applies on the
    rebalance tick."""
    n_local = reqs.key.shape[1]
    T = reqs.key.shape[0]
    base = tier.budget // n_shards
    idx = jax.lax.axis_index(axis)
    sb0 = (base + jnp.where(idx == 0, tier.budget % n_shards, 0)
           ).astype(jnp.int32)
    trade = tier.resizable and tier.arbiter.pooled
    k_min = tier.k_min

    def body(carry, xs):
        req, t = xs
        st, acc, ksum, asum, hist, sb = carry
        st, (hit, bm, pen, k, alive) = _fleet_step(tier, st, req, sb)
        acc = _acc_fleet(acc, req, hit, bm, pen, alive)
        hist = hist.at[jnp.arange(n_local),
                       telemetry.penalty_bucket(pen)].add(
            alive.astype(hist.dtype))
        if trade:
            outstanding = jnp.sum(
                jnp.where(alive, jnp.maximum(st["p"]["cap"] - k, 0), 0))
            committed = (jnp.sum(k) + k_min * jnp.sum(~alive)
                         + outstanding)
            w = jnp.sum(st["util"]) + 1.0      # +1: idle shards keep a bid
            total = jax.lax.psum(committed, axis)
            wsum = jax.lax.psum(w, axis)
            slack = jnp.maximum(tier.budget - total, 0).astype(jnp.float32)
            sb_new = (committed
                      + jnp.floor(slack * w / wsum).astype(jnp.int32))
            sb = jnp.where(t % rebalance == 0, sb_new.astype(jnp.int32), sb)
        carry = (st, acc, ksum + k.astype(jnp.float32),
                 asum + alive.astype(jnp.float32), hist, sb)
        return carry, ({"k": k, "alive": alive} if observe else None)

    carry0 = (tier.init(n_local), _zero_acc_fleet(n_local),
              jnp.zeros((n_local,), jnp.float32),
              jnp.zeros((n_local,), jnp.float32),
              jnp.zeros((n_local, telemetry.BINS), jnp.int32), sb0)
    (_, acc, ksum, asum, hist, _), obs = jax.lax.scan(
        body, carry0, (reqs, jnp.arange(T, dtype=jnp.int32)))
    return FleetResult(metrics=acc, avg_k=ksum / T, alive_frac=asum / T,
                       hist=hist, obs=obs)


@partial(jax.jit, static_argnames=("tier", "mesh", "axis", "rebalance",
                                   "observe", "use_pallas"))
def _replay_fleet_sharded(tier, reqs, mesh, axis, rebalance, observe,
                          use_pallas):
    n_shards = int(mesh.shape[axis])
    if tier.n_lanes % n_shards:
        raise ValueError(
            f"n_lanes={tier.n_lanes} must divide evenly over the "
            f"{n_shards}-device {axis!r} mesh axis")
    n_local = tier.n_lanes // n_shards
    if tier.resizable and tier.budget // n_shards < n_local * tier.k_min:
        raise ValueError(
            f"per-shard budget {tier.budget // n_shards} cannot float "
            f"{n_local} lanes at k_min={tier.k_min}; raise the budget or "
            "use fewer shards")

    def shard_fn(r):
        with pallas_mode(use_pallas):
            return _scan_fleet_sharded(tier, r, axis, n_shards, rebalance,
                                       observe)

    lane = P(axis)
    out_specs = FleetResult(
        metrics=Metrics(*([lane] * 6)),
        avg_k=lane, alive_frac=lane, hist=P(axis, None),
        obs={"k": P(None, axis), "alive": P(None, axis)} if observe
        else None)
    return jax.shard_map(shard_fn, mesh=mesh, in_specs=(P(None, axis),),
                         out_specs=out_specs, axis_names={axis},
                         check_vma=False)(reqs)


def replay_fleet(tier: FleetTier, requests, *, sizes=None, costs=None,
                 observe: bool = False, mesh=None, axis: str = "data",
                 rebalance: int = 256, use_pallas=False) -> FleetResult:
    """Replay a dynamic-fleet request stream through ``tier``.

    ``requests``: a :class:`~repro.core.Request` (or bare keys, with
    ``sizes``/``costs`` broadcast per ``Request.of``) of shape ``[T, N]``
    — key ``-1`` marks a lane with no active tenant that step (the
    ``fleet(...)`` trace family's lifecycle encoding) — or ``[S, T, N]``
    to vmap a seed axis.  Sizes/costs at idle positions are ignored.

    With ``mesh=`` the lane axis is sharded over the mesh's ``axis`` via
    ``shard_map`` (``[T, N]`` input only): per-shard budget splits with a
    ``psum`` utility-weighted re-deal every ``rebalance`` steps.
    ``use_pallas`` routes the fused policy step through the Pallas kernel
    exactly as in ``replay_tier``.
    """
    use_pallas = normalize_pallas_mode(use_pallas)
    reqs = Request.of(requests, sizes, costs)
    if reqs.key.ndim == 2:
        if reqs.key.shape[1] != tier.n_lanes:
            raise ValueError(
                f"requests [T, N] must have N == n_lanes "
                f"({tier.n_lanes}), got {reqs.key.shape}")
        if mesh is not None:
            return _replay_fleet_sharded(tier, reqs, mesh, axis,
                                         int(rebalance), observe,
                                         use_pallas)
        return _replay_fleet_single(tier, reqs, observe, use_pallas)
    if reqs.key.ndim == 3:
        if mesh is not None:
            raise ValueError(
                "mesh sharding takes a single [T, N] stream; vmap the "
                "seed axis on the host instead")
        if reqs.key.shape[2] != tier.n_lanes:
            raise ValueError(
                f"requests [S, T, N] must have N == n_lanes "
                f"({tier.n_lanes}), got {reqs.key.shape}")
        return _replay_fleet_batched(tier, reqs, observe, use_pallas)
    raise ValueError(
        f"requests must be [T, N] or [S, T, N], got shape {reqs.key.shape}")
