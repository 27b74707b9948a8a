"""Unified vectorized trace-replay engine.

One policy step is O(K) vector lanes; a trace replays under ``lax.scan``;
independent caches (different traces, seeds, or cache sizes) batch under
``vmap``; fleet-scale studies shard the batch over the device mesh.  This
replaces the paper's libCacheSim + thread-replay setup with a single SPMD
program, and the former ``replay`` / ``replay_batch`` / ``replay_observed``
/ ``replay_sharded`` quartet with one entrypoint::

    result = Engine().replay(policy, requests, K)

``requests`` is a :class:`~repro.core.policy.Request` pytree (or a bare key
array — coerced with unit size/cost) of shape ``[T]`` or ``[B, T]``; pass
``mesh=`` to spread a ``[B, T]`` batch over a device axis, ``observe=True``
to collect per-step policy observables (e.g. DAC's ``k``/``jump``).  Hit,
byte-miss and penalty totals are reduced *inside* the jitted program (per
lane, under vmap/SPMD) — callers read ratios off the result instead of
recomputing them post-hoc from hit masks.

Two scale paths (the paper's Tables IV/V throughput regime):

* ``replay(..., collect_info=False)`` reduces :class:`Metrics` inside the
  scan carry — the jitted program allocates NO ``[T]``-shaped ``StepInfo``
  output, only O(1) totals per lane (``result.info is None``).
* ``replay_stream(...)`` scans arbitrarily long traces in fixed-size
  chunks, donating the policy-state and accumulator buffers between chunks
  and summing per-chunk totals on the host in 64-bit — multi-billion-
  request streams never materialize on device and never wrap int32.  It
  also accepts an *iterator* of request chunks (the out-of-core path for
  file-backed traces, see ``repro.data.ingest``) and reports time-mean
  policy observables under ``observe=True``.

``use_pallas`` (an ``Engine`` or per-call switch) chooses how the
rank-policy hot path (find + promote) is lowered.  The default,
``"auto"``, decides by backend and row width where the row is traced:
on a TPU, rank rows whose 8-lane block fits the kernel's VMEM budget take
the compiled dense Pallas kernel (``repro.kernels.policy_step``), updated
in place; everything else (CPU, GPU, wider rows) takes plain jnp.  An
explicit value wins: ``False`` (plain jnp), ``"interpret"`` (the kernel
under the Pallas interpreter — runs anywhere, bit-identical to jnp),
``"compiled"`` (the real Mosaic/Triton lowering — TPU/GPU), or ``True``
("kernel with the per-backend default": compiled on tpu/gpu, interpreted
elsewhere; see ``repro.kernels.policy_step.resolve_interpret``).
"""
from __future__ import annotations

import itertools
import warnings
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import tracing
from .policy import (Policy, Request, StepInfo, normalize_pallas_mode,
                     pallas_mode)


def _count_dtype():
    """Dtype for request/hit counters: int32 wraps at 2.1e9 requests, so
    widen to int64 whenever x64 is enabled (CPU CI keeps int32; the
    streaming path additionally accumulates on the host in 64-bit)."""
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


class Metrics(NamedTuple):
    """Per-lane replay totals, reduced inside the jitted replay program.
    ``requests``/``hits`` widen to int64 under x64 (multi-billion-request
    streams wrap int32); byte/cost totals accumulate in float32 (object
    sizes in bytes overflow int32 over long traces).

    >>> from repro.core import Engine
    >>> m = Engine().replay("lru", [0, 0, 1], K=2, collect_info=False).metrics
    >>> int(m.requests), int(m.hits), float(m.bytes_missed)
    (3, 1, 2.0)
    """

    requests: jax.Array      # int32/int64 — trace length
    hits: jax.Array          # int32/int64
    bytes_total: jax.Array   # float32 — sum of request sizes
    bytes_missed: jax.Array  # float32 — sum of sizes over misses
    cost_total: jax.Array    # float32 — sum of request costs
    penalty: jax.Array       # float32 — sum of costs over misses


class ReplayResult(NamedTuple):
    """Engine output: per-step ``StepInfo`` (leading dims match the input;
    ``None`` in metrics-only mode), per-lane ``Metrics``, and optional
    stacked observables.

    >>> from repro.core import Engine
    >>> res = Engine().replay("lru", [0, 0, 0, 1], K=2)
    >>> res.hit_ratio, res.miss_ratio
    (0.5, 0.5)
    >>> [bool(h) for h in res.hits]
    [False, True, True, False]
    """

    info: StepInfo | None
    metrics: Metrics
    obs: Any

    # -- conveniences (host-side; float for one lane, ndarray for a batch) --
    @property
    def hits(self):
        if self.info is None:
            raise ValueError(
                "per-step info was not collected (collect_info=False / "
                "replay_stream); read totals off result.metrics instead")
        return self.info.hit

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
        """Cost-weighted miss ratio: sum(cost * miss) / sum(cost)."""
        return _ratio(self.metrics.penalty, self.metrics.cost_total)

    @property
    def total_penalty(self):
        out = np.asarray(self.metrics.penalty, dtype=np.float64)
        return float(out) if out.ndim == 0 else out


def _ratio(num, den):
    num = np.asarray(num, dtype=np.float64)
    den = np.asarray(den, dtype=np.float64)
    out = np.divide(num, den, out=np.zeros_like(num), where=den > 0)
    return float(out) if out.ndim == 0 else out


def _zero_acc():
    return Metrics(
        requests=jnp.zeros((), _count_dtype()),
        hits=jnp.zeros((), _count_dtype()),
        bytes_total=jnp.zeros((), jnp.float32),
        bytes_missed=jnp.zeros((), jnp.float32),
        cost_total=jnp.zeros((), jnp.float32),
        penalty=jnp.zeros((), jnp.float32),
    )


def _acc_step(acc: Metrics, req: Request, info: StepInfo) -> Metrics:
    """Fold one request's StepInfo into the running totals (scan carry)."""
    return Metrics(
        requests=acc.requests + 1,
        hits=acc.hits + info.hit.astype(_count_dtype()),
        bytes_total=acc.bytes_total + req.size.astype(jnp.float32),
        bytes_missed=acc.bytes_missed + info.bytes_missed.astype(jnp.float32),
        cost_total=acc.cost_total + req.cost,
        penalty=acc.penalty + info.penalty,
    )


def _scan_replay(policy: Policy, reqs: Request, K: int, observe: bool,
                 collect_info: bool = True,
                 state: Any = None) -> tuple[ReplayResult, Any]:
    """Scan one lane; returns (result, final_state).  With
    ``collect_info=False`` the metrics ride in the scan carry and no
    ``[T]``-shaped StepInfo is ever stacked."""
    if state is None:
        state = policy.init(K)
    want_obs = observe and hasattr(policy, "observables")

    if collect_info:
        def body(st, req):
            st, info = policy.step(st, req)
            obs = policy.observables(st) if want_obs else None
            return st, (info, obs)

        state, (info, obs) = jax.lax.scan(body, state, reqs)
        metrics = Metrics(
            requests=jnp.asarray(reqs.key.shape[0], _count_dtype()),
            hits=jnp.sum(info.hit, dtype=_count_dtype()),
            bytes_total=jnp.sum(reqs.size.astype(jnp.float32)),
            bytes_missed=jnp.sum(info.bytes_missed.astype(jnp.float32)),
            cost_total=jnp.sum(reqs.cost),
            penalty=jnp.sum(info.penalty),
        )
        return ReplayResult(info=info, metrics=metrics, obs=obs), state

    def body(carry, req):
        st, acc = carry
        st, info = policy.step(st, req)
        obs = policy.observables(st) if want_obs else None
        return (st, _acc_step(acc, req, info)), obs

    (state, acc), obs = jax.lax.scan(body, (state, _zero_acc()), reqs)
    return ReplayResult(info=None, metrics=acc, obs=obs), state


@partial(jax.jit,
         static_argnames=("policy", "K", "observe", "collect_info",
                          "use_pallas"))
def _replay_single(policy, reqs, K, observe, collect_info, use_pallas):
    with pallas_mode(use_pallas):
        return _scan_replay(policy, reqs, K, observe, collect_info)[0]


@partial(jax.jit,
         static_argnames=("policy", "K", "observe", "collect_info",
                          "use_pallas", "mesh", "axis"))
def _replay_batched(policy, reqs, K, observe, collect_info, use_pallas,
                    mesh, axis):
    """Vmapped lanes; with a ``mesh``, each device scans its own block of
    lanes under ``shard_map`` (a Pallas kernel cannot be partitioned by
    the compiler, and independent lanes need no collective)."""
    def lanes(r):
        with pallas_mode(use_pallas):
            return jax.vmap(
                lambda x: _scan_replay(policy, x, K, observe, collect_info)[0]
            )(r)

    if mesh is None:
        return lanes(reqs)
    return jax.shard_map(lanes, mesh=mesh, in_specs=P(axis),
                         out_specs=P(axis), axis_names={axis},
                         check_vma=False)(reqs)


@partial(jax.jit, static_argnames=("policy", "use_pallas", "observe"),
         donate_argnums=(1,))
def _replay_chunk(policy, state, reqs, use_pallas, observe):
    """One streaming chunk: advance donated policy state, return per-chunk
    totals (plus the chunk's stacked observables under ``observe`` — only
    ever chunk-shaped, summed into time means on the host).  Handles [T]
    and [B, T] chunks (state batched alike)."""
    with jax.named_scope("replay_chunk"), pallas_mode(use_pallas):
        def one(st, r):
            res, st = _scan_replay(policy, r, K=0, observe=observe,
                                   collect_info=False, state=st)
            return st, res.metrics, res.obs

        if reqs.key.ndim == 2:
            return jax.vmap(one)(state, reqs)
        return one(state, reqs)


# rank-row lowering noted when each chunk program was traced, by
# (policy, use_pallas, observe, K, lane shape): what a call whose program
# came from the jit cache reports
_RANK_ROW: dict = {}


def _put_bytes(keys, sizes, costs) -> int:
    """Bytes ``Request.of(keys, sizes, costs)`` copies to the device: each
    host column lands as int32 or float32, a ``None`` as one scalar."""
    if isinstance(keys, Request):
        return 0
    return 4 * sum(int(np.size(1 if c is None else c))
                   for c in (keys, sizes, costs)
                   if not isinstance(c, jax.Array))


class Engine:
    """The single replay entrypoint: scans one trace, vmaps a ``[B, T]``
    batch, and — given a mesh — shards the batch axis SPMD (each device
    replays B/axis_size independent caches, the TPU-native version of the
    paper's multi-threaded trace replay, Tables IV/V).

    ``use_pallas`` (overridable per call) lowers the rank-policy hot path:
    ``"auto"`` (the default) takes the compiled dense Pallas kernel on a
    TPU for rows that fit its VMEM budget and jnp everywhere else
    (:func:`repro.core.policy.auto_lowering`); ``False`` / ``"interpret"``
    / ``"compiled"`` / ``True`` force one lowering.  Slot-based policies
    are unaffected by the flag.

    >>> import numpy as np
    >>> res = Engine().replay("dac", np.zeros((2, 5), np.int32), K=4)
    >>> res.miss_ratio.tolist()       # [B, T] batch -> per-lane ratios
    [0.2, 0.2]
    """

    def __init__(self, mesh=None, axis: str = "data",
                 use_pallas="auto"):
        self.mesh = mesh
        self.axis = axis
        self.use_pallas = normalize_pallas_mode(use_pallas)

    def _resolve(self, policy, use_pallas):
        if isinstance(policy, str):
            from . import make_policy
            policy = make_policy(policy)
        use_pallas = (self.use_pallas if use_pallas is None
                      else normalize_pallas_mode(use_pallas))
        return policy, use_pallas

    def replay(self, policy, requests, K: int, *, sizes=None, costs=None,
               mesh=None, axis=None, observe: bool = False,
               collect_info: bool = True,
               use_pallas=None) -> ReplayResult:
        """Replay ``requests`` through ``policy`` at capacity ``K``.

        ``policy`` may be a :class:`Policy` instance or a spec string for
        :func:`repro.core.make_policy` (e.g. ``"dac(eps=0.5)"``).
        ``requests``: a :class:`Request`, or bare keys (``sizes``/``costs``
        then broadcast per :meth:`Request.of`).

        ``collect_info=False`` skips the ``[T]`` ``StepInfo`` stack and
        reduces :class:`Metrics` inside the scan carry — ``result.info`` is
        ``None`` and peak memory is O(K) per lane instead of O(T).
        """
        policy, use_pallas = self._resolve(policy, use_pallas)
        # a np.int32 capacity would be a fresh jit cache key (static args
        # compare with strict type equality) — normalize at the boundary
        K = int(K)
        reqs = Request.of(requests, sizes, costs)
        if reqs.key.ndim == 1:
            return _replay_single(policy, reqs, K, observe, collect_info,
                                  use_pallas)
        if reqs.key.ndim != 2:
            raise ValueError(
                f"requests must be [T] or [B, T], got shape {reqs.key.shape}")
        mesh = self.mesh if mesh is None else mesh
        axis = axis or self.axis
        if mesh is not None:
            if reqs.key.shape[0] % mesh.shape[axis]:
                raise ValueError(
                    f"{reqs.key.shape[0]} lanes do not divide evenly over "
                    f"the {mesh.shape[axis]}-device {axis!r} mesh axis")
            reqs = jax.device_put(reqs, NamedSharding(mesh, P(axis, None)))
        return _replay_batched(policy, reqs, K, observe, collect_info,
                               use_pallas, mesh, axis)

    def replay_tier(self, tier, requests, *, sizes=None, costs=None,
                    observe: bool = False, use_pallas=None):
        """Replay an interleaved multi-tenant stream through a
        :class:`repro.tier.CacheTier` (metrics-only, per-tenant
        :class:`Metrics` + time-mean occupancy in the scan carry).

        ``requests`` is ``[T, N]`` (one request per tenant per global
        step) or ``[S, T, N]`` for a vmapped seed axis; returns a
        :class:`repro.tier.TierResult`.  This is the first experiment
        family the single-cache ``replay`` cannot express — tenants
        compete for one budget, so their lanes are *not* independent.
        """
        from ..tier import CacheTier, replay_tier as _replay_tier
        if not isinstance(tier, CacheTier):
            raise TypeError(f"expected a CacheTier, got {type(tier).__name__}")
        use_pallas = (self.use_pallas if use_pallas is None
                      else normalize_pallas_mode(use_pallas))
        return _replay_tier(tier, requests, sizes=sizes, costs=costs,
                            observe=observe, use_pallas=use_pallas)

    def replay_fleet(self, tier, requests, *, sizes=None, costs=None,
                     observe: bool = False, mesh=None, axis=None,
                     rebalance: int = 256, use_pallas=None):
        """Replay a dynamic-fleet stream (``-1`` keys = idle lane) through
        a :class:`repro.fleet.FleetTier`: tenant arrivals/departures inside
        the scan, arbiter-priced capacity, per-lane SLO telemetry.

        ``requests`` is ``[T, N]`` (or ``[S, T, N]`` for a vmapped seed
        axis; unsharded only); with ``mesh=`` the lane axis is sharded via
        ``shard_map`` with a ``psum`` budget re-deal every ``rebalance``
        steps.  Returns a :class:`repro.fleet.FleetResult`.
        """
        from ..fleet import FleetTier, replay_fleet as _replay_fleet
        if not isinstance(tier, FleetTier):
            raise TypeError(
                f"expected a FleetTier, got {type(tier).__name__}")
        use_pallas = (self.use_pallas if use_pallas is None
                      else normalize_pallas_mode(use_pallas))
        return _replay_fleet(tier, requests, sizes=sizes, costs=costs,
                             observe=observe, mesh=mesh,
                             axis=axis or self.axis, rebalance=rebalance,
                             use_pallas=use_pallas)

    def replay_stream(self, policy, requests, K: int, *, sizes=None,
                      costs=None, chunk: int | None = None,
                      observe: bool = False,
                      use_pallas=None) -> ReplayResult:
        """Metrics-only replay of an arbitrarily long trace in fixed-size
        chunks.

        ``requests`` stays on the host; each chunk is shipped to the
        device, scanned with the metrics-in-carry body, and the policy
        state + accumulator buffers are *donated* between chunks, so device
        memory is O(K + chunk) regardless of trace length.  Per-chunk
        totals are summed on the host in 64-bit, so multi-billion-request
        streams cannot wrap int32 even without x64.  At most two programs
        compile: the full-chunk shape and one remainder shape.

        ``requests`` is either dense — ``[T]`` / ``[B, T]`` keys or a
        :class:`Request`, with per-request ``sizes`` / ``costs`` as scalars
        or same-shape arrays, sliced into ``chunk``-request pieces
        (default 2^18) — or an **iterator of chunks** (each item a
        ``Request``, a key array, or a ``(keys, sizes, costs)`` record
        like ``repro.data.ingest.TraceChunk``, unwrapped with its
        sizes/costs — ``replay_stream(pol,
        ingest.iter_chunks(path), K)`` just works), in which case the
        caller owns the chunking, nothing longer than one chunk is ever
        resident, and ``sizes``/``costs``/``chunk`` must be left unset
        (enforced — this method does not re-chunk an iterator) — the
        out-of-core path for file-backed traces.

        ``observe=True`` accumulates each policy observable's time total
        in 64 bits on the host and returns its **time mean** per lane in
        ``result.obs`` (e.g. DAC's average active size ``obs["k"]``) —
        the streaming equivalent of averaging :meth:`replay`'s stacked
        per-step observables, without ever materializing a ``[T]`` stack.
        For integer observables the two are bit-identical.

        Each chunk's phases are ``engine.*`` spans, and its compiles and
        bytes put are counted (:mod:`repro.core.tracing`): under
        ``jax.profiler.trace`` the spans sit beside the device ops, and
        ``tracing.last_stream()`` gives the call's per-chunk record and
        the rank-row lowering its chunk program traced (``rank_row``).

        Returns a :class:`ReplayResult` with ``info=None`` and host-side
        metrics.  Unlike :meth:`replay`, streaming does not consult the
        engine's ``mesh`` — chunks run unsharded on the default device;
        for mesh-sharded batch replay use ``replay(..., mesh=...)``.
        """
        policy, use_pallas = self._resolve(policy, use_pallas)
        K = int(K)   # strict-type static-arg key; see replay()

        if hasattr(requests, "__next__"):      # iterator of chunks
            if sizes is not None or costs is not None:
                raise ValueError(
                    "iterator input: sizes/costs travel inside each chunk")
            if chunk is not None:
                raise ValueError(
                    "iterator input owns its chunking — chunk= is not "
                    "applied to an iterator; size the chunks at the source")

            def columns(item):
                # unwrap (keys, sizes, costs) chunk records —
                # repro.data.ingest.TraceChunk or a plain 3-tuple of
                # array-or-None columns — instead of letting them stack
                # into a bogus [3, T] key batch (lane batches are
                # arrays, never tuples)
                if isinstance(item, (tuple, list)) and len(item) == 3 \
                        and not isinstance(item, Request) \
                        and np.ndim(item[0]) > 0 \
                        and all(x is None or np.ndim(x) > 0
                                for x in item[1:]):
                    keys, sizes, costs = item
                    return np.asarray(keys), sizes, costs
                return item, None, None

            items = requests
            lead = None                        # lane shape learned on entry
        else:                                  # dense host array
            chunk = (1 << 18) if chunk is None else chunk
            if chunk <= 0:
                raise ValueError(f"chunk must be positive, got {chunk}")
            if isinstance(requests, Request):
                if sizes is not None or costs is not None:
                    raise ValueError("pass sizes/costs inside the Request")
                keys = np.asarray(requests.key)
                sizes = np.asarray(requests.size)
                costs = np.asarray(requests.cost)
            else:
                keys = np.asarray(requests)
            if keys.ndim not in (1, 2):
                raise ValueError(
                    f"requests must be [T] or [B, T], got shape {keys.shape}")
            lead = keys.shape[:-1]

            def sl(x, lo, hi):
                if x is None or np.ndim(x) == 0:
                    return x
                return np.asarray(x)[..., lo:hi]

            def dense_chunks():
                for lo in range(0, keys.shape[-1], chunk):
                    hi = min(lo + chunk, keys.shape[-1])
                    yield keys[..., lo:hi], sl(sizes, lo, hi), \
                        sl(costs, lo, hi)

            def columns(item):
                return item

            items = dense_chunks()

        def init_state(lead):
            state = policy.init(K)
            if lead:
                state = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x, lead + x.shape).copy(),
                    state)
            return state

        state = totals = None
        obs_sums, T_total = None, 0
        end = object()
        with tracing.stream() as rec, warnings.catch_warnings():
            # buffer donation is a no-op on some backends (CPU) — harmless
            warnings.filterwarnings(
                "ignore", message=".*[Dd]onat.*", category=UserWarning)
            for i in itertools.count():
                with tracing.span("engine.source", i):
                    item = next(items, end)
                if item is end:
                    break
                with tracing.span("engine.put", i):
                    cols = columns(item)
                    reqs = Request.of(*cols)
                    rec.add_h2d(_put_bytes(*cols))
                if reqs.key.ndim not in (1, 2):
                    raise ValueError(
                        f"chunks must be [T] or [B, T], got shape "
                        f"{reqs.key.shape}")
                if state is None:              # first chunk
                    lead = tuple(reqs.key.shape[:-1])
                    with tracing.span("engine.init", i):
                        state = init_state(lead)
                    totals = np.zeros((6,) + lead, np.float64)
                elif tuple(reqs.key.shape[:-1]) != tuple(lead):
                    raise ValueError(
                        f"chunk lane shape changed mid-stream: "
                        f"{tuple(reqs.key.shape[:-1])} != {tuple(lead)}")
                with tracing.span("engine.dispatch", i):
                    state, m, obs = _replay_chunk(policy, state, reqs,
                                                  use_pallas, observe)
                if i == 0:
                    # a jit-cache hit traces nothing: carry the lowering
                    # noted when this chunk program was traced
                    prog = (policy, use_pallas, observe, K, lead)
                    if rec.rank_row is None:
                        rec.rank_row = _RANK_ROW.get(prog)
                    _RANK_ROW[prog] = rec.rank_row
                with tracing.span("engine.wait", i):
                    jax.block_until_ready((m, obs))
                with tracing.span("engine.fetch", i):
                    m = [np.asarray(f) for f in m]
                    if obs is not None:
                        obs = {k: np.asarray(v) for k, v in obs.items()}
                with tracing.span("engine.sum", i):
                    totals += np.stack([f.astype(np.float64) for f in m])
                    T_total += reqs.key.shape[-1]
                    if obs is not None:
                        part = {k: v.astype(np.float64).sum(axis=-1)
                                for k, v in obs.items()}
                        obs_sums = part if obs_sums is None else {
                            k: obs_sums[k] + part[k] for k in part}
                rec.end_chunk(i)
        if totals is None:                     # no chunk
            totals = np.zeros((6,) + tuple(lead or ()), np.float64)
        metrics = Metrics(
            requests=totals[0].astype(np.int64),
            hits=totals[1].astype(np.int64),
            bytes_total=totals[2], bytes_missed=totals[3],
            cost_total=totals[4], penalty=totals[5],
        )
        obs_out = None
        if obs_sums is not None and T_total:
            obs_out = {k: v / T_total for k, v in obs_sums.items()}
        return ReplayResult(info=None, metrics=metrics, obs=obs_out)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def miss_ratio(hits) -> float:
    """Miss ratio of a boolean hit mask (host-side convenience).

    >>> miss_ratio([True, False, False, False])
    0.75
    """
    return float(1.0 - np.asarray(hits, dtype=np.float64).mean())


def mrr(mr_algo: float, mr_fifo: float) -> float:
    """Miss-ratio reduction relative to FIFO (paper's signed definition).
    Both-zero is explicitly no-reduction (0.0) rather than falling through
    either signed branch.

    >>> mrr(0.2, 0.4)       # halved the misses
    0.5
    >>> mrr(0.4, 0.2)       # doubled them
    -0.5
    >>> mrr(0.0, 0.0)
    0.0
    """
    if mr_algo == 0.0 and mr_fifo == 0.0:
        return 0.0
    if mr_algo <= mr_fifo:
        return (mr_fifo - mr_algo) / mr_fifo if mr_fifo > 0 else 0.0
    return (mr_fifo - mr_algo) / mr_algo if mr_algo > 0 else 0.0
