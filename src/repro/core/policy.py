"""Policy interface + shared vectorized primitives.

The paper formulates its caches as ordered lists (rank 1 = top).  The
TPU-native representation used throughout this repo is a dense ``int32[W]``
array of keys ordered by rank (index 0 = top of the cache); ``EMPTY`` (-1)
marks unused slots.  The paper's "shift elements between a and b down one
position" becomes a masked select against a rolled copy of the array — an
O(W) *vector* operation that lowers to a handful of VPU selects instead of a
data-dependent pointer splice.

Rank rows are **lane-padded**: the array width ``W = lane_pad(K)`` is the
logical capacity ``K`` rounded up to a multiple of :data:`LANE` (128, the
TPU vector-lane count), with the padding filled with ``EMPTY``.  The
logical length rides alongside as a control *scalar* (``len`` for
fixed-size policies, ``k``/``kmax`` for DynamicAdaptiveClimb), never as an
array shape — so the same state pytree batches under ``vmap``, resizes
under Alg. 2, and tiles cleanly through the compiled Pallas kernel.  The
padding invariants every rank policy maintains:

  * ranks ``>= k`` (the active length) are ``EMPTY`` after every step —
    in particular the padding ``[K, W)`` never holds a key;
  * ``find``/``promote``/``demote``/``rank_step`` are equivalent on the
    padded row and the tight row: the roll wrap value is never selected
    (``t <= src`` keeps rank 0 out of the shifted range) and a wipe only
    ever clears already-``EMPTY`` padding ranks.

Every policy is a pure-functional object::

    state = policy.init(K)                      # pytree of fixed-shape arrays
    state, info = policy.step(state, request)   # Request -> StepInfo

``Request`` carries ``(key, size, cost)`` so size-aware (byte miss ratio)
and cost-aware (miss penalty) objectives flow through the engine natively;
``size``/``cost`` default to 1/1.0, so plain key traces reproduce the
classic unit-object model bit-for-bit.  ``StepInfo`` reports, per request,
the hit bit, the key that left residency this step (``EMPTY`` if none), and
the size/cost charged on a miss.

``step`` is traceable (scan/vmap/jit safe).  Policy instances are hashable
(static) so ``jax.jit(..., static_argnames='policy')`` works.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import tracing

EMPTY = jnp.int32(-1)  # repolint: waive[empty-sentinel] -- the definition

# TPU vector-lane count: rank rows are padded to a multiple of LANE so the
# fused policy-step kernel can tile them through VMEM with Mosaic-legal
# (…, 128k) blocks.  The jnp lowering runs on the same padded rows — state
# shapes are identical across lowerings, so parity tests compare pytrees
# directly and switching `use_pallas` never retraces a different program.
LANE = 128


def lane_pad(n: int) -> int:
    """Padded rank-row width for logical capacity ``n``: the smallest
    multiple of :data:`LANE` that holds ``n`` (at least one full lane).

    >>> lane_pad(1), lane_pad(128), lane_pad(129), lane_pad(1000)
    (128, 128, 256, 1024)
    """
    if n < 0:
        raise ValueError(f"capacity must be non-negative, got {n}")
    return max(LANE, -(-int(n) // LANE) * LANE)


def padded_row(n: int) -> jax.Array:
    """A fresh all-``EMPTY`` rank row of padded width ``lane_pad(n)``.

    >>> row = padded_row(5)
    >>> row.shape, int(row[0])
    ((128,), -1)
    """
    return jnp.full((lane_pad(n),), EMPTY, dtype=jnp.int32)


class Request(NamedTuple):
    """One cache request: object key + size (bytes/pages/slots) + miss cost
    (latency, backend load, ...).  A pytree, so a ``Request`` of ``[T]`` (or
    ``[B, T]``) arrays scans/vmaps exactly like a bare key trace."""

    key: jax.Array    # int32
    size: jax.Array   # int32
    cost: jax.Array   # float32

    @classmethod
    def of(cls, keys, sizes=None, costs=None) -> "Request":
        """Build a ``Request`` from keys, broadcasting ``sizes``/``costs``
        (scalars or per-key arrays; default 1 / 1.0).

        >>> r = Request.of([3, 1, 3], sizes=4096)
        >>> r.key.shape, int(r.size[0]), float(r.cost[0])
        ((3,), 4096, 1.0)
        """
        if isinstance(keys, Request):
            if sizes is not None or costs is not None:
                raise ValueError("pass sizes/costs inside the Request")
            return keys
        key = jnp.asarray(keys, jnp.int32)
        # sizes are int32 on device; reject concrete values that would
        # silently wrap (an object >= 2 GiB corrupts every byte-miss
        # metric).  Tracers can't be inspected — they stay caller-checked.
        if sizes is not None and not isinstance(sizes, jax.core.Tracer):
            smax = np.max(np.asarray(sizes)) if np.size(sizes) else 0
            if smax > np.iinfo(np.int32).max:
                raise ValueError(
                    f"sizes exceed int32 range (max {smax}); rescale to "
                    "coarser units (KiB/pages) before building Requests")
        size = jnp.broadcast_to(
            jnp.asarray(1 if sizes is None else sizes, jnp.int32), key.shape)
        cost = jnp.broadcast_to(
            jnp.asarray(1.0 if costs is None else costs, jnp.float32),
            key.shape)
        return cls(key=key, size=size, cost=cost)


class StepInfo(NamedTuple):
    """Per-request policy output (a pytree; scan stacks it along time)."""

    hit: jax.Array           # bool
    evicted_key: jax.Array   # int32; EMPTY when nothing left residency
    bytes_missed: jax.Array  # int32; == request size on miss, else 0
    penalty: jax.Array       # float32; == request cost on miss, else 0


def step_info(hit, req: Request, evicted_key=EMPTY) -> StepInfo:
    """Assemble a ``StepInfo``: evictions only happen on misses, and a miss
    charges the request's full size and cost.

    >>> info = step_info(False, Request.of(jnp.int32(7), sizes=100))
    >>> int(info.bytes_missed), float(info.penalty)
    (100, 1.0)
    >>> int(step_info(True, Request.of(jnp.int32(7), sizes=100)).bytes_missed)
    0
    """
    hit = jnp.asarray(hit, jnp.bool_)
    return StepInfo(
        hit=hit,
        evicted_key=jnp.where(hit, EMPTY,
                              jnp.asarray(evicted_key, jnp.int32)),
        bytes_missed=jnp.where(hit, jnp.int32(0), req.size),
        penalty=jnp.where(hit, jnp.float32(0.0), req.cost),
    )


class Policy:
    """Base class for all replacement policies; subclasses implement
    ``init(K) -> state`` and ``step(state, req) -> (state, StepInfo)``.
    Instances are static: hashable and comparable by constructor fields,
    so they work as ``jax.jit`` static arguments.

    >>> from repro.core import make_policy
    >>> make_policy("lru") == make_policy("lru")
    True
    >>> make_policy("dac(eps=0.25)") == make_policy("dac")
    False
    """

    name: str = "base"

    def init(self, K: int) -> dict:
        raise NotImplementedError

    def step(self, state: dict, req: Request):
        raise NotImplementedError

    # hashability for jit static args -----------------------------------
    def _fields(self):
        return tuple(sorted(self.__dict__.items()))

    def __hash__(self):
        return hash((type(self).__name__, self._fields()))

    def __eq__(self, other):
        return type(self) is type(other) and self._fields() == other._fields()

    def __repr__(self):
        args = ", ".join(f"{k}={v}" for k, v in self.__dict__.items())
        return f"{type(self).__name__}({args})"


# ---------------------------------------------------------------------------
# shared vectorized primitives
# ---------------------------------------------------------------------------

def find(cache: jax.Array, key: jax.Array):
    """Return (found, rank) of `key` in the rank-ordered `cache` array.

    >>> hit, i = find(jnp.array([5, 3, 9], jnp.int32), jnp.int32(3))
    >>> bool(hit), int(i)
    (True, 1)
    """
    eq = cache == key
    return jnp.any(eq), jnp.argmax(eq).astype(jnp.int32)


def promote(cache: jax.Array, i: jax.Array, t: jax.Array, key: jax.Array):
    """Move `key` (currently at rank ``i``) to rank ``t`` (t <= i), shifting
    ranks [t, i-1] down one.  Also implements miss-insertion when ``i`` is the
    eviction rank (the old occupant of rank ``i`` simply disappears).

    >>> promote(jnp.array([5, 3, 9], jnp.int32), 2, 0, 9).tolist()
    [9, 5, 3]
    """
    r = jnp.arange(cache.shape[0], dtype=jnp.int32)
    rolled = jnp.roll(cache, 1)  # rolled[r] = cache[r-1]
    return jnp.where(r == t, key, jnp.where((r > t) & (r <= i), rolled, cache))


def demote(cache: jax.Array, i: jax.Array, t: jax.Array, key: jax.Array):
    """Move `key` from rank ``i`` down to rank ``t`` (t >= i); [i+1, t] shift up.

    >>> demote(jnp.array([5, 3, 9], jnp.int32), 0, 2, 5).tolist()
    [3, 9, 5]
    """
    r = jnp.arange(cache.shape[0], dtype=jnp.int32)
    rolled = jnp.roll(cache, -1)  # rolled[r] = cache[r+1]
    return jnp.where(r == t, key, jnp.where((r >= i) & (r < t), rolled, cache))


# ---------------------------------------------------------------------------
# fused rank step: find + plan + promote in one pass
# ---------------------------------------------------------------------------

_PALLAS_STEP = contextvars.ContextVar("repro_use_pallas_step", default=False)

# the use_pallas knob threaded through every replay entrypoint:
#   "auto"      — the engine's default: the compiled dense kernel on a TPU
#                 for rank rows whose 8-lane block fits its VMEM budget
#                 (repro.kernels.policy_step.dense_lane_block), the jnp
#                 lowering everywhere else (CPU, GPU, wider rows)
#   False       — pure-jnp lowering (find + promote as separate jnp ops)
#   "interpret" — fused Pallas kernel under the Pallas interpreter (any
#                 backend; the CPU CI path)
#   "compiled"  — fused Pallas kernel compiled for real (Mosaic on TPU,
#                 Triton on GPU); fails on CPU, which cannot execute
#                 compiled Pallas
#   True        — the kernel with per-backend interpret resolution
#                 (see repro.kernels.policy_step.resolve_interpret)
PALLAS_MODES = (False, True, "interpret", "compiled", "auto")


def normalize_pallas_mode(mode):
    """Coerce a ``use_pallas`` value to one of :data:`PALLAS_MODES`.

    >>> normalize_pallas_mode(1), normalize_pallas_mode("interpret")
    (True, 'interpret')
    >>> normalize_pallas_mode("fast")
    Traceback (most recent call last):
        ...
    ValueError: use_pallas must be one of (False, True, 'interpret', \
'compiled', 'auto'), got 'fast'
    """
    if isinstance(mode, str):
        if mode not in ("interpret", "compiled", "auto"):
            raise ValueError(
                f"use_pallas must be one of {PALLAS_MODES}, got {mode!r}")
        return mode
    return bool(mode)


@contextlib.contextmanager
def pallas_mode(mode):
    """Trace-time switch: inside this context, :func:`rank_step` lowers to
    the fused Pallas kernel (``repro.kernels.policy_step``) instead of the
    pure-jnp ``find``/``promote`` pair.  ``mode`` is any of
    :data:`PALLAS_MODES` — ``False`` (jnp), ``"interpret"``, ``"compiled"``,
    ``True`` (kernel with per-backend interpret resolution), or ``"auto"``
    (:func:`auto_lowering`, decided per row width where the row is traced).

    Engine-internal: the Engine sets it around tracing and threads the mode
    through its jit static args so all lowerings coexist in the cache.
    Wrapping an already-jitted function in this context does NOT retrace it
    — use ``Engine(use_pallas=...)`` / ``replay(..., use_pallas=...)``,
    which is the supported switch."""
    tok = _PALLAS_STEP.set(normalize_pallas_mode(mode))
    try:
        yield
    finally:
        _PALLAS_STEP.reset(tok)


def auto_lowering(W: int):
    """What ``use_pallas="auto"`` (the engine's default) runs for a rank
    row of ``W`` ranks: ``"compiled"`` — the dense kernel — on a TPU
    backend when eight padded rows fit the kernel's VMEM budget, else
    ``False`` (jnp), the lowering on CPU and GPU backends and for rows too
    wide for the budget.

    >>> jax.default_backend() == "tpu" or auto_lowering(4224) is False
    True
    """
    if jax.default_backend() != "tpu":
        return False
    from ..kernels.policy_step import dense_lane_block
    return "compiled" if dense_lane_block(8, lane_pad(W)) else False


def rank_step(cache: jax.Array, key: jax.Array, scalars: tuple, plan):
    """One fused step of a rank-array policy.

    ``plan(hit, i, scalars) -> (src, t, wipe_from, new_scalars)`` is the
    policy's O(1) control law: given the find result it picks the shift
    source rank ``src`` (the eviction rank on a miss), the insertion rank
    ``t`` (``t <= src``), a deactivation boundary ``wipe_from`` (ranks >=
    ``wipe_from`` are cleared to ``EMPTY``; pass ``K`` for none), and the
    updated control scalars (int32 each).

    Returns ``(new_cache, new_scalars, hit, evicted)``; ``evicted`` is the
    pre-update occupant of rank ``src`` — callers mask it with
    :func:`step_info` (hits never evict).

    This is the single entrypoint behind which ``find`` + ``promote`` fuse:
    under :func:`pallas_mode` the whole step — compare, iota-min reduce,
    scalar plan, rolled masked-select shift, wipe — is one Pallas kernel
    (``repro.kernels.policy_step``): a dense pass over blocks of whole
    rows, updated in place, where a block of rows fits VMEM, else a
    tiled pass that streams the row in :data:`LANE`-multiple tiles.
    ``"interpret"`` runs the same kernel body under the Pallas interpreter
    (the CPU fallback); ``"compiled"`` lowers it for real (Mosaic/Triton);
    ``"auto"`` picks per backend and row width (:func:`auto_lowering`).
    Rows of non-padded width are padded with ``EMPTY`` for the kernel and
    sliced back — bit-identical to the jnp lowering either way.  The
    lowering traced is noted in the open stream record
    (:func:`repro.core.tracing.note_rank_row`).

    A CLIMB-shaped plan (miss replaces the bottom rank in place):

    >>> def plan(hit, i, scalars):
    ...     src = jnp.where(hit, i, jnp.int32(2))
    ...     t = jnp.where(hit, jnp.maximum(i - 1, 0), jnp.int32(2))
    ...     return src, t, jnp.int32(3), ()
    >>> cache = jnp.array([5, 3, 9], jnp.int32)
    >>> new, _, hit, ev = rank_step(cache, jnp.int32(7), (), plan)
    >>> new.tolist(), bool(hit), int(ev)
    ([5, 3, 7], False, 9)
    """
    mode = _PALLAS_STEP.get()
    if mode == "auto":
        mode = auto_lowering(cache.shape[0])
    if mode:
        from ..kernels.policy_step import fused_policy_step
        interpret = {True: None, "interpret": True, "compiled": False}[mode]
        return fused_policy_step(cache, key, scalars, plan,
                                 interpret=interpret)
    tracing.note_rank_row("jnp", 0)
    hit, i = find(cache, key)
    src, t, wipe_from, new_scalars = plan(hit, i, scalars)
    evicted = cache[src]
    new_cache = promote(cache, src, t, key)
    r = jnp.arange(cache.shape[0], dtype=jnp.int32)
    new_cache = jnp.where(r >= wipe_from, EMPTY, new_cache)
    return new_cache, new_scalars, hit, evicted
