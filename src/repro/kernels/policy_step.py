"""Pallas TPU kernels: fused rank-policy step (find + plan + promote).

Every rank-based policy in this repo (CLIMB, AdaptiveClimb,
DynamicAdaptiveClimb) has the same per-request shape:

  1. ``find``     — locate the requested key in the rank row (``[W]`` int32,
                    index 0 = top of the cache, ``W`` a 128-lane multiple);
  2. ``plan``     — O(1) scalar control arithmetic (jump updates, resize
                    checks) deciding the shift source/target ranks;
  3. ``promote``  — masked-select shift of ranks ``(t, src]`` against a
                    lane-rolled copy, inserting the key at rank ``t``;
  4. ``wipe``     — ranks ``>= wipe_from`` cleared to EMPTY (the
                    DynamicAdaptiveClimb shrink).

Two kernels do it.  Which one runs is decided per call from ``W``, the
lane count ``B`` and ``tile`` (:func:`_batched_call`):

**Dense** (rows of up to ``tile`` ranks whose block of 8 lanes fits
:data:`DENSE_VMEM_BYTES`): grid ``(B_pad / bl,)`` over blocks of ``bl``
whole rows (:func:`dense_lane_block`; a multiple of 8, so every vreg's
sublanes are full, or all ``B < 8`` lanes).  Each block is read from HBM
once and written back in place — the cache input aliases the cache
output, so inside a ``lax.scan`` the carried ``[B, W]`` state is updated
where it lies, with no copy.  Per slab of 8 lanes and column of 128
ranks: a find pass, ``plan`` once on ``(8, 1)`` columns, then the shift
(``pltpu.roll`` plus the previous column's last lane), insert and wipe.
Key and control scalars travel lane-major, as the state's ``[B]`` arrays
lie, in ``(1 + n, L)`` VMEM blocks of 128 lanes (or all of them), and
the results as ``(n + 2, L)``: the kernel transposes them to one row per
lane and back, so XLA moves no scalar between layouts, and SMEM holds
nothing per lane.

**Tiled** (wider rows, or an explicit ``tile`` below ``W``):
a ``(lanes, 2, n_tiles)`` grid: the rank row streams
HBM→VMEM in ``tile``-lane blocks (BlockSpec-pipelined), so W no longer has
to fit one VMEM row.  Cross-tile state rides in an SMEM scratch:

    phase 0 (find)     per tile: compare + iota-min; the running global
                       argmin accumulates in SMEM (min-reduce across tiles).
    phase 1, tile 0:   the plan callback runs ONCE on the find result
                       (traced into the kernel; SMEM scalars in/out), and
                       its (src, t, wipe_from) decisions park in SMEM.
    phase 1, per tile: segmented promote — each tile shifts its lanes
                       right by one against a boundary carry (the previous
                       tile's last element, saved in SMEM at the end of
                       the prior iteration), masked to ``(t, src]``; the
                       requested key lands at rank ``t``; ranks >=
                       ``wipe_from`` wipe to EMPTY; the evicted occupant
                       of rank ``src`` is extracted by the tile owning it.

Tile/carry diagram (W = 3 tiles, promote range (t, src])::

      tile 0              tile 1              tile 2
    [ a b c d ]         [ e f g h ]         [ i j k l ]
          t ^..................... src ^
    carry: -1 ->  d  (last of tile 0) ->  h  (last of tile 1)
    shift:  [ a b key c ]  [ d e f g ]   [ h i j l ]     (h crosses tiles)

Mosaic details: integer vector reductions are unsupported on TPU, so the
iota-min runs in float32 (rank indices < 2^24 are exact) and the
evicted/carry element extraction splits int32 into 16-bit halves, sums each
in float32 (exactly one lane selected, so the sum is exact), and reassembles
with shifts — bit-exact for every int32 including EMPTY.

Contract (see :func:`repro.core.policy.rank_step` for the jnp oracle)::

    plan(hit, i, scalars) -> (src, t, wipe_from, new_scalars)

      hit        scalar bool  — key resident?
      i          scalar int32 — rank of the key (0 when miss, like argmax)
      scalars    tuple of int32 scalars (policy control state)
      src        shift source rank (eviction rank on a miss; t <= src)
      t          insertion rank for the requested key
      wipe_from  ranks >= wipe_from are cleared to EMPTY (>= K for none)

Returns ``(new_cache, new_scalars, hit, evicted)`` where ``evicted`` is the
pre-update occupant of rank ``src`` (the key shifted off the row on a miss).

Batching: a vmapped ``fused_policy_step`` does NOT fall back to the default
pallas batching of the single-lane call — a ``jax.custom_batching.custom_vmap``
rule swaps in the natively batched kernel, whose grid leads with the lane
axis (dense: ``(B_pad / bl,)``; tiled: ``(B, 2, n_tiles)``, with scalar
I/O through SMEM in per-lane ``(1, 1, n)`` blocks indexed by
``program_id(0)``), so SMEM use does not grow with the lane count (whole
``[B, n]`` SMEM arrays pad every row to 512 bytes and overflow SMEM from
about 1,024 lanes).  A second
(outer) vmap — the tier's seeds × tenants nesting — then hits the
standard pallas batching rule on the batched kernel, which prepends one
more grid dimension; both layers are Mosaic-lowerable and bit-identical
to the vmapped jnp oracle.

``interpret``: ``True`` runs the kernel body under the Pallas interpreter
(any backend — the CPU CI path); ``False`` compiles for real (Mosaic on
TPU, Triton on GPU); ``None`` resolves per backend via
:func:`resolve_interpret` (memoized, overridable with the
``REPRO_PALLAS_INTERPRET`` env knob, which may not force the interpreter
on a TPU).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import tracing
from ..core.policy import EMPTY, LANE, lane_pad

__all__ = ["fused_policy_step", "resolve_interpret", "dense_lane_block",
           "DEFAULT_TILE", "DENSE_VMEM_BYTES",
           "INTERPRET_ENV"]

# default VMEM tile, in lanes: 64 KiB of int32 per block — small enough to
# double-buffer input + output blocks comfortably, large enough that rows
# up to this width run as a single tile.  The effective tile is
# gcd(W, tile), so it always divides the padded width exactly.
DEFAULT_TILE = 16384

# VMEM the dense kernel's cache blocks may take: the input and output
# blocks, each double-buffered, so ``4 * bl * W * 4`` bytes.  Half of the
# 16 MiB default scoped VMEM of a TPU v5e, leaving the rest to the
# pipeline's small blocks and the kernel's per-slab temporaries.
DENSE_VMEM_BYTES = 8 << 20

# forced override for CI: "interpret" (or "1"/"true") forces the
# interpreter, "compiled" (or "0"/"false") forces real lowering —
# regardless of what the call site passed.  Empty/"auto" defers to the
# call site, then to the per-backend default.  Forcing the interpreter on
# a TPU is an error: it would silently replace the kernel being measured.
INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"


@functools.lru_cache(maxsize=None)
def _backend_default(backend: str) -> bool:
    # CPU has no compiled Pallas lowering — interpret.  TPU compiles via
    # Mosaic and GPU via Triton: both run the kernel for real.  (The old
    # `backend != "tpu"` test wrongly interpreted on GPU, silently
    # discarding the Triton lowering.)
    return backend not in ("tpu", "gpu")


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Resolve an ``interpret`` argument to a concrete bool.

    Priority: the :data:`INTERPRET_ENV` env knob (a *forced* override, so
    CI can pin one lowering across every call site) > an explicit
    ``True``/``False`` argument > the memoized per-backend default
    (cpu → interpret; tpu/gpu → compiled).  On a TPU backend an env
    override that forces the interpreter raises instead of falling back.

    >>> resolve_interpret(True), resolve_interpret(False)
    (True, False)
    """
    backend = jax.default_backend()
    env = os.environ.get(INTERPRET_ENV, "").strip().lower()
    if env in ("1", "true", "interpret"):
        if backend == "tpu":
            raise RuntimeError(
                f"{INTERPRET_ENV}={env!r} forces the Pallas interpreter on a "
                "TPU backend; unset it (or set 'compiled'/'auto') to run "
                "the compiled kernel")
        return True
    if env in ("0", "false", "compiled"):
        return False
    if env not in ("", "auto"):
        raise ValueError(
            f"{INTERPRET_ENV} must be interpret/compiled/auto (or a bool "
            f"spelling), got {env!r}")
    if interpret is None:
        return _backend_default(backend)
    return bool(interpret)


def _resolve_tile(W: int, tile: int | None) -> int:
    if tile is None:
        tile = DEFAULT_TILE
    tile = int(tile)
    if tile < LANE or tile % LANE:
        raise ValueError(
            f"tile must be a positive multiple of {LANE}, got {tile}")
    return math.gcd(W, tile)


# SMEM scratch slot indices (cross-tile carries)
_S_ARGMIN = 0   # running find argmin (W = "not found")
_S_CARRY = 1    # boundary element carried into the next tile's shift
_S_SRC = 2      # plan outputs, parked at (phase 1, tile 0)
_S_T = 3
_S_WIPE = 4
_N_SCRATCH = 8


def _split16_sum(x, axis=None):
    """Sum int32 ``x`` (along ``axis``, kept as a dimension of 1) where at
    most one element of each sum is nonzero, as float32 sums of its 16-bit
    halves (Mosaic has no integer vector reductions): exact, since each
    half sum is one value below 2^16."""
    def total(half):
        return jnp.sum(half.astype(jnp.float32), axis=axis,
                       keepdims=axis is not None)
    lo = total(x & 0xFFFF)
    hi = total((x >> 16) & 0xFFFF)
    return (hi.astype(jnp.int32) << 16) | lo.astype(jnp.int32)


def _split16_pick(row, mask):
    """The single int32 element of ``row`` selected by ``mask``, exact for
    every int32 including EMPTY (:func:`_split16_sum`)."""
    return _split16_sum(jnp.where(mask, row, 0))


def _tiled_kernel(sc_ref, cache_ref, out_ref, scal_out_ref, s_ref, *,
                  plan, n_sc: int, W: int, tile: int):
    """Grid (B, 2, n_tiles): lane b, phase (0 find / 1 plan+promote), tile j.

    ``sc_ref``/``scal_out_ref`` are lane ``b``'s ``(1, 1, 1+n)`` /
    ``(1, 1, n+2)`` SMEM blocks (key + control scalars in; new scalars +
    hit + evicted out).  ``cache_ref``/``out_ref`` see one ``(1, 1, tile)``
    VMEM block of the ``[B, 1, W]`` row per grid step.
    ``s_ref`` is the SMEM cross-tile scratch (per lane: grid iterations run
    lane-major, so one lane's phases/tiles complete before the next lane's
    begin and the scratch never interleaves)."""
    ph, j = pl.program_id(1), pl.program_id(2)
    base = j * tile
    r = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) + base
    row = cache_ref[0]                       # (1, tile) int32
    key = sc_ref[0, 0, 0]

    @pl.when((ph == 0) & (j == 0))
    def _init_find():
        s_ref[_S_ARGMIN] = jnp.int32(W)

    @pl.when(ph == 0)
    def _find():
        eq = row == key
        # min-reduce in float32: ranks < 2^24 are exact (W <= 2^24 by
        # construction of any realistic row)
        local = jnp.min(
            jnp.where(eq, r, W).astype(jnp.float32)).astype(jnp.int32)
        s_ref[_S_ARGMIN] = jnp.minimum(s_ref[_S_ARGMIN], local)
        # keep every output block defined even if phase 1 aborts a write
        out_ref[0] = row

    @pl.when((ph == 1) & (j == 0))
    def _plan():
        m = s_ref[_S_ARGMIN]
        hit = m < W
        i = jnp.where(hit, m, 0)             # match find()'s argmax-on-miss
        scalars = tuple(sc_ref[0, 0, 1 + q] for q in range(n_sc))
        src, t, wipe_from, new_sc = plan(hit, i, scalars)
        # EMPTY (-1) is created inline: a closure-captured device constant
        # would be rejected by the kernel tracer
        # repolint: waive[empty-sentinel] -- see above
        s_ref[_S_CARRY] = jnp.int32(-1)      # roll wrap value (never used:
        s_ref[_S_SRC] = src                  # t <= src keeps rank 0 out of
        s_ref[_S_T] = t                      # the shifted range)
        s_ref[_S_WIPE] = wipe_from
        for q, v in enumerate(new_sc):
            scal_out_ref[0, 0, q] = v
        scal_out_ref[0, 0, n_sc] = hit.astype(jnp.int32)

    @pl.when(ph == 1)
    def _promote():
        src, t, wipe = s_ref[_S_SRC], s_ref[_S_T], s_ref[_S_WIPE]
        carry = s_ref[_S_CARRY]
        # evicted occupant of rank src: exactly one tile owns it
        @pl.when((src >= base) & (src < base + tile))
        def _evicted():
            scal_out_ref[0, 0, n_sc + 1] = _split16_pick(row, r == src)
        # segmented shift-right-by-one: boundary element comes from the
        # previous tile via the SMEM carry
        rolled = jnp.concatenate(
            [jnp.full((1, 1), carry, jnp.int32), row[:, :-1]], axis=1)
        new = jnp.where(r == t, key,
                        jnp.where((r > t) & (r <= src), rolled, row))
        # repolint: waive[empty-sentinel] -- inline EMPTY, kernel tracer
        new = jnp.where(r >= wipe, jnp.int32(-1), new)
        out_ref[0] = new
        # save this tile's last pre-shift element for the next tile
        s_ref[_S_CARRY] = _split16_pick(row, r == base + tile - 1)


def _tiled_call(cache, keys, scalars, *, plan, n_sc: int, interpret: bool,
                t: int):
    """The tiled kernel: ``cache [B, W]``, ``keys [B]``, each scalar
    ``[B]`` — one grid lane per batch element, ``t`` ranks a block."""
    B, W = cache.shape
    kernel = functools.partial(_tiled_kernel, plan=plan, n_sc=n_sc, W=W,
                               tile=t)
    sc = jnp.stack([keys] + list(scalars), axis=-1)[:, None]   # [B, 1, 1+n]

    def lane_smem(n):
        return pl.BlockSpec((1, 1, n), lambda b, ph, j: (b, 0, 0),
                            memory_space=pltpu.SMEM)

    out, scal = pl.pallas_call(
        kernel,
        grid=(B, 2, W // t),
        in_specs=[
            lane_smem(1 + n_sc),
            pl.BlockSpec((1, 1, t), lambda b, ph, j: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, t), lambda b, ph, j: (b, 0, j)),
            lane_smem(n_sc + 2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, W), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, n_sc + 2), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((_N_SCRATCH,), jnp.int32)],
        interpret=interpret,
        name="rank_step",
    )(sc, cache[:, None, :])
    scal = scal[:, 0]
    return (out[:, 0], tuple(scal[:, q] for q in range(n_sc)),
            scal[:, n_sc].astype(bool), scal[:, n_sc + 1])


def _padded_lanes(B: int) -> int:
    """Lanes the dense kernel runs for ``B``: all of them below 8, else
    ``B`` rounded up to 8 (to 128 above 1,024 lanes, so that a scalar
    block never needs more than 128 lanes, :func:`_scalar_lanes`)."""
    if B < 8:
        return B
    m = 8 if B <= 1024 else LANE
    return -(-B // m) * m


def dense_lane_block(B: int, W: int) -> int | None:
    """Lanes per grid step of the dense kernel for ``B`` rows of ``W``
    ranks, or ``None`` when eight rows overflow :data:`DENSE_VMEM_BYTES`.

    The block is all ``B`` lanes when ``B < 8`` (a block equal to the
    array's dimension is Mosaic-legal); otherwise the largest multiple of
    8 within the budget that divides the padded lane count
    (:func:`_padded_lanes`), so a count such as 1,024 needs no padding.
    Where that count is a multiple of 128, the block also divides 128 or
    is a multiple of it, for the scalar blocks (:func:`_scalar_lanes`).

    >>> dense_lane_block(1, 4224), dense_lane_block(1024, 4224)
    (1, 64)
    >>> dense_lane_block(24, 128), dense_lane_block(1, 1 << 20) is None
    (24, True)
    """
    cap = DENSE_VMEM_BYTES // (4 * 4 * W) // 8 * 8
    if cap < 8:
        return None
    Bp = _padded_lanes(B)
    if Bp < 8:
        return Bp
    return max(bl for bl in range(8, min(cap, Bp) + 1, 8)
               if Bp % bl == 0
               and (Bp % LANE or LANE % bl == 0 or bl % LANE == 0))


def _scalar_lanes(Bp: int, bl: int) -> int:
    """Lanes per block of the lane-major scalar arrays ``[n, Bp]``: 128
    (or ``bl``, a multiple of it) where ``Bp`` is a multiple of 128, else
    all ``Bp <= 1,024`` lanes in one block.  A scalar block serves the
    ``L / bl`` consecutive grid steps whose lanes it holds."""
    if bl % LANE == 0:
        return bl
    return LANE if Bp % LANE == 0 else Bp


def _dense_kernel(sc_ref, cache_ref, out_ref, res_ref, sc_t, res_t, *,
                  plan, n_sc: int, W: int, rows: int):
    """Grid ``(B_pad / bl,)``: one ``(bl, W)`` block of lanes, read once
    and written back in place, in slabs of ``rows`` lanes (8: one sublane
    tile) and columns of :data:`LANE` ranks, so every value is one vreg
    and nothing row-sized is spilled to VMEM.

    ``sc_ref`` is the ``(1 + n_sc, L)`` block of key and control scalars
    lane-major, as the state holds them, and ``res_ref`` the ``(n_sc + 2,
    L)`` block of new scalars, hit and evicted; one such block serves
    ``L / bl`` grid steps.  The first of them transposes it into ``sc_t``
    (one row per lane), the last transposes ``res_t`` back.  Per slab: a
    find pass over the columns, ``plan`` once on ``(rows, 1)`` columns
    (the control law is shape-polymorphic), then a pass that shifts,
    inserts, wipes and stores each column and picks the evicted key."""
    bl = cache_ref.shape[0]
    L = sc_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (rows, n_sc + 2), 1)
    off = 0 if L == bl else pl.program_id(0) * bl % L
    # EMPTY made inside the kernel: the tracer rejects a captured constant
    # repolint: waive[empty-sentinel] -- see above
    empty = jnp.int32(-1)

    def columns(body, init):
        # traced once, unrolled by the lowering: code as if written out
        # column by column, without tracing every column
        return jax.lax.fori_loop(0, W // LANE, body, init, unroll=True)

    @pl.when(off == 0)
    def _scalars_in():
        sc_t[...] = sc_ref[...].T

    def slab(lo):
        rs = pl.ds(lo, rows)
        sc = sc_t[pl.ds(off + lo, rows), :]              # (rows, 1 + n_sc)
        key = jnp.broadcast_to(sc[:, 0:1], (rows, LANE))
        # first rank holding the key: a lane-wise min over the columns,
        # then across lanes in float32 (ranks < 2^24 are exact)
        def find_col(j, first):
            c = pl.multiple_of(j * LANE, LANE)
            row = cache_ref[rs, pl.ds(c, LANE)]
            return jnp.minimum(first, jnp.where(row == key, lane + c, W))

        first = columns(find_col, jnp.full((rows, LANE), W, jnp.int32))
        m = jnp.min(first.astype(jnp.float32), axis=1,
                    keepdims=True).astype(jnp.int32)
        hit = m < W
        i = jnp.where(hit, m, 0)             # match find()'s argmax-on-miss
        src, t, wipe, new_sc = plan(
            hit, i, tuple(sc[:, 1 + q:2 + q] for q in range(n_sc)))
        src_b, t_b, wipe_b = (jnp.broadcast_to(v, (rows, LANE))
                              for v in (src, t, wipe))

        def shift_col(j, carry):
            prev, picked = carry
            c = pl.multiple_of(j * LANE, LANE)
            row = cache_ref[rs, pl.ds(c, LANE)]
            r = lane + c
            # rolled[:, r] = row[:, r - 1]: lane 0 takes the previous
            # column's last lane; rank 0's (zero) is never selected,
            # since t <= src keeps rank 0 out of (t, src]
            rot = pltpu.roll(row, 1, axis=1)
            rolled = jnp.where(lane == 0, prev, rot)
            picked = picked + jnp.where(r == src_b, row, 0)
            new = jnp.where(r == t_b, key,
                            jnp.where((r > t_b) & (r <= src_b), rolled, row))
            out_ref[rs, pl.ds(c, LANE)] = jnp.where(r >= wipe_b, empty, new)
            return rot, picked

        zero = jnp.zeros((rows, LANE), jnp.int32)
        _, picked = columns(shift_col, (zero, zero))
        evicted = _split16_sum(picked, axis=1)  # one lane holds rank src
        packed = jnp.zeros((rows, n_sc + 2), jnp.int32)
        for q, v in enumerate((*new_sc, hit.astype(jnp.int32), evicted)):
            packed = jnp.where(slot == q, v, packed)
        res_t[pl.ds(off + lo, rows), :] = packed

    n_slabs = bl // rows
    if n_slabs == 1:
        slab(0)
    else:
        def body(s, carry):
            slab(pl.multiple_of(s * rows, rows))
            return carry
        jax.lax.fori_loop(0, n_slabs, body, 0)

    @pl.when(off + bl == L)
    def _scalars_out():
        res_ref[...] = res_t[...].T


def _dense_call(cache, keys, scalars, *, plan, n_sc: int, interpret: bool,
                bl: int):
    """The dense kernel: ``cache [B, W]`` in ``(bl, W)`` blocks, updated in
    place (the cache input aliases the cache output).  Key and scalars go
    in and come out lane-major, ``[n, B]``, the layout of the ``[B]``
    arrays the state holds: the kernel transposes them, not XLA."""
    B, W = cache.shape
    sc = jnp.stack([keys, *scalars])                         # [1 + n, B]
    Bp = _padded_lanes(B)
    pad = Bp - B
    if pad:
        cache = jnp.pad(cache, ((0, pad), (0, 0)), constant_values=EMPTY)
        sc = jnp.pad(sc, ((0, 0), (0, pad)))
    L = _scalar_lanes(Bp, bl)
    kernel = functools.partial(_dense_kernel, plan=plan, n_sc=n_sc, W=W,
                               rows=8 if bl % 8 == 0 else bl)
    out, res = pl.pallas_call(
        kernel,
        grid=(Bp // bl,),
        in_specs=[pl.BlockSpec((1 + n_sc, L), lambda b: (0, b * bl // L)),
                  pl.BlockSpec((bl, W), lambda b: (b, 0))],
        out_specs=[pl.BlockSpec((bl, W), lambda b: (b, 0)),
                   pl.BlockSpec((n_sc + 2, L), lambda b: (0, b * bl // L))],
        out_shape=[jax.ShapeDtypeStruct((Bp, W), jnp.int32),
                   jax.ShapeDtypeStruct((n_sc + 2, Bp), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((L, 1 + n_sc), jnp.int32),
                        pltpu.VMEM((L, n_sc + 2), jnp.int32)],
        input_output_aliases={1: 0},
        # a scalar block is revisited by consecutive grid steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="rank_step",
    )(sc, cache)
    if pad:
        out, res = out[:B], res[:, :B]
    return (out, tuple(res[q] for q in range(n_sc)),
            res[n_sc].astype(bool), res[n_sc + 1])


def _batched_call(cache, keys, scalars, *, plan, n_sc: int, interpret: bool,
                  tile: int | None):
    """The natively batched kernel call: ``cache [B, W]``, ``keys [B]``,
    each scalar ``[B]``.  Rows within one tile whose lane block fits the
    VMEM budget take the dense kernel, the rest the tiled one."""
    B, W = cache.shape
    bl = dense_lane_block(B, W)
    if bl is not None and (tile is None or W <= tile):
        tracing.note_rank_row("dense", bl)
        return _dense_call(cache, keys, scalars, plan=plan, n_sc=n_sc,
                           interpret=interpret, bl=bl)
    tracing.note_rank_row("tiled", 1)
    return _tiled_call(cache, keys, scalars, plan=plan, n_sc=n_sc,
                       interpret=interpret, t=_resolve_tile(W, tile))


def fused_policy_step(cache, key, scalars, plan, *, interpret=None,
                      tile=None):
    """One fused rank-policy step.

    ``cache``: ``[K]`` int32 rank row (any K — padded internally to a
    :data:`~repro.core.policy.LANE` multiple and sliced back, so direct
    calls with tight rows stay bit-identical to the jnp oracle);
    ``key``: scalar int32; ``scalars``: tuple of int32 control scalars.
    Rows of at most ``tile`` ranks (default: any width) whose block of 8
    lanes fits :data:`DENSE_VMEM_BYTES` take the dense kernel; the rest
    take the tiled one, whose tile is ``gcd(padded_W, tile)`` (default
    :data:`DEFAULT_TILE`).

    Batches under ``vmap`` through a ``custom_vmap`` rule that swaps in
    the natively batched lane-grid kernel (nested vmaps compose via the
    standard pallas batching rule on top); scans under ``lax.scan``.
    """
    interpret = resolve_interpret(interpret)
    K = cache.shape[0]
    W = lane_pad(K)
    n_sc = len(scalars)
    call = functools.partial(_batched_call, plan=plan, n_sc=n_sc,
                             interpret=interpret, tile=tile)

    @custom_vmap
    def step(cache, key, sc):
        out, new_sc, hit, ev = call(cache[None], key[None],
                                    tuple(s[None] for s in sc))
        return out[0], tuple(s[0] for s in new_sc), hit[0], ev[0]

    @step.def_vmap
    def _step_vmap(axis_size, in_batched, cache, key, sc):
        cache_b, key_b, sc_b = in_batched

        def bc(x, batched):
            return x if batched else jnp.broadcast_to(
                x, (axis_size,) + jnp.shape(x))

        out = call(bc(cache, cache_b), bc(key, key_b),
                   tuple(bc(s, b) for s, b in zip(sc, sc_b)))
        return out, jax.tree_util.tree_map(lambda _: True, out)

    key = jnp.asarray(key, jnp.int32)
    scalars = tuple(jnp.asarray(s, jnp.int32) for s in scalars)
    padded = cache if W == K else jnp.concatenate(
        [cache, jnp.full((W - K,), EMPTY, jnp.int32)])
    new_cache, new_sc, hit, ev = step(padded, key, scalars)
    return (new_cache if W == K else new_cache[:K]), new_sc, hit, ev
