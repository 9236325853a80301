"""Pallas TPU kernels for the Saddle-SVC per-iteration hot loop.

Theorem 6's O(n)-per-iteration bound comes from two passes over the n
points.  The PACKED kernels (``momentum_dot_packed``/``mwu_update_packed``
-- the ones the single-sweep engine launches, 2 launches per step) run
each pass ONCE over both classes: the operand is the packed layout of
:func:`repro.core.preprocess.pack_points` -- one lane-padded point set
with a +-1 ``sign`` vector -- and the sampled coordinate block is
gathered INSIDE the kernel from the column-major mirror ``x_t`` via
scalar-prefetched block indices (``pltpu.PrefetchScalarGridSpec``).

Row-tile layout.  A TPU keeps a 2-D f32 array in (8, 128) tiles, and
Mosaic only DMAs blocks whose last two dims are multiples of (8, 128)
(or the whole dims): ONE row of a (d, n_pad) matrix is not such a
block.  The kernels therefore read ``x_t`` as (d, n_pad/128, 128) --
every coordinate row is its own stack of whole (8, 128) tiles -- and
the point vectors as (n_pad/128, 128).  :func:`row_tiles` is that
reshape; on a TPU it is one relayout copy of the operand, which the
engine's chunk executables pay once per call, outside their step loop.

Slot axis.  The grid's leading axis walks S independent problems
(solver slots or simulated clients), each with its own prefetched
index block.  Under ``jax.vmap`` the public wrappers lower to ONE
launch with that axis (``custom_vmap``) instead of Pallas's default
batching, which loops over the batch when scalar-prefetch operands are
batched.

  * ``momentum_dot_packed``  (lines 2-3 of Algorithm 2, both classes):
        delta = sum_i sign_i (lam_i + theta (lam_i - lam_prev_i)) x_t[idx, i]
    Grid (S, tiles, b).  The signed momentum weights are computed once
    per tile (at j == 0) into VMEM scratch; row j's lane partials
    accumulate into a resident (b, 128) output block, summed over lanes
    outside the kernel.

  * ``mwu_update_packed``    (lines 5-6 + incremental u, both classes):
        dv accumulates rank-1 over the j grid axis in VMEM scratch
        (``dw`` is read from SMEM); at j == b-1 the tile emits u_new,
        the unnormalized log weights, and PER-CLASS (max, sum-exp)
        normalizer partials in lanes 0-3 of a (1, 128) row.

The unpacked per-class kernels (``momentum_dot``/``mwu_update``, 4
launches per step over materialized (n, B) cols) are the reference path
the packed engine is parity-tested against.

B = 1 is the paper-faithful single-coordinate mode; B = 128 is the
beyond-paper block mode.

Every ``pl.pallas_call`` here builds its grid/BlockSpecs through a
``*_program`` builder (the registry contract of
:mod:`repro.analysis.pallas_audit`): the builder returns the EXACT grid,
in/out specs, shapes, scratch and accumulation metadata the launch uses,
so the static auditor proves properties of the real kernel programs, not
of a parallel description that could drift.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import custom_batching
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

NEG = -1e30

F32_BYTES = 4
LANE = 128          # TPU lane width (preprocess.LANE)
SUBLANE = 8         # f32 sublanes per vreg / HBM tile
# default points per grid step of the packed kernels: 1,024 rows of 128,
# a 512 KB block of one coordinate row.  At 2^20 x 256, B = 128 on a
# v5e each pass takes 0.78 ms against 0.93 ms at 65,536 points and
# 1.38 ms at 32,768 (0.655 ms is the pass's HBM bound): the fixed cost
# of a grid step is what a larger block amortizes.
PACKED_TILE = 131072
UNPACKED_TILE = 1024


def _smem(n: int) -> pl.BlockSpec:
    """A whole (n,) f32/int32 vector resident in SMEM (trivial window:
    exempt from the VMEM tiling rule, read with dynamic scalar
    indices)."""
    return pl.BlockSpec((n,), lambda *_: (0,), memory_space=pltpu.SMEM)


def _check_tiling(n_pad: int, tile: int) -> None:
    if tile <= 0 or n_pad % tile:
        raise ValueError(
            f"tile {tile} must evenly divide padded length {n_pad}")


def tile_rows(n_pad: int, tile: int = PACKED_TILE) -> int:
    """Rows of 128 points one packed grid step covers: the whole
    (n_pad/128) extent when it fits in ``tile`` points, else the
    largest multiple of 8 rows that divides it and fits (the TPU tiling
    rule); falls back to the whole extent when none does."""
    if n_pad % LANE:
        raise ValueError(
            f"packed length {n_pad} must be lane-aligned (multiple of "
            "128); use preprocess.pack_points / packed_length")
    r, want = n_pad // LANE, max(tile // LANE, 1)
    if r <= want:
        return r
    for rows in range(want - want % SUBLANE, 0, -SUBLANE):
        if r % rows == 0:
            return rows
    return r


def row_tiles(a: jax.Array) -> jax.Array:
    """(..., n_pad) -> (..., n_pad/128, 128): the kernels' view of a
    packed operand or point vector (see the module docstring)."""
    return a.reshape(a.shape[:-1] + (a.shape[-1] // LANE, LANE))


# ==========================================================================
# Program builders -- single source of truth for grid + BlockSpecs.
#
# Each returns a dict (a "kernel program") consumed BOTH by the
# pallas_call launch below and by repro.analysis.pallas_audit:
#   grid                 -- pallas grid tuple
#   num_scalar_prefetch  -- 0, or 1 when index maps take a prefetched idx
#   prefetch_length/bound-- idx vector length and exclusive value bound d
#   in_shapes/out_shapes -- full (unblocked) operand/result shapes
#   in_specs/out_specs   -- the pl.BlockSpec lists passed to pallas_call
#   scratch_shapes       -- pltpu scratch allocations for the launch
#   scratch_bytes        -- their total VMEM footprint
#   extra_vmem_bytes     -- kernel-private temporaries beyond blocks+scratch
#   accum_axes           -- {out position: grid axes along which output
#                           block revisits are legal accumulation}
# Shapes are element counts; the auditor budgets 4 bytes/element.
# ==========================================================================


def momentum_dot_program(*, n_pad: int, b: int, tile: int) -> dict:
    _check_tiling(n_pad, tile)
    grid = (n_pad // tile,)
    return dict(
        name="momentum_dot",
        grid=grid,
        num_scalar_prefetch=0,
        prefetch_length=None,
        prefetch_bound=None,
        in_shapes=[(n_pad, b), (1, n_pad), (1, n_pad), (1,)],
        in_specs=[
            pl.BlockSpec((tile, b), lambda i: (i, 0)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            _smem(1),
        ],
        out_shapes=[(grid[0], 1, b)],
        out_specs=[pl.BlockSpec((None, 1, b), lambda i: (i, 0, 0))],
        scratch_shapes=[],
        scratch_bytes=0,
        extra_vmem_bytes=F32_BYTES * tile * 3,    # lam, lam_prev, mom
        accum_axes={},
    )


def mwu_update_program(*, n_pad: int, b: int, tile: int) -> dict:
    _check_tiling(n_pad, tile)
    grid = (n_pad // tile,)
    vec = pl.BlockSpec((1, tile), lambda i: (0, i))
    part = pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0))
    return dict(
        name="mwu_update",
        grid=grid,
        num_scalar_prefetch=0,
        prefetch_length=None,
        prefetch_bound=None,
        in_shapes=[(n_pad, b), (1, n_pad), (1, n_pad), (1, b), (4,)],
        in_specs=[
            pl.BlockSpec((tile, b), lambda i: (i, 0)),
            vec, vec,
            pl.BlockSpec((1, b), lambda i: (0, 0)),
            _smem(4),
        ],
        out_shapes=[(1, n_pad), (1, n_pad), (grid[0], 1, 1),
                    (grid[0], 1, 1)],
        out_specs=[vec, vec, part, part],
        scratch_shapes=[],
        scratch_bytes=0,
        extra_vmem_bytes=F32_BYTES * tile * 3,    # dv, v, log_new temps
        accum_axes={},
    )


def _packed_specs(b: int, rows: int):
    """Shared blocks of the packed programs: the gathered x_t row tile
    and a (rows, 128) point-vector tile, grid (S, tiles, b)."""
    x_row = pl.BlockSpec((None, None, rows, LANE),
                         lambda s, i, j, idx: (s, idx[s * b + j], i, 0))
    vec = pl.BlockSpec((None, rows, LANE), lambda s, i, j, idx: (s, i, 0))
    return x_row, vec


def momentum_dot_packed_program(*, n_pad: int, d: int, b: int,
                                tile: int = PACKED_TILE,
                                num_slots: int = 1) -> dict:
    rows = tile_rows(n_pad, tile)
    r = n_pad // LANE
    grid = (num_slots, r // rows, b)
    x_row, vec = _packed_specs(b, rows)
    vshape = (num_slots, r, LANE)
    return dict(
        name="momentum_dot_packed",
        grid=grid,
        num_scalar_prefetch=1,
        prefetch_length=num_slots * b,
        prefetch_bound=d,
        in_shapes=[(num_slots, d, r, LANE), vshape, vshape, vshape,
                   (num_slots,)],
        in_specs=[x_row, vec, vec, vec, _smem(num_slots)],
        out_shapes=[(num_slots, b, LANE)],
        out_specs=[pl.BlockSpec((None, b, LANE),
                                lambda s, i, j, idx: (s, 0, 0))],
        scratch_shapes=[pltpu.VMEM((rows, LANE), jnp.float32)],
        scratch_bytes=F32_BYTES * rows * LANE,
        extra_vmem_bytes=F32_BYTES * rows * LANE * 3,  # lam, prev, product
        # one (b, 128) lane-partial block per slot, accumulated over
        # every (tile, row) grid point of that slot
        accum_axes={0: (1, 2)},
    )


def mwu_update_packed_program(*, n_pad: int, d: int, b: int,
                              tile: int = PACKED_TILE,
                              num_slots: int = 1) -> dict:
    rows = tile_rows(n_pad, tile)
    r = n_pad // LANE
    grid = (num_slots, r // rows, b)
    x_row, vec = _packed_specs(b, rows)
    vshape = (num_slots, r, LANE)
    return dict(
        name="mwu_update_packed",
        grid=grid,
        num_scalar_prefetch=1,
        prefetch_length=num_slots * b,
        prefetch_bound=d,
        in_shapes=[(num_slots, d, r, LANE), vshape, vshape, vshape,
                   (num_slots * b,), (num_slots * 3,)],
        in_specs=[x_row, vec, vec, vec, _smem(num_slots * b),
                  _smem(num_slots * 3)],
        out_shapes=[vshape, vshape, (num_slots, grid[1], 1, LANE)],
        out_specs=[
            vec, vec,
            pl.BlockSpec((None, None, 1, LANE),
                         lambda s, i, j, idx: (s, i, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((rows, LANE), jnp.float32)],
        scratch_bytes=F32_BYTES * rows * LANE,
        # j == nb-1 epilogue: v, log_new, per-class masks/exp temps
        extra_vmem_bytes=F32_BYTES * rows * LANE * 6,
        # every output is written once per tile (at j == nb-1), so
        # revisits along grid axis 2 (the b block-coordinate walk) are
        # declared accumulation, not races
        accum_axes={0: (2,), 1: (2,), 2: (2,)},
    )


# ==========================================================================
# Unpacked per-class kernels (reference path, 4 launches per step)
# ==========================================================================


def _unpacked_tile(n: int, tile: int) -> int:
    """Lane-aligned tile covering ``n`` points: ``n`` is padded up to
    a multiple of it."""
    return min(tile, -(-max(n, 1) // LANE) * LANE)


def _momentum_dot_kernel(cols_ref, log_lam_ref, log_prev_ref, theta_ref,
                         part_ref):
    lam = jnp.exp(log_lam_ref[...])               # (1, TILE)
    lam_prev = jnp.exp(log_prev_ref[...])
    mom = lam + theta_ref[0] * (lam - lam_prev)
    part_ref[...] = jnp.dot(mom, cols_ref[...],   # (1, B)
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _momentum_dot_jit(cols, log_lam, log_prev, theta, *, tile: int,
                      interpret: bool) -> jax.Array:
    n, b = cols.shape
    tile = _unpacked_tile(n, tile)
    pad = (-n) % tile
    cols = jnp.pad(cols, ((0, pad), (0, 0)))
    log_lam = jnp.pad(log_lam, (0, pad), constant_values=NEG)
    log_prev = jnp.pad(log_prev, (0, pad), constant_values=NEG)
    prog = momentum_dot_program(n_pad=cols.shape[0], b=b, tile=tile)
    theta = jnp.asarray(theta, jnp.float32).reshape(1)
    parts = pl.pallas_call(
        _momentum_dot_kernel,
        grid=prog["grid"],
        in_specs=prog["in_specs"],
        out_specs=prog["out_specs"][0],
        out_shape=jax.ShapeDtypeStruct(prog["out_shapes"][0], cols.dtype),
        interpret=interpret,
    )(cols, log_lam[None], log_prev[None], theta)
    return parts.sum(axis=(0, 1))


def momentum_dot(cols: jax.Array, log_lam: jax.Array, log_prev: jax.Array,
                 theta: jax.Array, *, tile: int = UNPACKED_TILE,
                 interpret: bool | None = None) -> jax.Array:
    """delta (B,) = cols^T (lam + theta (lam - lam_prev)), tiled over n."""
    if interpret is None:
        interpret = default_interpret()
    return _momentum_dot_jit(cols, log_lam, log_prev, theta, tile=tile,
                             interpret=interpret)


def _mwu_kernel(cols_ref, log_lam_ref, u_ref, dw_ref, scal_ref,
                log_new_ref, u_new_ref, pmax_ref, psum_ref):
    sign, gamma, tau, d_eff = (scal_ref[0], scal_ref[1], scal_ref[2],
                               scal_ref[3])
    u = u_ref[...]                                # (1, TILE)
    dv = jax.lax.dot_general(                     # dw (1,B) . cols^T
        dw_ref[...], cols_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    v = sign * (u + d_eff * dv)
    c = 1.0 / (gamma + d_eff / tau)
    log_new = c * ((d_eff / tau) * log_lam_ref[...] - v)
    u_new_ref[...] = u + dv
    log_new_ref[...] = log_new
    tile_max = jnp.max(log_new)
    pmax_ref[...] = jnp.full((1, 1), tile_max, jnp.float32)
    psum_ref[...] = jnp.full((1, 1), jnp.sum(jnp.exp(log_new - tile_max)),
                             jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("tile", "interpret", "normalize"))
def _mwu_update_jit(cols, log_lam, u, dw, sign, gamma, tau, d_eff, *,
                    tile: int, interpret: bool, normalize: bool):
    n, b = cols.shape
    tile = _unpacked_tile(n, tile)
    pad = (-n) % tile
    cols = jnp.pad(cols, ((0, pad), (0, 0)))
    log_lam = jnp.pad(log_lam, (0, pad), constant_values=NEG)
    u = jnp.pad(u, (0, pad))
    prog = mwu_update_program(n_pad=cols.shape[0], b=b, tile=tile)
    scal = jnp.stack([jnp.asarray(s, jnp.float32)
                      for s in (sign, gamma, tau, d_eff)])
    log_new, u_new, pmax, psum = pl.pallas_call(
        _mwu_kernel,
        grid=prog["grid"],
        in_specs=prog["in_specs"],
        out_specs=prog["out_specs"],
        out_shape=[jax.ShapeDtypeStruct(s, cols.dtype)
                   for s in prog["out_shapes"]],
        interpret=interpret,
    )(cols, log_lam[None], u[None], dw[None], scal)
    log_new, u_new = log_new[0, :n], u_new[0, :n]
    # combine per-tile (max, sumexp) partials into the global logsumexp;
    # a padded tail holds NEG log weights, which add exp(NEG - m) == 0
    m = jnp.max(pmax)
    s = jnp.sum(psum * jnp.exp(pmax - m))
    if not normalize:
        return log_new, u_new, m, s
    return log_new - (m + jnp.log(s)), u_new


def mwu_update(cols: jax.Array, log_lam: jax.Array, u: jax.Array,
               dw: jax.Array, sign: jax.Array, gamma: jax.Array,
               tau: jax.Array, d_eff: jax.Array, *,
               tile: int = UNPACKED_TILE,
               interpret: bool | None = None, normalize: bool = True):
    """Fused dual update.  Returns (log_new_normalized, u_new), or --
    with ``normalize=False`` -- (log_new_unnormalized, u_new, m, s)
    where lse = m + log(s), so a caller can combine the normalizer
    partials across clients (distributed rounds 2-3) before applying."""
    if interpret is None:
        interpret = default_interpret()
    return _mwu_update_jit(cols, log_lam, u, dw, sign, gamma, tau, d_eff,
                           tile=tile, interpret=interpret,
                           normalize=normalize)


# --------------------------------------------------------------------------
# Packed single-sweep kernels (2 launches per engine step)
# --------------------------------------------------------------------------


def _momentum_dot_packed_kernel(idx_ref, x_row_ref, log_lam_ref,
                                log_prev_ref, sign_ref, theta_ref,
                                acc_ref, mom_ref):
    del idx_ref  # consumed by the BlockSpec index maps
    s, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():                       # signed momentum weights, once per tile
        lam = jnp.exp(log_lam_ref[...])
        lam_prev = jnp.exp(log_prev_ref[...])
        mom_ref[...] = sign_ref[...] * (
            lam + theta_ref[s] * (lam - lam_prev))

    @pl.when((i == 0) & (j == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    part = jnp.sum(x_row_ref[...] * mom_ref[...], axis=0, keepdims=True)
    acc_ref[pl.ds(j, 1), :] += part               # (1, 128) lane partials


def _mwu_packed_kernel(idx_ref, x_row_ref, log_lam_ref, u_ref, sign_ref,
                       dw_ref, scal_ref, log_new_ref, u_new_ref, part_ref,
                       dv_ref):
    del idx_ref
    s, j = pl.program_id(0), pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        dv_ref[...] = jnp.zeros_like(dv_ref)

    dv_ref[...] += x_row_ref[...] * dw_ref[s * nb + j]   # rank-1 accumulate

    @pl.when(j == nb - 1)
    def _():
        gamma, tau, d_eff = (scal_ref[3 * s], scal_ref[3 * s + 1],
                             scal_ref[3 * s + 2])
        sign = sign_ref[...]
        dv = dv_ref[...]
        u = u_ref[...]
        v = sign * (u + d_eff * dv)
        c = 1.0 / (gamma + d_eff / tau)
        log_new = c * ((d_eff / tau) * log_lam_ref[...] - v)
        u_new_ref[...] = u + dv
        log_new_ref[...] = log_new
        # per-class (max, sumexp) normalizer partials in the same sweep;
        # the sum is masked (not filled with NEG) so an all-padding /
        # single-class tile contributes (NEG, 0) instead of (NEG, inf)
        is_p = sign > 0
        is_m = sign < 0
        m_p = jnp.max(jnp.where(is_p, log_new, NEG))
        m_m = jnp.max(jnp.where(is_m, log_new, NEG))
        s_p = jnp.sum(jnp.where(is_p, jnp.exp(log_new - m_p), 0.0))
        s_m = jnp.sum(jnp.where(is_m, jnp.exp(log_new - m_m), 0.0))
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
        part_ref[...] = jnp.where(
            lane == 0, m_p, jnp.where(
                lane == 1, s_p, jnp.where(lane == 2, m_m, s_m)))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _momentum_packed_slots(xk, idx, log_lam, log_prev, sign, theta, *,
                           tile: int, interpret: bool) -> jax.Array:
    """Slot-axis launch: xk (S, d, R, 128), idx (S, b), point vectors
    (S, n_pad), theta (S,).  Returns delta (S, b)."""
    num_slots, d, r, _ = xk.shape
    b = idx.shape[1]
    n_pad = r * LANE
    prog = momentum_dot_packed_program(n_pad=n_pad, d=d, b=b, tile=tile,
                                       num_slots=num_slots)
    acc = pl.pallas_call(
        _momentum_dot_packed_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=prog["num_scalar_prefetch"],
            grid=prog["grid"],
            in_specs=prog["in_specs"],
            out_specs=prog["out_specs"][0],
            scratch_shapes=prog["scratch_shapes"],
        ),
        out_shape=jax.ShapeDtypeStruct(prog["out_shapes"][0], jnp.float32),
        interpret=interpret,
    )(idx.reshape(-1), xk, row_tiles(log_lam), row_tiles(log_prev),
      row_tiles(sign), theta)
    return acc.sum(axis=-1)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _mwu_packed_slots(xk, idx, log_lam, u, dw, sign, scal, *, tile: int,
                      interpret: bool):
    """Slot-axis launch: scal (S, 3) = (gamma, tau, d_eff) per slot.
    Returns (log_new, u_new, m_p, s_p, m_m, s_m), each with a leading
    S axis."""
    num_slots, d, r, _ = xk.shape
    b = idx.shape[1]
    n_pad = r * LANE
    prog = mwu_update_packed_program(n_pad=n_pad, d=d, b=b, tile=tile,
                                     num_slots=num_slots)
    log_new, u_new, parts = pl.pallas_call(
        _mwu_packed_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=prog["num_scalar_prefetch"],
            grid=prog["grid"],
            in_specs=prog["in_specs"],
            out_specs=prog["out_specs"],
            scratch_shapes=prog["scratch_shapes"],
        ),
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float32)
                   for s in prog["out_shapes"]],
        interpret=interpret,
    )(idx.reshape(-1), xk, row_tiles(log_lam), row_tiles(u),
      row_tiles(sign), dw.reshape(-1), scal.reshape(-1))
    # combine per-tile per-class partials into the two global logsumexps
    parts = parts[:, :, 0, :4]                    # (S, tiles, 4)
    m_p = jnp.max(parts[..., 0], axis=1)
    s_p = jnp.sum(parts[..., 1] * jnp.exp(parts[..., 0] - m_p[:, None]),
                  axis=1)
    m_m = jnp.max(parts[..., 2], axis=1)
    s_m = jnp.sum(parts[..., 3] * jnp.exp(parts[..., 2] - m_m[:, None]),
                  axis=1)
    return (log_new.reshape(num_slots, n_pad), u_new.reshape(num_slots, n_pad),
            m_p, s_p, m_m, s_m)


def _slot_launch(slots_fn):
    """Wrap a slot-axis launch as a per-problem function whose
    ``jax.vmap`` is the slot axis itself (one launch for the batch)."""

    @custom_batching.custom_vmap
    def one(*args):
        outs = slots_fn(*(a[None] for a in args))
        return jax.tree.map(lambda o: o[0], outs)

    @one.def_vmap
    def _batched(axis_size, in_batched, *args):
        args = [a if bat else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, bat in zip(args, in_batched)]
        outs = slots_fn(*args)
        return outs, jax.tree.map(lambda _: True, outs)

    return one


@functools.lru_cache(maxsize=None)
def _packed_launchers(tile: int, interpret: bool):
    return (
        _slot_launch(functools.partial(_momentum_packed_slots, tile=tile,
                                       interpret=interpret)),
        _slot_launch(functools.partial(_mwu_packed_slots, tile=tile,
                                       interpret=interpret)),
    )


def _kernel_operand(x_t: jax.Array) -> jax.Array:
    """Accept the packed operand as (d, n_pad) or already in the
    kernels' row-tile layout (d, n_pad/128, 128)."""
    return row_tiles(x_t) if x_t.ndim == 2 else x_t


def momentum_dot_packed(x_t: jax.Array, idx: jax.Array, log_lam: jax.Array,
                        log_prev: jax.Array, sign: jax.Array,
                        theta: jax.Array, *, tile: int = PACKED_TILE,
                        interpret: bool | None = None) -> jax.Array:
    """delta (b,) = sum_i sign_i mom_i x_t[idx, i] -- lines 2-3 of
    Algorithm 2 for BOTH classes in one sweep, gathering the coordinate
    block inside the kernel.  ``x_t`` is (d, n_pad) or its
    :func:`row_tiles` view (the view avoids a relayout per call)."""
    if interpret is None:
        interpret = default_interpret()
    mom, _ = _packed_launchers(tile, interpret)
    return mom(_kernel_operand(x_t), idx.astype(jnp.int32), log_lam,
               log_prev, sign, jnp.asarray(theta, jnp.float32))


def mwu_update_packed(x_t: jax.Array, idx: jax.Array, log_lam: jax.Array,
                      u: jax.Array, dw: jax.Array, sign: jax.Array,
                      gamma: jax.Array, tau: jax.Array, d_eff: jax.Array,
                      *, tile: int = PACKED_TILE,
                      interpret: bool | None = None):
    """Fused packed dual update (lines 5-6 + incremental u for BOTH
    classes).  Returns (log_new_unnormalized, u_new, m_p, s_p, m_m, s_m)
    with per-class lse = m + log(s); the caller combines the partials
    across clients (distributed rounds 2-3) and normalizes per class."""
    if interpret is None:
        interpret = default_interpret()
    _, mwu = _packed_launchers(tile, interpret)
    scal = jnp.stack([jnp.asarray(v, jnp.float32)
                      for v in (gamma, tau, d_eff)])
    return mwu(_kernel_operand(x_t), idx.astype(jnp.int32), log_lam, u,
               dw.astype(jnp.float32), sign, scal)
