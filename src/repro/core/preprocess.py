"""Pre-processing for Saddle-SVC (Algorithm 1 of the paper).

Steps:
  1. scale all points by 1/max_i ||x_i||  (footnote 3),
  2. apply the randomized Walsh--Hadamard transform ``WD`` so that with
     high probability every coordinate of every point is
     O(sqrt(log n / d))  -- this makes uniform coordinate sampling in
     Algorithm 2 effective.

``W`` is the (normalized) d x d Walsh--Hadamard matrix and ``D`` a random
+-1 diagonal.  We use the *normalized* transform (W W^T = I) so the map
is orthonormal: optima are preserved exactly and ``w`` can be mapped back
by the inverse transform.  Dimensions that are not a power of two are
zero-padded (see DESIGN.md assumption log #3).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def next_pow2(d: int) -> int:
    p = 1
    while p < d:
        p *= 2
    return p


def fwht(x: jax.Array, *, normalize: bool = True) -> jax.Array:
    """Fast Walsh--Hadamard transform along the LAST axis (pure jnp).

    The last axis length must be a power of two.  O(d log d) butterflies
    implemented with reshapes; used as the reference implementation (the
    Pallas kernel in ``repro.kernels.fwht`` is benchmarked against it).
    """
    d = x.shape[-1]
    if d & (d - 1):
        raise ValueError(f"fwht needs a power-of-two axis, got {d}")
    orig_shape = x.shape
    x = x.reshape(-1, d)
    h = 1
    while h < d:
        x = x.reshape(-1, d // (2 * h), 2, h)
        a = x[..., 0, :]
        b = x[..., 1, :]
        x = jnp.stack([a + b, a - b], axis=-2)
        x = x.reshape(-1, d)
        h *= 2
    if normalize:
        x = x / jnp.sqrt(jnp.asarray(d, x.dtype))
    return x.reshape(orig_shape)


LANE = 128  # TPU lane width; packed point counts are padded to this
SUBLANE = 8  # f32 rows per TPU memory tile


def packed_length(n: int, lane: int = LANE) -> int:
    """Smallest multiple of ``lane`` >= n (>= lane, so the Pallas tile
    grid always divides evenly) -- and, beyond ``SUBLANE`` lanes, of
    ``SUBLANE * lane``: the kernels view the point axis as rows of
    ``lane`` points and block them in whole (8, 128) tiles
    (``kernels.saddle_update.tile_rows``)."""
    rows = max(-(-n // lane), 1)
    if rows > SUBLANE:
        rows = -(-rows // SUBLANE) * SUBLANE
    return rows * lane


def bucket_length(n: int, lane: int = LANE) -> int:
    """The pow-2 BUCKET ladder for the point axis: ``lane * 2^k``
    (128, 256, 512, 1024, ...), the smallest rung >= n.

    Where :func:`packed_length` pads to the next lane multiple (tight,
    one executable per distinct multiple), the bucket ladder trades at
    most 2x padding for O(log n) distinct shapes -- the multi-tenant
    serving layer compiles ONE slot-batched executable per rung and
    every request whose n lands in the rung shares it."""
    return lane * next_pow2(max(-(-n // lane), 1))


def bucket_shape(n: int, d: int) -> tuple[int, int]:
    """(n_bucket, d_bucket) for a problem with n points in d dims: the
    pow-2 point-axis rung and the pow-2 coordinate count (d is already
    a power of two after :func:`preprocess`, so the d rung is the
    identity on preprocessed problems; :func:`pack_points_to` can pad
    d further for callers sharing one executable across
    dimensionalities)."""
    return bucket_length(n), next_pow2(d)


class PackedPoints(NamedTuple):
    """Both classes packed into ONE lane-padded operand (the single-sweep
    engine's view of the data; see :mod:`repro.core.engine`).

    Slots ``[0, n1)`` hold the +1 class, ``[n1, n1+n2)`` the -1 class,
    and the lane-padding tail is all-zero points.  ``sign`` doubles as
    the validity mask: +1 / -1 for real points, 0 for padding (padding
    additionally carries log-weight NEG_INF in the solver state, so it
    contributes exactly 0 to every sum).
    """

    x_t: jax.Array       # (d, n_pad) COLUMN-major mirror: x_t[c] is
                         #   coordinate c of every packed point, so a
                         #   sampled block is b contiguous rows
    sign: jax.Array      # (n_pad,) +1 class P, -1 class Q, 0 padding
    n1: int
    n2: int

    @property
    def n_pad(self) -> int:
        return self.x_t.shape[-1]


@functools.partial(jax.jit, static_argnames=("n_pad",))
def _pack(xp, xm, n_pad):
    n1, d = xp.shape
    n2 = xm.shape[0]
    x_t = jnp.zeros((d, n_pad), jnp.float32)
    x_t = x_t.at[:, :n1].set(xp.T).at[:, n1:n1 + n2].set(xm.T)
    sign = jnp.zeros((n_pad,), jnp.float32)
    sign = sign.at[:n1].set(1.0).at[n1:n1 + n2].set(-1.0)
    return x_t, sign


def pack_points(xp: jax.Array, xm: jax.Array,
                pad_to: int | None = None) -> PackedPoints:
    """Pack the two (row-major) class matrices into the single-sweep
    layout: one (d, n_pad) column-major mirror plus the +-1 sign vector.

    The mirror is materialized ONCE here so the per-iteration coordinate
    block gather ``x_t[idx]`` reads b contiguous rows instead of b
    strided columns of a row-major (n, d) matrix.
    """
    xp = jnp.asarray(xp, jnp.float32)
    xm = jnp.asarray(xm, jnp.float32)
    n1, d = xp.shape
    n2 = xm.shape[0]
    assert xm.shape[1] == d, "class matrices must share dimensionality"
    n_pad = packed_length(n1 + n2) if pad_to is None else pad_to
    if n_pad < n1 + n2:
        raise ValueError(f"pad_to={pad_to} < n1+n2={n1 + n2}")
    if n_pad % LANE:
        raise ValueError(f"pad_to={pad_to} must be a multiple of the "
                         f"lane width {LANE}")
    x_t, sign = _pack(xp, xm, n_pad)
    return PackedPoints(x_t=x_t, sign=sign, n1=n1, n2=n2)


def pack_points_to(xp: jax.Array, xm: jax.Array, n_pad: int,
                   d_pad: int) -> PackedPoints:
    """BUCKETED packing: pack into an exact (d_pad, n_pad) target shape
    so every problem assigned to the same bucket shares one compiled
    executable (see :func:`bucket_shape`).

    Beyond :func:`pack_points`' lane padding of the point axis, the
    COORDINATE axis is zero-padded to ``d_pad``: padding coordinates
    are all-zero rows of ``x_t``, so a sampled block touching them
    contributes exactly 0 to every dot product and the corresponding
    ``w`` entries stay pinned at 0 (the update is w <- w / (sigma+1)
    from w = 0).  The solver must be configured with d = d_pad so its
    uniform coordinate sampling covers the padded axis -- that is what
    makes a bucketed solve reproducible slot-for-slot against a solo
    solve at the same bucket.
    """
    xp = jnp.asarray(xp, jnp.float32)
    xm = jnp.asarray(xm, jnp.float32)
    d = xp.shape[1]
    if d_pad < d:
        raise ValueError(f"d_pad={d_pad} < d={d}")
    if d_pad > d:
        xp = jnp.pad(xp, ((0, 0), (0, d_pad - d)))
        xm = jnp.pad(xm, ((0, 0), (0, d_pad - d)))
    return pack_points(xp, xm, pad_to=n_pad)


class Preprocessed(NamedTuple):
    """Output of :func:`preprocess` -- the transformed problem."""

    xp: jax.Array        # (n1, d_pad) transformed +1 points (rows)
    xm: jax.Array        # (n2, d_pad) transformed -1 points (rows)
    signs: jax.Array     # (d_pad,) the +-1 diagonal of D
    scale: jax.Array     # scalar: 1 / max ||x_i||
    d_orig: int          # original dimensionality before padding


def hadamard_transform(x: jax.Array, signs: jax.Array) -> jax.Array:
    """Apply ``W D`` to rows of ``x`` (already padded to len(signs))."""
    return fwht(x * signs[None, :])


def inverse_hadamard_transform(v: jax.Array, signs: jax.Array) -> jax.Array:
    """Apply ``(W D)^-1 = D W^T`` to a vector in transformed space."""
    return fwht(v) * signs


@functools.partial(jax.jit, static_argnames=("d_pad",))
def _transform(xp, xm, signs, d_pad):
    def pad(x):
        return jnp.pad(x, ((0, 0), (0, d_pad - x.shape[1])))

    xp, xm = pad(xp), pad(xm)
    norms = jnp.concatenate(
        [jnp.linalg.norm(xp, axis=1), jnp.linalg.norm(xm, axis=1)]
    )
    scale = 1.0 / jnp.maximum(jnp.max(norms), 1e-30)
    return (
        hadamard_transform(xp * scale, signs),
        hadamard_transform(xm * scale, signs),
        scale,
    )


def preprocess(xp: np.ndarray | jax.Array, xm: np.ndarray | jax.Array,
               key: jax.Array) -> Preprocessed:
    """Algorithm 1: scale to the unit ball and apply the WD transform."""
    xp = jnp.asarray(xp, jnp.float32)
    xm = jnp.asarray(xm, jnp.float32)
    d = xp.shape[1]
    assert xm.shape[1] == d, "class matrices must share dimensionality"
    d_pad = next_pow2(d)
    signs = jax.random.rademacher(key, (d_pad,), dtype=jnp.float32)
    txp, txm, scale = _transform(xp, xm, signs, d_pad)
    return Preprocessed(xp=txp, xm=txm, signs=signs, scale=scale, d_orig=d)


def transform_like(pre: Preprocessed, x: np.ndarray | jax.Array) -> jax.Array:
    """Apply a tenant's FIXED preprocessing transform to NEW raw points.

    Streaming updates must keep the transform (the +-1 diagonal ``D``
    and the unit-ball scale) of the tenant's ORIGINAL :func:`preprocess`
    call: carried saddle state lives in the transformed space, so
    re-deriving either one would silently re-base the warm start.  The
    scale therefore stays pinned even if an arriving point's norm
    exceeds the original max -- the unit-ball guarantee (footnote 3)
    degrades gracefully for such points while optima are still exact
    (the map stays a fixed orthonormal transform times a constant).
    """
    x = jnp.asarray(x, jnp.float32)
    if x.ndim != 2 or x.shape[1] != pre.d_orig:
        raise ValueError(
            f"transform_like expects (m, d_orig={pre.d_orig}) points; "
            f"got shape {tuple(x.shape)}")
    d_pad = pre.signs.shape[0]
    x = jnp.pad(x, ((0, 0), (0, d_pad - x.shape[1])))
    return hadamard_transform(x * pre.scale, pre.signs)


def repack_warm_duals(log_lam: np.ndarray, n1_old: int, n2_old: int,
                      n1_new: int, n2_new: int,
                      n_pad_new: int) -> np.ndarray:
    """Transfer packed per-class log dual mass across bucket shapes.

    The packed layout is ``[eta (n1) | xi (n2) | NEG_INF pad]``, so
    appending points to either class SHIFTS the other class's block:
    a warm start cannot just zero-pad the old vector, it must re-place
    each class segment at its new offset.  Carried entries keep their
    old log weights; new points are seeded at the NEW uniform level
    (``-log(n_class_new)``).  The carried segment still sums to the OLD
    class's total mass, so the class sum is temporarily != 1 -- by
    design: the next MWU round's per-class logsumexp renormalizes each
    class to exactly 1 (normalization IS the repair, the same rule the
    sharded paths use for dropped shards), so no host-side repair pass
    and no extra executable is needed.

    ``n1_old = n2_old = 0`` ignores ``log_lam`` entirely and yields the
    pure uniform init on the new shape (the replace-mode dual reset).
    """
    from repro.core.engine import NEG_INF  # engine never imports us back
    if not (0 <= n1_old <= n1_new and 0 <= n2_old <= n2_new):
        raise ValueError(
            f"warm dual transfer needs old class sizes within new ones; "
            f"got ({n1_old}, {n2_old}) -> ({n1_new}, {n2_new})")
    if n1_new + n2_new > n_pad_new:
        raise ValueError(
            f"n1_new+n2_new={n1_new + n2_new} > n_pad_new={n_pad_new}")
    lam = np.asarray(log_lam, np.float32)
    out = np.full((n_pad_new,), NEG_INF, np.float32)
    out[:n1_old] = lam[:n1_old]
    out[n1_old:n1_new] = -math.log(n1_new)
    out[n1_new:n1_new + n2_old] = lam[n1_old:n1_old + n2_old]
    out[n1_new + n2_old:n1_new + n2_new] = -math.log(n2_new)
    return out


def recover_direction(w: jax.Array, pre: Preprocessed) -> jax.Array:
    """Map a direction from transformed space back to the input space.

    Predictions on raw points x use sign(w_orig . x - b_orig); the
    orthonormal transform gives w_orig = scale * (WD)^T w.
    """
    w_orig = inverse_hadamard_transform(w, pre.signs)[: pre.d_orig]
    return w_orig * pre.scale
