"""Fused solver core shared by every Saddle-SVC execution mode.

The paper's Algorithm 2 (serial) and Algorithm 4 (distributed) are the
same iteration: the serial solver is the k=1 degenerate client, where
every all-reduce is the identity.  This module implements that single
step ONCE, parameterized along two orthogonal axes:

  ``axis_name``   None          -> serial (all psum/pmax collapse away)
                  "clients"     -> distributed, under ``jax.vmap``
                                   (bit-exact k-client simulation) or
                                   ``shard_map`` (real device mesh)

  ``backend``     "jnp"         -> pure jax.numpy step
                  "pallas"      -> the Pallas kernels in
                                   ``repro.kernels.ops``

Packed single-sweep step
------------------------

The PRIMARY step (:func:`step_packed`, what ``saddle.solve`` and
``distributed.solve_distributed`` run) works on the packed +- layout of
:func:`repro.core.preprocess.pack_points`: both classes live in ONE
lane-padded point set with a +-1 ``sign`` vector (0 marks lane-padding,
which also carries log-weight NEG_INF so it contributes exactly 0 to
every reduction).  The packed state holds THREE point-length vectors
(``log_lam``, ``log_lam_prev``, ``u``) plus ``w`` where the unpacked
state needs six, and every per-point pass runs ONCE per step instead of
once per class:

  pass 1  signed momentum dot: delta = sum_i sign_i mom_i x_t[idx, i]
          (the sign folds delta+ - delta- into a single sweep)
  pass 2  MWU update + incremental u + BOTH per-class logsumexp
          normalizer partials, masked by sign in the same sweep

so the Pallas backend launches 2 kernels per step (vs 4 for the
unpacked reference).  Coordinate blocks are gathered from the
column-major mirror ``x_t`` (d, n_pad): a sampled block is b CONTIGUOUS
rows (``jnp.take(x_t, idx, axis=0)``), not b strided columns of a
row-major (n, d) matrix; the Pallas kernels go further and gather
tile-by-tile inside the kernel from scalar-prefetched indices, never
materializing a cols intermediate (they read ``x_t`` through a
(d, n_pad/128, 128) row-tile view that the chunk drivers build once
per call, see ``kernels.saddle_update``).

The nu-Saddle capped-simplex projection is SORT-FREE: a fixed-round
bisection on the cap scale (the shared core
:func:`repro.core.projections.capped_bisect_masked`) whose every round
is one masked O(n) reduction -- both classes share the sweep, and
under an axis each round all-reduces a single (2,) vector, so the
round-4 budget is a DETERMINISTIC O(k) scalars per iteration
(BISECT_ROUNDS_SOLVER two-scalar all-reduces; Theorem 8).  The
reference path pays an O(n log n) argsort + scatter per class per
iteration serially, and a data-dependent loop -- worst case O(1/nu)
rounds -- distributed.

The unpacked :func:`step` is retained as the reference oracle the
packed path is parity-tested against (serial/distributed x jnp/pallas x
nu=0/nu>0) and as the baseline ``benchmarks/engine_bench.py`` measures
the packed speedup over.

On top of either step sits the fixed-shape chunk driver:

  * ``chunk_body*`` pre-splits the per-step keys at a static
    ``chunk_steps`` shape but runs the step under a ``fori_loop`` with
    a DYNAMIC trip count, so one executable serves every chunk length
    and the padded tail of a partial final chunk is never executed.
  * ``run_chunk*`` (the serial jit wrappers) donate the state buffers
    (``donate_argnums``) so the solver state is updated in place.
  * The objective is computed on device at the end of each chunk and
    returned as a device scalar; drivers accumulate those and do ONE
    host transfer at the end of the solve.

Coordinate blocks are sampled WITHOUT replacement (a duplicated index
would corrupt the incremental invariant ``u == X w``) by a partial
Fisher--Yates shuffle: b swap rounds on an iota array, O(d + b) work
per draw instead of the O(d log d) full ``jax.random.permutation``.
"""

from __future__ import annotations

import collections
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import projections

CLIENT_AXIS = "clients"
NEG_INF = -1e30     # log-weight of padding points (exp() == 0 exactly)

# Incremented at TRACE time inside the chunk bodies, keyed by the static
# configuration -- i.e. it counts XLA compilations, not calls.  Tests
# use this to assert that chunked solves with a partial final chunk
# compile the chunk exactly once.
trace_counts: collections.Counter = collections.Counter()


def sample_block(key: jax.Array, d: int, b: int) -> jax.Array:
    """b distinct coordinates, uniform without replacement, via a
    partial Fisher--Yates shuffle: swap slot i with a uniform slot in
    [i, d) for i < b, then read the b-prefix.  O(d + b) work -- the
    full ``jax.random.permutation`` sort is O(d log d) for b << d --
    and exactly the uniform without-replacement distribution (each
    prefix outcome has probability 1 / (d (d-1) ... (d-b+1))).
    b=1 keeps the cheap single-draw path; the distributions coincide.
    """
    if b == 1:
        return jax.random.randint(key, (1,), 0, d)
    offs = jnp.arange(b)
    swap = offs + jax.random.randint(key, (b,), 0, d - offs)  # j_i ~ U[i, d)

    def body(i, a):
        ai, aj = a[i], a[swap[i]]
        return a.at[i].set(aj).at[swap[i]].set(ai)

    arr = jax.lax.fori_loop(0, b, body, jnp.arange(d))
    return arr[:b]


def _all_sum(x, axis_name):
    return x if axis_name is None else jax.lax.psum(x, axis_name)


def _all_max(x, axis_name):
    return x if axis_name is None else jax.lax.pmax(x, axis_name)


# ==========================================================================
# Reference (unpacked) step: two passes per class, retained as the
# parity oracle and the engine_bench baseline.
# ==========================================================================

def _dual_update(cols, log_lam, u, dw, sign, p, axis_name, backend):
    """Lines 5-6 of Algorithm 2 + incremental u maintenance, normalized
    with a (possibly distributed) logsumexp.  Returns (log_new, u_new).

    Both backends produce the UNNORMALIZED log weights plus local
    normalizer partials (m, s) with lse = m + log(s); the partials are
    then combined across clients (rounds 2-3 of Algorithm 4) or used
    directly in serial mode.
    """
    d_eff = p.d / p.block_size
    if backend == "pallas":
        from repro.kernels import ops as kops
        log_new, u_new, m_local, s_local = kops.mwu_update(
            cols, log_lam, u, dw, sign=sign, gamma=p.gamma, tau=p.tau,
            d_eff=d_eff, normalize=False)
    else:
        dv = cols @ dw
        v = sign * (u + d_eff * dv)
        c = 1.0 / (p.gamma + d_eff / p.tau)
        log_new = c * ((d_eff / p.tau) * log_lam - v)
        u_new = u + dv
        m_local = jnp.max(log_new)
        s_local = jnp.sum(jnp.exp(log_new - m_local))
    m = _all_max(m_local, axis_name)
    s = _all_sum(s_local * jnp.exp(m_local - m), axis_name)
    return log_new - (m + jnp.log(s)), u_new


def _capped_project(log_lam, nu, axis_name):
    """Reference nu-projection: Rule 2 (serial: one sort per iteration)
    or the distributed Rule-3 loop (round 4 of Algorithm 4).  The packed
    step replaces both with the sort-free fixed-round bisection."""
    if axis_name is None:
        eta = projections.capped_simplex_project_sorted(
            jnp.exp(log_lam), nu)
        return jnp.log(jnp.maximum(eta, 1e-38))

    max_rounds = int(1.0 / nu) + 2

    def cond(state):
        eta, it = state
        varsig = jax.lax.psum(
            jnp.sum(jnp.where(eta > nu, eta - nu, 0.0)), axis_name)
        return (varsig > 1e-12) & (it < max_rounds)

    def body(state):
        eta, it = state
        varsig = jax.lax.psum(
            jnp.sum(jnp.where(eta > nu, eta - nu, 0.0)), axis_name)
        omega = jax.lax.psum(
            jnp.sum(jnp.where(eta < nu, eta, 0.0)), axis_name)
        eta = jnp.where(eta >= nu, nu,
                        eta * (1.0 + varsig / jnp.maximum(omega, 1e-30)))
        return eta, it + 1

    eta = jnp.exp(log_lam)
    eta, _ = jax.lax.while_loop(cond, body, (eta, jnp.array(0, jnp.int32)))
    return jnp.where(eta > 0, jnp.log(jnp.maximum(eta, 1e-38)), NEG_INF)


def step(state, key: jax.Array, xp: jax.Array, xm: jax.Array, p, *,
         axis_name: str | None = None, backend: str = "jnp"):
    """One REFERENCE Algorithm-2/4 iteration from a single client's
    viewpoint (two passes per class; the production path is
    :func:`step_packed`).

    ``state`` is any NamedTuple with the canonical eight fields
    (SaddleState / ShardedState); the same type is returned.  ``xp`` and
    ``xm`` are the client's local (m1, d)/(m2, d) slices -- the full
    matrices in serial mode.  Under an axis, the key is identical across
    clients (the server broadcasts i*).
    """
    d, b = p.d, p.block_size
    d_eff = d / b
    idx = sample_block(key, d, b)
    cols_p = xp[:, idx]                              # (n1, B) rows X_{i*,.}
    cols_m = xm[:, idx]                              # (n2, B)

    # Lines 2-3 (round 1): momentum-extrapolated dual dot products,
    # all-reduced over clients.
    if backend == "pallas":
        from repro.kernels import ops as kops
        delta_p = kops.momentum_dot(cols_p, state.log_eta,
                                    state.log_eta_prev, p.theta)
        delta_m = kops.momentum_dot(cols_m, state.log_xi,
                                    state.log_xi_prev, p.theta)
    else:
        eta = jnp.exp(state.log_eta)
        eta_prev = jnp.exp(state.log_eta_prev)
        xi = jnp.exp(state.log_xi)
        xi_prev = jnp.exp(state.log_xi_prev)
        delta_p = cols_p.T @ (eta + p.theta * (eta - eta_prev))
        delta_m = cols_m.T @ (xi + p.theta * (xi - xi_prev))
    delta_p = _all_sum(delta_p, axis_name)
    delta_m = _all_sum(delta_m, axis_name)

    # Line 4 (round 2): every client performs the identical w update.
    # Multiply by the precomputed reciprocal instead of dividing by
    # (sigma + 1): bit-identical to what XLA's divide-by-constant
    # rewrite produced, and -- crucially -- ALSO bit-identical when the
    # scalar is a traced per-slot value (a runtime divide rounds
    # differently), keeping every engine mode in lockstep.
    w_old = state.w[idx]
    w_new = (w_old + p.sigma * (delta_p - delta_m)) * (1.0 / (p.sigma + 1.0))
    dw = w_new - w_old

    # Lines 5-6 (rounds 2-3): MWU dual updates.
    log_eta_new, u_p_new = _dual_update(
        cols_p, state.log_eta, state.u_p, dw, 1.0, p, axis_name, backend)
    log_xi_new, u_m_new = _dual_update(
        cols_m, state.log_xi, state.u_m, dw, -1.0, p, axis_name, backend)

    # Rule 2 / round 4: nu-Saddle capped-simplex projection.
    if p.nu > 0.0:
        log_eta_new = _capped_project(log_eta_new, p.nu, axis_name)
        log_xi_new = _capped_project(log_xi_new, p.nu, axis_name)

    return type(state)(
        w=state.w.at[idx].set(w_new),
        log_eta=log_eta_new, log_eta_prev=state.log_eta,
        log_xi=log_xi_new, log_xi_prev=state.log_xi,
        u_p=u_p_new, u_m=u_m_new,
        t=state.t + 1,
    )


def objective_from_state(state, xp, xm, axis_name=None) -> jax.Array:
    """C-Hull / RC-Hull objective 0.5 * ||A eta - B xi||^2, all-reduced
    over clients when run under an axis."""
    diff = jnp.exp(state.log_eta) @ xp - jnp.exp(state.log_xi) @ xm
    diff = _all_sum(diff, axis_name)
    return 0.5 * jnp.sum(diff * diff)


def chunk_body(state, key, xp, xm, params, num_steps, *,
               chunk_steps: int, axis_name: str | None = None,
               backend: str = "jnp"):
    """Reference chunk: run ``num_steps`` (dynamic) of at most
    ``chunk_steps`` (static) unpacked iterations and record the
    objective on device.  Returns (new_state, objective_scalar)."""
    trace_counts[(axis_name, backend, chunk_steps)] += 1  # trace-time only

    keys = jax.random.split(key, chunk_steps)

    def body(i, st):
        return step(st, keys[i], xp, xm, params,
                    axis_name=axis_name, backend=backend)

    state = jax.lax.fori_loop(0, num_steps, body, state)
    return state, objective_from_state(state, xp, xm, axis_name)


@functools.partial(jax.jit,
                   static_argnames=("params", "chunk_steps", "backend"),
                   donate_argnums=(0,))
def run_chunk(state, key, xp, xm, num_steps, *, params, chunk_steps: int,
              backend: str = "jnp"):
    """Serial reference chunk: state buffers donated, objective returned
    as a device scalar (no host sync), one compile for all chunk lengths
    up to ``chunk_steps``."""
    return chunk_body(state, key, xp, xm, params, num_steps,
                      chunk_steps=chunk_steps, axis_name=None,
                      backend=backend)


# ==========================================================================
# Packed single-sweep step (the production path)
# ==========================================================================


class PackedState(NamedTuple):
    """Solver state over the packed +- layout: one point-length vector
    per role instead of one per class per role.  Slot i belongs to the
    class given by ``sign[i]`` of the accompanying
    :class:`repro.core.preprocess.PackedPoints`; padding slots carry
    log-weight NEG_INF forever."""
    w: jax.Array             # (d,)
    log_lam: jax.Array       # (n_pad,)  [log eta | log xi | NEG_INF pad]
    log_lam_prev: jax.Array  # (n_pad,)
    u: jax.Array             # (n_pad,)  <w, x_i> maintained incrementally
    t: jax.Array             # iteration counter


def init_packed_state(sign: jax.Array, n1: int, n2: int,
                      d: int) -> PackedState:
    """Line 5 of Algorithm 1 on the packed layout: w=0, eta=1/n1,
    xi=1/n2 (global counts -- under sharding each client passes its own
    sign slice but the same n1/n2)."""
    log_lam = jnp.where(
        sign > 0, -math.log(n1),
        jnp.where(sign < 0, -math.log(n2), NEG_INF)).astype(jnp.float32)
    zeros_w = jnp.zeros(sign.shape[:-1] + (d,), jnp.float32)
    # distinct buffers for the "prev" copy: the chunk drivers donate the
    # state, and XLA rejects donating the same buffer twice
    return PackedState(
        w=zeros_w,
        log_lam=log_lam, log_lam_prev=jnp.copy(log_lam),
        u=jnp.zeros_like(log_lam),
        t=jnp.zeros(sign.shape[:-1], jnp.int32),
    )


@functools.partial(jax.jit, donate_argnums=(1, 2, 3))
def warm_packed_state(x_t: jax.Array, w: jax.Array, log_lam: jax.Array,
                      log_lam_prev: jax.Array) -> PackedState:
    """WARM-START packed state from a previous solution: carry ``w``
    and the (re-placed, see ``preprocess.repack_warm_duals``) log duals
    plus their momentum copy, and recompute ``u = x_t^T w`` ON DEVICE
    so the incremental invariant ``u_i == <w, x_i>`` holds EXACTLY for
    every point -- carried, appended and padding alike (recomputing IS
    carrying u: it is the unique value consistent with the carried w
    over the new operand, with zero accumulated drift).

    ``t`` resets to 0: the warm run's iteration counter counts the
    UPDATE round's own work, which is what iterations-to-gap accounting
    (``serve/stream/warm_iters_ratio``) compares against a cold solve.

    The state leaves are donated (the caller hands over freshly staged
    buffers); ``x_t`` is not -- it is the batch operand the chunk
    executable keeps reading.  This helper is jitted OUTSIDE the
    ``trace_counts`` accounting, like ``admit_into_slot``: warm
    admission must not perturb the zero-recompile contract of the hot
    chunk executables.
    """
    return PackedState(
        w=w, log_lam=log_lam, log_lam_prev=log_lam_prev,
        u=w @ x_t, t=jnp.zeros((), jnp.int32))


def unpack_state(pstate: PackedState, n1: int, n2: int, cls):
    """Slice a packed state back into the per-class 8-field view
    (``cls`` is SaddleState or ShardedState -- same field names; the
    ``...`` slicing serves both the flat and the stacked-client
    layouts).  Slots [0, n1) are eta, [n1, n1+n2) are xi; the
    lane-padding tail is dropped."""
    lam, prev, u = pstate.log_lam, pstate.log_lam_prev, pstate.u
    return cls(
        w=pstate.w,
        log_eta=lam[..., :n1], log_eta_prev=prev[..., :n1],
        log_xi=lam[..., n1:n1 + n2], log_xi_prev=prev[..., n1:n1 + n2],
        u_p=u[..., :n1], u_m=u[..., n1:n1 + n2],
        t=pstate.t,
    )


def _dual_update_packed(x_t, idx, cols_t, log_lam, u, dw, sign, sc,
                        d_eff, axis_name, backend):
    """Packed lines 5-6 + incremental u for BOTH classes in one pass,
    with per-class logsumexp normalizers computed in the same sweep
    (masked partials) and combined across clients as (2,)-vector
    all-reduces.  ``sc`` carries the per-problem scalars (python floats
    on the static SaddleParams path, traced per-slot f32 scalars under
    the slot-batched driver).  Returns (log_new_normalized, u_new)."""
    if backend == "pallas":
        from repro.kernels import ops as kops
        log_new, u_new, m_p, s_p, m_m, s_m = kops.mwu_update_packed(
            x_t, idx, log_lam, u, dw, sign,
            gamma=sc.gamma, tau=sc.tau, d_eff=d_eff)
    else:
        dv = dw @ cols_t                       # (n_pad,) rank-B update
        v = sign * (u + d_eff * dv)
        log_new = sc.mwu_c * (sc.mwu_dot * log_lam - v)
        u_new = u + dv
        is_p = sign > 0
        is_m = sign < 0
        m_p = jnp.max(jnp.where(is_p, log_new, NEG_INF))
        m_m = jnp.max(jnp.where(is_m, log_new, NEG_INF))
        s_p = jnp.sum(jnp.where(is_p, jnp.exp(log_new - m_p), 0.0))
        s_m = jnp.sum(jnp.where(is_m, jnp.exp(log_new - m_m), 0.0))
    # combine the per-class partials across clients (rounds 2-3): one
    # (2,) pmax + one (2,) psum
    m_loc = jnp.stack([m_p, m_m])
    s_loc = jnp.stack([s_p, s_m])
    m = _all_max(m_loc, axis_name)
    s = _all_sum(s_loc * jnp.exp(m_loc - m), axis_name)
    lse = m + jnp.log(s)
    return log_new - jnp.where(sign > 0, lse[0], lse[1]), u_new


def _capped_project_packed(log_lam, sign, nu, axis_name):
    """Sort-free round 4: the shared masked bisection core
    (projections.capped_bisect_masked) over BOTH classes in the same
    sweep.  Each round reduces one (2,) vector -- under an axis that is
    one psum of 2 scalars -- for a FIXED BISECT_ROUNDS_SOLVER rounds,
    so the round-4 scalar budget is deterministic and O(k) (Theorem 8);
    the reference Rule-3 loop's worst case is O(1/nu) data-dependent
    rounds.  Padding (sign 0) belongs to neither mask, projects to 0,
    and so keeps its NEG_INF marker."""
    masks = jnp.stack([sign > 0, sign < 0])
    eta = projections.capped_bisect_masked(
        jnp.exp(log_lam), nu, masks,
        rounds=projections.BISECT_ROUNDS_SOLVER,
        all_sum=lambda x: _all_sum(x, axis_name),
        all_max=lambda x: _all_max(x, axis_name))
    return jnp.where(eta > 0, jnp.log(jnp.maximum(eta, 1e-38)), NEG_INF)


class SlotParams(NamedTuple):
    """Per-problem step scalars, decoupled from the shape-static fields
    of ``SaddleParams`` (d, block_size) so ONE compiled executable can
    serve problems that differ only in their parameter values.

    On the classic ``step_packed(p: SaddleParams)`` path the fields are
    python floats derived at trace time (:func:`scalarize_params`) --
    the arithmetic is done in f64 on the host and baked as f32
    constants, exactly as the inline expressions used to be, so the op
    graph is unchanged.  Under the slot-batched driver each field is a
    traced per-slot f32 scalar holding the SAME f32 value (the host
    derivation also runs in f64 before the cast), which keeps the slot
    path numerically aligned with the static path.

    ``nu`` is the EFFECTIVE capped-simplex cap: for hard-margin
    problems it is 1.0, which makes the projection an exact identity
    (each class simplex already satisfies max eta_i <= 1), so
    hard-margin and nu-SVM slots can share a projecting executable.
    Whether the projection runs at all stays a STATIC choice
    (``project``).  ``gap_tol`` is the relative duality-gap early-stop
    threshold (0 disables; only read by the slot chunk driver).
    """
    theta: float | jax.Array
    sigma: float | jax.Array
    inv_sig1: float | jax.Array  # 1 / (sigma + 1), the w-update scale
    gamma: float | jax.Array
    tau: float | jax.Array
    mwu_c: float | jax.Array     # 1 / (gamma + d_eff / tau)
    mwu_dot: float | jax.Array   # d_eff / tau
    nu: float | jax.Array        # effective cap (1.0 == identity)
    gap_tol: float | jax.Array


def scalarize_params(p, gap_tol: float = 0.0) -> SlotParams:
    """Derive the per-problem step scalars from a SaddleParams in host
    (f64) arithmetic -- identical to the constants the static step has
    always baked."""
    d_eff = p.d / p.block_size
    return SlotParams(
        theta=p.theta, sigma=p.sigma, inv_sig1=1.0 / (p.sigma + 1.0),
        gamma=p.gamma, tau=p.tau,
        mwu_c=1.0 / (p.gamma + d_eff / p.tau),
        mwu_dot=d_eff / p.tau,
        nu=p.nu if p.nu > 0.0 else 1.0,
        gap_tol=gap_tol)


def slot_params_row(p, gap_tol: float = 0.0) -> SlotParams:
    """:func:`scalarize_params` as a row of f32 arrays, ready to be
    stacked into the (S,)-shaped SlotParams of a slot batch."""
    import numpy as np
    sc = scalarize_params(p, gap_tol)
    return SlotParams(*(np.float32(v) for v in sc))


def _step_packed_core(state: PackedState, key: jax.Array, x_t: jax.Array,
                      sign: jax.Array, sc: SlotParams, *, d: int,
                      block_size: int, project: bool,
                      axis_name: str | None = None,
                      backend: str = "jnp") -> PackedState:
    """The packed iteration parameterized by step SCALARS (see
    :class:`SlotParams`): shared verbatim by the classic per-problem
    step (python-float scalars) and the slot-batched driver (traced
    per-slot scalars under ``vmap``).  On the pallas backend ``x_t``
    may already be the kernels' row-tile view (:func:`_step_operand`);
    the jnp backend reads the (d, n_pad) operand."""
    d_eff = d / block_size
    idx = sample_block(key, d, block_size)
    # the named scopes only label the ops in a profile (op metadata);
    # the compiled program is the same with or without them
    with jax.named_scope("momentum_pass"):
        if backend == "pallas":
            from repro.kernels import ops as kops
            cols_t = None                    # gathered inside the kernels
            delta = kops.momentum_dot_packed(
                x_t, idx, state.log_lam, state.log_lam_prev, sign,
                sc.theta)
        else:
            cols_t = jnp.take(x_t, idx, axis=0)      # (B, n_pad) CONTIGUOUS
            lam = jnp.exp(state.log_lam)
            lam_prev = jnp.exp(state.log_lam_prev)
            delta = cols_t @ (sign * (lam + sc.theta * (lam - lam_prev)))
        delta = _all_sum(delta, axis_name)           # round 1

    # Line 4 (round 2): every client performs the identical w update
    # (delta already IS delta+ - delta-, folded by the sign).
    w_old = state.w[idx]
    w_new = (w_old + sc.sigma * delta) * sc.inv_sig1
    dw = w_new - w_old

    # Lines 5-6 (rounds 2-3): ONE packed MWU pass for both classes.
    with jax.named_scope("mwu_pass"):
        log_new, u_new = _dual_update_packed(
            x_t, idx, cols_t, state.log_lam, state.u, dw, sign, sc, d_eff,
            axis_name, backend)

    # Round 4: sort-free nu-Saddle capped-simplex projection.
    if project:
        with jax.named_scope("nu_projection"):
            log_new = _capped_project_packed(log_new, sign, sc.nu,
                                             axis_name)

    return PackedState(
        w=state.w.at[idx].set(w_new),
        log_lam=log_new, log_lam_prev=state.log_lam,
        u=u_new, t=state.t + 1,
    )


def step_packed(state: PackedState, key: jax.Array, x_t: jax.Array,
                sign: jax.Array, p, *, axis_name: str | None = None,
                backend: str = "jnp") -> PackedState:
    """One PACKED Algorithm-2/4 iteration: both classes in every sweep.

    ``x_t`` is the client's (d, n_pad) column-major mirror and ``sign``
    its +-1/0 slot vector (see preprocess.pack_points).  Under an axis,
    the key is identical across clients (the server broadcasts i*).
    """
    return _step_packed_core(state, key, x_t, sign, scalarize_params(p),
                             d=p.d, block_size=p.block_size,
                             project=p.nu > 0.0, axis_name=axis_name,
                             backend=backend)


def objective_from_duals(log_lam: jax.Array, x_t: jax.Array,
                         sign: jax.Array, axis_name=None) -> jax.Array:
    """0.5 * ||A eta - B xi||^2 from packed log duals: the signed dual
    combination x_t @ (sign * lam) IS A eta - B xi.  (Single source of
    truth -- the per-problem and per-slot objectives both call this.)"""
    diff = x_t @ (sign * jnp.exp(log_lam))
    diff = _all_sum(diff, axis_name)
    return 0.5 * jnp.sum(diff * diff)


def objective_packed(state: PackedState, x_t: jax.Array, sign: jax.Array,
                     axis_name=None) -> jax.Array:
    return objective_from_duals(state.log_lam, x_t, sign, axis_name)


def _step_operand(x_t: jax.Array, backend: str) -> jax.Array:
    """The operand the packed step reads: on the pallas backend, the
    kernels' row-tile view (``saddle_update.row_tiles``) -- one relayout
    per chunk, outside the step loop, instead of one per kernel launch."""
    if backend != "pallas":
        return x_t
    from repro.kernels.saddle_update import row_tiles
    return row_tiles(x_t)


def chunk_body_packed(state, key, x_t, sign, params, num_steps, *,
                      chunk_steps: int, axis_name: str | None = None,
                      backend: str = "jnp"):
    """Packed chunk: identical driver discipline to :func:`chunk_body`
    (static key shape, dynamic trip count, on-device objective)."""
    trace_counts[("packed", axis_name, backend, chunk_steps)] += 1

    keys = jax.random.split(key, chunk_steps)
    x_step = _step_operand(x_t, backend)

    def body(i, st):
        return step_packed(st, keys[i], x_step, sign, params,
                           axis_name=axis_name, backend=backend)

    state = jax.lax.fori_loop(0, num_steps, body, state)
    return state, objective_packed(state, x_t, sign, axis_name)


@functools.partial(jax.jit,
                   static_argnames=("params", "chunk_steps", "backend"),
                   donate_argnums=(0,))
def run_chunk_packed(state, key, x_t, sign, num_steps, *, params,
                     chunk_steps: int, backend: str = "jnp"):
    """Serial packed chunk: state buffers donated, objective returned as
    a device scalar, one compile for all chunk lengths."""
    return chunk_body_packed(state, key, x_t, sign, params, num_steps,
                             chunk_steps=chunk_steps, axis_name=None,
                             backend=backend)


# ==========================================================================
# Slot-batched driver (multi-tenant serving): S independent problems
# through ONE compiled step via vmap over a leading slot axis.
# ==========================================================================


class SlotState(NamedTuple):
    """S independent packed solver states stacked on a leading SLOT
    axis, plus the per-slot serving lifecycle fields.

    A slot is a reusable execution lane of the multi-tenant driver:

      FREE      ``active == False`` and no request assigned.  The lane
                still flows through the vmapped step every iteration
                (that is what keeps the executable shape-static), but
                every result is discarded by the active mask.
      RUNNING   ``active == True``: the slot steps while
                ``t < max_t`` and its duality gap is above the slot's
                ``gap_tol``.
      FINISHED  the chunk driver flipped ``active`` off (budget
                exhausted or gap converged).  The state stays intact
                until the host harvests it and either re-admits a new
                request into the lane (:func:`admit_into_slot`
                overwrites EVERY field -- no state can leak from the
                previous occupant) or leaves it FREE.

    ``key`` is the per-slot PRNG chain: each chunk splits it exactly
    like the serial driver splits its solve key, so a slot admitted at
    seed s replays the SAME block-coordinate schedule as a solo
    ``saddle.solve(seed=s)`` at the same bucket shape.
    """
    w: jax.Array             # (S, d)
    log_lam: jax.Array       # (S, n_pad)
    log_lam_prev: jax.Array  # (S, n_pad)
    u: jax.Array             # (S, n_pad)
    t: jax.Array             # (S,) per-slot iteration counter
    max_t: jax.Array         # (S,) per-slot iteration budget
    key: jax.Array           # (S,) per-slot PRNG chains
    active: jax.Array        # (S,) bool lifecycle mask

    @property
    def num_slots(self) -> int:
        return self.w.shape[0]


def init_slot_state(num_slots: int, n_pad: int, d: int) -> SlotState:
    """An all-FREE slot table for one (n_pad, d) bucket."""
    s = num_slots
    neg = jnp.full((s, n_pad), NEG_INF, jnp.float32)
    return SlotState(
        w=jnp.zeros((s, d), jnp.float32),
        log_lam=neg, log_lam_prev=jnp.copy(neg),
        u=jnp.zeros((s, n_pad), jnp.float32),
        t=jnp.zeros((s,), jnp.int32),
        max_t=jnp.zeros((s,), jnp.int32),
        key=jax.random.split(jax.random.key(0), s),
        active=jnp.zeros((s,), bool),
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def admit_into_slot(state: SlotState, slot: jax.Array,
                    pstate: PackedState, key: jax.Array,
                    max_t: jax.Array) -> SlotState:
    """Admit a freshly initialized problem into lane ``slot`` (a traced
    index: one compile serves every lane).  Every per-slot field is
    overwritten -- w, duals, u, t, budget, PRNG chain, active flag --
    so a reused lane cannot leak its previous occupant's state."""
    return SlotState(
        w=state.w.at[slot].set(pstate.w),
        log_lam=state.log_lam.at[slot].set(pstate.log_lam),
        log_lam_prev=state.log_lam_prev.at[slot].set(pstate.log_lam_prev),
        u=state.u.at[slot].set(pstate.u),
        t=state.t.at[slot].set(pstate.t),
        max_t=state.max_t.at[slot].set(jnp.asarray(max_t, jnp.int32)),
        key=state.key.at[slot].set(key),
        active=state.active.at[slot].set(True),
    )


def _capped_min_masked(scores: jax.Array, mask: jax.Array,
                       nu: jax.Array) -> jax.Array:
    """min_{eta in D(nu)} <scores, eta> restricted to ``mask`` with a
    TRACED cap: greedy water-filling puts weight min(nu, max(0, 1-i*nu))
    on the i-th smallest masked score.  nu=1 degenerates to the plain
    min (the hard-margin inner problem), so one formula serves both
    slot kinds."""
    big = jnp.float32(1e30)
    s = jnp.sort(jnp.where(mask, scores, big))
    w = jnp.clip(1.0 - jnp.arange(s.shape[0]) * nu, 0.0, nu)
    return jnp.sum(jnp.where(w > 0, s * w, 0.0))


def saddle_gap_packed(w: jax.Array, x_t: jax.Array, sign: jax.Array,
                      nu: jax.Array) -> jax.Array:
    """g(w) = min_{eta,xi} w^T A eta - w^T B xi - ||w||^2/2 on the
    packed layout (the per-slot early-stop diagnostic; nu here is the
    EFFECTIVE cap, 1.0 for hard margin)."""
    s = w @ x_t                                      # (n_pad,) <w, x_i>
    inner_p = _capped_min_masked(s, sign > 0, nu)
    inner_m = -_capped_min_masked(-s, sign < 0, nu)
    return inner_p - inner_m - 0.5 * jnp.sum(w * w)


@functools.partial(jax.jit, donate_argnums=(0,))
def deactivate_slot(state: SlotState, slot) -> SlotState:
    """Freeze one lane (traced ``slot`` index: one compile total) --
    the serving layer's cancellation path.  The lane's buffers are
    left as-is; admission overwrites every field anyway."""
    return state._replace(active=state.active.at[slot].set(False))


def slot_trace_key(num_slots: int, n_pad: int, d: int, block_size: int,
                   chunk_steps: int, project: bool, check_gap: bool,
                   backend: str, axis_name=None) -> tuple:
    """The ``trace_counts`` key of one slot-chunk executable -- i.e.
    the compile-cache key a serving layer warms per bucket.  Shapes are
    the PER-DEVICE shapes the chunk body is traced at (``shard_map``
    hands the body its local shard); ``axis_name`` is the point-axis
    tuple of a sharded-slot chunk, None for the collective-free kinds.
    """
    key = ("slots", num_slots, n_pad, d, block_size, chunk_steps,
           project, check_gap, backend)
    if axis_name is not None:
        key += ("axis", axis_name)
    return key


def chunk_body_slots(state: SlotState, x_t: jax.Array, sign: jax.Array,
                     sp: SlotParams, num_steps, *, chunk_steps: int,
                     d: int, block_size: int, project: bool,
                     check_gap: bool, backend: str = "jnp",
                     axis_name=None):
    """One slot-batched chunk: ``num_steps`` (dynamic, <= static
    ``chunk_steps``) vmapped packed iterations over every lane.

    Per iteration each slot advances iff ``active & (t < max_t)`` --
    the step is computed for every lane (shape-static) and discarded
    by the mask, so a lane that exhausts its budget mid-chunk freezes
    at exactly ``max_t`` iterations (same schedule as a solo solve)
    without halting the batch.  Each slot draws its block coordinates
    from its OWN key chain: the chain is split once per chunk (exactly
    the serial driver's ``key, sub = split(key)`` discipline) and the
    per-step keys are pre-split at the static ``chunk_steps`` shape.

    At the chunk boundary every slot's objective is computed on device
    and -- when ``check_gap`` -- its duality gap (:func:
    `saddle_gap_packed`); a slot whose relative gap falls below its
    ``gap_tol`` or whose budget is exhausted goes inactive, freeing
    its lane for mid-run admission.

    Slot health: the same boundary computes a per-slot finite-health
    flag -- ``w``/``u`` all finite, ``log_lam`` free of NaN/+inf (the
    ``NEG_INF`` padding sentinel is finite and passes), objective
    finite.  An unhealthy slot is deactivated ON DEVICE in the same
    masked style as convergence, so a diverged/poisoned lane freezes
    immediately instead of burning its remaining budget -- and because
    lanes are vmapped independently, batch-mates' trajectories are
    bit-for-bit unaffected.  The serving layer reads the flag from the
    chunk's single host transfer and quarantines the lane.

    Under ``axis_name`` (the sharded-slot serving path) every slot's
    POINT axis is a shard: the vmapped step runs the same Theorem-8
    collective rounds as the solo distributed step -- vmap batches each
    round into ONE launch whose payload scales by S -- and the chunk
    boundary adds exactly two more: the objective's psum and a health
    agreement reduce that keeps ``active`` replica-consistent (``u`` /
    ``log_lam`` are shard-local, so one shard's overflow must
    quarantine the slot on EVERY shard).  ``check_gap`` is rejected:
    the gap's water-filling sorts the full point axis and does not
    distribute.

    Returns (new_state, obj (S,), healthy (S,) bool).
    """
    if check_gap and axis_name is not None:
        raise ValueError(
            "check_gap is not supported for point-sharded slot chunks "
            "(saddle_gap_packed sorts the full point axis); submit "
            "sharded fits with gap_tol=0")
    trace_counts[slot_trace_key(
        state.num_slots, x_t.shape[-1], d, block_size, chunk_steps,
        project, check_gap, backend, axis_name)] += 1  # trace-time only

    splits = jax.vmap(jax.random.split)(state.key)   # (S, 2)
    chain, chunk_key = splits[:, 0], splits[:, 1]
    keys = jax.vmap(lambda k: jax.random.split(k, chunk_steps))(chunk_key)
    x_step = _step_operand(x_t, backend)

    def step_slot(ps, key_i, x_t_i, sign_i, row):
        return _step_packed_core(ps, key_i, x_t_i, sign_i, row, d=d,
                                 block_size=block_size, project=project,
                                 axis_name=axis_name, backend=backend)

    def body(i, st):
        ps = PackedState(w=st.w, log_lam=st.log_lam,
                         log_lam_prev=st.log_lam_prev, u=st.u, t=st.t)
        new = jax.vmap(step_slot)(ps, keys[:, i], x_step, sign, sp)
        do = st.active & (st.t < st.max_t)           # (S,)
        sel = lambda n, o: jnp.where(
            do.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
        return st._replace(
            w=sel(new.w, st.w), log_lam=sel(new.log_lam, st.log_lam),
            log_lam_prev=sel(new.log_lam_prev, st.log_lam_prev),
            u=sel(new.u, st.u), t=sel(new.t, st.t))

    state = jax.lax.fori_loop(0, num_steps, body, state)
    state = state._replace(key=chain)

    obj = jax.vmap(
        lambda ll, xt, sg: objective_from_duals(ll, xt, sg, axis_name)
    )(state.log_lam, x_t, sign)

    with jax.named_scope("health_check"):
        healthy = (jnp.isfinite(state.w).all(axis=-1)
                   & jnp.isfinite(state.u).all(axis=-1)
                   & ~jnp.isnan(state.log_lam).any(axis=-1)
                   & ~jnp.isposinf(state.log_lam).any(axis=-1)
                   & jnp.isfinite(obj))
        if axis_name is not None:
            # u / log_lam health is shard-local: agree across point
            # shards so the replicated ``active`` mask stays
            # replica-consistent.
            healthy = _all_sum(
                jnp.where(healthy, 0.0, 1.0), axis_name) == 0.0

    done = (state.t >= state.max_t) | ~healthy
    if check_gap:
        with jax.named_scope("gap_check"):
            gap = jax.vmap(saddle_gap_packed)(state.w, x_t, sign, sp.nu)
            converged = (sp.gap_tol > 0) & (
                obj - gap <= sp.gap_tol * jnp.maximum(obj, 1e-12))
        done = done | converged
    return state._replace(active=state.active & ~done), obj, healthy


@functools.partial(jax.jit,
                   static_argnames=("chunk_steps", "d", "block_size",
                                    "project", "check_gap", "backend"),
                   donate_argnums=(0,))
def run_chunk_slots(state: SlotState, x_t: jax.Array, sign: jax.Array,
                    sp: SlotParams, num_steps, *, chunk_steps: int,
                    d: int, block_size: int, project: bool,
                    check_gap: bool = False, backend: str = "jnp"):
    """Jitted slot-batched chunk: slot-state buffers donated (updated in
    place), per-slot objectives AND finite-health flags returned as
    device vectors (see :func:`chunk_body_slots`).  One compile serves
    every chunk length up to ``chunk_steps`` and every admission
    pattern -- the data buffers (``x_t``, ``sign``) and the per-slot
    SlotParams are plain dynamic arguments."""
    return chunk_body_slots(state, x_t, sign, sp, num_steps,
                            chunk_steps=chunk_steps, d=d,
                            block_size=block_size, project=project,
                            check_gap=check_gap, backend=backend)


# --------------------------------------------------------------------------
# Mesh-sharded slot chunk: the SAME chunk body under shard_map, with two
# orthogonal placements a serving layer composes per slot group:
#
#   slot_axes    the SLOT axis is data-parallel over these mesh axes --
#                each device owns its own lanes, steps them with
#                axis_name=None, and exchanges ZERO loop collectives
#                (the unsharded slot-group placement).
#   point_axes   every slot's POINT axis spans these mesh axes and the
#                step runs the Theorem-8 collective rounds over them
#                (the sharded-slot placement for large-n fits).
# --------------------------------------------------------------------------


def _normalize_axes(point_axes) -> tuple | None:
    """The in-step ``axis_name`` for a point-axis tuple (None == serial)."""
    return tuple(point_axes) or None


def sharded_slot_run_fn(mesh: jax.sharding.Mesh, *, slot_axes=(),
                        point_axes=(), chunk_steps: int, d: int,
                        block_size: int, project: bool,
                        check_gap: bool = False, backend: str = "jnp"):
    """UN-jitted ``shard_map``-wrapped slot chunk over ``mesh`` (AOT
    lowering / audit entry; :func:`run_chunk_slots_sharded` is the
    dispatch path).  Placement per the module-level table: the slot
    axis shards over ``slot_axes``, the point axis over ``point_axes``
    (disjoint; either may be empty).  Per-slot lifecycle rows (``t``,
    ``max_t``, ``key``, ``active``) and ``w`` are replicated across
    ``point_axes``; ``check_vma=False`` because psum-produced outputs
    defeat shard_map's static replication check.
    """
    from jax.sharding import PartitionSpec as P

    slot_axes, point_axes = tuple(slot_axes), tuple(point_axes)
    overlap = set(slot_axes) & set(point_axes)
    if overlap:
        raise ValueError(f"slot_axes and point_axes overlap: {overlap}")
    for a in slot_axes + point_axes:
        if a not in mesh.axis_names:
            raise ValueError(f"axis {a!r} not in mesh {mesh.axis_names}")
    axis_name = _normalize_axes(point_axes)

    s = slot_axes or None           # slot-dim placement
    p = point_axes or None          # point-dim placement
    state_spec = SlotState(
        w=P(s), log_lam=P(s, p), log_lam_prev=P(s, p), u=P(s, p),
        t=P(s), max_t=P(s), key=P(s), active=P(s))
    sp_spec = SlotParams(*(P(s) for _ in SlotParams._fields))

    def local_fn(st, x_t, sign, sp, num_steps):
        return chunk_body_slots(
            st, x_t, sign, sp, num_steps, chunk_steps=chunk_steps, d=d,
            block_size=block_size, project=project, check_gap=check_gap,
            backend=backend, axis_name=axis_name)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(state_spec, P(s, None, p), P(s, p), sp_spec, P()),
        out_specs=(state_spec, P(s), P(s)),
        check_vma=False)


@functools.lru_cache(maxsize=None)
def _sharded_slot_runner(mesh, slot_axes, point_axes, chunk_steps, d,
                         block_size, project, check_gap, backend):
    return jax.jit(
        sharded_slot_run_fn(mesh, slot_axes=slot_axes,
                            point_axes=point_axes, chunk_steps=chunk_steps,
                            d=d, block_size=block_size, project=project,
                            check_gap=check_gap, backend=backend),
        donate_argnums=(0,))


def run_chunk_slots_sharded(state: SlotState, x_t: jax.Array,
                            sign: jax.Array, sp: SlotParams, num_steps, *,
                            mesh: jax.sharding.Mesh, slot_axes=(),
                            point_axes=(), chunk_steps: int, d: int,
                            block_size: int, project: bool,
                            check_gap: bool = False,
                            backend: str = "jnp"):
    """Mesh-sharded :func:`run_chunk_slots`: same signature and return
    contract plus the (mesh, slot_axes, point_axes) placement, slot
    state donated.  The jitted runner is cached per placement+statics
    (``Mesh`` hashes by device assignment), so the serving layer pays
    one trace per warmed bucket exactly as on a single device."""
    run = _sharded_slot_runner(mesh, tuple(slot_axes), tuple(point_axes),
                               chunk_steps, d, block_size, project,
                               check_gap, backend)
    return run(state, x_t, sign, sp, jnp.asarray(num_steps, jnp.int32))


def sharded_slot_trace_key(num_slots: int, n_pad: int, d: int,
                           block_size: int, chunk_steps: int,
                           project: bool, check_gap: bool, backend: str,
                           mesh: jax.sharding.Mesh, slot_axes=(),
                           point_axes=()) -> tuple:
    """:func:`slot_trace_key` of one mesh-sharded chunk executable, from
    GLOBAL shapes: shard_map traces the body at the per-device shard, so
    the slot dim divides by the slot-axes extent and the point dim by
    the point-axes extent."""
    ks = math.prod(mesh.shape[a] for a in slot_axes) if slot_axes else 1
    kp = math.prod(mesh.shape[a] for a in point_axes) if point_axes else 1
    return slot_trace_key(num_slots // ks, n_pad // kp, d, block_size,
                          chunk_steps, project, check_gap, backend,
                          _normalize_axes(tuple(point_axes)))


@functools.partial(jax.jit,
                   static_argnames=("chunk_steps", "num_chunks", "d",
                                    "block_size", "project", "check_gap",
                                    "backend"),
                   donate_argnums=(0,))
def run_solve_slots(state: SlotState, x_t: jax.Array, sign: jax.Array,
                    sp: SlotParams, num_iters, *, chunk_steps: int,
                    num_chunks: int, d: int, block_size: int,
                    project: bool, check_gap: bool = False,
                    backend: str = "jnp"):
    """DEVICE-RESIDENT multi-chunk solve driver: the whole chunked solve
    in ONE executable, so a full solve is a single dispatch and a single
    end-of-solve host transfer.

    The host chunk loop this replaces re-dispatched
    :func:`run_chunk_slots` once per chunk and -- whenever the duality
    gap was enabled -- blocked on a ``device_get`` of the active mask at
    every chunk boundary, serializing host<->device round-trips into the
    hot path.  Here the outer loop is a ``lax.while_loop`` keyed on the
    slot-active flag: it runs the SAME :func:`chunk_body_slots` the
    per-chunk driver jits (bit-for-bit identical state trajectory, key
    schedule and gap/health semantics), writes each boundary's per-slot
    objective and iteration mark into preallocated device history
    buffers, and exits as soon as every lane is inactive (budget
    exhausted, gap converged, or health-frozen) or ``num_iters`` is
    dispatched.  The gap-enabled path therefore needs ZERO per-chunk
    host polls -- convergence is consumed by the loop condition on
    device.

    ``num_chunks`` (static) is the history capacity,
    ``ceil(num_iters / chunk_steps)`` for a full-budget run; a gap stop
    leaves the tail unwritten.  Returns ``(state, objs (num_chunks, S),
    marks (num_chunks, S), chunks_done)`` -- callers slice the history
    to ``chunks_done`` rows after ONE transfer.  ``marks`` records each
    slot's iteration counter at the boundary, which equals the
    cumulative dispatched iterations while the slot is live (and the
    exact stop iteration on a gap stop).

    The per-chunk :func:`run_chunk_slots` stays the serving entry point:
    ``SolverService`` needs the host back between chunks to harvest
    finished lanes and admit queued requests; a solo solve does not.
    """
    S = state.num_slots
    objs = jnp.zeros((num_chunks, S), jnp.float32)
    marks = jnp.zeros((num_chunks, S), jnp.int32)
    num_iters = jnp.asarray(num_iters, jnp.int32)

    def cond(carry):
        st, done, i, _objs, _marks = carry
        return (done < num_iters) & st.active.any()

    def body(carry):
        st, done, i, objs, marks = carry
        ns = jnp.minimum(chunk_steps, num_iters - done)
        st, obj, _healthy = chunk_body_slots(
            st, x_t, sign, sp, ns, chunk_steps=chunk_steps, d=d,
            block_size=block_size, project=project, check_gap=check_gap,
            backend=backend)
        return (st, done + ns, i + 1,
                objs.at[i].set(obj), marks.at[i].set(st.t))

    state, _done, i, objs, marks = jax.lax.while_loop(
        cond, body, (state, jnp.asarray(0, jnp.int32),
                     jnp.asarray(0, jnp.int32), objs, marks))
    return state, objs, marks, i


def drive(state, key, num_iters: int, chunk: int, run) -> tuple:
    """Shared host loop: split one key per chunk, dispatch fixed-shape
    chunks, accumulate device scalars, transfer history ONCE at the end.

    ``run(state, subkey, steps_remaining) -> (state, obj)`` is the
    mode-specific jitted chunk.  Returns (state, [(done, obj), ...]).
    """
    import numpy as np

    objs, marks = [], []
    done = 0
    while done < num_iters:
        key, sub = jax.random.split(key)
        ns = min(chunk, num_iters - done)
        state, obj = run(state, sub, ns)
        done += ns
        objs.append(obj)
        marks.append(done)
    # per-client objectives (k,) are identical across clients; take [0]
    objs = [float(np.asarray(o).reshape(-1)[0]) for o in jax.device_get(objs)]
    return state, list(zip(marks, objs))
