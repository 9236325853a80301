"""Saddle-SVC (Algorithm 2): stochastic primal--dual coordinate solver for
HM-Saddle (hard-margin SVM) and nu-Saddle (nu-SVM).

Layout convention: the USER-facing point matrices are row-major,
``xp[i] = x_i^+`` (shape (n1, d)) -- the paper's column ``X_{.i}``
(point i) is ``xp[i]``.  The SOLVER, however, runs on the packed +-
layout of :func:`repro.core.preprocess.pack_points`: both classes in
one lane-padded point set with a +-1 ``sign`` vector, stored as the
COLUMN-major mirror ``x_t`` of shape (d, n_pad) so the sampled
coordinate row ``X_{i*,.}`` is the CONTIGUOUS row ``x_t[i*]`` rather
than a strided column of a row-major matrix.  ``solve`` packs on entry
and unpacks the final state back into this module's per-class
:class:`SaddleState`, so the packed layout never leaks to callers.

The actual iteration lives in :mod:`repro.core.engine` -- ONE fused
single-sweep step (``engine.step_packed``) shared by this serial front
end, the distributed solver (:mod:`repro.core.distributed`), and the
Pallas-kernel backend (``backend="pallas"`` / ``use_kernels=True``).
This module keeps the paper-facing API: parameter formulas (Algorithm 1
line 4), state init, the objective/saddle-gap diagnostics, and
:func:`solve`.

Faithfulness notes:
  * With ``block_size=1`` this is exactly Algorithm 2: one uniformly
    random coordinate i* per iteration, momentum theta on the duals,
    momentum d*(w[t+1]-w[t]) on the primal, entropy-prox (MWU) dual
    updates, and the nu-Saddle capped-simplex projection (Rule 2).
  * The per-point inner products u_i = <w, x_i> are maintained
    incrementally (rank-1 update) so one iteration costs O(n), matching
    Theorem 6.
  * ``block_size=B>1`` is the beyond-paper TPU block-coordinate mode
    (DESIGN.md section 2): B lane-aligned coordinates per iteration,
    sampled WITHOUT replacement so the rank-B update of u stays exact.
    B=1 recovers the paper exactly.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core import preprocess as pp
from repro.kernels import resolve_use_kernels
from repro.utils.spans import span


class SaddleParams(NamedTuple):
    gamma: float
    q: float
    tau: float
    sigma: float
    theta: float
    d: int
    block_size: int
    nu: float          # 0.0 => HM-Saddle (no cap)


class SaddleState(NamedTuple):
    w: jax.Array            # (d,)
    log_eta: jax.Array      # (n1,)
    log_eta_prev: jax.Array
    log_xi: jax.Array       # (n2,)
    log_xi_prev: jax.Array
    u_p: jax.Array          # (n1,)  <w, x_i^+> maintained incrementally
    u_m: jax.Array          # (n2,)
    t: jax.Array            # iteration counter


def make_params(n: int, d: int, eps: float, beta: float,
                nu: float = 0.0, block_size: int = 1,
                block_scaling: str = "lane") -> SaddleParams:
    """Line 4 of Algorithm 1 (with the paper's q = O(sqrt(log n))).

    block_scaling (only matters for block_size > 1; B=1 is identical):
      "lane"   -- keep the PAPER's (tau, sigma, theta) and simply update
                  B coordinates per iteration.  Empirically dominant
                  (EXPERIMENTS.md section Perf: 70x fewer outer
                  iterations at B=128 on d=256), because each block step
                  makes ~B coordinates of primal progress against an
                  unchanged dual step size.
      "scaled" -- rescale with d_eff = d/B (the naive extension treating
                  a block step as B averaged coordinate steps); measured
                  strictly worse -- kept for the ablation.
    """
    if not 1 <= block_size <= d:
        raise ValueError(
            f"block_size={block_size} must be in [1, d={d}] (blocks are "
            "sampled without replacement)")
    gamma = eps * beta / (2.0 * math.log(max(n, 3)))
    q = max(1.0, math.sqrt(math.log(max(n, 3))))
    d_eff = d / block_size if block_scaling == "scaled" else d
    tau = 0.5 / q * math.sqrt(d_eff / gamma)
    sigma = 0.5 / q * math.sqrt(d_eff * gamma)
    theta = 1.0 - 1.0 / (d_eff + q * math.sqrt(d_eff) / math.sqrt(gamma))
    return SaddleParams(gamma=gamma, q=q, tau=tau, sigma=sigma, theta=theta,
                        d=d, block_size=block_size, nu=float(nu))


def default_iterations(d: int, eps: float, beta: float,
                       n: int = 1000) -> int:
    """Theorem 6 iteration count: Õ(d + sqrt(d / (eps * beta)))."""
    logn = math.log(max(n, 3))
    return int(2 * (d + math.sqrt(2.0 * d / (eps * beta)) * logn))


def validate_nu(nu: float, n1: int, n2: int) -> None:
    """The nu-SVM cap is feasible only when each class simplex can
    absorb total mass 1: nu >= 1/min(n1, n2)."""
    if nu > 0.0 and nu * min(n1, n2) < 1.0:
        raise ValueError(
            f"nu={nu} infeasible: need nu >= 1/min(n1,n2) = {1.0/min(n1,n2)}")


def resolve_num_iters(num_iters: int | None, d: int, eps: float,
                      beta: float, n: int, block_size: int) -> int:
    """THE iteration-budget derivation (defaulting + block scaling),
    shared by :func:`solve` and the serving layer so a request's
    schedule cannot drift from a solo solve's."""
    if num_iters is None:
        num_iters = default_iterations(d, eps, beta, n)
    return max(1, num_iters // block_size)


def init_state(n1: int, n2: int, d: int,
               xp: jax.Array, xm: jax.Array) -> SaddleState:
    """Line 5 of Algorithm 1: w=0, eta=1/n1, xi=1/n2 (two copies)."""
    del xp, xm  # u starts at zero because w starts at zero
    log_eta = jnp.full((n1,), -math.log(n1), jnp.float32)
    log_xi = jnp.full((n2,), -math.log(n2), jnp.float32)
    # distinct buffers for the "prev" copies: the engine donates the
    # state, and XLA rejects donating the same buffer twice
    return SaddleState(
        w=jnp.zeros((d,), jnp.float32),
        log_eta=log_eta, log_eta_prev=jnp.copy(log_eta),
        log_xi=log_xi, log_xi_prev=jnp.copy(log_xi),
        u_p=jnp.zeros((n1,), jnp.float32),
        u_m=jnp.zeros((n2,), jnp.float32),
        t=jnp.zeros((), jnp.int32),
    )


def saddle_step(state: SaddleState, key: jax.Array, xp: jax.Array,
                xm: jax.Array, p: SaddleParams) -> SaddleState:
    """One iteration of Algorithm 2 (thin wrapper over the engine)."""
    return engine.step(state, key, xp, xm, p)


def saddle_step_kernels(state: SaddleState, key: jax.Array, xp: jax.Array,
                        xm: jax.Array, p: SaddleParams) -> SaddleState:
    """Algorithm 2 iteration backed by the Pallas kernels (same engine
    step behind ``backend="pallas"``); numerically equivalent to
    :func:`saddle_step` (tested), validated here in interpret mode."""
    return engine.step(state, key, xp, xm, p, backend="pallas")


@functools.partial(jax.jit,
                   static_argnames=("num_steps", "params", "use_kernels"))
def run_chunk(state: SaddleState, key: jax.Array, xp: jax.Array,
              xm: jax.Array, params: SaddleParams, num_steps: int,
              use_kernels: bool = False) -> SaddleState:
    """Run exactly ``num_steps`` REFERENCE (unpacked) iterations under
    jit.

    Compatibility entry point: compiles per distinct ``num_steps`` (it
    is static here) and runs the unpacked oracle step.  Solves should
    use :func:`solve`, which runs the packed single-sweep engine with a
    dynamic trip count (one compile for all chunk lengths).
    """
    backend = "pallas" if use_kernels else "jnp"
    state, _ = engine.chunk_body(state, key, xp, xm, params, num_steps,
                                 chunk_steps=num_steps, backend=backend)
    return state


def objective(log_eta: jax.Array, log_xi: jax.Array, xp: jax.Array,
              xm: jax.Array) -> jax.Array:
    """C-Hull / RC-Hull objective 0.5 * ||A eta - B xi||^2."""
    diff = jnp.exp(log_eta) @ xp - jnp.exp(log_xi) @ xm
    return 0.5 * jnp.sum(diff * diff)


def saddle_gap(state: SaddleState, xp: jax.Array, xm: jax.Array,
               nu: float = 0.0) -> jax.Array:
    """g(w) = min_{eta,xi} w^T A eta - w^T B xi - ||w||^2 / 2.

    For HM-Saddle the inner min over the simplex is attained at a vertex;
    for nu-Saddle at a capped-simplex vertex (greedy water-filling:
    put nu on the 1/nu smallest entries).
    """
    sp = xp @ state.w     # (n1,) <w, x_i^+>
    sm = xm @ state.w
    if nu <= 0.0:
        inner = jnp.min(sp) - jnp.max(sm)
    else:
        inner = _capped_min(sp, nu) - (-_capped_min(-sm, nu))
    return inner - 0.5 * jnp.sum(state.w ** 2)


def _capped_min(scores: jax.Array, nu: float) -> jax.Array:
    """min_{eta in D} <scores, eta>: greedily fill nu on smallest scores."""
    n = scores.shape[0]
    s = jnp.sort(scores)
    k = int(math.floor(1.0 / nu))
    weights = jnp.where(jnp.arange(n) < k, nu, 0.0)
    weights = weights.at[min(k, n - 1)].add(max(1.0 - k * nu, 0.0))
    return jnp.dot(s, weights)


def unpack_state(pstate: engine.PackedState, n1: int,
                 n2: int) -> SaddleState:
    """Slice a packed solver state back into the per-class view (see
    engine.unpack_state for the slot layout)."""
    return engine.unpack_state(pstate, n1, n2, SaddleState)


# Default duality-gap checking cadence when gap_tol > 0 and the caller
# gave no record_every: frequent enough to realize most of the early
# stop's savings, coarse enough that the per-boundary gap evaluation
# (one masked sort + objective, on device -- the device-resident driver
# issues NO host sync at boundaries) stays negligible against the
# chunk's iterations.  Re-derived by the predict-then-verify cadence
# study in benchmarks/engine_bench.py (full mode): the boundary check
# costs ~4-6 iterations, so the sqrt(2 * T * check / step) optimum for
# typical stop horizons (T ~ 3k-30k) lands in the 128-512 band; 256
# stays the default.
GAP_CHECK_EVERY = 256


class SolveResult(NamedTuple):
    state: SaddleState
    history: list            # [(iteration, objective)]


def solve(xp: jax.Array, xm: jax.Array, *, eps: float = 1e-3,
          beta: float = 0.1, nu: float = 0.0, num_iters: int | None = None,
          block_size: int = 1, seed: int = 0,
          record_every: int | None = None,
          use_kernels: bool | None = None, n_pad: int | None = None,
          d_pad: int | None = None, gap_tol: float = 0.0,
          driver: str = "device",
          warm_start: SaddleState | None = None) -> SolveResult:
    """Run Saddle-SVC on (already preprocessed) data.

    Args:
      xp, xm: (n1, d), (n2, d) transformed point matrices.
      nu: 0 for hard margin; else the nu-SVM cap (must be >= 1/min(n1,n2)).
      n_pad, d_pad: optional BUCKET shape (see preprocess.bucket_shape):
        pad the packed point axis to n_pad and the coordinate axis to
        d_pad so the solve is slot-for-slot reproducible against the
        multi-tenant serving engine running the same bucket.  Padding
        coordinates are inert (w stays 0 there) but DO change the
        block-sampling schedule, which is exactly what sharing a
        bucket's executable requires.
      gap_tol: relative duality-gap early stop -- terminate once
        (objective - saddle_gap) <= gap_tol * objective, checked at
        chunk boundaries.  0 disables (the default: fixed iteration
        budget, reproducible schedule).  With gap_tol > 0 and no
        record_every, the chunk defaults to GAP_CHECK_EVERY iterations
        so the check actually fires before the budget is spent.
      driver: "device" (default) runs the WHOLE chunked solve as one
        executable (``engine.run_solve_slots``: a ``lax.while_loop``
        over the chunk body keyed on the slot-active flag, history in a
        preallocated device buffer, ONE host transfer at the end -- zero
        per-chunk host syncs, gap-enabled or not).  "host" is the
        per-chunk dispatch loop it replaced (one ``run_chunk_slots``
        launch per chunk; with gap_tol > 0, a blocking active-mask
        readback per boundary), retained for the transition as the
        bit-for-bit parity oracle of the device driver.
      warm_start: a previous :class:`SaddleState` (typically a prior
        fit of a PREFIX of this problem: its classes must be leading
        subsets of the new ones, in order).  The solve then starts from
        the carried ``w``, duals and momentum instead of the uniform
        init: new points' dual mass is seeded at the new uniform level
        and the next MWU normalizer round renormalizes each class
        (``preprocess.repack_warm_duals``), ``u`` is recomputed on
        device from the carried w (``engine.warm_packed_state``), and
        ``t`` restarts at 0 so the result's history counts the warm
        run's own iterations.  The trace keys of the hot chunk
        executables are UNCHANGED -- warm and cold solves at the same
        bucket share one compiled chunk.
      use_kernels: True runs the step on the Pallas kernels, False on
        jax.numpy; None (the default) chooses from the platform
        (:func:`repro.kernels.resolve_use_kernels`: the kernels on a
        TPU, jnp elsewhere).

    The hot loop is the SLOT-BATCHED engine driver at S=1 (one engine
    serves the serial solver and the multi-tenant service; the unpacked
    ``engine.step`` remains the parity oracle).  Both drivers run the
    same ``engine.chunk_body_slots`` chunk with the same key schedule,
    so their histories and final states are bit-for-bit equal; the
    chunk's trip count is dynamic, so the final partial chunk neither
    recompiles nor executes padded steps.
    """
    n1, d = xp.shape
    n2 = xm.shape[0]
    validate_nu(nu, n1, n2)
    if driver not in ("device", "host"):
        raise ValueError(f"driver={driver!r} must be 'device' or 'host'")
    if d_pad is not None:
        d = d_pad
    params = make_params(n1 + n2, d, eps, beta, nu=nu, block_size=block_size)
    num_iters = resolve_num_iters(num_iters, d, eps, beta, n1 + n2,
                                  block_size)
    check_gap = gap_tol > 0.0
    if record_every is None and check_gap:
        record_every = GAP_CHECK_EVERY   # else the gap never fires
    chunk = min(record_every or num_iters, num_iters)
    use_kernels = resolve_use_kernels(use_kernels)
    backend = "pallas" if use_kernels else "jnp"

    with span("saddle.solve"):
        with span("saddle.pack"):
            pts = pp.pack_points_to(
                xp, xm, n_pad or pp.packed_length(n1 + n2), d)
            if warm_start is None:
                pstate = engine.init_packed_state(pts.sign, n1, n2, d)
            else:
                n1_w = warm_start.log_eta.shape[0]
                n2_w = warm_start.log_xi.shape[0]
                lam_old = np.concatenate(
                    [np.asarray(warm_start.log_eta),
                     np.asarray(warm_start.log_xi)])
                prev_old = np.concatenate(
                    [np.asarray(warm_start.log_eta_prev),
                     np.asarray(warm_start.log_xi_prev)])
                lam = pp.repack_warm_duals(lam_old, n1_w, n2_w, n1, n2,
                                           pts.n_pad)
                prev = pp.repack_warm_duals(prev_old, n1_w, n2_w, n1, n2,
                                            pts.n_pad)
                w = np.zeros((d,), np.float32)
                w[: warm_start.w.shape[0]] = np.asarray(warm_start.w)
                pstate = engine.warm_packed_state(
                    pts.x_t, jnp.asarray(w), jnp.asarray(lam),
                    jnp.asarray(prev))
            sstate = engine.init_slot_state(1, pts.n_pad, d)
            sstate = engine.admit_into_slot(
                sstate, 0, pstate, jax.random.key(seed), num_iters)
            sp = jax.tree.map(lambda v: jnp.asarray(v)[None],
                              engine.slot_params_row(params, gap_tol))
            x_t_b, sign_b = pts.x_t[None], pts.sign[None]

        with span("saddle.run", steps=num_iters, pallas=int(use_kernels)):
            if driver == "device":
                sstate, objs_d, marks_d, nc_d = engine.run_solve_slots(
                    sstate, x_t_b, sign_b, sp, num_iters,
                    chunk_steps=chunk, num_chunks=-(-num_iters // chunk), d=d,
                    block_size=block_size, project=nu > 0.0,
                    check_gap=check_gap, backend=backend)
                # the solve's ONE host transfer: history + chunk count
                objs_h, marks_h, nc = jax.device_get(
                    (objs_d, marks_d, nc_d))
                objs = [float(o) for o in objs_h[:nc, 0]]
                marks = [int(m) for m in marks_h[:nc, 0]]
            else:
                objs, marks = [], []
                done = 0
                while done < num_iters:
                    ns = min(chunk, num_iters - done)
                    sstate, obj, _healthy = engine.run_chunk_slots(
                        sstate, x_t_b, sign_b, sp, ns, chunk_steps=chunk,
                        d=d, block_size=block_size, project=nu > 0.0,
                        check_gap=check_gap, backend=backend)
                    done += ns
                    objs.append(obj)
                    marks.append(done)
                    if check_gap and not bool(
                            jax.device_get(sstate.active)[0]):
                        # gap stop
                        marks[-1] = int(jax.device_get(sstate.t)[0])
                        break
                objs = [float(np.asarray(o)[0])
                        for o in jax.device_get(objs)]
        pstate = engine.PackedState(
            w=sstate.w[0], log_lam=sstate.log_lam[0],
            log_lam_prev=sstate.log_lam_prev[0], u=sstate.u[0],
            t=sstate.t[0])
        return SolveResult(state=unpack_state(pstate, n1, n2),
                           history=list(zip(marks, objs)))
