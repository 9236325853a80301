"""Saddle-DSVC (Section 4 / Algorithm 4): the distributed solver.

The paper's server/clients protocol maps onto JAX collectives:

  round 1  server broadcasts i*; clients send partial delta+-    -> psum
  round 2  server broadcasts summed delta+-; clients update w,
           eta, xi locally and send partial normalizers Z+-      -> psum
  round 3  server broadcasts Z+-; clients normalize               (local)
  round 4  (nu-Saddle only) repeat: clients send partial
           varsigma+-, Omega+-; server broadcasts sums            -> psum
           until varsigma == 0  (at most ceil(1/nu) rounds)

Every "send partials / broadcast sum" pair is exactly one all-reduce of
O(1) scalars over the client axis, so the whole protocol is a handful of
scalar ``lax.psum``s per iteration -- the TPU-native realization of the
O(k) communication bound (Theorem 8).

The step itself is :func:`repro.core.engine.step_packed` with
``axis_name=CLIENT_AXIS`` -- the SAME code the serial solver runs (the
serial path is the k=1 degenerate client).  Each client packs its two
class shards into one +- operand (column-major mirror + sign vector,
see :func:`repro.core.preprocess.pack_points`), so rounds 1-3 are one
signed sweep each and round 4 (nu-Saddle) is the fixed-round bisection
whose per-round traffic is a single (2,) psum.  It executes in two
modes:
  * ``shard_map`` over a real mesh axis (multi-device / dry-run), or
  * ``jax.vmap(..., axis_name=CLIENT_AXIS)`` over a stacked (k, n/k, ...)
    state -- a bit-exact single-device simulation of k clients (psum is
    supported under vmap's axis_name), used for the paper's k=20
    experiments on this host.

Both produce the SAME iterates as serial Saddle-SVC (tested), because
summing per-client partial dot products/normalizers is exact.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core import preprocess
from repro.core import projections
from repro.core import saddle
from repro.core.engine import CLIENT_AXIS, NEG_INF
from repro.core.saddle import SaddleParams


class ShardedState(NamedTuple):
    """Per-client slice of the solver state.  Leading axis (under vmap)
    or shard axis (under shard_map) is the client."""
    w: jax.Array            # (d,) -- every client keeps the same w
    log_eta: jax.Array      # (n1/k,)
    log_eta_prev: jax.Array
    log_xi: jax.Array       # (n2/k,)
    log_xi_prev: jax.Array
    u_p: jax.Array
    u_m: jax.Array
    t: jax.Array


class CommModel(NamedTuple):
    """Analytic communication accounting for Algorithm 4.

    Two views of the same protocol:

    * ``scalars_per_iteration`` -- the PAPER's convention (Theorem 8):
      numbers exchanged per iteration, counting every client's up/down
      traffic, O(k).
    * ``collectives_per_iteration`` / ``collective_multiset`` /
      ``payload_elements_per_iteration`` -- the IMPLEMENTATION's view:
      how many collective launches (and of what reduction/shape) one
      ``engine.step_packed`` must emit per iteration.  This is what
      ``repro.utils.comm_audit`` checks against the post-SPMD HLO XLA
      actually compiles, making the O(k) bound a tested invariant: the
      per-device launch count and payload are independent of n, d and
      k, so total traffic is exactly (payload) x O(k).
    """
    k: int
    nu_rounds_per_iter: float   # 0 for HM-Saddle; else BISECT_ROUNDS

    def scalars_per_iteration(self) -> float:
        k = self.k
        # round 1: broadcast i* (k) + 2 scalars up from each client (2k)
        # round 2: broadcast 2 (2k) + Z's up (2k)
        # round 3: broadcast Z's (2k)
        base = k + 2 * k + 2 * k + 2 * k + 2 * k
        # round 4 (nu-Saddle): the sort-free bisection all-reduces one
        # (2,) vector per round -- 2 scalars up (2k) + 2 down (2k) --
        # for a FIXED round count, independent of n and of the data
        # (the old Rule-3 loop was data-dependent, up to ceil(1/nu)
        # rounds of 8k), plus two fixed out-of-loop all-reduces: the
        # (2,) per-class feasibility pmax (4k) and the (4,) cap-set
        # stats psum for the exact rescale (8k)
        nu_fixed = 12 * k if self.nu_rounds_per_iter else 0
        return base + self.nu_rounds_per_iter * 4 * k + nu_fixed

    def total(self, iters: int) -> float:
        return self.scalars_per_iteration() * iters

    def collective_multiset(self, block_size: int = 1) -> dict:
        """Predicted per-iteration collective launches of the packed
        step, as a multiset keyed (op, reduce_kind, result_elements) --
        directly comparable against the post-SPMD HLO (see
        repro.utils.comm_audit).  Per iteration:

          round 1    momentum psum           add  (B,)
          rounds 2-3 normalizer pmax + psum  max/add  (2,)
          round 4    feasibility pmax        max  (2,)
                     BISECT_ROUNDS psums     add  (2,)  (one per round)
                     cap-set stats psum      add  (4,)
        """
        ms: dict = {}

        def bump(kind, elems, cnt=1):
            key = ("all-reduce", kind, elems)
            ms[key] = ms.get(key, 0) + cnt

        bump("add", block_size)          # momentum delta
        bump("max", 2)                   # normalizer pmax
        bump("add", 2)                   # normalizer psum
        if self.nu_rounds_per_iter:
            bump("max", 2)               # feasibility pmax
            bump("add", 2, int(self.nu_rounds_per_iter))   # bisection
            bump("add", 4)               # cap-set |cap| + Omega stats
        return ms

    def collectives_per_iteration(self, block_size: int = 1) -> int:
        """Predicted collective LAUNCH count per iteration -- constant
        in n, d and k (3 for HM-Saddle; 5 + BISECT_ROUNDS for
        nu-Saddle)."""
        return sum(self.collective_multiset(block_size).values())

    def payload_elements_per_iteration(self, block_size: int = 1) -> int:
        """Predicted per-device all-reduce payload elements per
        iteration: O(B + rounds), independent of n (the O(k*d) bound of
        Theorem 8 with the momentum round's B <= d elements)."""
        return sum(elems * cnt for (_, _, elems), cnt
                   in self.collective_multiset(block_size).items())


class ServeCommModel(NamedTuple):
    """Collective budget of the POINT-SHARDED serving chunk
    (``engine.run_chunk_slots_sharded`` with non-empty ``point_axes``).

    The sharded slot driver vmaps ``engine._step_packed_core`` over the
    S lanes of a slot group with the SAME ``axis_name`` rounds as the
    solo distributed step, and vmap batches each round's collective into
    ONE launch whose payload scales by S.  The per-iteration multiset is
    therefore :class:`CommModel`'s with every payload multiplied by
    ``num_slots`` -- the LAUNCH count stays the Theorem-8 constant (3
    for HM-Saddle, 5 + BISECT_ROUNDS for nu-Saddle), so serving S fits
    across k shards costs exactly one fit's collective rounds.

    ``num_slots`` is the PER-DEVICE slot extent the chunk body is traced
    at (the group's full S for the pure point-sharded placement; S over
    the slot-axes extent when slot- and point-sharding compose).
    Unsharded slot groups need no model: their placement is
    collective-FREE and the audit pins the empty multiset.
    """
    k: int
    num_slots: int
    nu_rounds_per_iter: float   # 0 for HM-Saddle; else BISECT_ROUNDS

    def collective_multiset(self, block_size: int = 1) -> dict:
        """Per-iteration launches inside the chunk's step loop, keyed
        (op, reduce_kind, result_elements).  Identical launch structure
        to :meth:`CommModel.collective_multiset`; payloads are the
        vmap-batched (S, .) shapes.  Keys whose payloads collide (e.g.
        momentum S*B vs cap-set 4S when B == 4) merge, exactly as the
        measured HLO multiset merges them."""
        s = self.num_slots
        ms: dict = {}

        def bump(kind, elems, cnt=1):
            key = ("all-reduce", kind, elems)
            ms[key] = ms.get(key, 0) + cnt

        bump("add", s * block_size)      # momentum delta   (S, B)
        bump("max", 2 * s)               # normalizer pmax  (S, 2)
        bump("add", 2 * s)               # normalizer psum  (S, 2)
        if self.nu_rounds_per_iter:
            bump("max", 2 * s)           # feasibility pmax (S, 2)
            bump("add", 2 * s, int(self.nu_rounds_per_iter))  # bisection
            bump("add", 4 * s)           # cap-set stats    (S, 4)
        return ms

    def per_chunk_multiset(self, d: int) -> dict:
        """Launches at the chunk boundary, OUTSIDE the step loop: the
        per-slot objective psum ((S, d) -- each slot's shard holds only
        its points' dual-weighted sum) and the health agreement psum
        ((S,) -- one shard's overflow must deactivate the slot on every
        shard).  Constant per chunk, amortized over chunk_steps."""
        s = self.num_slots
        return {("all-reduce", "add", s * d): 1,
                ("all-reduce", "add", s): 1}

    def collectives_per_iteration(self, block_size: int = 1) -> int:
        return sum(self.collective_multiset(block_size).values())

    def payload_elements_per_iteration(self, block_size: int = 1) -> int:
        return sum(elems * cnt for (_, _, elems), cnt
                   in self.collective_multiset(block_size).items())


def dsvc_step(state: ShardedState, key: jax.Array, xp: jax.Array,
              xm: jax.Array, p: SaddleParams) -> ShardedState:
    """One Algorithm-4 iteration from a single client's viewpoint
    (engine step under the client axis).  ``xp``/``xm`` are the client's
    local (m1, d)/(m2, d) slices; the key is identical across clients
    (server broadcasts i*)."""
    return engine.step(state, key, xp, xm, p, axis_name=CLIENT_AXIS)


def shard_points(x: np.ndarray, k: int):
    """Round-robin partition of n points into k equal shards (padded with
    zero points whose log-weight is NEG_INF).  Returns (k, m, d) array and
    (k, m) validity mask."""
    n, d = x.shape
    m = -(-n // k)
    pad = k * m - n
    xpad = np.concatenate([x, np.zeros((pad, d), x.dtype)], 0)
    mask = np.concatenate([np.ones(n, bool), np.zeros(pad, bool)])
    order = np.arange(k * m).reshape(m, k).T.reshape(-1)   # round robin
    return xpad[order].reshape(k, m, d), mask[order].reshape(k, m)


def gather_duals(state: ShardedState, n1: int, n2: int, k: int):
    """Undo the round-robin sharding of :func:`shard_points`: shard c,
    slot j holds original point index j*k + c, so stacking slot-major
    (transpose then flatten) restores the original order.  Returns
    (eta, xi) of length n1, n2."""
    def unshard(log_v, n):
        if log_v.shape[0] != k:
            raise ValueError(
                f"state has {log_v.shape[0]} client shards, expected k={k}")
        flat = np.asarray(log_v).T.reshape(-1)   # flat[j*k + c] = v[c, j]
        return np.exp(flat[:n])
    return unshard(state.log_eta, n1), unshard(state.log_xi, n2)


def pack_shards(xp_sh: np.ndarray, mask_p: np.ndarray, xm_sh: np.ndarray,
                mask_m: np.ndarray):
    """Pack each client's two class shards into the single-sweep +-
    layout (see preprocess.pack_points): returns the stacked
    column-major mirrors (k, d, m_pad) and sign vectors (k, m_pad).
    Round-robin padding slots (mask False) get sign 0, like the lane
    padding, so they belong to neither class in any masked reduction."""
    k, m1, d = xp_sh.shape
    m2 = xm_sh.shape[1]
    m_pad = preprocess.packed_length(m1 + m2)
    x = np.zeros((k, m_pad, d), np.float32)
    x[:, :m1] = xp_sh
    x[:, m1:m1 + m2] = xm_sh
    sign = np.zeros((k, m_pad), np.float32)
    sign[:, :m1] = np.where(mask_p, 1.0, 0.0)
    sign[:, m1:m1 + m2] = np.where(mask_m, -1.0, 0.0)
    return np.ascontiguousarray(x.transpose(0, 2, 1)), sign


def unpack_sharded_state(pstate: engine.PackedState, m1: int,
                         m2: int) -> ShardedState:
    """Slice the stacked packed state back into the per-class
    ShardedState view (slot layout [eta | xi | lane pad] per client;
    see engine.unpack_state)."""
    return engine.unpack_state(pstate, m1, m2, ShardedState)


def init_sharded_state(n1: int, n2: int, d: int, mask_p: np.ndarray,
                       mask_m: np.ndarray) -> ShardedState:
    """Stacked (k, ...) client states; padding points get NEG_INF."""
    k, m1 = mask_p.shape
    m2 = mask_m.shape[1]
    log_eta = jnp.where(jnp.asarray(mask_p), -math.log(n1), NEG_INF)
    log_xi = jnp.where(jnp.asarray(mask_m), -math.log(n2), NEG_INF)
    zeros = jnp.zeros((k, d), jnp.float32)
    log_eta = log_eta.astype(jnp.float32)
    log_xi = log_xi.astype(jnp.float32)
    # prev copies are distinct buffers (the state is donated downstream)
    return ShardedState(
        w=zeros,
        log_eta=log_eta, log_eta_prev=jnp.copy(log_eta),
        log_xi=log_xi, log_xi_prev=jnp.copy(log_xi),
        u_p=jnp.zeros((k, m1), jnp.float32),
        u_m=jnp.zeros((k, m2), jnp.float32),
        t=jnp.zeros((k,), jnp.int32),
    )


@functools.partial(jax.jit,
                   static_argnames=("params", "chunk_steps", "backend"),
                   donate_argnums=(0,))
def run_chunk_sim(state: ShardedState, key: jax.Array, xp: jax.Array,
                  xm: jax.Array, num_steps, *, params: SaddleParams,
                  chunk_steps: int, backend: str = "jnp"):
    """Single-device simulation: vmap the engine chunk over the stacked
    client axis (dynamic trip count + donated state, like the serial
    path).  Returns (state, per-client objective (k,))."""

    def one_client(st, xp_c, xm_c):
        return engine.chunk_body(st, key, xp_c, xm_c, params, num_steps,
                                 chunk_steps=chunk_steps,
                                 axis_name=CLIENT_AXIS, backend=backend)

    return jax.vmap(one_client, in_axes=(0, 0, 0),
                    axis_name=CLIENT_AXIS)(state, xp, xm)


@functools.partial(jax.jit,
                   static_argnames=("params", "chunk_steps", "backend"),
                   donate_argnums=(0,))
def run_chunk_sim_packed(state: engine.PackedState, key: jax.Array,
                         x_t: jax.Array, sign: jax.Array, num_steps, *,
                         params: SaddleParams, chunk_steps: int,
                         backend: str = "jnp"):
    """Single-device simulation of the packed step: vmap the packed
    engine chunk over the stacked client axis (dynamic trip count +
    donated state).  Returns (state, per-client objective (k,))."""

    def one_client(st, x_t_c, sign_c):
        return engine.chunk_body_packed(
            st, key, x_t_c, sign_c, params, num_steps,
            chunk_steps=chunk_steps, axis_name=CLIENT_AXIS,
            backend=backend)

    return jax.vmap(one_client, in_axes=(0, 0, 0),
                    axis_name=CLIENT_AXIS)(state, x_t, sign)


def sharded_run_fn(mesh: jax.sharding.Mesh, axis=CLIENT_AXIS,
                   backend: str = "jnp", *, params: SaddleParams,
                   chunk_steps: int):
    """UN-jitted shard_map chunk runner over a real device mesh:
    ``run(state, key, x_t, sign, num_steps) -> (state, obj)``.

    ``axis`` may be a single mesh axis name or a tuple of axis names
    (the dry-run maps clients onto ALL mesh axes, so a 16x16 pod is
    k=256 clients); psum/pmax accept either.  Exposed separately from
    :func:`make_sharded_runner` so the communication audit and the
    launch specs can AOT-lower the exact production chunk from
    ShapeDtypeStructs without allocating anything."""
    from jax.sharding import PartitionSpec as P

    def run(state, key, x_t, sign, num_steps):
        def client_fn(st, x_t_c, sign_c, key_r, ns_r):
            st = jax.tree.map(lambda a: a[0], st)        # drop shard dim
            x_t_c, sign_c = x_t_c[0], sign_c[0]
            st, obj = engine.chunk_body_packed(
                st, key_r, x_t_c, sign_c, params, ns_r,
                chunk_steps=chunk_steps, axis_name=axis, backend=backend)
            return jax.tree.map(lambda a: a[None], st), obj[None]

        spec = P(axis)
        fn = jax.shard_map(client_fn, mesh=mesh,
                           in_specs=(spec, spec, spec, P(), P()),
                           out_specs=(spec, spec), check_vma=False)
        return fn(state, x_t, sign, key, jnp.asarray(num_steps, jnp.int32))

    return run


def make_sharded_runner(mesh: jax.sharding.Mesh, axis=CLIENT_AXIS,
                        backend: str = "jnp"):
    """shard_map runner for a real device mesh: the production path used
    by the multi-pod dry-run (clients = the mesh 'data' axis), running
    the packed single-sweep chunk per shard."""
    from repro.launch.mesh import auto_axes

    mesh = auto_axes(mesh)

    @functools.partial(jax.jit,
                       static_argnames=("params", "chunk_steps"),
                       donate_argnums=(0,))
    def run(state, key, x_t, sign, num_steps, *, params, chunk_steps):
        inner = sharded_run_fn(mesh, axis, backend, params=params,
                               chunk_steps=chunk_steps)
        return inner(state, key, x_t, sign, num_steps)

    return run


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _apply_client_drop(state: engine.PackedState, sign: jax.Array,
                       client):
    """Remove one client from the stacked vmap simulation IN SHAPE:
    its sign row goes to 0 (its points leave every masked class
    reduction, including the feasibility pmax rounds) and its dual
    weights to NEG_INF / momentum to 0 (exp(NEG_INF) = 0, so the
    client contributes nothing to any psum).  ``client`` is traced --
    one compile serves every drop target -- and no operand shape
    changes, so the chunk executable is NOT retraced.

    Recovery rule (renormalized mass): the very next iteration's
    normalizer round -- pmax + psum of the survivors' partial Z's --
    rescales each class's total dual mass back to 1 over the k-1
    survivors, exactly as if the protocol had been restarted on the
    survivor shard set with the current iterates.  No host-side repair
    step is needed; the MWU normalization IS the repair."""
    drop = (jnp.arange(sign.shape[0]) == client)[:, None]
    return state._replace(
        log_lam=jnp.where(drop, NEG_INF, state.log_lam),
        log_lam_prev=jnp.where(drop, NEG_INF, state.log_lam_prev),
        u=jnp.where(drop, 0.0, state.u),
    ), jnp.where(drop, 0.0, sign)


@functools.partial(jax.jit, donate_argnums=(0, 1),
                   static_argnames=("num_shards",))
def drop_slot_shard(state: engine.SlotState, sign: jax.Array, slot,
                    shard, *, num_shards: int):
    """:func:`_apply_client_drop` for ONE point-sharded serving slot:
    zero the lost shard's sign range and send its dual weights to
    NEG_INF / momentum to 0, so the shard's points leave every masked
    reduction of that slot while batch-mates' rows are untouched
    bit-for-bit.  ``slot``/``shard`` are traced (one compile per group
    shape serves every drop target).

    The point axis of a sharded slot is split CONTIGUOUSLY by
    ``shard_map`` (unlike :func:`shard_points`' round-robin layout), so
    shard ``s`` owns columns [s*m, (s+1)*m) with m = n_pad/num_shards.
    The same renormalized-mass recovery rule applies: the next
    iteration's normalizer round rescales each class's surviving dual
    mass to 1 -- the MWU normalization IS the repair."""
    n_pad = sign.shape[-1]
    m = n_pad // num_shards
    cols = (jnp.arange(n_pad) // m) == shard
    rows = jnp.arange(sign.shape[0]) == slot
    drop = rows[:, None] & cols[None, :]
    return state._replace(
        log_lam=jnp.where(drop, NEG_INF, state.log_lam),
        log_lam_prev=jnp.where(drop, NEG_INF, state.log_lam_prev),
        u=jnp.where(drop, 0.0, state.u),
    ), jnp.where(drop, 0.0, sign)


class DistSolveResult(NamedTuple):
    state: ShardedState
    history: list
    comm: CommModel
    scalars_sent: float


def solve_distributed(xp: np.ndarray, xm: np.ndarray, *, k: int = 20,
                      eps: float = 1e-3, beta: float = 0.1, nu: float = 0.0,
                      num_iters: int | None = None, block_size: int = 1,
                      seed: int = 0, record_every: int | None = None,
                      mesh: jax.sharding.Mesh | None = None,
                      use_kernels: bool = False,
                      drop_client: tuple[int, int] | None = None
                      ) -> DistSolveResult:
    """Run Saddle-DSVC with k clients (simulation unless a mesh is given).

    Data must already be preprocessed (Algorithm 3 runs WD per client with
    the same shared D -- equivalent to transforming up front).

    ``drop_client=(c, at_iter)`` injects a client loss into the vmap
    SIMULATION path: at outer iteration ``at_iter`` client ``c``
    vanishes (see :func:`_apply_client_drop` -- shape-preserving, no
    retrace) and the solve continues on the k-1 survivors with their
    dual mass renormalized by the next MWU normalizer round.  The
    survivor problem is the round-robin complement of shard ``c``
    (original point index j*k + c belongs to the dropped client), and
    the k-1 solve converges on IT -- the duality-gap tolerance is
    pinned in ``tests/test_distributed.py``."""
    xp = np.asarray(xp, np.float32)
    xm = np.asarray(xm, np.float32)
    n1, d = xp.shape
    n2 = xm.shape[0]
    params = saddle.make_params(n1 + n2, d, eps, beta, nu=nu,
                                block_size=block_size)
    if num_iters is None:
        num_iters = saddle.default_iterations(d, eps, beta, n1 + n2)
    num_iters = max(1, num_iters // block_size)

    xp_sh, mask_p = shard_points(xp, k)
    xm_sh, mask_m = shard_points(xm, k)
    m1, m2 = mask_p.shape[1], mask_m.shape[1]
    x_t, sign = pack_shards(xp_sh, mask_p, xm_sh, mask_m)
    x_t = jnp.asarray(x_t)
    sign = jnp.asarray(sign)
    state = engine.init_packed_state(sign, n1, n2, d)
    chunk = min(record_every or num_iters, num_iters)
    backend = "pallas" if use_kernels else "jnp"

    if drop_client is not None and mesh is not None:
        raise ValueError("drop_client injection is simulation-only "
                         "(mesh=None)")
    if mesh is not None:
        runner = make_sharded_runner(mesh, backend=backend)
        run = lambda st, kk, ns: runner(st, kk, x_t, sign, ns,
                                        params=params, chunk_steps=chunk)
    else:
        # late-bound ``sign`` so the drop injection below takes effect
        # mid-solve without rebuilding the runner (shapes unchanged ->
        # the chunk executable is shared across the drop boundary)
        run = lambda st, kk, ns: run_chunk_sim_packed(st, kk, x_t, sign,
                                                      ns, params=params,
                                                      chunk_steps=chunk,
                                                      backend=backend)

    # nu-projection rounds per iteration: the sort-free bisection runs a
    # FIXED round count (one (2,) psum per round) -- deterministic and
    # worst-case O(k) scalars, where the data-dependent Rule-3 loop was
    # worst-case O(k / nu)
    nu_rounds = float(projections.BISECT_ROUNDS_SOLVER) if nu > 0 else 0.0
    comm = CommModel(k=k, nu_rounds_per_iter=nu_rounds)

    if drop_client is None:
        state, hist = engine.drive(state, jax.random.key(seed),
                                   num_iters, chunk, run)
    else:
        # drive's loop with one extra chunk boundary at the drop
        # iteration (same one-key-split-per-chunk discipline; the trip
        # count is dynamic, so the split chunk costs no retrace)
        drop_c, drop_at = drop_client
        drop_at = max(0, min(int(drop_at), num_iters))
        key = jax.random.key(seed)
        hist, done, dropped = [], 0, False
        while done < num_iters:
            if not dropped and done >= drop_at:
                state, sign = _apply_client_drop(
                    state, sign, jnp.asarray(drop_c, jnp.int32))
                dropped = True
            bound = num_iters if dropped else min(drop_at, num_iters)
            bound = bound if bound > done else num_iters
            key, sub = jax.random.split(key)
            ns = min(chunk, bound - done)
            state, obj = run(state, sub, ns)
            done += ns
            # per-client objectives agree across LIVE clients; read a
            # survivor's row (the dropped client's is stale)
            ridx = ((drop_c + 1) % k) if dropped else 0
            hist.append((done, float(np.asarray(
                jax.device_get(obj)).reshape(-1)[ridx])))
    history = [(done, comm.total(done), obj) for done, obj in hist]
    return DistSolveResult(state=unpack_sharded_state(state, m1, m2),
                           history=history, comm=comm,
                           scalars_sent=comm.total(num_iters))
