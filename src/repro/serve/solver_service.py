"""Multi-tenant SVM fit serving: continuous batching over the
slot-batched saddle engine.

The paper's per-iteration work is tiny -- O(B + n) after preprocessing
(Theorem 6) -- so a single fit request cannot saturate the hardware.
At serving scale the unit of work is therefore MANY independent small
problems, not one large one: this service packs S concurrent fit
requests into ONE compiled slot-batched step
(:func:`repro.core.engine.run_chunk_slots`, a ``vmap`` over the
leading slot axis) and keeps that executable busy by admitting queued
requests into lanes as they free up mid-run.

Scheduling is delegated to the shared latency-aware core
(:class:`repro.serve.scheduler.Scheduler`): the service is a thin
WORKLOAD ADAPTER that owns only the device side -- per-bucket slot
buffers (:class:`_Batch`), engine chunk dispatch, and harvest through
the svm.py recovery path.  Queue ordering (arrival / priority /
deadline urgency), cross-bucket policy (``oldest`` default,
``round_robin`` retained for bit-compat), admission-into-freed-slots,
idle-batch eviction, queue-to-result latency stamps and compile-cache
accounting all live in the scheduler and are shared verbatim with the
LM service (:mod:`repro.serve.lm_service`).

Mesh-sharded serving
--------------------

Constructed with a ``mesh`` the service runs every chunk through
``engine.run_chunk_slots_sharded`` and composes the paper's two scale
axes under ONE scheduler and one executable family: ordinary requests
land in LANE-PARALLEL groups (the slot axis shards over every mesh
axis; each device steps its own lanes with zero cross-device traffic
-- admission, quarantine and cancel all stay lane-local), while
requests above ``shard_points_above`` points land in POINT-SHARDED
groups whose slots span the mesh and pay exactly the solo distributed
step's Theorem-8 collective rounds per iteration (vmap batches each
round across the group's lanes into one launch; see
``distributed.ServeCommModel``).  The shard placement is part of the
scheduler group key -- see :meth:`repro.serve.scheduler.Scheduler.
group` -- and a 1-device mesh reproduces the meshless service
bit-for-bit (tested in ``tests/test_mesh_service.py``).

Streaming updates (warm starts)
-------------------------------

A fit submitted with ``stream=True`` declares a LIVE TENANT whose data
keeps changing.  :meth:`SolverService.submit_update` takes an
:class:`UpdateRequest` -- append points, replace the set, or pure
re-fit -- applies the tenant's FIXED preprocessing transform to the
new points (``preprocess.transform_like``), supersedes the tenant's
in-flight request (``Status.SUPERSEDED``), and enqueues a re-fit that
WARM-STARTS from the tenant's last completed saddle state instead of
the uniform init: ``w`` and the dual momentum carry over, carried
points keep their dual mass re-placed at the new class offsets, new
points are seeded at the uniform level and the next MWU normalizer
round renormalizes each class (``preprocess.repack_warm_duals`` --
normalization IS the repair, no host-side fix-up pass), and ``u`` is
recomputed from the carried w on device
(``engine.warm_packed_state``).  When the updated point count still
fits the tenant's pow-2 rung, the update re-packs in place and reuses
the SAME hot chunk executable (the warm helpers are jitted outside the
chunk trace keys, so the zero-recompile contract holds); an overflow
jumps one rung (one new bucket, compiled once).  Warm-vs-cold
iterations-to-gap is gated in ``benchmarks/serve_bench.py``
(``serve/stream/warm_iters_ratio``).

Shape buckets
-------------

One executable serves exactly one (n_bucket, d_bucket) shape.  To keep
the number of distinct executables logarithmic in problem size,
requests are packed onto a POW-2 BUCKET LADDER
(:func:`repro.core.preprocess.bucket_shape`):

  * point axis: ``LANE * 2^k``  (128, 256, 512, ...) -- at most 2x
    padding, each rung lane-aligned for the Pallas kernels;
  * coordinate axis: ``2^k`` -- already satisfied by the WD transform
    of Algorithm 1, so requests of different dimensionality simply
    land on different d rungs (cross-d sharing via inert coordinate
    padding is what ``saddle.solve(..., d_pad)`` /
    ``preprocess.pack_points_to`` provide for callers that want it).

Padding points carry sign 0 / log-weight NEG_INF (inert in every
reduction); padding coordinates are all-zero rows of the column-major
mirror, so ``w`` stays pinned at 0 there.  Because the solver samples
coordinate blocks over the FULL bucket axis, a bucketed solve is
reproducible slot-for-slot against ``saddle.solve(..., n_pad, d_pad)``
at the same bucket -- that is the service's parity contract (tested in
``tests/test_solver_service.py``).  Scheduling policy can never change
a request's numbers: a slot's trajectory depends only on its own seed,
budget and bucket, and every chunk is a FULL chunk, so policies differ
in WHEN a request runs, never in WHAT it computes.

Slot lifecycle (see also :class:`repro.core.engine.SlotState`)
--------------------------------------------------------------

  queue -> ADMIT -> RUNNING -> FINISHED -> harvest -> (lane FREE)

  * ADMIT (between chunks only): the scheduler assigns urgency-ordered
    tickets to free lanes; :func:`engine.admit_into_slot` then
    overwrites EVERY per-slot field -- state, PRNG chain, budget,
    active flag -- so a reused lane cannot leak its previous
    occupant's duals; the request's packed operand is written into the
    batch buffers by a donated updater (in-place, no reallocation).
  * RUNNING: the slot steps while ``t < max_t`` and (if the request
    set ``gap_tol``) its relative duality gap is above threshold.
    The per-slot active mask freezes finished slots WITHOUT halting
    the batch.
  * FINISHED -> harvest: the host reads the (S,) active/t vectors
    after each chunk, extracts finished slots, and recovers each
    request's input-space (w, b) via the exact ``svm.py`` path
    (:func:`repro.core.svm.recover_hyperplane`).

Compile discipline
------------------

The chunk executable is keyed by (S, bucket shape, block size,
chunk_steps, project, check_gap, backend) -- all admission patterns,
chunk lengths and per-request parameter VALUES share it.  The
scheduler tracks trace counts per key (``engine.trace_counts``); after
a bucket is warm, every chunk must be a compile-cache hit
(``SolverService.stats`` is asserted in ``benchmarks/serve_bench.py``).

Status contract & fault handling
--------------------------------

Every request walks the scheduler's :class:`~repro.serve.scheduler.
Status` lifecycle (PENDING -> RUNNING -> DONE / FAILED / CANCELLED /
DEADLINE_EXCEEDED), readable any time via ``status(rid)``:

  * INTAKE: ``submit`` fails fast with ``ValueError`` on non-finite
    ``x``/``y``, shape mismatches, single-class ``y``, infeasible
    ``nu`` and over-ladder shapes -- a malformed request never reaches
    a device lane.
  * QUARANTINE: the chunk executable returns a per-slot finite-health
    flag (:func:`repro.core.engine.run_chunk_slots`); a slot whose
    state diverged to NaN/Inf is quarantined at the chunk boundary --
    lane freed for re-admission, batch-mates bit-for-bit unaffected
    (lanes are vmapped independently) -- and either retried
    (``FitRequest.max_retries``, re-enqueued BEHIND waiting tickets:
    backoff ordering) or failed with a structured
    :class:`~repro.serve.scheduler.RequestFailure`.
  * DEADLINES: constructed with a ``clock``, the service sheds every
    queued ticket whose deadline has passed at the top of each step
    (DEADLINE_EXCEEDED) so hopeless requests never occupy a lane.
    Without a clock, deadlines remain pure urgency ordering.
  * CANCEL: ``cancel(rid)`` removes a queued ticket eagerly or frees a
    running lane between chunks (the device slot is deactivated; the
    executable shape never changes).

``result(rid)`` returns the ``FitResult`` OR the ``RequestFailure``;
on a known-but-unfinished rid it raises
:class:`~repro.serve.scheduler.ResultNotReady` (a ``KeyError``
subclass -- unknown rids keep the historical bare ``KeyError``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace as dc_replace
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import engine
from repro.core import preprocess as pp
from repro.core import saddle
from repro.core import svm as svm_mod
from repro.serve import faults as faults_mod
from repro.serve.scheduler import (RequestFailure, ResultNotReady,
                                   Scheduler, Status)
from repro.utils.spans import span


@dataclass
class FitRequest:
    """One SVM fit: raw (x, y) plus the solver configuration a
    ``SaddleSVC``/``SaddleNuSVC`` would take.  ``nu=0`` is hard margin.
    ``gap_tol > 0`` enables the per-slot duality-gap early stop (the
    request may then finish before ``num_iters``).  ``max_retries``
    bounds how many times a quarantined (non-finite) run is re-admitted
    before the request fails for good.  ``stream=True`` declares a LIVE
    TENANT: the service retains the request's preprocessing transform
    and, at harvest, its final saddle state, so later
    :class:`UpdateRequest`\\ s can edit the data and warm-start the
    re-fit (see ``submit_update``)."""
    x: np.ndarray
    y: np.ndarray
    eps: float = 1e-3
    beta: float = 0.1
    nu: float = 0.0
    num_iters: int | None = None
    block_size: int = 1
    seed: int = 0
    gap_tol: float = 0.0
    max_retries: int = 0
    stream: bool = False


@dataclass
class UpdateRequest:
    """One STREAMING UPDATE of a live tenant's problem: edit the data
    (append new labelled points, replace the whole set, or neither for
    a pure re-fit) and re-solve -- warm-started from the tenant's last
    completed saddle state unless ``warm=False`` (the cold-reference
    knob the benchmarks and parity tests use).

    ``tenant`` is the rid of the original ``stream=True`` fit.  ``x``/
    ``y`` are new raw points in the tenant's ORIGINAL input space (the
    tenant's fixed WD transform+scale is applied at intake,
    ``preprocess.transform_like``); ``mode="append"`` may carry a
    single class (the tenant already has both), ``mode="replace"``
    must carry both.  ``nu``/``num_iters``/``gap_tol``/``max_retries``
    default to the tenant's original configuration when None.

    An accepted update SUPERSEDES the tenant's in-flight request, if
    any (its ticket terminates with ``Status.SUPERSEDED``); already
    completed results stay claimable.  The dataset edit is applied at
    intake and survives even if this update's solve later fails."""
    tenant: int
    x: np.ndarray | None = None
    y: np.ndarray | None = None
    mode: str = "append"
    warm: bool = True
    nu: float | None = None
    num_iters: int | None = None
    gap_tol: float | None = None
    max_retries: int | None = None


class FitResult(NamedTuple):
    """Input-space hyperplane (the ``svm.py`` recovery path) plus the
    serving metadata of the request's ride through the batch."""
    request_id: int
    w: np.ndarray
    b: float
    objective: float
    margin: float
    iterations: int          # iterations actually run (gap stop <= budget)
    bucket: tuple            # (n_bucket, d_bucket) the request shared
    history: list            # [(iteration, objective)] at chunk marks


class _WarmState(NamedTuple):
    """A tenant's last COMPLETED saddle state, host-retained at harvest
    (idle-group eviction frees the device lane, so warm state cannot
    stay slot-resident).  ``log_lam``/``log_lam_prev`` are in the
    packed layout of the bucket the state was harvested at; only the
    first ``n1 + n2`` entries are meaningful
    (``preprocess.repack_warm_duals`` re-places them at admission)."""
    w: np.ndarray            # (d_bucket,) transformed-space direction
    log_lam: np.ndarray      # (n_pad_old,) packed log duals
    log_lam_prev: np.ndarray
    n1: int                  # class sizes the state was fit at
    n2: int


class _Tenant:
    """Host-side record of one live streaming tenant: the FIXED
    preprocessing transform, the CURRENT transformed class matrices
    (updates edit these at intake), the original request as the config
    template for derived update fits, and the warm-start state."""

    __slots__ = ("pre", "xp_t", "xm_t", "req", "warm", "live_rid",
                 "version")

    def __init__(self, pre: Any, xp_t: jax.Array, xm_t: jax.Array,
                 req: FitRequest):
        self.pre = pre
        self.xp_t = xp_t
        self.xm_t = xm_t
        self.req = req
        self.warm: _WarmState | None = None
        self.live_rid: int | None = None   # in-flight fit/update rid
        self.version = 0                   # bumped per accepted update


class _Admission(NamedTuple):
    """Everything the admission path needs to (re-)stage one request
    into a device lane: the transform, the class matrices, the warm
    state to start from (None = cold uniform init) and the owning
    streaming tenant (None = plain fit).  Stored per queued rid; a
    quarantine retry re-stashes the SAME record, so the retry re-enters
    from the last good warm state."""
    pre: Any
    xp_t: jax.Array
    xm_t: jax.Array
    warm: _WarmState | None
    tenant: int | None


class _Slot(NamedTuple):
    """Host-side bookkeeping for one RUNNING lane (attached to the
    scheduler ticket as ``ticket.note``)."""
    request_id: int
    req: FitRequest
    pre: Any                 # Preprocessed (transform to undo at harvest)
    xp_t: jax.Array          # transformed + bucket-padded class matrices
    xm_t: jax.Array
    warm: Any                # _WarmState | None (admission's init state)
    tenant: int | None       # owning streaming tenant, if any
    history: list


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_slot_data(x_t_b, sign_b, slot, x_t, sign):
    """Write one request's packed operand into lane ``slot`` of the
    batch buffers.  Donated: the (S, d, n) buffer is updated in place,
    and ``slot`` is traced so one compile serves every lane."""
    return x_t_b.at[slot].set(x_t), sign_b.at[slot].set(sign)


def _zero_buffers(num_slots: int, n_pad: int, d_pad: int):
    """A FREE slot table plus zero (S, d, n) operand and (S, n) sign
    buffers for one bucket."""
    return (engine.init_slot_state(num_slots, n_pad, d_pad),
            jnp.zeros((num_slots, d_pad, n_pad), jnp.float32),
            jnp.zeros((num_slots, n_pad), jnp.float32))


class _Batch:
    """One bucket's DEVICE buffers: slot-batched engine state, the
    (S, d, n) packed operands and the per-slot SlotParams mirror.  The
    host-side queue and lane occupancy live in the scheduler's Group
    (this object is that group's ``payload``).

    ``project``/``check_gap`` are FIXED at batch creation (hard-margin
    and nu-SVM requests live in separate batches): a request's
    executable -- and therefore its numeric trajectory -- is fully
    determined by the request itself, never by which co-tenants happen
    to share its bucket at admission time.

    On a device ``mesh`` the batch also owns its SHARD PLACEMENT (the
    second component of the scheduler group key):

      * lane-parallel (``point_sharded=False``): the slot axis shards
        over every mesh axis -- each device owns ``S / mesh.size``
        whole lanes and the chunk exchanges ZERO collectives;
      * point-sharded (``point_sharded=True``): every slot's POINT axis
        spans the mesh and the chunk runs the Theorem-8 collective
        rounds (large-n fits; see ``engine.run_chunk_slots_sharded``).

    The buffers are created directly under their
    :class:`~jax.sharding.NamedSharding` -- each device allocates only
    its own shard, so a group sized to the whole mesh's memory can be
    admitted -- and the first chunk already lowers at the placement the
    whole group lifetime keeps."""

    def __init__(self, bucket: tuple[int, int], num_slots: int,
                 project: bool, check_gap: bool,
                 mesh: jax.sharding.Mesh | None = None,
                 point_sharded: bool = False):
        n_pad, d_pad = bucket
        self.bucket = bucket
        self.project = project
        self.check_gap = check_gap
        self.mesh = mesh
        self.point_sharded = point_sharded
        self.sp = jax.tree.map(
            lambda v: np.repeat(np.asarray(v, np.float32), num_slots),
            engine.SlotParams(theta=0.0, sigma=0.0, inv_sig1=1.0,
                              gamma=1.0, tau=1.0, mwu_c=1.0, mwu_dot=1.0,
                              nu=1.0, gap_tol=0.0))
        self.sp_dev = None                      # device mirror of sp
        if mesh is None:
            self.slot_axes: tuple = ()
            self.point_axes: tuple = ()
            self.shardings = None
            self.sp_sharding = None
            self.state, self.x_t, self.sign = _zero_buffers(
                num_slots, n_pad, d_pad)
        else:
            axes = tuple(mesh.axis_names)
            self.slot_axes, self.point_axes = (
                ((), axes) if point_sharded else (axes, ()))
            s = self.slot_axes or None
            p = self.point_axes or None
            mk = lambda spec: NamedSharding(mesh, spec)   # noqa: E731
            state_sh = engine.SlotState(
                w=mk(PartitionSpec(s)),
                log_lam=mk(PartitionSpec(s, p)),
                log_lam_prev=mk(PartitionSpec(s, p)),
                u=mk(PartitionSpec(s, p)),
                t=mk(PartitionSpec(s)), max_t=mk(PartitionSpec(s)),
                key=mk(PartitionSpec(s)), active=mk(PartitionSpec(s)))
            self.shardings = (state_sh,
                              mk(PartitionSpec(s, None, p)),
                              mk(PartitionSpec(s, p)))
            self.sp_sharding = engine.SlotParams(
                *(mk(PartitionSpec(s))
                  for _ in engine.SlotParams._fields))
            self.state, self.x_t, self.sign = jax.jit(
                _zero_buffers, static_argnums=(0, 1, 2),
                out_shardings=self.shardings)(num_slots, n_pad, d_pad)

    def ensure_placement(self) -> None:
        """Re-pin any buffer whose sharding drifted off the batch's
        placement (admission writers are sharding-preserving in
        practice; this is the cheap invariant guard that keeps the
        chunk executable's jit cache keyed at ONE sharding)."""
        if self.shardings is None:
            return
        fix = lambda a, sh: (a if a.sharding == sh          # noqa: E731
                             else jax.device_put(a, sh))
        self.state = jax.tree.map(fix, self.state, self.shardings[0])
        self.x_t = fix(self.x_t, self.shardings[1])
        self.sign = fix(self.sign, self.shardings[2])


class SolverService:
    """Continuous-batching fit endpoint over the slot-batched engine.

    ``submit`` enqueues a request (assigning it a ticket id); ``step``
    runs ONE chunk of one bucket's batch -- admitting queued requests
    into free lanes first, harvesting finished slots after -- and
    returns any completed :class:`FitResult`s; ``run`` drains
    everything.  ``fit`` is the one-shot convenience wrapper.

    ``policy`` selects the cross-bucket scheduler: ``"oldest"``
    (default, latency-aware oldest-request-first, fill-rate tie-break)
    or ``"round_robin"`` (PR 4's cursor).  Results are policy-invariant
    (see the module docstring); only queue latency changes.

    The service is deliberately host-driven between chunks (admission
    and harvest are O(S) scalar decisions); all per-iteration work
    stays inside the one compiled chunk per bucket.
    """

    def __init__(self, num_slots: int = 8, chunk_steps: int = 64,
                 backend: str = "jnp", policy: str = "oldest",
                 clock=None, fault_injector=None,
                 max_points: int = 1 << 20, max_dim: int = 1 << 14,
                 mesh: jax.sharding.Mesh | None = None,
                 shard_points_above: int | None = None,
                 shard_num_slots: int = 2):
        self.num_slots = num_slots
        self.chunk_steps = chunk_steps
        self.backend = backend
        # Mesh-sharded serving (opt-in): with a ``mesh`` every batch
        # runs under shard_map.  Ordinary requests land in
        # lane-parallel groups (slots sharded over every mesh axis,
        # zero collectives -- ``num_slots`` must divide into
        # ``mesh.size`` whole lanes per device).  Requests with more
        # than ``shard_points_above`` points land in POINT-SHARDED
        # groups of ``shard_num_slots`` lanes whose points span the
        # mesh (Theorem-8 collectives); None disables point sharding.
        # A 1-device mesh reproduces the meshless service bit-for-bit:
        # shard_map over one device partitions nothing and the chunk
        # body is the identical computation.
        if mesh is not None:
            from repro.launch.mesh import auto_axes
            mesh = auto_axes(mesh)
        self.mesh = mesh
        self._mesh_k = 1 if mesh is None else int(mesh.size)
        if mesh is not None and num_slots % self._mesh_k:
            raise ValueError(
                f"num_slots={num_slots} must be divisible by the mesh "
                f"device count {self._mesh_k} (whole lanes per device)")
        self.shard_points_above = shard_points_above
        self.shard_num_slots = shard_num_slots
        # Deadline semantics are OPT-IN: without a clock, deadlines are
        # pure urgency ordering (any orderable float, the historical
        # contract); with ``clock`` (e.g. ``time.monotonic``) queued
        # tickets whose deadline is past clock() are shed each step.
        self._clock = clock
        self._injector = fault_injector     # faults.FaultInjector | None
        self.max_points = max_points        # over-ladder intake bounds:
        self.max_dim = max_dim              # largest admissible bucket
        self._sched = Scheduler(num_slots=num_slots, policy=policy)
        self._results: dict[int, FitResult | RequestFailure] = {}
        self._pre_cache: dict[int, _Admission] = {}
        self._tickets: dict[int, Any] = {}  # rid -> live (non-terminal)
        self._tenants: dict[int, _Tenant] = {}   # streaming tenants
        self._rid_tenant: dict[int, int] = {}    # live rid -> tenant id
        self._next_id = 0

    @property
    def _batches(self) -> dict:
        """Legacy view: bucket key -> device-buffer payload (kept for
        tests/introspection; the scheduler owns the group table)."""
        return {g.key: g.payload for g in self._sched.groups}

    # ------------------------------------------------------------ intake
    def submit(self, req: FitRequest, *, priority: int = 0,
               deadline: float | None = None) -> int:
        """Validate, preprocess and enqueue a fit request; returns its
        ticket id.  The heavy per-request work here (split, WD
        transform, bucket packing) is exactly Algorithm 1 --
        preprocessing is NOT the serving bottleneck the slot engine
        addresses, so it runs at intake.  ``priority``/``deadline``
        feed the scheduler's urgency order (see
        :mod:`repro.serve.scheduler`).

        Fails fast (``ValueError`` naming the offending field) on
        malformed requests -- non-finite ``x``/``y``, shape
        mismatches, single-class ``y``, infeasible ``nu``, over-ladder
        shapes -- so one bad tenant is rejected at intake instead of
        poisoning a device lane."""
        x = np.asarray(req.x)
        y = np.asarray(req.y)
        if x.ndim != 2:
            raise ValueError(
                f"FitRequest.x must be 2-D (n, d); got shape {x.shape}")
        if y.shape != (x.shape[0],):
            raise ValueError(
                f"FitRequest.y must be shape ({x.shape[0]},) to match "
                f"x; got {y.shape}")
        if not np.isfinite(x).all():
            raise ValueError(
                "FitRequest.x contains non-finite values (NaN/Inf)")
        if not np.isfinite(y.astype(np.float64, copy=False)).all():
            raise ValueError(
                "FitRequest.y contains non-finite values (NaN/Inf)")
        if x.shape[0] > self.max_points or x.shape[1] > self.max_dim:
            raise ValueError(
                f"FitRequest.x shape {x.shape} exceeds the service's "
                f"bucket ladder (max_points={self.max_points}, "
                f"max_dim={self.max_dim})")
        rid = self._next_id
        with span("svc.submit", rid=rid, n=x.shape[0], d=x.shape[1]):
            self._next_id += 1
            xp, xm = svm_mod.split_classes(req.x, req.y)   # raises on 1 class
            n1, n2 = len(xp), len(xm)
            saddle.validate_nu(req.nu, n1, n2)
            with span("svc.preprocess"):
                k_pre, _ = jax.random.split(jax.random.key(req.seed))
                pre = pp.preprocess(xp, xm, k_pre)
            self._enqueue(rid, req, n1, n2, pre.xp.shape[1],
                          priority=priority, deadline=deadline)
            self._pre_cache[rid] = _Admission(
                pre=pre, xp_t=pre.xp, xm_t=pre.xm, warm=None,
                tenant=rid if req.stream else None)
            if req.stream:
                self._tenants[rid] = _Tenant(pre, pre.xp, pre.xm, req)
                self._tenants[rid].live_rid = rid
                self._rid_tenant[rid] = rid
        return rid

    def _enqueue(self, rid: int, req: FitRequest, n1: int, n2: int,
                 d_pre: int, *, priority: int,
                 deadline: float | None):
        """Shared tail of ``submit``/``submit_update``: derive the
        bucket + placement group key and enqueue the ticket.  ONE
        derivation for both intakes, so an update can never land beside
        a plain fit under a different key discipline."""
        bucket = pp.bucket_shape(n1 + n2, d_pre)
        # everything that keys the compiled chunk also keys the batch:
        # block_size (shape), project (nu>0) and check_gap (gap_tol>0)
        # statics -- so co-tenancy can never change a request's
        # executable and the warm-up set is exactly the batch set
        project = req.nu > 0.0
        check_gap = req.gap_tol > 0.0
        point_sharded = (self.mesh is not None
                         and self.shard_points_above is not None
                         and n1 + n2 > self.shard_points_above)
        if point_sharded and check_gap:
            raise ValueError(
                "FitRequest.gap_tol > 0 is not supported for "
                "point-sharded fits (the duality gap's water-filling "
                "sorts the full point axis and does not distribute); "
                "submit with gap_tol=0 or below the shard threshold")
        if point_sharded:
            # the point axis must split into whole lane-aligned shards:
            # per-shard pow-2 rung times the mesh extent (>= the plain
            # rung whenever mesh.size is a power of two)
            k = self._mesh_k
            bucket = (k * pp.bucket_length(-(-(n1 + n2) // k)), bucket[1])
        # on a mesh, placement is part of the group key (see
        # Scheduler.group): same bucket, different shard_map program
        if self.mesh is None:
            placement: tuple = ()
            group_slots = self.num_slots
        elif point_sharded:
            placement = ("points", self._mesh_k)
            group_slots = self.shard_num_slots
        else:
            placement = ("lanes", self._mesh_k)
            group_slots = self.num_slots
        batch_key = bucket + (req.block_size, project, check_gap) \
            + placement
        ticket = self._sched.submit(
            batch_key, rid, req, priority=priority, deadline=deadline,
            payload_factory=lambda: _Batch(bucket, group_slots,
                                           project, check_gap,
                                           mesh=self.mesh,
                                           point_sharded=point_sharded),
            num_slots=group_slots)
        self._tickets[rid] = ticket
        return ticket

    # ---------------------------------------------------------- updates
    def submit_update(self, ureq: UpdateRequest, *, priority: int = 0,
                      deadline: float | None = None) -> int:
        """Edit a live tenant's problem and enqueue its re-fit;
        returns the new ticket id.

        Validation-first, then commit: shape/finiteness/label checks,
        nu RE-validation at the post-edit class sizes, and the bucket
        ladder bound (an update that would overflow ``max_points``
        fails fast HERE with a ValueError -- it never reaches a device
        lane, so it cannot masquerade as a quarantine).  Only once the
        update is accepted does it mutate the tenant: the dataset edit
        is applied (and survives even if the re-fit later fails), the
        tenant's in-flight request -- if any -- is SUPERSEDED, and the
        re-fit is enqueued exactly like any admission.  When the new
        point count still fits the tenant's current pow-2 rung the
        update re-packs in place (same bucket, same hot executable);
        when it does not, the re-fit simply lands on the next rung
        (whose executable compiles once and is then shared like any
        bucket's).

        The re-fit WARM-STARTS from the tenant's last completed state
        (``warm=False`` forces the cold uniform init -- the reference
        the warm ratio is measured against): append mode carries the
        old points' dual mass and seeds only the new points at the
        uniform level; replace mode carries ``w`` (and momentum zero)
        but resets all dual mass, since the old points no longer exist.
        A tenant with no completed fit yet falls back to cold."""
        ten = self._tenants.get(ureq.tenant)
        if ten is None:
            raise KeyError(
                f"unknown streaming tenant {ureq.tenant} (submit the "
                f"original fit with stream=True)")
        if ureq.mode not in ("append", "replace"):
            raise ValueError(
                f"UpdateRequest.mode must be 'append' or 'replace'; "
                f"got {ureq.mode!r}")
        if (ureq.x is None) != (ureq.y is None):
            raise ValueError(
                "UpdateRequest.x and .y must be given together "
                "(both None = pure re-fit of the current data)")
        xp_t, xm_t = ten.xp_t, ten.xm_t
        if ureq.x is not None:
            x = np.asarray(ureq.x)
            y = np.asarray(ureq.y)
            if x.ndim != 2:
                raise ValueError(
                    f"UpdateRequest.x must be 2-D (m, d); got shape "
                    f"{x.shape}")
            if y.shape != (x.shape[0],):
                raise ValueError(
                    f"UpdateRequest.y must be shape ({x.shape[0]},) to "
                    f"match x; got {y.shape}")
            if not np.isfinite(x).all():
                raise ValueError(
                    "UpdateRequest.x contains non-finite values "
                    "(NaN/Inf)")
            if not np.isfinite(y.astype(np.float64, copy=False)).all():
                raise ValueError(
                    "UpdateRequest.y contains non-finite values "
                    "(NaN/Inf)")
            xp_new = x[y > 0]
            xm_new = x[y < 0]
            if len(xp_new) + len(xm_new) != len(x):
                raise ValueError(
                    "UpdateRequest.y must be +-1 labels; got "
                    f"{np.unique(y).tolist()}")
            # the tenant's FIXED transform (raises on a d mismatch)
            txp = pp.transform_like(ten.pre, xp_new) if len(xp_new) \
                else ten.xp_t[:0]
            txm = pp.transform_like(ten.pre, xm_new) if len(xm_new) \
                else ten.xm_t[:0]
            if ureq.mode == "append":
                xp_t = jnp.concatenate([ten.xp_t, txp]) if len(xp_new) \
                    else ten.xp_t
                xm_t = jnp.concatenate([ten.xm_t, txm]) if len(xm_new) \
                    else ten.xm_t
            else:
                xp_t, xm_t = txp, txm
        n1, n2 = int(xp_t.shape[0]), int(xm_t.shape[0])
        if n1 == 0 or n2 == 0:
            raise ValueError(
                "UpdateRequest(mode='replace') must carry both classes "
                f"(+1 and -1); got {n1} positive and {n2} negative "
                f"points")
        nu_eff = ten.req.nu if ureq.nu is None else ureq.nu
        saddle.validate_nu(nu_eff, n1, n2)   # nu RE-validation post-edit
        if n1 + n2 > self.max_points:
            raise ValueError(
                f"update for tenant {ureq.tenant} grows the problem to "
                f"{n1 + n2} points, exceeding the service's bucket "
                f"ladder (max_points={self.max_points})")

        # -- validated: commit the edit and enqueue the re-fit --------
        rid = self._next_id
        self._next_id += 1
        replaced = ureq.mode == "replace" and ureq.x is not None
        if ten.live_rid is not None:
            self._supersede(ten.live_rid, rid)
        ten.xp_t, ten.xm_t = xp_t, xm_t
        ten.version += 1
        if replaced and ten.warm is not None:
            # old points no longer exist: dual mass cannot transfer.
            # Keep w (same transformed space) but reset the dual
            # segments to uniform -- n1=n2=0 makes repack_warm_duals
            # ignore the stale arrays entirely.
            ten.warm = ten.warm._replace(n1=0, n2=0)
        req = dc_replace(
            ten.req,
            # raw x/y are never read for updates (the transformed
            # matrices above are authoritative); drop the stale arrays
            x=None, y=None,
            nu=nu_eff,
            num_iters=(ten.req.num_iters if ureq.num_iters is None
                       else ureq.num_iters),
            gap_tol=(ten.req.gap_tol if ureq.gap_tol is None
                     else ureq.gap_tol),
            max_retries=(ten.req.max_retries if ureq.max_retries is None
                         else ureq.max_retries),
            # deterministic per-revision schedule: warm and cold
            # re-fits of the same revision share it, revisions differ
            seed=ten.req.seed + 1000003 * ten.version,
            stream=True)
        self._enqueue(rid, req, n1, n2, int(xp_t.shape[1]),
                      priority=priority, deadline=deadline)
        warm = ten.warm if ureq.warm else None
        self._pre_cache[rid] = _Admission(
            pre=ten.pre, xp_t=xp_t, xm_t=xm_t, warm=warm,
            tenant=ureq.tenant)
        ten.live_rid = rid
        self._rid_tenant[rid] = ureq.tenant
        return rid

    def _supersede(self, rid_old: int, rid_new: int) -> None:
        """Terminate the tenant's stale in-flight request with
        SUPERSEDED: a queued ticket is removed eagerly, a running one
        has its lane deactivated and freed (between chunks -- the
        service is host-driven).  The stale outcome is a claimable
        :class:`RequestFailure` naming the superseding rid."""
        ticket = self._tickets.get(rid_old)
        if ticket is None:
            return
        reason = f"superseded by update request {rid_new}"
        hit = self._sched.cancel_queued(rid_old, Status.SUPERSEDED)
        if hit is not None:
            g, t = hit
            self._record_failure(t, Status.SUPERSEDED, reason)
            self._sched.evict_idle(g)
            return
        for g in self._sched.groups:
            for lane, t in list(g.slots.items()):
                if t.rid == rid_old:
                    g.payload.state = engine.deactivate_slot(
                        g.payload.state, lane)
                    self._record_failure(t, Status.SUPERSEDED, reason)
                    self._sched.release(g, lane, Status.SUPERSEDED)
                    self._sched.evict_idle(g)
                    return

    def close_stream(self, tenant: int) -> bool:
        """Drop a streaming tenant's host-side record (transform,
        transformed matrices, warm state).  An in-flight re-fit keeps
        running and its result stays claimable; it just no longer
        updates warm state at harvest.  Returns False on unknown
        tenants."""
        return self._tenants.pop(tenant, None) is not None

    # --------------------------------------------------------- admission
    def _admit(self, group) -> None:
        """Realize the scheduler's urgency-ordered lane assignments in
        device state (between chunks)."""
        batch = group.payload
        n_pad, d_pad = batch.bucket
        for lane, ticket in self._sched.admit(group):
            req = ticket.payload
            adm = self._pre_cache.pop(ticket.rid)
            with span("svc.admit", rid=ticket.rid, lane=lane,
                      warm=int(adm.warm is not None)):
                xp_t, xm_t = adm.xp_t, adm.xm_t
                # preprocess() already padded d to a power of two, so the
                # request's dimensionality IS the batch's d rung
                assert xp_t.shape[1] == d_pad, (xp_t.shape, batch.bucket)
                n1, n2 = xp_t.shape[0], xm_t.shape[0]
                pts = pp.pack_points(xp_t, xm_t, pad_to=n_pad)
                params = saddle.make_params(
                    n1 + n2, d_pad, req.eps, req.beta, nu=req.nu,
                    block_size=req.block_size)
                # the SAME budget derivation as saddle.solve (shared
                # helper), so a request's schedule equals its solo solve's
                num_iters = saddle.resolve_num_iters(
                    req.num_iters, d_pad, req.eps, req.beta, n1 + n2,
                    req.block_size)

                batch.x_t, batch.sign = _write_slot_data(
                    batch.x_t, batch.sign, lane, pts.x_t, pts.sign)
                if adm.warm is not None:
                    # WARM admission: re-place the carried dual segments at
                    # the new class offsets (appended points seeded at the
                    # uniform level; the next MWU normalizer round
                    # renormalizes each class -- no host-side repair), and
                    # recompute u from the carried w on device.  Both
                    # helpers are jitted OUTSIDE the chunk trace keys, so
                    # the hot executables stay zero-recompile.
                    lam = pp.repack_warm_duals(
                        adm.warm.log_lam, adm.warm.n1, adm.warm.n2,
                        n1, n2, n_pad)
                    prev = pp.repack_warm_duals(
                        adm.warm.log_lam_prev, adm.warm.n1, adm.warm.n2,
                        n1, n2, n_pad)
                    pstate = engine.warm_packed_state(
                        pts.x_t, jnp.asarray(adm.warm.w),
                        jnp.asarray(lam), jnp.asarray(prev))
                else:
                    pstate = engine.init_packed_state(pts.sign, n1, n2,
                                                      d_pad)
                batch.state = engine.admit_into_slot(
                    batch.state, lane, pstate,
                    jax.random.key(req.seed), num_iters)
                row = engine.slot_params_row(params, req.gap_tol)
                for f in engine.SlotParams._fields:
                    getattr(batch.sp, f)[lane] = getattr(row, f)
                batch.sp_dev = None                 # refresh device mirror
                ticket.note = _Slot(request_id=ticket.rid, req=req,
                                    pre=adm.pre, xp_t=xp_t, xm_t=xm_t,
                                    warm=adm.warm, tenant=adm.tenant,
                                    history=[])

    # ----------------------------------------------------------- failure
    def _record_failure(self, ticket, status: Status, reason: str) -> None:
        """Terminal non-result: structured record claimable via
        ``result(rid)``, live bookkeeping dropped.  A streaming
        tenant's failed/superseded re-fit clears the tenant's live-rid
        (the tenant itself, its dataset and its last good warm state
        all survive -- the next update retries from there)."""
        self._results[ticket.rid] = RequestFailure(
            request_id=ticket.rid, status=status, reason=reason,
            attempts=ticket.attempts)
        self._pre_cache.pop(ticket.rid, None)
        self._tickets.pop(ticket.rid, None)
        ten_id = self._rid_tenant.pop(ticket.rid, None)
        if ten_id is not None:
            ten = self._tenants.get(ten_id)
            if ten is not None and ten.live_rid == ticket.rid:
                ten.live_rid = None

    # ----------------------------------------------------------- harvest
    def _harvest(self, group, obj, healthy) -> list[FitResult]:
        """Record per-slot history, QUARANTINE unhealthy slots (retry
        or structured FAILED -- batch-mates are untouched), extract
        every FINISHED healthy slot through the svm.py recovery path,
        and free its lane."""
        batch = group.payload
        # ONE blocking transfer per chunk for all (S,)-sized lifecycle
        # vectors; the big per-slot state only moves for finished slots
        with span("svc.wait"):
            active, t, obj, healthy = map(np.asarray, jax.device_get(
                (batch.state.active, batch.state.t, obj, healthy)))
        out = []
        for lane, ticket in list(group.slots.items()):
            slot = ticket.note
            if not healthy[lane]:
                # Quarantine: the engine already deactivated the lane
                # on device; free it host-side.  Within the retry
                # budget the ticket re-queues BEHIND waiting tickets
                # (fresh arrival = backoff ordering); past it, the
                # request fails with a structured record.
                if ticket.attempts <= ticket.payload.max_retries:
                    # re-stash the FULL admission record: the retry
                    # re-enters from the same (last good) warm state
                    # the poisoned attempt started from, so a clean
                    # retry is bit-for-bit a clean first run
                    self._pre_cache[ticket.rid] = _Admission(
                        pre=slot.pre, xp_t=slot.xp_t, xm_t=slot.xm_t,
                        warm=slot.warm, tenant=slot.tenant)
                    self._sched.resubmit(group, lane, ticket)
                else:
                    self._record_failure(
                        ticket, Status.FAILED,
                        f"non-finite solver state detected at "
                        f"iteration {int(t[lane])} (quarantined; "
                        f"attempts={ticket.attempts})")
                    self._sched.release(group, lane, Status.FAILED)
                continue
            slot.history.append((int(t[lane]), float(obj[lane])))
            if active[lane]:
                continue
            with span("svc.recover", rid=slot.request_id):
                lam = np.asarray(jax.device_get(batch.state.log_lam[lane]))
                n1 = slot.xp_t.shape[0]
                n2 = slot.xm_t.shape[0]
                if slot.tenant is not None:
                    # STREAMING harvest: host-retain the final saddle state
                    # (w + dual momentum; lam is already here) BEFORE the
                    # lane is freed -- idle-group eviction drops the device
                    # buffers, so warm state cannot stay slot-resident.
                    ten = self._tenants.get(slot.tenant)
                    if ten is not None and ten.live_rid == slot.request_id:
                        w_h, prev_h = map(np.asarray, jax.device_get(
                            (batch.state.w[lane],
                             batch.state.log_lam_prev[lane])))
                        ten.warm = _WarmState(
                            w=w_h, log_lam=lam, log_lam_prev=prev_h,
                            n1=n1, n2=n2)
                        ten.live_rid = None
                    self._rid_tenant.pop(slot.request_id, None)
                eta = jnp.exp(jnp.asarray(lam[:n1]))
                xi = jnp.exp(jnp.asarray(lam[n1:n1 + n2]))
                w, b, objective, margin, _ = svm_mod.recover_hyperplane(
                    slot.pre, eta, xi, slot.xp_t, slot.xm_t)
            res = FitResult(request_id=slot.request_id, w=w, b=b,
                            objective=objective, margin=margin,
                            iterations=int(t[lane]), bucket=batch.bucket,
                            history=slot.history)
            self._results[slot.request_id] = res
            self._tickets.pop(slot.request_id, None)
            out.append(res)
            self._sched.release(group, lane)
        return out

    # -------------------------------------------------------------- run
    def step(self) -> list[FitResult]:
        """One scheduling round: shed expired deadlines -> policy pick
        -> admit -> one chunk -> harvest (quarantining unhealthy
        slots) -> evict-if-drained.  Returns the requests that
        finished this round."""
        with span("svc.step"):
            # Deadline shedding FIRST (opt-in via clock): expired queued
            # tickets must neither drive the policy pick nor occupy a lane.
            if self._clock is not None:
                for g, ticket in self._sched.shed_expired(self._clock()):
                    self._record_failure(
                        ticket, Status.DEADLINE_EXCEEDED,
                        f"deadline {ticket.deadline} passed before "
                        f"admission")
                    self._sched.evict_idle(g)
            group = self._sched.next_group()
            if group is None:
                return []
            self._admit(group)
            if not group.slots:
                return []
            batch = group.payload
            n_pad, d_pad = batch.bucket
            project, check_gap = batch.project, batch.check_gap
            block_size = next(iter(group.slots.values())).payload.block_size
            if batch.mesh is None:
                key = engine.slot_trace_key(group.num_slots, n_pad, d_pad,
                                            block_size, self.chunk_steps,
                                            project, check_gap, self.backend)
            else:
                key = engine.sharded_slot_trace_key(
                    group.num_slots, n_pad, d_pad, block_size,
                    self.chunk_steps, project, check_gap, self.backend,
                    batch.mesh, batch.slot_axes, batch.point_axes)
            # Always run FULL chunks: a slot near its budget is frozen by
            # the per-slot mask at exactly max_t, which keeps every slot's
            # chunk/key schedule identical to a solo solve with
            # record_every == chunk_steps (the parity contract).  A
            # shortened trip count here would give a mid-run-admitted slot
            # a partial FIRST chunk no solo schedule ever takes.
            if batch.sp_dev is None:
                batch.sp_dev = jax.tree.map(jnp.asarray, batch.sp)
                if batch.sp_sharding is not None:
                    batch.sp_dev = jax.device_put(batch.sp_dev,
                                                  batch.sp_sharding)
            # Deterministic fault injection (tests/bench only): poison a
            # targeted lane BEFORE its chunk; the jitted helper is keyed
            # outside the chunk executables, so zero-recompile accounting
            # is untouched.  A request's chunk index is the length of its
            # recorded history.
            if self._injector is not None:
                for lane, ticket in group.slots.items():
                    if self._injector.poison_due(ticket.rid,
                                                 len(ticket.note.history)):
                        batch.state = faults_mod.poison_slot_state(
                            batch.state, lane)
            batch.ensure_placement()
            queued = sum(g.queued for g in self._sched.groups)
            with self._sched.stats.chunk(key, engine.trace_counts), \
                    span("svc.dispatch", lanes=len(group.slots),
                         slots=group.num_slots, queued=queued, n_pad=n_pad):
                if batch.mesh is None:
                    batch.state, obj, healthy = engine.run_chunk_slots(
                        batch.state, batch.x_t, batch.sign, batch.sp_dev,
                        self.chunk_steps,
                        chunk_steps=self.chunk_steps, d=d_pad,
                        block_size=block_size, project=project,
                        check_gap=check_gap, backend=self.backend)
                else:
                    batch.state, obj, healthy = \
                        engine.run_chunk_slots_sharded(
                            batch.state, batch.x_t, batch.sign,
                            batch.sp_dev, self.chunk_steps,
                            mesh=batch.mesh, slot_axes=batch.slot_axes,
                            point_axes=batch.point_axes,
                            chunk_steps=self.chunk_steps, d=d_pad,
                            block_size=block_size, project=project,
                            check_gap=check_gap, backend=self.backend)
            with span("svc.harvest"):
                out = self._harvest(group, obj, healthy)
            # Idle-batch eviction: a drained batch's device buffers (slot
            # state + the (S, d, n) operand) would otherwise leak device
            # memory across varied request shapes.  The COMPILED executable
            # survives in the jit cache regardless.
            with span("svc.evict"):
                self._sched.evict_idle(group)
            return out

    def run(self) -> dict[int, FitResult]:
        """Drain every queue; returns (and RELEASES) every result
        completed since the last drain -- results are not retained
        service-side, so a long-running service stays O(active slots),
        not O(requests served)."""
        while self._sched.has_work():
            self.step()
        out, self._results = self._results, {}
        return out

    # ------------------------------------------------------------ status
    def status(self, rid: int) -> Status:
        """The request's lifecycle state: DONE/FAILED/CANCELLED/
        DEADLINE_EXCEEDED once terminal (until its result is claimed),
        PENDING/RUNNING while live.  KeyError on unknown/claimed
        rids."""
        res = self._results.get(rid)
        if res is not None:
            return (res.status if isinstance(res, RequestFailure)
                    else Status.DONE)
        return self._tickets[rid].status

    def result(self, rid: int) -> FitResult | RequestFailure:
        """Pop one terminal outcome: the :class:`FitResult`, or the
        structured :class:`RequestFailure` (quarantined / cancelled /
        deadline-shed).  A KNOWN rid still in flight raises
        :class:`ResultNotReady`; an unknown (or already claimed) rid
        keeps the historical bare ``KeyError``."""
        if rid in self._results:
            return self._results.pop(rid)
        if rid in self._tickets:
            raise ResultNotReady(
                f"request {rid} is {self._tickets[rid].status.value}")
        raise KeyError(rid)

    def cancel(self, rid: int) -> bool:
        """Cancel a live request: a QUEUED ticket is removed eagerly, a
        RUNNING one has its device lane deactivated and freed (the
        service is host-driven, so this is always between chunks).
        Returns True if cancelled; False for unknown/terminal rids.
        The outcome is a claimable CANCELLED :class:`RequestFailure`."""
        ticket = self._tickets.get(rid)
        if ticket is None:
            return False
        hit = self._sched.cancel_queued(rid)
        if hit is not None:
            g, t = hit
            self._record_failure(t, Status.CANCELLED,
                                 "cancelled while queued")
            self._sched.evict_idle(g)
            return True
        for g in self._sched.groups:
            for lane, t in list(g.slots.items()):
                if t.rid == rid:
                    g.payload.state = engine.deactivate_slot(
                        g.payload.state, lane)
                    self._record_failure(t, Status.CANCELLED,
                                         "cancelled while running")
                    self._sched.release(g, lane, Status.CANCELLED)
                    self._sched.evict_idle(g)
                    return True
        return False

    def fit(self, x, y, **kw) -> FitResult:
        """One-shot convenience: submit + drain (still exercises the
        full slot path, S=1 occupancy).  Other requests completed by
        the drain stay claimable via ``result()``.  Raises
        ``RuntimeError`` if the request was quarantined past its retry
        budget."""
        rid = self.submit(FitRequest(x=x, y=y, **kw))
        out = self.run()
        res = out.pop(rid)
        self._results.update(out)      # keep co-drained results claimable
        if isinstance(res, RequestFailure):
            raise RuntimeError(
                f"fit request {rid} failed: {res.status.value} "
                f"({res.reason})")
        return res

    # ------------------------------------------------------------- stats
    @property
    def stats(self) -> dict:
        """Compile-cache accounting (scheduler-tracked): ``compiles``
        counts the traces observed during THIS service's chunk
        dispatches (trace-count delta around each call -- other
        services or solo solves sharing an executable key are never
        misattributed), ``cache_hits`` the chunk calls served without
        tracing.  After warm-up every call must be a hit (asserted by
        the serve bench)."""
        return self._sched.stats.as_dict()

    @property
    def latencies(self):
        """(request_id, queue-to-result seconds) per completed request
        -- stamped by the scheduler at submit and release (bounded
        sliding window)."""
        return self._sched.latencies

    def latency_percentiles(self, *pcts: float) -> dict[float, float]:
        """Queue-to-result latency percentiles (seconds), e.g.
        ``svc.latency_percentiles(50.0, 95.0)``."""
        return self._sched.latency_percentiles(*pcts)
