"""Communication audit: Theorem 8 against the collectives XLA emits.

The paper's headline distributed result (Theorem 8) bounds Saddle-DSVC
communication by O~(k(d + sqrt(d/eps))) -- realized here as a CONSTANT
number of tiny all-reduces per iteration (see
:class:`repro.core.distributed.CommModel`).  Until this module, the
repo only *asserted* that via the analytic model; nothing ever counted
the collectives the compiler actually emits, so a regression that
sneaks a per-point all-gather into the shard_map hot loop (the classic
failure mode of sublinear optimization implementations) would pass the
whole suite.

This module closes the loop from theory to compiler output:

* :func:`lower_step` AOT-lowers ONE ``engine.step_packed`` iteration
  under ``shard_map`` on a k-client mesh (ShapeDtypeStructs only -- no
  device allocation) and compiles it to post-SPMD HLO.
* :func:`lower_runner` does the same for the FULL production chunk
  (``distributed.sharded_run_fn``, the multi-pod dry-run path).
* :func:`audit_hlo` parses the compiled module with
  :mod:`repro.utils.hlo_analysis`, expands while bodies by the trip
  counts XLA proved (``known_trip_count``), and returns the measured
  per-iteration / per-chunk collective multisets keyed
  ``(op, reduce_kind, result_elements)`` -- directly comparable to
  ``CommModel.collective_multiset``.
* :func:`run_specs` / :func:`collect_audits` run a batch of audits in
  a subprocess with ``--xla_force_host_platform_device_count`` forced
  high enough for the largest k (jax pins the device count at first
  init, so in-process tests cannot raise it).

The per-iteration boundary in the chunk lowering is structural: the
engine's chunk loop is the ONLY collective-bearing while with a
DYNAMIC trip count (``num_steps`` is a runtime operand), while the
bisection loop inside it carries ``known_trip_count = BISECT_ROUNDS``.
Anything XLA hoists out of the loop (e.g. the once-per-chunk objective
psum) lands in the per-chunk multiset instead.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import NamedTuple

import numpy as np

from repro.utils import hlo_analysis as ha

CHANNEL_SENTINEL = "COMM_AUDIT_JSON="


def _key_str(key: tuple) -> str:
    op, kind, elems = key
    return f"{op}|{kind}|{elems}"


def multiset_to_json(ms: dict) -> dict:
    return {_key_str(k): v for k, v in sorted(ms.items())}


class HloCommCounts(NamedTuple):
    """Collective multisets recovered from one compiled module."""
    per_iteration: dict      # (op, reduce_kind, elements) -> count
    per_chunk: dict          # collectives OUTSIDE the dynamic step loop
    per_iteration_count: int
    per_iteration_bytes: int

    def to_json(self) -> dict:
        return {
            "per_iteration": multiset_to_json(self.per_iteration),
            "per_chunk": multiset_to_json(self.per_chunk),
            "per_iteration_count": self.per_iteration_count,
            "per_iteration_bytes": self.per_iteration_bytes,
        }


def _expand(comp: str, colls_by_comp: dict, whiles_by_comp: dict,
            depth: int = 0) -> dict:
    """Collectives of ``comp`` with every known-trip-count while body
    expanded (body x trip count), recursively.  Returns
    ``(op, reduce_kind, elements) -> [count, bytes]`` -- bytes carry
    the dtype-aware result sizes from hlo_analysis, not an assumed
    element width."""
    if depth > 8:
        raise ValueError("while nesting too deep -- unexpected HLO "
                         "structure, refusing to audit")
    ms: dict = {}

    def bump(key, cnt, nbytes):
        ent = ms.setdefault(key, [0, 0])
        ent[0] += cnt
        ent[1] += nbytes

    for c in colls_by_comp.get(comp, []):
        bump((c.op, c.reduce_kind, c.elements), 1, c.bytes)
    for w in whiles_by_comp.get(comp, []):
        body_ms = _expand(w.body, colls_by_comp, whiles_by_comp,
                          depth + 1)
        if not body_ms:
            continue
        if w.trip_count is None:
            raise ValueError(
                f"collective-bearing while body {w.body} has no "
                "known_trip_count -- cannot expand to per-iteration "
                "counts (unexpected dynamic loop below the step loop)")
        for key, (cnt, nbytes) in body_ms.items():
            bump(key, cnt * w.trip_count, nbytes * w.trip_count)
    return ms


def _counts(ms: dict) -> dict:
    return {key: cnt for key, (cnt, _) in ms.items()}


def _bytes(ms: dict) -> int:
    return sum(nbytes for _, nbytes in ms.values())


def audit_hlo(hlo_text: str, *, has_step_loop: bool) -> HloCommCounts:
    """Measured collective multisets of a compiled module.

    ``has_step_loop=False``: the module IS one iteration (a single
    ``step_packed`` lowering); everything (with known-trip-count whiles
    such as the bisection expanded) is per-iteration, and per_chunk is
    empty.

    ``has_step_loop=True``: the module is a chunk; the unique dynamic
    collective-bearing while is the step loop -- its expanded body is
    the per-iteration multiset, everything outside it per-chunk.
    """
    colls = ha.collective_records(hlo_text)
    whiles = ha.while_records(hlo_text)
    entry = ha.entry_computation(hlo_text)

    colls_by_comp: dict = {}
    for c in colls:
        colls_by_comp.setdefault(c.computation, []).append(c)
    whiles_by_comp: dict = {}
    for w in whiles:
        whiles_by_comp.setdefault(w.computation, []).append(w)

    # sanity: every collective-bearing computation must be reachable
    # from the entry through while bodies (no collectives hidden in
    # call/fusion computations this walk would miss)
    reachable = set()
    stack = [entry]
    while stack:
        comp = stack.pop()
        if comp in reachable:
            continue
        reachable.add(comp)
        stack.extend(w.body for w in whiles_by_comp.get(comp, []))
    hidden = sorted(set(colls_by_comp) - reachable)
    if hidden:
        raise ValueError(
            f"collectives in computations not reachable from entry via "
            f"while bodies: {hidden} -- audit walk would undercount")

    if not has_step_loop:
        per_iter = _expand(entry, colls_by_comp, whiles_by_comp)
        per_chunk: dict = {}
    else:
        def bears_collectives(body):
            if colls_by_comp.get(body):
                return True
            return any(bears_collectives(w.body)
                       for w in whiles_by_comp.get(body, []))

        step_loops = [w for w in whiles_by_comp.get(entry, [])
                      if w.trip_count is None and bears_collectives(w.body)]
        if len(step_loops) != 1:
            raise ValueError(
                f"expected exactly one dynamic collective-bearing while "
                f"(the engine chunk loop), found {len(step_loops)}")
        per_iter = _expand(step_loops[0].body, colls_by_comp,
                           whiles_by_comp)
        # per-chunk = the entry expansion with the step loop removed;
        # any OTHER dynamic collective-bearing while still fails loudly
        # inside _expand
        minus_step = {comp: [w for w in ws if w is not step_loops[0]]
                      for comp, ws in whiles_by_comp.items()}
        per_chunk = _expand(entry, colls_by_comp, minus_step)

    return HloCommCounts(
        per_iteration=_counts(per_iter), per_chunk=_counts(per_chunk),
        per_iteration_count=sum(cnt for cnt, _ in per_iter.values()),
        per_iteration_bytes=_bytes(per_iter))


# ==========================================================================
# Lowering helpers (require >= k jax devices; see collect_audits for the
# subprocess path that forces the host device count).
# ==========================================================================

def client_mesh(k: int):
    """A (k,)-device mesh over the first k local devices, axis name =
    the engine's client axis."""
    import jax
    from repro.core.engine import CLIENT_AXIS

    devs = jax.devices()
    if len(devs) < k:
        raise ValueError(
            f"need {k} devices for a k={k} client mesh, have "
            f"{len(devs)}; run under --xla_force_host_platform_"
            f"device_count (see comm_audit.collect_audits)")
    return jax.sharding.Mesh(np.array(devs[:k]), (CLIENT_AXIS,))


def problem_structs(mesh, axis, *, n1: int, n2: int, d: int):
    """ShapeDtypeStruct stand-ins for the packed sharded problem:
    (state, x_t, sign, key) with dim-0 client sharding."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import engine, preprocess

    k = int(np.prod([mesh.shape[a] for a in
                     (axis if isinstance(axis, tuple) else (axis,))]))
    m1, m2 = -(-n1 // k), -(-n2 // k)
    m_pad = preprocess.packed_length(m1 + m2)
    shard = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())

    def sds(shape, dtype=jnp.float32, sharding=shard):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    state = engine.PackedState(
        w=sds((k, d)), log_lam=sds((k, m_pad)),
        log_lam_prev=sds((k, m_pad)), u=sds((k, m_pad)),
        t=sds((k,), jnp.int32))
    x_t = sds((k, d, m_pad))
    sign = sds((k, m_pad))
    key_aval = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key_aval.shape, key_aval.dtype,
                               sharding=repl)
    return state, x_t, sign, key, repl


def lower_step(k: int, *, n1: int, n2: int, d: int, nu: float,
               block_size: int = 1, backend: str = "jnp",
               mesh=None, axis=None) -> str:
    """Compile ONE ``engine.step_packed`` iteration under shard_map on a
    k-client mesh and return the post-SPMD HLO text."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core import engine, saddle
    from repro.core.engine import CLIENT_AXIS

    mesh = mesh if mesh is not None else client_mesh(k)
    axis = axis if axis is not None else CLIENT_AXIS
    params = saddle.make_params(n1 + n2, d, 1e-3, 0.1, nu=nu,
                                block_size=block_size)
    state, x_t, sign, key, _ = problem_structs(mesh, axis, n1=n1,
                                                n2=n2, d=d)

    def client(st, x_t_c, sign_c, key_r):
        st = jax.tree.map(lambda a: a[0], st)
        st = engine.step_packed(st, key_r, x_t_c[0], sign_c[0], params,
                                axis_name=axis, backend=backend)
        return jax.tree.map(lambda a: a[None], st)

    spec = P(axis)
    fn = jax.shard_map(client, mesh=mesh,
                       in_specs=(spec, spec, spec, P()), out_specs=spec,
                       check_vma=False)
    return jax.jit(fn).lower(state, x_t, sign, key).compile().as_text()


def runner_lowerable(mesh, axis, *, n1: int, n2: int, d: int, nu: float,
                     block_size: int = 1, chunk_steps: int = 8,
                     backend: str = "jnp"):
    """(fn, args) for ``jit(fn).lower(*args)``: the FULL production
    chunk (distributed.sharded_run_fn -- the multi-pod dry-run path)
    over ShapeDtypeStructs.  Single source of the chunk-lowering
    recipe, shared with ``launch.specs.build_saddle_dsvc_lowerable``."""
    import jax
    import jax.numpy as jnp

    from repro.core import distributed, saddle

    params = saddle.make_params(n1 + n2, d, 1e-3, 0.1, nu=nu,
                                block_size=block_size)
    state, x_t, sign, key, repl = problem_structs(mesh, axis, n1=n1,
                                                  n2=n2, d=d)
    num_steps = jax.ShapeDtypeStruct((), jnp.int32, sharding=repl)
    fn = distributed.sharded_run_fn(mesh, axis, backend, params=params,
                                    chunk_steps=chunk_steps)
    return fn, (state, key, x_t, sign, num_steps)


def lower_runner(k: int, *, n1: int, n2: int, d: int, nu: float,
                 block_size: int = 1, chunk_steps: int = 8,
                 backend: str = "jnp", mesh=None, axis=None) -> str:
    """Compile the full production chunk and return its post-SPMD HLO
    text."""
    import jax

    from repro.core.engine import CLIENT_AXIS

    mesh = mesh if mesh is not None else client_mesh(k)
    axis = axis if axis is not None else CLIENT_AXIS
    fn, args = runner_lowerable(mesh, axis, n1=n1, n2=n2, d=d, nu=nu,
                                block_size=block_size,
                                chunk_steps=chunk_steps, backend=backend)
    return jax.jit(fn).lower(*args).compile().as_text()


def serve_structs(mesh, *, num_slots: int, n_pad: int, d: int,
                  slot_axes=(), point_axes=()):
    """ShapeDtypeStruct stand-ins for one serving slot chunk:
    (state, x_t, sign, sp, num_steps) with the placement's
    NamedShardings (slot dim over ``slot_axes``, point dim over
    ``point_axes``) -- the exact argument layout
    ``engine.run_chunk_slots_sharded`` dispatches with."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import engine

    s = tuple(slot_axes) or None
    p = tuple(point_axes) or None

    def sds(shape, dtype=jnp.float32, spec=P()):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))

    key_aval = jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), num_slots))
    state = engine.SlotState(
        w=sds((num_slots, d), spec=P(s)),
        log_lam=sds((num_slots, n_pad), spec=P(s, p)),
        log_lam_prev=sds((num_slots, n_pad), spec=P(s, p)),
        u=sds((num_slots, n_pad), spec=P(s, p)),
        t=sds((num_slots,), jnp.int32, spec=P(s)),
        max_t=sds((num_slots,), jnp.int32, spec=P(s)),
        key=sds(key_aval.shape, key_aval.dtype, spec=P(s)),
        active=sds((num_slots,), jnp.bool_, spec=P(s)))
    x_t = sds((num_slots, d, n_pad), spec=P(s, None, p))
    sign = sds((num_slots, n_pad), spec=P(s, p))
    sp = engine.SlotParams(*(sds((num_slots,), spec=P(s))
                             for _ in engine.SlotParams._fields))
    num_steps = sds((), jnp.int32)
    return state, x_t, sign, sp, num_steps


def serve_runner_lowerable(mesh, *, num_slots: int, n_pad: int, d: int,
                           nu: float, block_size: int = 1,
                           chunk_steps: int = 8, backend: str = "jnp",
                           slot_axes=(), point_axes=()):
    """(fn, args) for ``jit(fn, donate_argnums=(0,)).lower(*args)``: the
    serving slot chunk (``engine.sharded_slot_run_fn``) over
    ShapeDtypeStructs.  Single source of the serve-chunk lowering
    recipe, shared with ``launch.specs.build_saddle_serve_lowerable``.
    ``project`` follows the service rule (nu > 0)."""
    from repro.core import engine

    fn = engine.sharded_slot_run_fn(
        mesh, slot_axes=tuple(slot_axes), point_axes=tuple(point_axes),
        chunk_steps=chunk_steps, d=d, block_size=block_size,
        project=nu > 0.0, check_gap=False, backend=backend)
    args = serve_structs(mesh, num_slots=num_slots, n_pad=n_pad, d=d,
                         slot_axes=slot_axes, point_axes=point_axes)
    return fn, args


def lower_serve_chunk(k: int, *, num_slots: int, n_pad: int, d: int,
                      nu: float, block_size: int = 1,
                      chunk_steps: int = 8, backend: str = "jnp",
                      sharded: bool, mesh=None) -> str:
    """Compile one serving slot chunk on a k-client mesh and return the
    post-SPMD HLO text.  ``sharded=False`` is the lanes placement (slot
    dim over the mesh, zero collectives anywhere); ``sharded=True`` is
    the point-sharded placement (point dim over the mesh, Theorem-8
    rounds).  ``num_slots``/``n_pad`` are GLOBAL extents."""
    import jax

    mesh = mesh if mesh is not None else client_mesh(k)
    axes = tuple(mesh.axis_names)
    slot_axes, point_axes = ((), axes) if sharded else (axes, ())
    fn, args = serve_runner_lowerable(
        mesh, num_slots=num_slots, n_pad=n_pad, d=d, nu=nu,
        block_size=block_size, chunk_steps=chunk_steps, backend=backend,
        slot_axes=slot_axes, point_axes=point_axes)
    return (jax.jit(fn, donate_argnums=(0,))
            .lower(*args).compile().as_text())


# ==========================================================================
# Spec-driven audits (subprocess-friendly records).
# ==========================================================================

def audit_spec(spec: dict) -> dict:
    """Run one audit spec and return a JSON-able record.

    Spec keys: k, n1, n2, d, nu, block_size (default 1), backend
    (default jnp), runner (bool: also audit the full chunk lowering),
    chunk_steps (runner only, default 8).  ``kind="serve"`` audits a
    serving slot chunk instead (see :func:`audit_serve_spec`): extra
    keys num_slots and sharded (lanes vs point-sharded placement).
    """
    from repro.core import projections
    from repro.core.distributed import CommModel

    if spec.get("kind") == "serve":
        return audit_serve_spec(spec)

    k = int(spec["k"])
    n1, n2, d = int(spec["n1"]), int(spec["n2"]), int(spec["d"])
    nu = float(spec.get("nu", 0.0))
    block_size = int(spec.get("block_size", 1))
    backend = spec.get("backend", "jnp")
    rounds = float(projections.BISECT_ROUNDS_SOLVER) if nu > 0 else 0.0
    model = CommModel(k=k, nu_rounds_per_iter=rounds)
    predicted = model.collective_multiset(block_size)

    hlo = lower_step(k, n1=n1, n2=n2, d=d, nu=nu,
                     block_size=block_size, backend=backend)
    step = audit_hlo(hlo, has_step_loop=False)

    rec = {
        "k": k, "n1": n1, "n2": n2, "d": d, "nu": nu,
        "block_size": block_size, "backend": backend,
        "predicted": multiset_to_json(predicted),
        "measured": multiset_to_json(step.per_iteration),
        "match": step.per_iteration == predicted,
        "per_iteration_count": step.per_iteration_count,
        "per_iteration_bytes": step.per_iteration_bytes,
        "model_collectives": model.collectives_per_iteration(block_size),
        "model_payload_bytes":
            4 * model.payload_elements_per_iteration(block_size),
        "model_scalars": model.scalars_per_iteration(),
    }

    if spec.get("runner"):
        chunk_steps = int(spec.get("chunk_steps", 8))
        rhlo = lower_runner(k, n1=n1, n2=n2, d=d, nu=nu,
                            block_size=block_size,
                            chunk_steps=chunk_steps, backend=backend)
        run = audit_hlo(rhlo, has_step_loop=True)
        rec.update({
            "chunk_steps": chunk_steps,
            "runner_measured": multiset_to_json(run.per_iteration),
            "runner_per_chunk": multiset_to_json(run.per_chunk),
            "runner_match": run.per_iteration == predicted,
            "runner_matches_step":
                run.per_iteration == step.per_iteration,
        })
    return rec


def audit_serve_spec(spec: dict) -> dict:
    """Audit one SERVING slot chunk against :class:`ServeCommModel`.

    Spec keys: kind="serve", k, num_slots (global), n1, n2 (per-slot
    point counts), d, nu, sharded (bool placement switch), block_size
    (default 1), chunk_steps (default 8), backend (default jnp).

    The bucket rule mirrors ``SolverService.submit``: lanes placement
    pads to ``bucket_length(n1 + n2)``; the point-sharded placement to
    ``k * bucket_length(ceil((n1 + n2) / k))`` so every shard holds a
    lane-aligned power-of-2 rung.

    Contract pinned here: the lanes placement compiles to ZERO
    collectives anywhere in the module (``has_step_loop=False``, both
    multisets empty -- slot groups never talk across devices); the
    point-sharded placement's step loop carries EXACTLY
    ``ServeCommModel.collective_multiset`` and its chunk boundary
    EXACTLY ``ServeCommModel.per_chunk_multiset``."""
    from repro.core import preprocess, projections
    from repro.core.distributed import ServeCommModel

    k = int(spec["k"])
    num_slots = int(spec["num_slots"])
    n1, n2, d = int(spec["n1"]), int(spec["n2"]), int(spec["d"])
    nu = float(spec.get("nu", 0.0))
    block_size = int(spec.get("block_size", 1))
    chunk_steps = int(spec.get("chunk_steps", 8))
    backend = spec.get("backend", "jnp")
    sharded = bool(spec["sharded"])

    n = n1 + n2
    if sharded:
        n_pad = k * preprocess.bucket_length(-(-n // k))
        # point-sharded groups keep their full slot extent per device
        s_local = num_slots
        rounds = (float(projections.BISECT_ROUNDS_SOLVER)
                  if nu > 0 else 0.0)
        model = ServeCommModel(k=k, num_slots=s_local,
                               nu_rounds_per_iter=rounds)
        predicted_iter = model.collective_multiset(block_size)
        predicted_chunk = model.per_chunk_multiset(d)
    else:
        n_pad = preprocess.bucket_length(n)
        if num_slots % k:
            raise ValueError(
                f"lanes placement needs k | num_slots, got "
                f"{num_slots} over k={k}")
        model = None
        predicted_iter, predicted_chunk = {}, {}

    hlo = lower_serve_chunk(k, num_slots=num_slots, n_pad=n_pad, d=d,
                            nu=nu, block_size=block_size,
                            chunk_steps=chunk_steps, backend=backend,
                            sharded=sharded)
    # the lanes placement has no collective-bearing while AT ALL -- the
    # step-loop walk would fail to find one, which is exactly the
    # property we pin by auditing the whole module as one flat scope
    counts = audit_hlo(hlo, has_step_loop=sharded)

    rec = {
        "kind": "serve", "k": k, "num_slots": num_slots,
        "n1": n1, "n2": n2, "n_pad": n_pad, "d": d, "nu": nu,
        "block_size": block_size, "chunk_steps": chunk_steps,
        "backend": backend, "sharded": sharded,
        "predicted": multiset_to_json(predicted_iter),
        "measured": multiset_to_json(counts.per_iteration),
        "predicted_per_chunk": multiset_to_json(predicted_chunk),
        "measured_per_chunk": multiset_to_json(counts.per_chunk),
        "match": (counts.per_iteration == predicted_iter
                  and counts.per_chunk == predicted_chunk),
        "per_iteration_count": counts.per_iteration_count,
        "per_iteration_bytes": counts.per_iteration_bytes,
    }
    if model is not None:
        rec.update({
            "model_collectives":
                model.collectives_per_iteration(block_size),
            "model_payload_bytes":
                4 * model.payload_elements_per_iteration(block_size),
        })
    return rec


def run_specs(specs: list[dict]) -> list[dict]:
    return [audit_spec(s) for s in specs]


_SUBPROCESS_CODE = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=" + sys.argv[1])
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, sys.argv[2])
from repro.utils import comm_audit
specs = json.loads(sys.stdin.read())
recs = comm_audit.run_specs(specs)
print(comm_audit.CHANNEL_SENTINEL + json.dumps(recs))
"""


def collect_audits(specs: list[dict], *, device_count: int | None = None,
                   timeout: int = 900) -> list[dict]:
    """Run a batch of audit specs in a fresh subprocess with the host
    device count forced to max(k) (jax locks the device count at first
    init, so the calling process usually cannot lower k-client meshes
    itself).  Returns the list of :func:`audit_spec` records."""
    if not specs:
        return []
    devs = device_count or max(int(s["k"]) for s in specs)
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_CODE, str(devs), src],
        input=json.dumps(specs), capture_output=True, text=True,
        timeout=timeout)
    for line in out.stdout.splitlines():
        if line.startswith(CHANNEL_SENTINEL):
            return json.loads(line[len(CHANNEL_SENTINEL):])
    raise RuntimeError(
        f"comm audit subprocess produced no result (exit "
        f"{out.returncode}):\n{out.stdout[-2000:]}\n{out.stderr[-4000:]}")
