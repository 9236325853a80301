"""Multi-tenant fit-serving throughput.

Requests/sec of the continuous-batching solver service
(repro.serve.solver_service) at S in {1, 4, 8} slots against the
sequential baseline -- the same R requests solved one ``SaddleSVC.fit``
at a time.  Every path runs the SAME slot-batched engine (a sequential
fit is the S=1 degenerate batch), so the delta is pure batching: S
problems per compiled step amortize the per-iteration fixed costs
(dispatch, RNG, scalar ops) that a single tiny fit cannot.

The request shape is deliberately SMALL (n=200, d=32): the paper's
per-iteration work is O(B + n) after preprocessing, so small fits are
the overhead-dominated regime the service exists for (the motivation's
"many independent instances as the unit of work").

Besides requests/sec, the bench records per-request QUEUE-TO-RESULT
latency percentiles (p50/p95, stamped by the scheduler at submit and
release) for the default latency-aware policy AND the round-robin
policy at S=8 -- so scheduler policies are comparable on tail latency,
not just throughput, from `BENCH_serve.json`.

Also asserted here (hard, in both quick and full mode): ZERO
recompiles after bucket warm-up -- the timed phase must be 100%
compile-cache hits, checked via the service's trace accounting AND a
global engine.trace_counts snapshot.

Sharded mode (always on, subprocess): the SAME service on a forced
8-device CPU mesh (lanes placement: every device owns whole slots, zero
collectives) at EQUAL TOTAL LANES vs the single-device service --
S=32 lanes either vmapped on one device or spread 4-per-device over the
mesh.  All 8 "devices" share this host's core(s), so per-device rps
equals the mesh-vs-single wall-clock ratio at equal work; the 0.9x
floor asserts sharding overhead (shard_map partitioning, per-device
dispatch) stays under 10% (fails in full mode, warns in quick, like the
speedup floor).  Zero recompiles after warm-up is asserted HARD under
sharding, and a point-sharded big fit (points spanning the mesh's data
axis inside the slot driver) is timed alongside with its per-chunk
collective budget from ServeCommModel.  Emitted as ``serve/sharded/*``.

Streaming mode (always on): ST_TENANTS live (``stream=True``) tenants
each take ST_ROUNDS of appended points (2+2 per round -- the regime
warm starts exist for; the per-tenant point count crosses the 128-rung
boundary exactly and then JUMPS to the 256 rung in the last round),
re-fit warm (carry w + re-placed duals from the previous solution) vs
cold (same edits, fresh state), both under the same duality-gap stop.
``serve/stream/warm_iters_ratio`` = total warm update iterations over
cold -- the tentpole's sublinear-re-fit claim as a tracked number --
with a <= 0.7x floor (warn in quick mode, FAIL in full), plus
requests/sec for both passes.  ZERO recompiles across update rounds
(in-bucket re-packs AND the rung jump) is asserted HARD in both modes
via the same trace_counts snapshot discipline as above.

Chaos mode (always on): a seed-keyed fault plan
(repro.serve.faults.FaultPlan) poisons a fixed subset of the requests
mid-run and delays others' submissions; the pass asserts (hard) that
EXACTLY the poisoned requests fail (structured FAILED), that every
survivor's objective is BIT-EQUAL to its fault-free run (quarantine
invariance at bench scale), that zero recompiles happen under chaos,
and that goodput (completed requests/sec under faults) stays above a
floor fraction of the fault-free S=8 throughput.  Goodput lands in
BENCH_serve.json so the degradation trajectory is tracked per run.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import emit, emit_count
from repro.core import engine
from repro.core.svm import SaddleSVC
from repro.data import synthetic
from repro.serve import faults as faults_mod
from repro.serve.scheduler import RequestFailure
from repro.serve.solver_service import (FitRequest, SolverService,
                                        UpdateRequest)

R = 8            # requests per trial
N1 = N2 = 100    # points per class  -> (256, 32) bucket
D = 32
ITERS = 2000
CHUNK = 250      # service chunk == sequential record_every (same sync
                 # cadence for both paths)


def _requests():
    return [(synthetic.blobs(N1, N2, D, gap=0.8, spread=0.3, seed=i), i)
            for i in range(R)]


def _seq_pass(reqs) -> float:
    t0 = time.perf_counter()
    for ds, seed in reqs:
        SaddleSVC(num_iters=ITERS, seed=seed,
                  record_every=CHUNK).fit(ds.x, ds.y)
    return time.perf_counter() - t0


def _svc_pass(reqs, num_slots: int, policy: str = "oldest"):
    svc = SolverService(num_slots=num_slots, chunk_steps=CHUNK,
                        policy=policy)
    t0 = time.perf_counter()
    for ds, seed in reqs:
        svc.submit(FitRequest(x=ds.x, y=ds.y, seed=seed,
                              num_iters=ITERS))
    svc.run()
    return time.perf_counter() - t0, svc


def _lat_pcts(svc) -> tuple[float, float]:
    pcts = svc.latency_percentiles(50.0, 95.0)
    return pcts[50.0], pcts[95.0]


CHAOS_SEED = 7
GOODPUT_FLOOR = 0.3   # completed-rps under faults vs fault-free rps


def _objectives(reqs) -> dict[int, float]:
    """Fault-free reference objectives keyed by request seed."""
    svc = SolverService(num_slots=8, chunk_steps=CHUNK)
    rid2seed = {svc.submit(FitRequest(x=ds.x, y=ds.y, seed=seed,
                                      num_iters=ITERS)): seed
                for ds, seed in reqs}
    return {rid2seed[rid]: res.objective
            for rid, res in svc.run().items()}


def _chaos_pass(reqs, plan: faults_mod.FaultPlan):
    """Drive one service pass under the plan: delayed submissions feed
    in as their step comes up, poison faults fire in-service via the
    injector.  Returns (elapsed, svc, rid->seed, drained results)."""
    svc = SolverService(num_slots=8, chunk_steps=CHUNK,
                        fault_injector=faults_mod.FaultInjector(plan))
    delays = plan.delays()
    # the plan's rids are SUBMISSION-ORDER ids; sort by delay so the
    # service assigns each rid at its planned step
    order = sorted(((delays.get(i, 0), i, ds, seed)
                    for i, (ds, seed) in enumerate(reqs)))
    rid2seed: dict[int, int] = {}
    t0 = time.perf_counter()
    step_i, qi = 0, 0
    while qi < len(order) or svc._sched.has_work():
        while qi < len(order) and order[qi][0] <= step_i:
            _, _, ds, seed = order[qi]
            rid2seed[svc.submit(FitRequest(x=ds.x, y=ds.y, seed=seed,
                                           num_iters=ITERS))] = seed
            qi += 1
        svc.step()
        step_i += 1
    dt = time.perf_counter() - t0
    return dt, svc, rid2seed, svc.run()


def run(quick: bool = True) -> None:
    reqs = _requests()
    reps = 3 if quick else 4
    slots = (1, 4, 8)

    # ---- warm-up: sequential path + every bucket executable ---------
    _seq_pass(reqs)
    for s in slots:
        _svc_pass(reqs, s)
    snap = dict(engine.trace_counts)

    # ---- timed passes, INTERLEAVED so transient host load hits the
    # baseline and the service alike (wall-clock ratios on a shared
    # CPU are otherwise dominated by when, not what, you measure) ----
    t_seq = None
    best: dict[int, float] = {}
    stats: dict[int, dict] = {}
    lat: dict[int, tuple[float, float]] = {}
    for _ in range(reps):
        dt = _seq_pass(reqs)
        t_seq = dt if t_seq is None else min(t_seq, dt)
        for s in slots:
            dt, svc = _svc_pass(reqs, s)
            if s not in best or dt < best[s]:
                best[s] = dt
                lat[s] = _lat_pcts(svc)
            assert svc.stats["compiles"] == 0 and \
                svc.stats["cache_hits"] == svc.stats["chunk_calls"], \
                svc.stats
            stats[s] = svc.stats
    # policy comparison on tail latency: one round-robin pass at S=8
    # (results are policy-invariant; only queue latency differs)
    _, svc_rr = _svc_pass(reqs, 8, policy="round_robin")
    assert svc_rr.stats["compiles"] == 0, svc_rr.stats
    delta = {k: v - snap.get(k, 0) for k, v in engine.trace_counts.items()
             if v != snap.get(k, 0)}
    assert delta == {}, f"recompile after bucket warm-up: {delta}"

    emit("serve/sequential_fit_loop", t_seq / R,
         f"n={N1 + N2};d={D};iters={ITERS};R={R};rps={R / t_seq:.1f}")
    for s in slots:
        emit(f"serve/slots{s}", best[s] / R,
             f"rps={R / best[s]:.1f};speedup={t_seq / best[s]:.2f}x;"
             f"chunks={stats[s]['chunk_calls']};cache_hits=100%")
        p50, p95 = lat[s]
        emit(f"serve/slots{s}/latency_p50", p50, "queue_to_result;oldest")
        emit(f"serve/slots{s}/latency_p95", p95, "queue_to_result;oldest")
    p50, p95 = _lat_pcts(svc_rr)
    emit("serve/slots8_rr/latency_p50", p50, "queue_to_result;round_robin")
    emit("serve/slots8_rr/latency_p95", p95, "queue_to_result;round_robin")
    speedup8 = t_seq / best[8]
    emit_count("serve/recompiles_after_warmup", 0, "asserted_zero")

    # ---- acceptance floor: >= 2x over the sequential loop at S=8 ----
    if speedup8 < 2.0:
        # Wall-clock ratios are load sensitive (engine_bench precedent):
        # the quick/ci smoke only WARNS; the full run fails.
        msg = (f"S=8 serving speedup {speedup8:.2f}x < 2.0x floor "
               f"(typically measures 2.2-2.4x on an idle CPU)")
        if not quick:
            raise AssertionError(msg)
        print(f"# WARNING: {msg}")

    # ---- chaos mode: goodput + quarantine invariance under faults ----
    base_obj = _objectives(reqs)
    plan = faults_mod.FaultPlan.generate(
        CHAOS_SEED, list(range(R)), poison_frac=0.3, delay_frac=0.3,
        max_chunk=3, max_delay=2)
    assert plan.poisoned_rids(), "chaos plan degenerated: no poison"
    _chaos_pass(reqs, plan)            # warm the poison helper compile
    snap_chaos = dict(engine.trace_counts)
    dt, svc, rid2seed, results = _chaos_pass(reqs, plan)

    failed = {rid for rid, r in results.items()
              if isinstance(r, RequestFailure)}
    assert failed == plan.poisoned_rids(), \
        f"failed {failed} != poisoned {plan.poisoned_rids()}"
    for rid, res in results.items():
        if rid in failed:
            continue
        # quarantine invariance, bench scale: survivors' objectives
        # are BIT-EQUAL to their fault-free runs
        assert res.objective == base_obj[rid2seed[rid]], \
            (rid, res.objective, base_obj[rid2seed[rid]])
    assert svc.stats["compiles"] == 0, svc.stats
    delta = {k: v - snap_chaos.get(k, 0)
             for k, v in engine.trace_counts.items()
             if v != snap_chaos.get(k, 0)}
    assert delta == {}, f"recompile under chaos: {delta}"

    ok = R - len(failed)
    goodput = ok / dt
    ratio = goodput / (R / best[8])
    emit("serve/chaos/goodput_rps", dt / max(ok, 1),
         f"ok={ok}/{R};goodput_rps={goodput:.1f};"
         f"poisoned={len(failed)};seed={CHAOS_SEED}")
    emit_count("serve/chaos/failed_as_planned", len(failed),
               "failed==poisoned;survivors_bit_equal")
    emit_count("serve/chaos/recompiles", 0, "asserted_zero")
    # goodput floor: completing the survivors under faults must retain
    # at least GOODPUT_FLOOR of the fault-free S=8 request rate (the
    # quarantined requests' burned chunks are the degradation budget)
    assert ratio >= GOODPUT_FLOOR, \
        (f"chaos goodput {goodput:.2f} rps is {ratio:.2f}x of the "
         f"fault-free rate; floor {GOODPUT_FLOOR}x")
    emit_count("serve/chaos/goodput_ratio", round(ratio, 3),
               f"floor={GOODPUT_FLOOR};hard_assert")

    # ---- streaming mode: warm-start update rounds vs cold re-fits ----
    _streaming_pass(quick)

    # ---- sharded mode: mesh service in a forced-8-device subprocess --
    _sharded_pass(quick)


# -------------------------------------------------------- streaming pass
ST_TENANTS = 4
ST_ROUNDS = 3          # appends of 2+2/round walk each tenant's point
ST_N1 = ST_N2 = 60     # count 120 -> 124 -> 128 (exact boundary, same
ST_D = 16              # rung) -> 132: a JUMP to the 256 rung in the
ST_APPEND = 2          # last round -- both re-pack paths are timed
ST_ITERS = 40960       # budget; the gap stop ends every solve early
ST_GAP = 0.05
ST_CHUNK = 256
WARM_ITERS_FLOOR = 0.7   # warm updates must need <= 0.7x the cold
                         # iterations-to-gap (measures ~0.14x)


def _stream_data():
    tenants = [synthetic.blobs(ST_N1, ST_N2, ST_D, gap=1.2, spread=0.15,
                               seed=i) for i in range(ST_TENANTS)]
    rounds = [[synthetic.blobs(ST_APPEND, ST_APPEND, ST_D, gap=1.2,
                               spread=0.15, seed=1000 + 10 * r + i)
               for i in range(ST_TENANTS)]
              for r in range(ST_ROUNDS)]
    return tenants, rounds


def _stream_trial(tenants, rounds, warm: bool):
    """One streaming trial: live fits, then per-tenant append rounds
    re-fit warm or cold.  Returns (wall, total update iterations,
    svc)."""
    svc = SolverService(num_slots=ST_TENANTS, chunk_steps=ST_CHUNK)
    t0 = time.perf_counter()
    rids = [svc.submit(FitRequest(x=ds.x, y=ds.y, seed=i,
                                  num_iters=ST_ITERS, gap_tol=ST_GAP,
                                  stream=True))
            for i, ds in enumerate(tenants)]
    svc.run()
    iters = 0
    for rnd in rounds:
        upd = [svc.submit_update(UpdateRequest(tenant=rid, x=ex.x,
                                               y=ex.y, warm=warm))
               for rid, ex in zip(rids, rnd)]
        res = svc.run()
        for u in upd:
            r = res[u]
            assert not isinstance(r, RequestFailure), r
            assert r.iterations < ST_ITERS, \
                "gap stop never fired; iterations-to-gap is meaningless"
            iters += r.iterations
    return time.perf_counter() - t0, iters, svc


def _streaming_pass(quick: bool) -> None:
    tenants, rounds = _stream_data()
    # warm-up traces BOTH rung executables (128 pre-jump, 256 post)
    # and the warm-admission staging helpers for either mode
    _stream_trial(tenants, rounds, True)
    _stream_trial(tenants, rounds, False)
    snap = dict(engine.trace_counts)
    t_warm, it_warm, svc_w = _stream_trial(tenants, rounds, True)
    t_cold, it_cold, svc_c = _stream_trial(tenants, rounds, False)
    # the zero-recompile contract ACROSS update rounds, rung jump
    # included, asserted hard in quick and full mode alike
    for svc in (svc_w, svc_c):
        assert svc.stats["compiles"] == 0, svc.stats
    delta = {k: v - snap.get(k, 0) for k, v in engine.trace_counts.items()
             if v != snap.get(k, 0)}
    assert delta == {}, f"recompile across streaming updates: {delta}"

    n_req = ST_TENANTS * (1 + ST_ROUNDS)
    shape = (f"tenants={ST_TENANTS};rounds={ST_ROUNDS};"
             f"n0={ST_N1 + ST_N2};append={2 * ST_APPEND}/round;"
             f"gap_tol={ST_GAP}")
    emit("serve/stream/warm_pass", t_warm / n_req,
         f"rps={n_req / t_warm:.1f};update_iters={it_warm};{shape}")
    emit("serve/stream/cold_pass", t_cold / n_req,
         f"rps={n_req / t_cold:.1f};update_iters={it_cold};{shape}")
    ratio = it_warm / it_cold
    emit_count("serve/stream/warm_iters_ratio", round(ratio, 4),
               f"warm={it_warm};cold={it_cold};"
               f"floor<={WARM_ITERS_FLOOR};incl_rung_jump_128_to_256")
    emit_count("serve/stream/recompiles_across_updates", 0,
               "asserted_zero;incl_rung_jump")
    if ratio > WARM_ITERS_FLOOR:
        msg = (f"warm-start update rounds took {ratio:.2f}x the cold "
               f"iterations-to-gap, floor {WARM_ITERS_FLOOR}x "
               f"(typically ~0.14x at 2+2-point appends)")
        if not quick:
            raise AssertionError(msg)
        print(f"# WARNING: {msg}")


# ---------------------------------------------------------- sharded pass
SHARD_DEVS = 8
SHARD_SLOTS = 32       # total lanes, both placements: 4/dev vs 32 vmapped
SHARD_N1 = SHARD_N2 = 384          # -> (1024, 32) bucket
SHARD_ITERS = 2000     # nu fits: heavy enough chunks that the mesh's
SHARD_CHUNK = 500      # fixed dispatch overhead stays under the floor
SHARD_POINTS_ITERS = 500
SHARD_RATIO_FLOOR = 0.9

_SHARDED_SUBPROCESS = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, sys.argv[1])
cfg = json.loads(sys.argv[2])

import jax
from repro.core import engine
from repro.data import synthetic
from repro.serve.solver_service import FitRequest, SolverService

S, N1, N2, D = cfg["slots"], cfg["n1"], cfg["n2"], cfg["d"]
ITERS, CHUNK, REPS = cfg["iters"], cfg["chunk"], cfg["reps"]
NU = 1.0 / (0.8 * N1)      # nu-Saddle lanes: the projecting executable
reqs = [(synthetic.blobs(N1, N2, D, gap=0.8, spread=0.3, seed=i), i)
        for i in range(S)]
mesh = jax.make_mesh((len(jax.devices()),), ("data",))

def svc_pass(mesh_arg):
    svc = SolverService(num_slots=S, chunk_steps=CHUNK, mesh=mesh_arg)
    t0 = time.perf_counter()
    for ds, seed in reqs:
        svc.submit(FitRequest(x=ds.x, y=ds.y, seed=seed,
                              num_iters=ITERS, nu=NU))
    svc.run()
    return time.perf_counter() - t0, svc

svc_pass(None)
svc_pass(mesh)
snap = dict(engine.trace_counts)
t_single = t_mesh = None
for _ in range(REPS):
    dt, svc = svc_pass(None)
    t_single = dt if t_single is None else min(t_single, dt)
    assert svc.stats["compiles"] == 0, svc.stats
    dt, svc = svc_pass(mesh)
    t_mesh = dt if t_mesh is None else min(t_mesh, dt)
    assert svc.stats["compiles"] == 0, svc.stats
delta = {k: v - snap.get(k, 0) for k, v in engine.trace_counts.items()
         if v != snap.get(k, 0)}
assert delta == {}, f"recompile after warm-up under sharding: {delta}"

# point-sharded big fit (nu-Saddle: the audited 29-collective regime):
# points span the mesh's data axis in-slot
big = synthetic.blobs(4 * N1, 4 * N2, D, gap=0.8, spread=0.3, seed=99)

def points_pass():
    svc = SolverService(num_slots=S, chunk_steps=CHUNK, mesh=mesh,
                        shard_points_above=N1 + N2)
    svc.submit(FitRequest(x=big.x, y=big.y, seed=99,
                          num_iters=cfg["points_iters"],
                          nu=1.0 / (0.8 * 4 * N1)))
    t0 = time.perf_counter()
    svc.run()
    return time.perf_counter() - t0, svc

points_pass()
t_points, svc = points_pass()
assert svc.stats["compiles"] == 0, svc.stats

print("SERVE_SHARDED_JSON=" + json.dumps(
    {"t_single": t_single, "t_mesh": t_mesh, "t_points": t_points,
     "stats_mesh": svc.stats}))
"""


def _sharded_pass(quick: bool) -> None:
    import jax

    from repro.core import distributed, projections

    if jax.default_backend() == "tpu":
        # the pass times a forced-8-device CPU child; on a chip host its
        # numbers would sit beside the chip's in one report
        print("# serve/sharded: refused on a TPU host -- this pass times "
              "8 virtual CPU devices in a child process; the mesh path "
              "on the chip runs in `python chip_smoke.py --chips 4`",
              file=sys.stderr)
        return

    cfg = {"slots": SHARD_SLOTS, "n1": SHARD_N1, "n2": SHARD_N2,
           "d": D, "iters": SHARD_ITERS, "chunk": SHARD_CHUNK,
           "points_iters": SHARD_POINTS_ITERS,
           "reps": 2 if quick else 3}
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_SUBPROCESS, src,
         json.dumps(cfg)],
        capture_output=True, text=True, timeout=1200)
    payload = None
    for line in out.stdout.splitlines():
        if line.startswith("SERVE_SHARDED_JSON="):
            payload = json.loads(line[len("SERVE_SHARDED_JSON="):])
    if payload is None:
        raise RuntimeError(
            f"sharded serve subprocess produced no result (exit "
            f"{out.returncode}):\n{out.stdout[-2000:]}\n"
            f"{out.stderr[-4000:]}")

    r = SHARD_SLOTS                       # one request per lane
    t_single, t_mesh = payload["t_single"], payload["t_mesh"]
    ratio = t_single / t_mesh
    # all 8 forced devices share this host's core(s): at equal total
    # lanes the wall-clock ratio IS per-device rps vs the single device
    emit(f"serve/sharded/slots{SHARD_SLOTS}_dev{SHARD_DEVS}",
         t_mesh / r,
         f"rps={r / t_mesh:.1f};single_rps={r / t_single:.1f};"
         f"ratio_vs_single={ratio:.2f};placement=lanes;"
         f"n={SHARD_N1 + SHARD_N2};iters={SHARD_ITERS}")
    emit_count("serve/sharded/recompiles_after_warmup", 0,
               "asserted_zero_in_subprocess")
    # per-chunk collective budget, pinned by comm_audit in CI: lanes
    # placement is collective-free; the point-sharded big fit runs the
    # vmap-batched Theorem-8 rounds
    emit_count("serve/sharded/lanes_collectives_per_chunk", 0,
               "audited==model;see comm/serve_lanes_*")
    # the big fit runs in a shard_num_slots=2 point-sharded group
    model = distributed.ServeCommModel(
        k=SHARD_DEVS, num_slots=2,
        nu_rounds_per_iter=float(projections.BISECT_ROUNDS_SOLVER))
    per_chunk = (model.collectives_per_iteration(1) * SHARD_CHUNK
                 + sum(model.per_chunk_multiset(D).values()))
    emit_count("serve/sharded/points_collectives_per_chunk", per_chunk,
               f"iter={model.collectives_per_iteration(1)}x{SHARD_CHUNK}"
               f"+boundary=2;audited==model;see comm/serve_points_*")
    emit("serve/sharded/points_big_fit",
         payload["t_points"],
         f"n={4 * (SHARD_N1 + SHARD_N2)};iters={SHARD_POINTS_ITERS};"
         f"placement=points;k={SHARD_DEVS}")

    if ratio < SHARD_RATIO_FLOOR:
        msg = (f"sharded serving at equal total lanes is {ratio:.2f}x "
               f"the single-device rate, floor {SHARD_RATIO_FLOOR}x "
               f"(typically 0.90-0.95 on an idle CPU)")
        if not quick:
            raise AssertionError(msg)
        print(f"# WARNING: {msg}")
