"""Chip smoke test: drive the SVM fit paths once on a TPU and check what
comes out.

    python chip_smoke.py             one chip: a solo 1M x 256 nu-SVM fit
                                     on both backends, then the
                                     slot-batched fit service
    python chip_smoke.py --chips 4   the 4-chip mesh service (a
                                     lane-parallel group and a
                                     point-sharded 1M x 256 fit) and the
                                     one-chip fits it is compared with
    python chip_smoke.py --tiny      the same phases at toy sizes, on any
                                     backend (a CPU rehearsal: with
                                     JAX_PLATFORMS=cpu, and --chips 4
                                     forces 4 host devices)

One process, no children.  Any mismatch, exception or non-finite value
ends the run with a non-zero exit before the result line.  The last
line of a passing chip run is
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
Off the chip the script exits non-zero without it (with --tiny it ends
after the rehearsal, also without it).  The times printed are smoke
numbers of one run, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from repro.core import engine, saddle                       # noqa: E402
from repro.core import preprocess as pp                     # noqa: E402
from repro.core.svm import (SaddleNuSVC, SaddleSVC,           # noqa: E402
                            recover_hyperplane, split_classes)
from repro.data import synthetic                            # noqa: E402
from repro.launch.mesh import make_test_mesh                # noqa: E402
from repro.serve.scheduler import RequestFailure            # noqa: E402
from repro.serve.solver_service import (FitRequest,         # noqa: E402
                                        SolverService, UpdateRequest)
from repro.utils import compile_cache                       # noqa: E402


@dataclasses.dataclass(frozen=True)
class Sizes:
    solo: dict          # solo fit: points, held-out points, d, B, iterations
    lane: dict          # one lane-bucket fit (B=1)
    wide: dict          # one wide-bucket fit
    stream: dict        # streaming tenant and its append
    chunk: int          # service chunk length and solo record interval


# The full sizes are the repo's production shapes: launch/specs.py
# SADDLE_DSVC_SHAPES["svm_1m_nu"] (2^19 + 2^19 points, d=256, nu at
# alpha=0.8, B=128) and SADDLE_SERVE_SHAPES["serve_lanes_512"]'s
# per-lane problem (1500 + 1400 points, d=64, B=1).
FULL = Sizes(
    solo=dict(n=1 << 20, n_test=1 << 16, d=256, block=128, iters=512),
    lane=dict(n1=1500, n2=1400, d=64, iters=1024),
    wide=dict(n=50_000, d=256, block=128, iters=256),
    stream=dict(n1=1000, n2=1000, d=64, append=150, iters=1024),
    chunk=64)
TINY = Sizes(
    solo=dict(n=4096, n_test=1024, d=64, block=8, iters=96),
    lane=dict(n1=60, n2=50, d=16, iters=96),
    wide=dict(n=600, d=32, block=8, iters=24),
    stream=dict(n1=50, n2=50, d=16, append=30, iters=64),
    chunk=16)
ALPHA = 0.8              # nu = 1 / (alpha * min(n1, n2))

# Tolerances, fixed before the chip run:
#  * service vs solo at the same seed and bucket: atol 1e-5 on w and b,
#    as tests/test_solver_service.py pins (same executable family);
#  * point-sharded vs one-chip solo: atol 1e-4, as
#    tests/test_mesh_service.py pins (psum order differs);
#  * jnp vs pallas backends: the kernels sum in another order, so the
#    trajectories drift apart in f32 -- histories within rtol 1e-3, w
#    within 1e-3 of its norm;
#  * first chunk vs the unpacked engine.step f32 oracle: hard margin
#    within rtol 1e-5 (the steps differ only in summation order).  With
#    the nu projection (sort-based in the oracle, 24-round bisection in
#    the packed step) rtol 1e-3: at 1M points and nu = 2.4e-6 the first
#    64 iterations amplify f32 rounding to ~1e-4 whatever computes them
#    -- the oracle alone lands 9.4e-05 apart on a v5e and on its host's
#    CPU (see PERF.md).
SERVE_ATOL = 1e-5
SHARD_ATOL = 1e-4
BACKEND_RTOL = 1e-3
ORACLE_RTOL_HM = 1e-5
ORACLE_RTOL_NU = 1e-3


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def finite(*arrays) -> bool:
    return all(np.isfinite(np.asarray(a)).all() for a in arrays)


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def max_dev(r, w, b) -> float:
    return max(float(np.max(np.abs(r.w - w))), abs(r.b - b))


def nu_of(n1: int, n2: int) -> float:
    return 1.0 / (ALPHA * min(n1, n2))


# ---------------------------------------------------------- solo fits
def solve_executable(n_pad: int, d: int, num_iters: int, chunk: int,
                     block: int, backend: str):
    """Lower and compile the executable ``saddle.solve`` runs for a
    1-slot nu fit (``engine.run_solve_slots``), from shapes alone.
    Returns (compile seconds, compiled HLO text)."""
    f32 = jnp.float32
    state = jax.eval_shape(
        lambda: engine.init_slot_state(1, n_pad, d))
    sp = engine.SlotParams(*(jax.ShapeDtypeStruct((1,), f32)
                             for _ in engine.SlotParams._fields))
    x_t = jax.ShapeDtypeStruct((1, d, n_pad), f32)
    sign = jax.ShapeDtypeStruct((1, n_pad), f32)
    t0 = time.perf_counter()
    compiled = engine.run_solve_slots.lower(
        state, x_t, sign, sp, num_iters, chunk_steps=chunk,
        num_chunks=-(-num_iters // chunk), d=d, block_size=block,
        project=True, check_gap=False, backend=backend).compile()
    return time.perf_counter() - t0, compiled.as_text()


def solo_phase(sz: Sizes, seed: int, on_tpu: bool) -> None:
    """SaddleNuSVC.fit on the svm_1m_nu shape, jnp then pallas."""
    so = sz.solo
    ds = synthetic.non_separable(so["n"] + so["n_test"], so["d"], seed=seed)
    x, y = ds.x[:so["n"]], ds.y[:so["n"]]
    x_test, y_test = ds.x[so["n"]:], ds.y[so["n"]:]
    n1, n2 = int((y > 0).sum()), int((y < 0).sum())
    block, chunk, iters = so["block"], sz.chunk, so["iters"]
    n_pad = pp.packed_length(n1 + n2)
    print(f"solo: {n1}+{n2} points d={so['d']} nu={nu_of(n1, n2):.3e} "
          f"B={block} iterations={iters} n_pad={n_pad}", flush=True)

    fits = {}
    for use_kernels in (False, True):
        backend = "pallas" if use_kernels else "jnp"
        t_compile, hlo = solve_executable(n_pad, so["d"], iters, chunk,
                                          block, backend)
        has_kernel = "tpu_custom_call" in hlo
        if on_tpu:
            check(has_kernel == use_kernels,
                  f"{backend}: tpu_custom_call in executable = "
                  f"{has_kernel}")
        models, times = [], []
        for _ in range(2):           # first fit, then a warm refit
            t0 = time.perf_counter()
            models.append(SaddleNuSVC(
                alpha=ALPHA, num_iters=iters * block, block_size=block,
                record_every=chunk, seed=seed,
                use_kernels=use_kernels).fit(x, y))
            times.append(time.perf_counter() - t0)
        m, m2 = models
        hist = np.array([o for _, o in m.history_])
        check(finite(m.w_, m.b_, hist), f"{backend}: non-finite fit")
        check(np.array_equal(m.w_, m2.w_) and m.b_ == m2.b_,
              f"{backend}: refit at the same seed differs")
        acc = m.score(x_test, y_test)
        check(acc > 0.6, f"{backend}: held-out accuracy {acc:.3f}")
        print(f"solo[{backend}]: tpu_custom_call={has_kernel} "
              f"compile_s={t_compile:.2f} first_fit_s={times[0]:.2f} "
              f"warm_fit_s={times[1]:.2f} heldout_acc={acc:.4f} "
              f"objective={hist[-1]:.6e}", flush=True)
        fits[backend] = m

    a, b = fits["jnp"], fits["pallas"]
    ha = np.array([o for _, o in a.history_])
    hb = np.array([o for _, o in b.history_])
    check([i for i, _ in a.history_] == [i for i, _ in b.history_],
          "backends recorded different iteration marks")
    hist_dev = float(np.max(np.abs(hb - ha) / np.abs(ha)))
    w_dev = rel(b.w_, a.w_)
    print(f"solo: jnp vs pallas history max rel dev={hist_dev:.3e} "
          f"w rel dev={w_dev:.3e} (tol {BACKEND_RTOL})", flush=True)
    check(hist_dev <= BACKEND_RTOL and w_dev <= BACKEND_RTOL,
          "jnp and pallas backends disagree")

    # first chunk vs the unpacked reference step (f32) at the same seed:
    # the nu fit above, and a one-chunk hard-margin fit (no projection)
    xp, xm = split_classes(x, y)
    k_pre, key = jax.random.split(jax.random.key(seed))
    pre = pp.preprocess(xp, xm, k_pre)
    d = pre.xp.shape[1]
    for nu, tol in ((nu_of(n1, n2), ORACLE_RTOL_NU), (0.0, ORACLE_RTOL_HM)):
        params = saddle.make_params(n1 + n2, d, 1e-3, 0.1, nu=nu,
                                    block_size=block)
        st0 = saddle.init_state(n1, n2, d, pre.xp, pre.xm)
        _, ref = engine.run_chunk(st0, key, pre.xp, pre.xm, chunk,
                                  params=params, chunk_steps=chunk)
        ref = float(ref)
        for backend in ("jnp", "pallas"):
            m = fits[backend] if nu else SaddleSVC(
                num_iters=chunk * block, block_size=block,
                record_every=chunk, seed=seed,
                use_kernels=backend == "pallas").fit(x, y)
            mark, obj = m.history_[0]
            dev = abs(obj - ref) / abs(ref)
            print(f"solo[{backend}]: {'nu' if nu else 'hard margin'} first "
                  f"chunk ({mark} iterations) objective {obj:.6e} vs "
                  f"unpacked oracle {ref:.6e}: rel dev {dev:.3e} "
                  f"(tol {tol})", flush=True)
            check(mark == chunk and dev <= tol,
                  f"{backend}: first chunk disagrees with the oracle")


# ------------------------------------------------------------ service
def solo_reference(x, y, seed, nu, num_iters, block, chunk, pre=None,
                   xp_t=None, xm_t=None, warm=None):
    """saddle.solve at the service's bucket and chunk schedule, through
    the same svm.py recovery path.  Returns (w, b, SolveResult, pre)."""
    if pre is None:
        xp, xm = split_classes(x, y)
        k_pre, _ = jax.random.split(jax.random.key(seed))
        pre = pp.preprocess(xp, xm, k_pre)
        xp_t, xm_t = pre.xp, pre.xm
    n_b, d_b = pp.bucket_shape(xp_t.shape[0] + xm_t.shape[0],
                               xp_t.shape[1])
    res = saddle.solve(xp_t, xm_t, nu=nu, num_iters=num_iters,
                       block_size=block, record_every=chunk, seed=seed,
                       n_pad=n_b, d_pad=d_b, warm_start=warm)
    eta = np.exp(np.asarray(res.state.log_eta))
    xi = np.exp(np.asarray(res.state.log_xi))
    w, b, *_ = recover_hyperplane(pre, eta, xi, xp_t, xm_t)
    return w, b, res, pre


def service_requests(sz: Sizes, seed: int):
    """16 fits in two buckets, hard margin and nu in each."""
    reqs = []
    lp, wp = sz.lane, sz.wide
    for i in range(10):
        ds = synthetic.blobs(lp["n1"], lp["n2"], lp["d"], gap=0.6,
                             spread=0.4, seed=seed + i)
        nu = nu_of(lp["n1"], lp["n2"]) if i % 2 else 0.0
        reqs.append(FitRequest(x=ds.x, y=ds.y, nu=nu, seed=seed + i,
                               num_iters=lp["iters"], block_size=1))
    for i in range(6):
        ds = synthetic.non_separable(wp["n"], wp["d"], seed=seed + 100 + i)
        n1, n2 = int((ds.y > 0).sum()), int((ds.y < 0).sum())
        nu = nu_of(n1, n2) if i % 2 else 0.0
        reqs.append(FitRequest(x=ds.x, y=ds.y, nu=nu, seed=seed + 100 + i,
                               num_iters=wp["iters"] * wp["block"],
                               block_size=wp["block"]))
    return reqs


def run_service(sz: Sizes, seed: int, backend: str, reqs, stream: bool):
    """One drain of ``reqs`` (+ the streaming tenant and its rung-jump
    append) through a fresh SolverService.  Returns (results by request
    index, stream results, service)."""
    svc = SolverService(num_slots=8, chunk_steps=sz.chunk, backend=backend)
    rids = [svc.submit(r) for r in reqs]
    out = svc.run()
    stream_out = None
    if stream:
        sp = sz.stream
        ds = synthetic.blobs(sp["n1"], sp["n2"], sp["d"], gap=0.6,
                             spread=0.4, seed=seed + 500)
        extra = synthetic.blobs(sp["append"], sp["append"], sp["d"],
                                gap=0.6, spread=0.4, seed=seed + 501)
        nu = nu_of(sp["n1"], sp["n2"])
        t_rid = svc.submit(FitRequest(x=ds.x, y=ds.y, nu=nu,
                                      seed=seed + 500,
                                      num_iters=sp["iters"], stream=True))
        first = svc.run()[t_rid]
        u_rid = svc.submit_update(UpdateRequest(tenant=t_rid, x=extra.x,
                                                y=extra.y))
        update = svc.run()[u_rid]
        stream_out = (ds, extra, nu, first, update)
    res = [out[r] for r in rids]
    for r in res:
        check(not isinstance(r, RequestFailure), f"request failed: {r}")
        check(finite(r.w, r.b), f"request {r.request_id}: non-finite")
    return res, stream_out, svc


def service_phase(sz: Sizes, seed: int) -> None:
    reqs = service_requests(sz, seed)
    buckets = sorted({pp.bucket_shape(len(r.x), r.x.shape[1])
                      for r in reqs})
    print(f"service: {len(reqs)} fits in buckets {buckets} + 1 streaming "
          f"tenant, num_slots=8", flush=True)
    t0 = time.perf_counter()
    res, stream_out, _ = run_service(sz, seed, "jnp", reqs, stream=True)
    t_cold = time.perf_counter() - t0
    worst = 0.0
    for r, q in zip(res, reqs):
        w, b, _, _ = solo_reference(q.x, q.y, q.seed, q.nu, q.num_iters,
                                    q.block_size, sz.chunk)
        dev = max_dev(r, w, b)
        worst = max(worst, dev)
        check(dev <= SERVE_ATOL,
              f"request {r.request_id} (bucket {r.bucket}, nu={q.nu:.2e}) "
              f"differs from its solo fit by {dev:.3e}")

    # streaming tenant: the cold fit, then the warm append that jumps a
    # rung, each against the solo solve of the same problem
    ds, extra, nu, first, update = stream_out
    check(update.bucket[0] > first.bucket[0],
          f"append did not jump a rung: {first.bucket} -> {update.bucket}")
    iters = sz.stream["iters"]
    w1, b1, res1, pre = solo_reference(ds.x, ds.y, seed + 500, nu, iters,
                                       1, sz.chunk)
    xp_new, xm_new = split_classes(extra.x, extra.y)
    xp_t = jnp.concatenate([pre.xp, pp.transform_like(pre, xp_new)])
    xm_t = jnp.concatenate([pre.xm, pp.transform_like(pre, xm_new)])
    w2, b2, _, _ = solo_reference(None, None, seed + 500 + 1000003, nu,
                                  iters, 1, sz.chunk, pre=pre, xp_t=xp_t,
                                  xm_t=xm_t, warm=res1.state)
    for tag, r, w, b in (("stream fit", first, w1, b1),
                         ("stream update", update, w2, b2)):
        dev = max_dev(r, w, b)
        worst = max(worst, dev)
        check(finite(r.w, r.b) and dev <= SERVE_ATOL,
              f"{tag} (bucket {r.bucket}) differs from solo by {dev:.3e}")
    print(f"service[jnp]: {len(res) + 2} results match their solo fits "
          f"(max abs dev {worst:.3e}, tol {SERVE_ATOL}); stream rung "
          f"{first.bucket[0]} -> {update.bucket[0]}; "
          f"first_drain_s={t_cold:.2f}", flush=True)

    # warm drain: same workload through a fresh service must trace
    # nothing new and return the same answers
    snap = dict(engine.trace_counts)
    t0 = time.perf_counter()
    res2, _, svc2 = run_service(sz, seed, "jnp", reqs, stream=True)
    t_warm = time.perf_counter() - t0
    grown = {k: v - snap.get(k, 0) for k, v in engine.trace_counts.items()
             if v != snap.get(k, 0)}
    check(not grown, f"recompiles after warm-up: {grown}")
    check(svc2.stats["compiles"] == 0, f"service stats {svc2.stats}")
    check(all(np.array_equal(a.w, b.w) for a, b in zip(res, res2)),
          "warm drain changed a result")
    print(f"service[jnp]: warm drain traced 0 executables "
          f"({svc2.stats['cache_hits']} chunk cache hits) "
          f"warm_drain_s={t_warm:.2f}", flush=True)

    # the Pallas kernels vmapped over an 8-lane group: the lane bucket's
    # fits through a pallas service, against the jnp service's answers
    lane_reqs = [q for q in reqs if q.block_size == 1]
    res_k, _, _ = run_service(sz, seed, "pallas", lane_reqs, stream=False)
    ref = [r for r, q in zip(res, reqs) if q.block_size == 1]
    dev = max(rel(a.w, b.w) for a, b in zip(res_k, ref))
    print(f"service[pallas]: {len(res_k)} lane fits vs jnp service: max w "
          f"rel dev {dev:.3e} (tol {BACKEND_RTOL})", flush=True)
    check(dev <= BACKEND_RTOL, "pallas service disagrees with jnp service")


# --------------------------------------------------------------- mesh
def mesh_phase(sz: Sizes, seed: int) -> None:
    """SolverService on a 4-device mesh: one lane-parallel group of 8
    nu fits and one point-sharded 1M x 256 nu fit, each against its
    one-chip solo solve."""
    mesh = make_test_mesh(4)
    print(f"mesh: {dict(mesh.shape)} over {mesh.devices.size} devices",
          flush=True)
    lp, so, chunk = sz.lane, sz.solo, sz.chunk
    lanes = []
    for i in range(8):
        ds = synthetic.blobs(lp["n1"], lp["n2"], lp["d"], gap=0.6,
                             spread=0.4, seed=seed + i)
        lanes.append(FitRequest(x=ds.x, y=ds.y, nu=nu_of(lp["n1"], lp["n2"]),
                                seed=seed + i, num_iters=lp["iters"]))
    big_ds = synthetic.non_separable(so["n"], so["d"], seed=seed)
    n1 = int((big_ds.y > 0).sum())
    n2 = int((big_ds.y < 0).sum())
    big_iters = so["iters"] // 2 * so["block"]
    big = FitRequest(x=big_ds.x, y=big_ds.y, nu=nu_of(n1, n2), seed=seed,
                     num_iters=big_iters, block_size=so["block"],
                     gap_tol=0.0)
    svc = SolverService(mesh=mesh, num_slots=8, chunk_steps=chunk,
                        shard_points_above=so["n"] // 4,
                        shard_num_slots=2)
    t0 = time.perf_counter()
    rids = [svc.submit(q) for q in lanes]
    big_rid = svc.submit(big)
    out = svc.run()
    t_mesh = time.perf_counter() - t0
    axes = tuple(mesh.axis_names)
    n_big = pp.bucket_length(-(-so["n"] // 4)) * 4
    placed = {
        "lanes": engine.sharded_slot_trace_key(
            8, pp.bucket_length(lp["n1"] + lp["n2"]), lp["d"], 1, chunk,
            True, False, "jnp", mesh, slot_axes=axes),
        "points": engine.sharded_slot_trace_key(
            2, n_big, so["d"], so["block"], chunk, True, False, "jnp",
            mesh, point_axes=axes)}
    for kind, key in placed.items():
        check(engine.trace_counts[key] > 0,
              f"no {kind} chunk executable ran ({key})")
    worst = 0.0
    for rid, q in zip(rids, lanes):
        r = out[rid]
        check(not isinstance(r, RequestFailure), f"lane fit failed: {r}")
        w, b, _, _ = solo_reference(q.x, q.y, q.seed, q.nu, q.num_iters, 1,
                                    chunk)
        dev = max_dev(r, w, b)
        worst = max(worst, dev)
        check(finite(r.w, r.b) and dev <= SERVE_ATOL,
              f"lane fit {rid} differs from its one-chip solo fit by "
              f"{dev:.3e}")
    print(f"mesh[lanes]: 8 fits match their one-chip solo fits (max abs "
          f"dev {worst:.3e}, tol {SERVE_ATOL})", flush=True)
    r = out[big_rid]
    check(not isinstance(r, RequestFailure), f"sharded fit failed: {r}")
    xp, xm = split_classes(big.x, big.y)
    k_pre, _ = jax.random.split(jax.random.key(seed))
    pre = pp.preprocess(xp, xm, k_pre)
    res = saddle.solve(pre.xp, pre.xm, nu=big.nu, num_iters=big_iters,
                       block_size=big.block_size, record_every=chunk,
                       seed=seed, n_pad=r.bucket[0], d_pad=r.bucket[1])
    eta = np.exp(np.asarray(res.state.log_eta))
    xi = np.exp(np.asarray(res.state.log_xi))
    w, b, *_ = recover_hyperplane(pre, eta, xi, pre.xp, pre.xm)
    dev = max_dev(r, w, b)
    print(f"mesh[points]: {n1}+{n2} points over 4 shards, bucket "
          f"{r.bucket}, {r.iterations} iterations: max abs dev vs one-chip "
          f"solo {dev:.3e} (tol {SHARD_ATOL}); mesh_drain_s={t_mesh:.2f}",
          flush=True)
    check(finite(r.w, r.b) and r.iterations == big_iters // big.block_size
          and dev <= SHARD_ATOL,
          "point-sharded fit differs from its one-chip solo fit")


# --------------------------------------------------------------- main
def _has_tpu_node() -> bool:
    return bool(glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh path and its references")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes on any backend (rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not args.tiny and not _has_tpu_node():
        # stop before JAX's backend start-up makes libtpu look for one
        print("chip_smoke: no TPU device on this machine", file=sys.stderr)
        return 1
    if args.tiny and args.chips > 1 and \
            os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                                   "--xla_force_host_platform_device_count=4")

    cache_dir = compile_cache.enable()
    devices = jax.devices()
    platform = devices[0].platform
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: platform={platform} kind={device['kind']} "
          f"count={device['count']} compile_cache={cache_dir}", flush=True)
    if platform != "tpu" and not args.tiny:
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    sz = TINY if args.tiny else FULL
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(sz, args.seed)
    else:
        solo_phase(sz, args.seed, on_tpu=platform == "tpu")
        service_phase(sz, args.seed)
    print(f"phases passed in {time.perf_counter() - t0:.1f} s", flush=True)
    if platform != "tpu":
        print(f"chip_smoke: rehearsal passed on {platform}; no chip result",
              file=sys.stderr)
        return 0 if args.tiny else 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
