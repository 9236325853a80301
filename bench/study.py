"""Readings that set a cell's limit: the program's compared number over
many seeds, and the control's.

    python3 bench/study.py --config dense_1m_nu --seeds 12 --control-seeds 3
    python3 bench/study.py --config libsvm_tenants --seeds 12 --control-seeds 3

One process, on the chip, at the cells' own sizes.  For each seed it
makes the cell's data, drives the program's timed path (``SaddleNuSVC.fit``
for the solo configuration; ``SolverService`` with the cells' requests
for the service) and prints
the reference's number for every answer.  The control is the reference
solver put in the program's place and computed in bfloat16, the nearest
precision below the float32 that the configurations state; beside it the
same solver in float32 shows that the reference itself meets the limit.
Not run by the benchmark's runs.  The last line is one JSON object of
the readings.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if sys.path and pathlib.Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def solo(cfg, seed):
    from repro.core.svm import SaddleNuSVC

    from bench import data, load, reference
    n1, n2, d = cfg["n1"], cfg["n2"], cfg["d"]
    x, y = data.problem(seed, n1, n2, d, beta2=cfg["beta2"])
    nu = load.nu_of(cfg["alpha"], n1, n2)
    m = SaddleNuSVC(alpha=cfg["alpha"], eps=cfg["eps"], beta=cfg["beta"],
                    block_size=cfg["block_size"], num_iters=cfg["num_iters"],
                    seed=load.solver_seed(seed, 1)).fit(x, y)
    return [reference.certificate(x, y, m.w_, m.b_, m.objective_, nu)], \
        [(x, y, nu)]


def service(cfg, seed, per_set=8):
    from repro.serve.solver_service import SolverService

    from bench import data, load, reference
    svc = SolverService()
    probs, rids = [], []
    for i, name in enumerate(cfg["sets"]):
        s = cfg["sets"][name]
        for j in range(per_set):
            x, y = data.problem(seed, s["n1"], s["n2"], s["d"], i, j % 4,
                                beta2=cfg["beta2"])
            nu = load.nu_of(cfg["alpha"], s["n1"], s["n2"])
            rids.append(svc.submit(load._fit_request(
                cfg, x, y, nu, load.solver_seed(seed, 100 * i + j))))
            probs.append((x, y, nu, name))
    out = svc.run()
    gaps = [reference.certificate(x, y, out[r].w, out[r].b,
                                  out[r].objective, nu)
            for r, (x, y, nu, _) in zip(rids, probs)]
    iters = [out[r].iterations for r in rids]
    return gaps, [(x, y, nu) for x, y, nu, _ in probs[::per_set]], iters


def control(cfg, problems, dtype, seed):
    """The reference solver in the program's place, in ``dtype``, with
    the cells' request settings; its gap for each problem."""
    import jax.numpy as jnp

    from bench import reference
    out = []
    for j, (x, y, nu) in enumerate(problems):
        w, b, obj, _ = reference.solve(
            x, y, nu, eps=cfg["eps"], beta=cfg["beta"],
            block=cfg["block_size"], seed=seed + j,
            dtype=getattr(jnp, dtype), num_iters=cfg.get("num_iters"),
            gap_tol=cfg.get("gap_tol", 0.0))
        out.append(reference.certificate(x, y, w, b, obj, nu))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1 << 32)
    args = ap.parse_args(argv)
    import jax

    from repro.utils import compile_cache
    compile_cache.enable()
    print(f"device {jax.devices()[0].device_kind} x {jax.device_count()}",
          flush=True)
    with open(ROOT / "bench" / "configs" / f"{args.config}.json") as f:
        cfg = json.load(f)
    readings = {"program": [], "control_bf16": [], "reference_f32": []}
    extra = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t = time.perf_counter()
        if "sets" not in cfg:
            gaps, probs = solo(cfg, seed)
        else:
            gaps, probs, iters = service(cfg, seed)
            extra.append({"iters": iters})
        readings["program"].append(max(gaps))
        print(f"seed {seed}: program gaps {gaps} "
              f"({time.perf_counter() - t:.1f} s) {extra[-1:] or ''}",
              flush=True)
        if i < args.control_seeds and probs:
            for key, dt in (("control_bf16", "bfloat16"),
                            ("reference_f32", "float32")):
                t = time.perf_counter()
                g = control(cfg, probs, dt, seed)
                readings[key].append(g)
                print(f"seed {seed}: {key} gaps {g} "
                      f"({time.perf_counter() - t:.1f} s)", flush=True)
    readings["extra"] = extra
    print(json.dumps(readings))
    return 0


if __name__ == "__main__":
    sys.exit(main())
