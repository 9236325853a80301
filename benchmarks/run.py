# One function per paper table. Print ``name,us_per_call,derived`` CSV.
import argparse
import sys
import time
import traceback

from benchmarks import (engine_bench, fig1_nusvm_convergence,
                        fig2_size_scaling, fig3_dist_hard_margin,
                        fig4_dist_nusvm, kernels_bench, lm_serve_bench,
                        serve_bench, table1_hard_margin, table3_nu_sweep,
                        table4_density, theory_iters_comm)
from benchmarks.common import emit, header, write_json

SUITES = [
    ("table1", table1_hard_margin),
    ("fig1", fig1_nusvm_convergence),
    ("fig2", fig2_size_scaling),
    ("fig3", fig3_dist_hard_margin),
    ("fig4", fig4_dist_nusvm),
    ("table3", table3_nu_sweep),
    ("table4", table4_density),
    ("theory", theory_iters_comm),
    ("kernels", kernels_bench),
    ("engine", engine_bench),
    ("serve", serve_bench),
    ("lm_serve", lm_serve_bench),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write every metric as JSON records "
                         "(e.g. BENCH_engine.json) for CI tracking")
    args = ap.parse_args()
    from repro.utils import compile_cache
    compile_cache.enable()
    only = set(args.only.split(",")) if args.only else None
    header()
    failures = []
    for name, mod in SUITES:
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            mod.run(quick=not args.full)
        except Exception as e:      # noqa: BLE001
            traceback.print_exc()
            failures.append(name)
            emit(f"{name}/ERROR", 0.0, str(e)[:80])
        emit(f"{name}/suite_total", time.perf_counter() - t0, "")
    if args.json:
        write_json(args.json)
    if failures:
        print(f"# FAILED suites: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == '__main__':
    main()
