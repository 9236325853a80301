"""The knee of a service cell: open-loop windows at a list of rates.

    python3 bench/sweep.py --workload libsvm_steady --seconds 50
        --rates 0.9,1.1,1.3,1.5,1.7

One process on the chip.  For each rate it runs the cell's traffic mix at
that rate for one window without a drain and prints the fits completed
per second and the backlog left at the window's end.  The knee is the
highest rate whose backlog does not grow over the window; the cells'
rates in ``bench/traffic/`` are fixed from it.  Not run by the
benchmark's runs.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="libsvm_steady")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=2 ** 35 + 1)
    args = ap.parse_args(argv)

    from bench import load
    from bench import run as harness
    from repro.utils import compile_cache
    compile_cache.enable()
    _, cell, cfg, traffic, _ = harness.load_cell(args.workload)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(traffic, rate=rate, judge="throughput")
        run = load.Run(cell, cfg, mix, args.seed, args.seconds, None,
                       time.time())
        run.cell_metric_names = []
        out = load.open_loop(run)
        row = {"rate": rate, "fits_per_s": out["e2e"]["fits_per_s"],
               "submitted": out["notes"]["submitted"],
               "backlog_at_end": out["notes"]["backlog_at_end"],
               "backlog_every_10s": out["notes"]["backlog_every_10s"],
               "iterations_mean": out["notes"]["iterations_mean"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
