"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` names the cell's configuration (whose ``file`` holds
the deployment) and its traffic mix (``bench/traffic/<mix>.json``);
each compared number's limit is in ``bench/limits/<cell>.json`` and each
per-layer metric has its reader in ``bench/metrics/<metric>.py``.

The run refuses to start without a TPU, turns on the persistent
compilation cache inside the checkout (``.jax_cache``), builds the data from ``--seed``,
warms every shape the window uses (set-up), measures for ``--seconds``,
and then judges every answer of the window against the plain reference
(``bench/reference.py``).  With ``--trace 1`` part of the window is
traced and the per-layer metrics are read from that trace instead of the
end-to-end ones.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``, each compared
number with its limit (repeated as the last lines of standard error).
"""

from __future__ import annotations

import time

T0 = time.time()        # set-up runs from here to the window

import argparse                                          # noqa: E402
import gc                                                # noqa: E402
import glob                                              # noqa: E402
import importlib.util                                    # noqa: E402
import json                                              # noqa: E402
import math                                              # noqa: E402
import os                                                # noqa: E402
import pathlib                                           # noqa: E402
import sys                                               # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"
if sys.path and pathlib.Path(sys.path[0]).resolve() == BENCH:
    sys.path[0] = str(ROOT)       # import as the ``bench`` package


def _has_tpu_node() -> bool:
    return bool(glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*"))


def load_cell(name: str):
    """(spec, cell, config file contents, traffic, limits) by name."""
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(ROOT / conf["file"]) as f:
        cfg = json.load(f)
    with open(BENCH / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    with open(BENCH / "limits" / f"{name}.json") as f:
        limits = json.load(f)
    return spec, cell, cfg, traffic, limits


def metrics_of(spec: dict, cell: str, kind: str) -> list[dict]:
    """The cell's end-to-end or per-layer metric entries."""
    e2e = spec["end_to_end"]
    names = {m["name"] for m in e2e
             if "workloads" not in m or cell in m["workloads"]}
    if kind == "end_to_end":
        return [m for m in e2e if m["name"] in names]
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in names)]


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def finite(obj):
    """``obj`` with every infinite or NaN float replaced by the largest
    float, so that the result line stays plain JSON (an infinite gap or
    latency is a run that failed its check)."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return sys.float_info.max
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [finite(v) for v in obj]
    return obj


class Context:
    """What a per-layer reader sees."""

    def __init__(self, summary, counters, cfg, peaks):
        self.summary, self.counters = summary, counters
        self.cfg, self.peaks = cfg, peaks


def judge(answers) -> tuple[float, int, list]:
    """Largest reference number over the window's answers, the count of
    answers judged, and the largest of each of its parts (gap, offset,
    length).  An answer's problem is ``(x, y, nu)`` or a function that
    builds it; answers to one problem share one float64 pass over its
    rows."""
    import numpy as np

    from bench import reference

    groups: dict[int, list] = {}
    for prob, ans in answers:
        groups.setdefault(id(prob), [prob, []])[1].append(ans)
    worst, count, parts = 0.0, 0, [0.0, 0.0, 0.0]
    for prob, anss in groups.values():
        x, y, nu = prob() if callable(prob) else prob
        ws = np.stack([np.asarray(a[0], np.float64) for a in anss], axis=1)
        s = reference.scores(x, ws)
        scale = reference.unit_scale(x)
        for j, (_, b, obj) in enumerate(anss):
            ps = reference.judge_parts(s[:, j], y, b, obj, nu,
                                       float(ws[:, j] @ ws[:, j]), scale)
            parts = [max(a, c) for a, c in zip(parts, ps)]
            g = max(ps)
            worst = max(worst, g if math.isfinite(g) else math.inf)
            count += 1
    return worst, count, parts


def run_cell(spec, cell, cfg, traffic, limits, seed: int, seconds: float,
             trace: bool, t0: float | None = None) -> dict:
    """Everything of a run after the look for a chip; returns the
    result line as a dict."""
    import jax

    from bench import load, roofline
    from bench import trace as tr

    devices = jax.devices()
    kind = devices[0].device_kind
    tracer = tr.Tracer(str(TRACE_DIR / cell["name"])) if trace else None
    run = load.Run(cell, cfg, traffic, seed, seconds, tracer,
                   T0 if t0 is None else t0)
    e2e_entries = metrics_of(spec, cell["name"], "end_to_end")
    run.cell_metric_names = [m["name"] for m in e2e_entries]
    out = load.MODES[traffic["mode"]](run)

    # the window is closed: read the device's peak, free the program's
    # state, then judge the answers
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    gc.collect()
    gap, judged, parts = judge(out["answers"])
    checks = {
        "gap_max": (gap, limits["gap"]),
        "failed": (out["failed"], 0),
        "compiles_in_window": (run.counters["compiles_in_window"], 0),
        "answers_judged": (judged, out["attempted"]),
    }
    correct = (gap <= limits["gap"] and out["failed"] == 0
               and run.counters["compiles_in_window"] == 0
               and judged == out["attempted"] and out["attempted"] > 0)

    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": jax.device_count(), "memory_peak_bytes": int(peak)}
    if trace:
        summary = None
        if tracer.file():
            summary = tr.reduce(tr.load(tracer.file()), tracer.window_s)
        peaks = roofline.peaks(kind) if devices[0].platform == "tpu" \
            else None
        ctx = Context(summary, run.counters, cfg, peaks)
        metrics = {}
        for m in metrics_of(spec, cell["name"], "per_layer"):
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            result["breakdown"] = summary.breakdown()
        out["notes"]["trace_executables_s"] = summary.exec_s \
            if summary else None
        out["notes"]["trace_largest_arrays"] = summary.exec_array \
            if summary else None
    else:
        values = dict(out["e2e"], setup_s=run.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]} for m in e2e_entries}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    result["notes"] = dict(out["notes"],
                           setup_parts_s=run.counters["setup_parts_s"],
                           judged_parts_max=dict(zip(
                               ("gap", "offset", "length"), parts)))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec, cell, cfg, traffic, limits = load_cell(args.workload)
    if not _has_tpu_node():
        # stop before JAX's start-up makes libtpu look for a chip
        print("bench: no TPU device on this machine", file=sys.stderr)
        return 1
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    # the compilation cache lives inside the checkout, at a fixed path,
    # whatever the machine sets: the program takes its directory from
    # this variable, and a cache outside the checkout would be shared
    # with other checkouts
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    from repro.utils import compile_cache

    compile_cache.enable()
    # cache every executable, the small preprocessing and recovery ones
    # included, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 1

    result = run_cell(spec, cell, cfg, traffic, limits, args.seed,
                      args.seconds, bool(args.trace))
    notes = result.pop("notes")
    print(json.dumps(finite({"notes": notes})), flush=True)
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
