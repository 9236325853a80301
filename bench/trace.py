"""From a profiler trace to the numbers the per-layer readers take.

A traced run starts JAX's profiler for part of its window
(:class:`Tracer`).  :func:`load` reads the ``.xplane.pb`` it wrote into
plain event tuples; :func:`reduce` turns those into a :class:`Summary`:

* device busy time, the union of the intervals in which an operation ran
  on each device (averaged over devices), and the traced window;
* device time per executable (the jit name, e.g. ``run_solve_slots``),
  summed over its runs;
* device time per operation name (the breakdown's ``device_ops``, which
  leaves out the loops that contain other operations);
* per executable, the largest array that its operations' HLO text names
  (its type and element count): the operand the program holds, in the
  type it holds it;
* the benchmark's host spans (written with ``TraceAnnotation``) on the
  same clock, and each idle gap of the device attributed to the
  innermost span that covers its midpoint (``idle_gaps``).

The reduction works on the tuples alone, so it is tested on a small
recorded chip trace (``bench/tests/data``).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import time

import numpy as np

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
GAPS = 10           # idle gaps named in the breakdown
# bytes of one value of each HLO element type
ITEMSIZE = {"pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1,
            "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e4m3b11fnuz": 1,
            "f8e4m3fnuz": 1, "f8e5m2fnuz": 1, "s16": 2, "u16": 2,
            "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8,
            "u64": 8, "f64": 8, "c64": 8, "c128": 16}
CONTAINERS = ("%while", "%conditional", "%call")


class Tracer:
    """Start and stop JAX's profiler around part of a window."""

    def __init__(self, path: str):
        self.path = path
        self.running = False
        self.window_s = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.path, ignore_errors=True)
        os.makedirs(self.path, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1        # the benchmark's spans, no more
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.path, profiler_options=opts)
        self.running = True
        self._t = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.window_s = time.perf_counter() - self._t
        jax.profiler.stop_trace()
        self.running = False

    def file(self) -> str | None:
        found = sorted(glob.glob(os.path.join(
            self.path, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def load(path: str) -> dict:
    """Event tuples from an ``.xplane.pb``: ``device`` holds
    ``[device, line, name, start_ns, dur_ns, array]`` for the TPU planes'
    op and module lines, where ``array`` is the largest typed array that
    an op's HLO text (its name or a text statistic) names, as ``[type,
    elements]``, or None; ``host`` holds ``[name, start_ns, dur_ns]``
    for the benchmark's spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev, host = [], []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_dev and line.name in (OPS_LINE, MODULES_LINE):
                for e in line.events:
                    arr = None
                    if line.name == OPS_LINE:
                        texts = [e.name] + [v for _, v in e.stats
                                            if isinstance(v, str)]
                        arr = largest_array(" ".join(texts))
                    dev.append([plane.name, line.name, short_name(e.name),
                                float(e.start_ns), float(e.duration_ns),
                                arr])
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return {"device": dev, "host": host}


ARRAY = re.compile(r"\b(pred|[suf]\d+|bf16|f8e\w+|c64|c128)\[([\d,]*)\]")


def largest_array(text: str):
    """The largest array a piece of HLO text names, ``[type, elements]``
    (``f32[128,1048576]`` -> ``["f32", 134217728]``), or None."""
    best = None
    for dtype, dims in ARRAY.findall(text):
        n = int(np.prod([int(x) for x in dims.split(",") if x]))
        if best is None or n > best[1]:
            best = [dtype, n]
    return best


def short_name(op: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return op.split(" = ", 1)[0]


def union(intervals) -> np.ndarray:
    """Merge (start, end) intervals into disjoint sorted ones, as an
    (m, 2) array."""
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(new)
    stop = np.append(last[1:] - 1, len(iv) - 1)
    return np.stack([starts, ends[stop]], axis=1)


def overlap(merged: np.ndarray, s: float, e: float) -> float:
    """Length of [s, e) covered by the disjoint sorted ``merged``."""
    if not len(merged):
        return 0.0
    lo = np.searchsorted(merged[:, 1], s, side="right")
    hi = np.searchsorted(merged[:, 0], e, side="left")
    part = merged[lo:hi]
    if not len(part):
        return 0.0
    return float(np.sum(np.minimum(part[:, 1], e)
                        - np.maximum(part[:, 0], s)))


def executable_name(module: str) -> str:
    """``jit_run_solve_slots(1234)`` -> ``run_solve_slots``."""
    name = re.sub(r"\(.*\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


@dataclasses.dataclass
class Summary:
    busy_s: float                       # mean over devices
    window_s: float
    devices: int
    exec_s: dict                        # executable -> device seconds
    exec_runs: dict                     # executable -> number of runs
    exec_array: dict                    # executable -> [type, elements]
    op_s: dict                          # op name -> device seconds
    busy: np.ndarray                    # merged busy intervals (s), dev 0
    spans: list                         # (name, start s, end s)
    gaps: list                          # (span name, seconds), longest first

    def breakdown(self) -> dict:
        # loops and calls contain the ops they run: leave them out
        ops = sorted(((k, v) for k, v in self.op_s.items()
                      if not k.startswith(CONTAINERS)),
                     key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def reduce(events: dict, window_s: float) -> Summary:
    """The reduction; all times in seconds on the trace's clock."""
    per_dev: dict[str, list] = {}
    exec_s: dict[str, float] = {}
    exec_runs: dict[str, int] = {}
    op_s: dict[str, float] = {}
    modules: dict[str, list] = {}       # device -> [(start, end, exec)]
    typed = []                          # (device, start, [type, elements])
    for dev, line, name, start, dur, *arr in events["device"]:
        s, e = start * 1e-9, (start + dur) * 1e-9
        if line == OPS_LINE:
            per_dev.setdefault(dev, []).append((s, e))
            op_s[name] = op_s.get(name, 0.0) + dur * 1e-9
            if arr and arr[0]:
                typed.append((dev, s, arr[0]))
        else:
            k = executable_name(name)
            exec_s[k] = exec_s.get(k, 0.0) + dur * 1e-9
            exec_runs[k] = exec_runs.get(k, 0) + 1
            modules.setdefault(dev, []).append((s, e, k))
    exec_array = _arrays_by_executable(modules, typed)
    merged = {d: union(v) for d, v in per_dev.items()}
    n_dev = max(len(merged), 1)
    busy_s = sum(float(np.sum(m[:, 1] - m[:, 0]))
                 for m in merged.values()) / n_dev
    first = merged[sorted(merged)[0]] if merged else union([])
    spans = sorted(((n, s * 1e-9, (s + d) * 1e-9)
                    for n, s, d in events["host"]), key=lambda sp: sp[1])
    # idle stretches between device operations, and before the first
    # and after the last where the benchmark's spans reach beyond them
    edges = np.concatenate([
        [min([sp[1] for sp in spans] + list(first[:1, 0]))],
        first.reshape(-1),
        [max([sp[2] for sp in spans] + list(first[-1:, 1]))]])
    if not len(first):
        edges = edges[:0]
    starts, ends = edges[0::2], edges[1::2]
    lens = ends - starts
    gaps = []
    for i in np.argsort(-lens, kind="stable")[:GAPS]:
        if lens[i] <= 0:
            break
        mid = 0.5 * (starts[i] + ends[i])
        inner = [sp for sp in spans if sp[1] <= mid < sp[2]]
        who = (min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner
               else "no benchmark span")
        gaps.append((who, float(lens[i])))
    return Summary(busy_s=busy_s, window_s=window_s, devices=n_dev,
                   exec_s=exec_s, exec_runs=exec_runs,
                   exec_array=exec_array, op_s=op_s,
                   busy=first, spans=spans, gaps=gaps)


def _arrays_by_executable(modules: dict, typed: list) -> dict:
    """Per executable, the largest typed array named by an op that runs
    inside one of its runs (on the same device); of two types at one
    size, the wider."""
    out: dict[str, list] = {}
    for dev, runs in modules.items():
        runs.sort()
        starts = np.asarray([r[0] for r in runs])
        for d, s, arr in typed:
            if d != dev:
                continue
            i = int(np.searchsorted(starts, s, side="right")) - 1
            if i < 0 or s > runs[i][1]:
                continue
            k = runs[i][2]
            have = out.get(k)
            if have is None or (arr[1], ITEMSIZE.get(arr[0], 0)) > (
                    have[1], ITEMSIZE.get(have[0], 0)):
                out[k] = list(arr)
    return out


def host_ms_per_span(summary: Summary, name: str) -> float | None:
    """Mean over the spans called ``name`` of their length minus the
    device busy time inside them, in milliseconds."""
    vals = [(e - s) - overlap(summary.busy, s, e)
            for n, s, e in summary.spans if n == name]
    return 1e3 * sum(vals) / len(vals) if vals else None
