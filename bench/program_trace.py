"""The program's own spans and scopes in a traced run's profile.

The program writes host spans (``repro.utils.spans``: ``svm.*``,
``saddle.*``, ``svc.*``, each with its counters as stats of the event)
and labels the phases of its engine step with ``jax.named_scope``
(``momentum_pass``, ``mwu_pass``, ``nu_projection``, ``gap_check``,
``health_check``).  Both land in the ``.xplane.pb`` that a ``--trace 1``
run writes under ``.bench_trace/<cell>/``, on the clock of the device
planes.  ``bench/trace.py`` keeps only the benchmark's own spans and no
op stats, so this module reads the same file again:

* :func:`load` turns it into plain event tuples, once per process;
* :func:`reduce` gives the program's spans (name, start, end, counters)
  and each device op's interval, the executable whose module run holds
  it, and the innermost of the scopes above on its path; and the same
  per device with the op's name, for the readers of a cell on several
  chips, which take the mean over the chips (:func:`per_chip_pct`).

Where the scope path is (read by hand from a TPU v5 lite trace): the
op events of a TPU plane's ``XLA Ops`` line carry only their timing
stats; their event metadata (one per HLO instruction, named by its HLO
text ``%fusion.179 = f32[...] fusion(...)``) carries the string stat
``tf_op``, the instruction's ``op_name`` and type, as
``jit(run_chunk_slots)/while/body/vmap(nu_projection)/while/body/``
``closed_call/reduce_sum:``.
``ProfileData`` does not expose event metadata, so :func:`event_metadata`
decodes it from the file's protobuf.  A fusion carries the ``op_name``
of the instruction XLA kept as its metadata (its root), so an op is put
under that instruction's scope.  Under ``vmap`` a scope appears as
``vmap(<scope>)``.

A trace of a program without these spans and scopes (an older commit)
gives empty lists, and every reader then returns None; so does a trace
taken off the chip.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import re

import numpy as np

from bench import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
SPAN_PREFIXES = ("svm.", "saddle.", "svc.")
SCOPES = ("momentum_pass", "mwu_pass", "nu_projection", "gap_check",
          "health_check")
SCOPE_STAT = "tf_op"
_WRAPPED = re.compile(r"^(?:[\w-]+\()*([\w.-]+)\)*$")


def _varint(b, i: int) -> tuple[int, int]:
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return r, i


def _fields(b):
    """(field number, value) pairs of one serialized protobuf message:
    an int for varints, a memoryview for everything else."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} in a trace")
        yield key >> 3, v


def event_metadata(path: str, planes) -> dict:
    """Per plane whose name starts with one of ``planes``: event metadata
    name -> {stat name: value} for the string and bytes stats
    (``XSpace`` field numbers from ``tsl/profiler/protobuf/xplane.proto``;
    ``ProfileData`` gives an event's own stats, not its metadata's)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for fn, plane in _fields(space):
        if fn != 1:                                  # XSpace.planes
            continue
        parts = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in parts if k == 2), "")
        if not name.startswith(planes):
            continue
        stat_names = {}
        for k, v in parts:
            if k == 5:                               # XPlane.stat_metadata
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        found = {}
        for k, v in parts:
            if k != 4:                               # XPlane.event_metadata
                continue
            meta = list(_fields(dict(_fields(v)).get(2, b"")))
            ev = next((bytes(x).decode() for j, x in meta if j == 2), "")
            stats = {}
            for j, x in meta:
                if j != 5:                           # XEventMetadata.stats
                    continue
                st = dict(_fields(x))
                key = stat_names.get(st.get(1, 0), "")
                if 5 in st:                          # str_value
                    stats[key] = bytes(st[5]).decode()
                elif 6 in st:                        # bytes_value
                    stats[key] = bytes(st[6])
                elif 7 in st:                        # ref_value
                    stats[key] = stat_names.get(st[7], "")
            found[ev] = stats
        out[name] = found
    return out


def load(path: str) -> dict:
    """Event tuples from an ``.xplane.pb``: ``spans`` holds ``[name,
    start_ns, dur_ns, counters]`` for the program's host spans;
    ``device`` holds ``[device, line, name, start_ns, dur_ns, path]``
    for the TPU planes' op and module lines, ``path`` being an op's
    scope path (its ``SCOPE_STAT``) or None."""
    from jax.profiler import ProfileData

    meta = event_metadata(path, ("/device:TPU:",))
    spans, dev = [], []
    for plane in ProfileData.from_file(path).planes:
        is_dev = plane.name.startswith("/device:TPU:")
        paths = meta.get(plane.name, {})
        for line in plane.lines:
            if is_dev and line.name in (trace.OPS_LINE, trace.MODULES_LINE):
                for e in line.events:
                    path_ = None
                    if line.name == trace.OPS_LINE:
                        path_ = paths.get(e.name, {}).get(SCOPE_STAT)
                    dev.append([plane.name, line.name,
                                trace.short_name(e.name),
                                float(e.start_ns), float(e.duration_ns),
                                path_])
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append([e.name, float(e.start_ns),
                                      float(e.duration_ns),
                                      {k: v for k, v in e.stats
                                       if not k.startswith("_")}])
    return {"spans": spans, "device": dev}


def innermost_scope(path) -> str | None:
    """The last of ``SCOPES`` on a name stack, or None."""
    found = None
    for part in (path or "").rsplit(":", 1)[0].split("/"):
        m = _WRAPPED.match(part)
        if m and m.group(1) in SCOPES:
            found = m.group(1)
    return found


@dataclasses.dataclass
class Program:
    spans: list         # (name, start s, end s, counters), by start
    ops: list           # (executable, scope or None, start s, end s)
    exec_s: dict        # executable -> device seconds of its module runs
    # per device: [(executable, scope or None, op name, start s, end s)]
    # and {executable: device seconds of its module runs}
    dev_ops: dict = dataclasses.field(default_factory=dict)
    dev_exec_s: dict = dataclasses.field(default_factory=dict)


def reduce(events: dict) -> Program:
    """The reduction; all times in seconds on the trace's clock.  Loops
    and calls, which contain the ops they run, are left out of
    ``ops``."""
    spans = sorted(((n, s * 1e-9, (s + d) * 1e-9, c)
                    for n, s, d, c in events["spans"]),
                   key=lambda sp: sp[1])
    modules: dict[str, list] = {}
    exec_s: dict[str, float] = {}
    dev_exec_s: dict[str, dict] = {}
    for dev, line, name, start, dur, _ in events["device"]:
        if line == trace.MODULES_LINE:
            k = trace.executable_name(name)
            modules.setdefault(dev, []).append(
                (start * 1e-9, (start + dur) * 1e-9, k))
            exec_s[k] = exec_s.get(k, 0.0) + dur * 1e-9
            per = dev_exec_s.setdefault(dev, {})
            per[k] = per.get(k, 0.0) + dur * 1e-9
    starts = {}
    for dev, runs in modules.items():
        runs.sort()
        starts[dev] = np.asarray([r[0] for r in runs])
    ops = []
    dev_ops: dict[str, list] = {}
    for dev, line, name, start, dur, path in events["device"]:
        if line != trace.OPS_LINE or name.startswith(trace.CONTAINERS):
            continue
        runs = modules.get(dev, [])
        s = start * 1e-9
        i = int(np.searchsorted(starts.get(dev, []), s, side="right")) - 1
        k = runs[i][2] if i >= 0 and s <= runs[i][1] else None
        scope = innermost_scope(path)
        ops.append((k, scope, s, s + dur * 1e-9))
        dev_ops.setdefault(dev, []).append(
            (k, scope, name, s, s + dur * 1e-9))
    return Program(spans=spans, ops=ops, exec_s=exec_s, dev_ops=dev_ops,
                   dev_exec_s=dev_exec_s)


@functools.lru_cache(maxsize=None)
def _load_file(path: str) -> Program:
    return reduce(load(path))


def of_cell(cell: str) -> Program | None:
    """The program's spans and scoped ops in the newest trace of
    ``cell`` (the file ``trace.Tracer.file()`` returns), read once per
    process.  None where the cell has no trace, or where the trace holds
    no TPU operation: the host spans of a run off the chip are not the
    chip's."""
    path = trace.Tracer(os.path.join(TRACE_DIR, cell)).file()
    prog = _load_file(path) if path else None
    return prog if prog and prog.exec_s else None


def spans_named(prog: Program | None, name: str) -> list:
    return [sp for sp in prog.spans if sp[0] == name] if prog else []


def mean_ms(prog: Program | None, name: str) -> float | None:
    """Mean length of the spans called ``name``, in milliseconds."""
    spans = spans_named(prog, name)
    if not spans:
        return None
    return 1e3 * sum(e - s for _, s, e, _ in spans) / len(spans)


def scope_pct(prog: Program | None, executable: str,
              scope: str) -> float | None:
    """Device time of ``executable``'s ops under ``scope`` (the union of
    their intervals) over the device time of its module runs, in
    percent; None where no op of it carries that scope."""
    if not prog or not prog.exec_s.get(executable):
        return None
    iv = [(s, e) for k, sc, s, e in prog.ops
          if k == executable and sc == scope]
    if not iv:
        return None
    merged = trace.union(iv)
    return 100.0 * float(np.sum(merged[:, 1] - merged[:, 0])) \
        / prog.exec_s[executable]


def per_chip_pct(prog: Program | None, executable: str,
                 pick) -> float | None:
    """Per device, the union of the intervals of ``executable``'s ops
    for which ``pick(op name, scope)`` holds, over that device's time in
    the executable's module runs; the mean over the devices that ran the
    executable, in percent.  None where no op of it is picked."""
    if not prog:
        return None
    shares, found = [], False
    for dev, per in prog.dev_exec_s.items():
        if not per.get(executable):
            continue
        iv = [(s, e) for k, sc, name, s, e in prog.dev_ops.get(dev, ())
              if k == executable and pick(name, sc)]
        found = found or bool(iv)
        merged = trace.union(iv)
        shares.append(float(np.sum(merged[:, 1] - merged[:, 0]))
                      / per[executable])
    if not found:
        return None
    return 100.0 * sum(shares) / len(shares)
