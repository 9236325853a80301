"""Share of the HBM roofline that the point-sharded chunk reaches on a
chip: the least bytes a chip moves for the traced lane-steps (its
quarter of each lane's points, ``bench/roofline.py``) over the chip's
HBM bandwidth, over the chip's device time in the chunk executable (the
mean over the chips).  A lane-step is one lane of one dispatched chunk
step: the ``lanes`` counter of the program's ``svc.dispatch`` spans
times the chunk's steps.  The points are counted in the type of the
largest array the executable's operations name, which has to hold at
least a chip's share of one lane's points."""

from bench import program_trace, roofline, trace

CELL = "mesh_points_1m_x8"
EXECUTABLE = "local_fn"         # the shard_map of the slot chunk


def read(ctx):
    s = ctx.summary
    runs = s.exec_runs.get(EXECUTABLE, 0) if s else 0
    spans = program_trace.spans_named(program_trace.of_cell(CELL),
                                      "svc.dispatch")
    # every traced dispatch ran its chunk on every chip inside the trace
    if not runs or not spans or runs != len(spans) * s.devices:
        return None
    cfg, svc = ctx.cfg, ctx.cfg["service"]
    n_chip = (cfg["n1"] + cfg["n2"]) // svc["mesh_chips"]
    d = cfg["d"]
    array = s.exec_array.get(EXECUTABLE)
    if not array or array[1] < n_chip * d or array[0] not in trace.ITEMSIZE:
        return None             # the trace does not show the operand
    lane_steps = sum(c.get("lanes", 0) for *_, c in spans) \
        * svc["chunk_steps"]
    need = roofline.step_bytes(n_chip, d, cfg["block_size"], lane_steps,
                               trace.ITEMSIZE[array[0]])
    chip_s = s.exec_s[EXECUTABLE] / s.devices
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / chip_s
