"""Share of the HBM roofline that the solve executable reaches: the
least bytes of the traced fits' block steps (``bench/roofline.py``)
over the chip's HBM bandwidth, over the device time of
``run_solve_slots`` in the trace.  The points are counted in the type
the executable holds them in: that of the largest array its operations
name, which has to hold at least the n x d points."""

from bench import roofline, trace

EXECUTABLE = "run_solve_slots"


def read(ctx):
    s = ctx.summary
    runs = s.exec_runs.get(EXECUTABLE, 0) if s else 0
    steps = ctx.counters.get("steps_traced")
    if not runs or not steps or runs != ctx.counters.get("fits_traced"):
        return None
    cfg = ctx.cfg
    n, d = cfg["n1"] + cfg["n2"], cfg["d"]
    array = s.exec_array.get(EXECUTABLE)
    if not array or array[1] < n * d or array[0] not in trace.ITEMSIZE:
        return None             # the trace does not show the operand
    need = roofline.step_bytes(n, d, cfg["block_size"], steps,
                               trace.ITEMSIZE[array[0]])
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / s.exec_s[EXECUTABLE]
