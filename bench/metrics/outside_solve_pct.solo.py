"""Share of a fit's wall time spent outside the solve executable: the
class split, transfer, preprocessing and hyperplane recovery of
``SaddleNuSVC.fit``.  1 - device time of ``run_solve_slots`` over the
wall time of the traced fits (benchmark spans)."""

EXECUTABLE = "run_solve_slots"


def read(ctx):
    s = ctx.summary
    wall = ctx.counters.get("fit_wall_traced_s")
    if not s or not wall or EXECUTABLE not in s.exec_s:
        return None
    return 100.0 * (1.0 - s.exec_s[EXECUTABLE] / wall)
