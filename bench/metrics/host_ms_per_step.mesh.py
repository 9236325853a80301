"""Host milliseconds per ``SolverService.step`` call (scheduler,
admission, harvest), from the trace; silent on a trace without the
point-sharded chunk."""

from bench.metrics import _host_step

CELL = "mesh_points_1m_x8"
EXECUTABLE = "local_fn"         # the shard_map of the slot chunk


def read(ctx):
    if not ctx.summary or EXECUTABLE not in ctx.summary.exec_s:
        return None
    return _host_step.read(ctx)
