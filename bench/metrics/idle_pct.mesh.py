"""Device idle share in the traced window, the mean over the chips, from
the profiler trace; silent on a trace without the point-sharded
chunk."""

from bench.metrics import _idle

CELL = "mesh_points_1m_x8"
EXECUTABLE = "local_fn"         # the shard_map of the slot chunk


def read(ctx):
    if not ctx.summary or EXECUTABLE not in ctx.summary.exec_s:
        return None
    return _idle.read(ctx)
