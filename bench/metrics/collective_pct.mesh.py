"""Share of the point-sharded chunk's device time in which a collective
runs (Algorithm 4's rounds between the chips): per chip, the union of
the intervals of the chunk executable's all-reduce operations over the
chip's time in that executable; the mean over the chips.  On the chip
an all-reduce is named by the primitive it lowers (``%psum.43``,
``%pmax.14``), or else ``%all-reduce...``."""

import re

from bench import program_trace

CELL = "mesh_points_1m_x8"
EXECUTABLE = "local_fn"         # the shard_map of the slot chunk
ALL_REDUCE = re.compile(r"%(psum|pmax|pmin|all-reduce)\b")


def read(ctx):
    return program_trace.per_chip_pct(
        program_trace.of_cell(CELL), EXECUTABLE,
        lambda name, scope: ALL_REDUCE.match(name) is not None)
