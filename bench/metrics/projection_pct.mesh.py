"""Share of the point-sharded chunk's device time spent in the nu
projection (24 of a step's 29 rounds): per chip, the union of the
intervals of the chunk executable's ops under the ``nu_projection``
scope over the chip's time in that executable; the mean over the
chips."""

from bench import program_trace

CELL = "mesh_points_1m_x8"
EXECUTABLE = "local_fn"         # the shard_map of the slot chunk


def read(ctx):
    return program_trace.per_chip_pct(
        program_trace.of_cell(CELL), EXECUTABLE,
        lambda name, scope: scope == "nu_projection")
