"""Share of the service chunk's device time spent in the nu projection:
device time of ``run_chunk_slots`` ops under the ``nu_projection`` scope
over that executable's device time."""

from bench import program_trace

CELL = "libsvm_steady"
EXECUTABLE = "run_chunk_slots"


def read(ctx):
    return program_trace.scope_pct(program_trace.of_cell(CELL),
                                   EXECUTABLE, "nu_projection")
