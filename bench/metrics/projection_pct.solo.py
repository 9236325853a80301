"""Share of the solve executable's device time spent in the nu
projection: device time of ``run_solve_slots`` ops under the
``nu_projection`` scope over that executable's device time."""

from bench import program_trace

CELL = "solo_nu_1m"
EXECUTABLE = "run_solve_slots"


def read(ctx):
    return program_trace.scope_pct(program_trace.of_cell(CELL),
                                   EXECUTABLE, "nu_projection")
