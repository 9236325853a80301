"""Host milliseconds of one chunk dispatch in ``SolverService.step``: the
mean length of the program's ``svc.dispatch`` spans (the chunk call into
the runtime)."""

from bench import program_trace

CELL = "libsvm_steady"


def read(ctx):
    return program_trace.mean_ms(program_trace.of_cell(CELL),
                                 "svc.dispatch")
