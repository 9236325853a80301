"""Host milliseconds per ``SolverService.step`` call (scheduler,
admission, harvest), from the trace."""

from bench.metrics._host_step import read  # noqa: F401
