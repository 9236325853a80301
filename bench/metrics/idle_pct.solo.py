"""Device idle share in the traced window, from the profiler trace."""

from bench.metrics._idle import read  # noqa: F401
