"""Shared arithmetic of the ``host_ms_per_step.*`` readers: mean over the
benchmark's ``bench.step`` spans (one ``SolverService.step`` call each)
of the span's wall time minus the device busy time inside it."""

from bench import trace


def read(ctx):
    if not ctx.summary or not len(ctx.summary.busy):
        return None             # no device operation in the trace
    return trace.host_ms_per_span(ctx.summary, "bench.step")
