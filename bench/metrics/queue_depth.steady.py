"""Tickets waiting for a lane when a chunk is dispatched: the mean of
the ``queued`` counter (over all groups) of the program's
``svc.dispatch`` spans."""

from bench import program_trace

CELL = "libsvm_steady"


def read(ctx):
    spans = [c for *_, c in program_trace.spans_named(
        program_trace.of_cell(CELL), "svc.dispatch") if "queued" in c]
    if not spans:
        return None
    return sum(c["queued"] for c in spans) / len(spans)
