"""Share of the dispatched lanes that hold a fit: the sum of the
``lanes`` counter over the sum of the ``slots`` counter of the program's
``svc.dispatch`` spans, in percent."""

from bench import program_trace

CELL = "libsvm_steady"


def read(ctx):
    spans = program_trace.spans_named(program_trace.of_cell(CELL),
                                      "svc.dispatch")
    slots = sum(c.get("slots", 0) for *_, c in spans)
    if not slots:
        return None
    return 100.0 * sum(c.get("lanes", 0) for *_, c in spans) / slots
