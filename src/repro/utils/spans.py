"""Host spans of the program, written into JAX's profiler trace.

``with span("svc.dispatch", lanes=3, slots=8): ...`` marks a stretch of
host work.  While a profile is being taken (``jax.profiler.trace`` or
``start_trace``) the span lands in the ``.xplane.pb`` as a host event of
that name, on the clock of the device planes, and each keyword becomes a
stat of the event; with no profile running it costs one Python call and
records nothing.

Counters are values the caller already holds (ints or strings).  A span
never waits for the device: it measures the host's time, and the device
planes of the same trace give the device's.

Span names, by prefix:

* ``svm.*`` -- ``SaddleSVC.fit``: ``svm.fit`` around the fit, with
  ``svm.split`` (the numpy class split), ``svm.preprocess`` (Algorithm 1:
  the host-to-device copy and its dispatch) and ``svm.recover``;
* ``saddle.*`` -- ``saddle.solve``: ``saddle.pack`` (packing and state
  init) and ``saddle.run`` (the solve's dispatch and its one blocking
  read; counters ``steps``, the block-step budget, and ``pallas``, 1
  where the step ran on the Pallas kernels and 0 on jax.numpy);
* ``svc.*`` -- ``SolverService``: ``svc.submit`` (``rid``, ``n``, ``d``)
  around ``svc.preprocess``; ``svc.step`` around ``svc.admit`` (one per
  admitted lane: ``rid``, ``lane``, ``warm``), ``svc.dispatch`` (the
  chunk call: ``lanes`` occupied, ``slots`` in the group, ``queued``
  tickets over all groups, ``n_pad``), ``svc.harvest`` (``svc.wait``,
  the blocking read of the lanes' lifecycle vectors, then ``svc.recover``
  per finished fit: ``rid``) and ``svc.evict``.  ``rid`` ties the spans
  of one request together.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **counters) -> TraceAnnotation:
    """A host span called ``name`` carrying ``counters`` as stats."""
    return TraceAnnotation(name, **counters)
