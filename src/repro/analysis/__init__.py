"""Static analysis for the Pallas kernels and the compiled hot paths.

Two layers, one gate (``python -m repro.analysis.run``):

* :mod:`repro.analysis.pallas_audit` -- Layer 1.  A registry of every
  ``pl.pallas_call`` kernel program in the repo, audited by CONCRETE
  evaluation of each BlockSpec index map over the full grid (including
  adversarial scalar-prefetched index vectors spanning ``[0, d)``):
  block bounds (BLOCK-001), output coverage (COVER-001), write-write
  races across grid points (RACE-001), the per-grid-point VMEM
  footprint against the 16 MiB TPU budget (VMEM-001) and Mosaic's
  (8, 128) block tiling rule (TILE-001).

* :mod:`repro.analysis.hlo_lint` -- Layer 2.  A rule-based lint over
  the AOT-lowered (compiled, post-optimization) HLO of the serving /
  distributed hot paths: donation survives to ``input_output_alias``
  (DONATE-001), no host round-trips inside chunk loops (HOST-001), no
  f64 ops (DTYPE-001), loop-body collectives within the analytic
  ``CommModel`` budget (COMM-001), static loops carry
  ``known_trip_count`` (TRIP-001).

Registry contract (how to add a kernel)
---------------------------------------

A kernel module exposes a ``<name>_program(**shape_params) -> dict``
builder, and its ``pl.pallas_call`` launch consumes THAT dict for the
grid, in/out BlockSpecs, out shapes and scratch allocations -- the
auditor then verifies the very objects the launch uses, so the audit
cannot drift from the kernel.  The dict keys:

``name``                  kernel name (registry key)
``grid``                  the pallas grid tuple
``num_scalar_prefetch``   0, or 1 when the index maps take a trailing
                          scalar-prefetched index-vector argument
``prefetch_length``       length of that vector (None when 0)
``prefetch_bound``        exclusive upper bound of its values (None)
``in_shapes``/``out_shapes``  full unblocked operand/result shapes
                          (element counts; the auditor budgets 4
                          bytes/element -- f32, an upper bound for the
                          bf16 variants)
``in_specs``/``out_specs``    the exact pl.BlockSpec lists launched
``scratch_shapes``        pltpu scratch allocations for the launch
``scratch_bytes``         their total byte footprint
``extra_vmem_bytes``      kernel-private temporaries beyond
                          blocks + scratch (butterfly stacks etc.)
``accum_axes``            ``{out position: (grid axes,)}`` along which
                          output-block revisits are declared legal
                          accumulation; any other revisit is RACE-001

Register the builder plus its shape cases in
``pallas_audit.registry()`` / ``pallas_audit.audit_cases()``.
"""
