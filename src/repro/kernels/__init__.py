# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.


def default_interpret() -> bool:
    """Pallas ``interpret=`` default: compiled kernels on TPU, the
    interpreter everywhere else.

    The interpreter accepts blocks the TPU compiler refuses, so an
    interpret-mode pass says nothing about the chip.  What the compiled
    path needs is checked twice off the chip: the static auditor
    (:mod:`repro.analysis.pallas_audit`, including Mosaic's tiling rule
    TILE-001) over the program builders the launches use, and
    ``tests/test_tpu_compile.py``, which compiles the kernels and the
    slot chunk for a described v5e with ``interpret=False``.
    """
    import jax

    return jax.default_backend() != "tpu"


def resolve_use_kernels(use_kernels: bool | None) -> bool:
    """The solo fit's backend: an explicit ``use_kernels`` wins, and
    ``None`` picks the Pallas kernels on a TPU and ``jax.numpy``
    everywhere else.

    On the chip the packed kernels gather the sampled coordinate rows
    tile by tile inside each pass; the jnp step materializes the
    (B, n_pad) block with ``jnp.take`` and reads it back, which costs
    more than both passes.  Off the chip the kernels only run in the
    interpreter, so jnp is the faster and the tested default there.
    """
    if use_kernels is not None:
        return bool(use_kernels)
    import jax

    return jax.default_backend() == "tpu"
