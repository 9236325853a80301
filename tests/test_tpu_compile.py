"""The fit path's Pallas kernels compile for a TPU v5e chip.

Each test lowers with ``interpret=False`` against a described (not
attached) v5e topology and compiles with the TPU compiler installed
alongside JAX, so what Mosaic would refuse on the chip -- an untiled
block, a vector layout it cannot lower -- fails here.  Nothing runs;
correctness is covered by the interpret-mode parity tests.  The
topology is described inside a fixture (never while a module is
imported) and the persistent compilation cache is off around these
compiles: an entry written for a described chip cannot be read back
without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.kernels import saddle_update as su

F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 -- any backend error
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(sharding, shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args, **static):
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile().as_text()


# serving rungs (lane bucket, service bucket, solo 1M) and the per-client
# shard shapes of the k=256 / k=512 dry-run meshes
PACKED_SHAPES = [
    pytest.param(128, 64, 1, id="rung128_d64_b1"),
    pytest.param(1 << 14, 256, 128, id="rung16k_d256_b128"),
    pytest.param(1 << 16, 64, 8, id="rung64k_d64_b8"),
    pytest.param(1 << 20, 256, 128, id="solo1m_d256_b128"),
    pytest.param(1 << 20, 256, 1, id="solo1m_d256_b1"),
    pytest.param(4096, 256, 128, id="dryrun_k256"),
    pytest.param(2048, 256, 1, id="dryrun_k512_hm"),
]


@pytest.mark.parametrize("n_pad,d,b", PACKED_SHAPES)
def test_packed_pair_compiles(one_chip, n_pad, d, b):
    vec = _sds(one_chip, (n_pad,))
    x_t = _sds(one_chip, (d, n_pad))
    idx = _sds(one_chip, (b,), jnp.int32)
    scalar = _sds(one_chip, ())

    def mom(x, i, ll, lp, s, th):
        return su.momentum_dot_packed(x, i, ll, lp, s, th, interpret=False)

    def mwu(x, i, ll, u, dw, s, g, t, de):
        return su.mwu_update_packed(x, i, ll, u, dw, s, g, t, de,
                                    interpret=False)

    assert "tpu_custom_call" in _compiled_text(
        mom, x_t, idx, vec, vec, vec, scalar)
    assert "tpu_custom_call" in _compiled_text(
        mwu, x_t, idx, vec, vec, _sds(one_chip, (b,)), vec, scalar, scalar,
        scalar)


@pytest.mark.parametrize("n,b", [(17, 1), (1000, 8), (4096, 128)])
def test_unpacked_pair_compiles(one_chip, n, b):
    cols = _sds(one_chip, (n, b))
    vec = _sds(one_chip, (n,))

    def mom(c, ll, lp, th):
        return su.momentum_dot(c, ll, lp, th, interpret=False)

    def mwu(c, ll, u, dw):
        return su.mwu_update(c, ll, u, dw, 1.0, 1e-3, 40.0, 128.0,
                             interpret=False, normalize=False)

    assert "tpu_custom_call" in _compiled_text(
        mom, cols, vec, vec, _sds(one_chip, ()))
    assert "tpu_custom_call" in _compiled_text(
        mwu, cols, vec, vec, _sds(one_chip, (b,)))


def test_slot_chunk_with_kernels_compiles(one_chip, monkeypatch):
    """The service's chunk executable on the pallas backend at S=8: the
    kernels vmapped over slots lower to one launch with a slot grid
    axis.  ``default_interpret`` sees the CPU here, so it is steered to
    the compiled kernels for this test."""
    monkeypatch.setattr(su, "default_interpret", lambda: False)
    s, n_pad, d, b = 8, 4096, 64, 8
    state = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: engine.init_slot_state(s, n_pad, d)))
    sp = engine.SlotParams(*(_sds(one_chip, (s,))
                             for _ in engine.SlotParams._fields))
    text = engine.run_chunk_slots.lower(
        state, _sds(one_chip, (s, d, n_pad)), _sds(one_chip, (s, n_pad)),
        sp, 16, chunk_steps=16, d=d, block_size=b, project=True,
        check_gap=False, backend="pallas").compile().as_text()
    assert text.count("tpu_custom_call") >= 2


def test_solo_solve_with_kernels_compiles(one_chip, monkeypatch):
    """The solo fit's whole-solve executable on the pallas backend, the
    backend a fit on a TPU defaults to, at the 1M rung (S = 1, B = 128):
    both passes are kernels, the sampled (B, n_pad) block is never
    materialized, and the program fits one chip's 16 GB."""
    monkeypatch.setattr(su, "default_interpret", lambda: False)
    s, n_pad, d, b = 1, 1 << 20, 256, 128
    state = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: engine.init_slot_state(s, n_pad, d)))
    sp = engine.SlotParams(*(_sds(one_chip, (s,))
                             for _ in engine.SlotParams._fields))
    compiled = engine.run_solve_slots.lower(
        state, _sds(one_chip, (s, d, n_pad)), _sds(one_chip, (s, n_pad)),
        sp, 494, chunk_steps=494, num_chunks=1, d=d, block_size=b,
        project=True, check_gap=False, backend="pallas").compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert f"{b},{n_pad}]" not in text
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16e9
