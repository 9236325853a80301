"""Static-analysis layers (repro.analysis): the auditor must PASS every
real kernel program and CATCH every seeded violation with the right
rule ID -- a detector that never fires proves nothing, so each rule is
exercised from both sides.  Also covers the hlo_analysis shape-parsing
fixes the lint rules stand on."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.analysis import hlo_lint, pallas_audit as pa
from repro.utils import hlo_analysis as ha

pytestmark = pytest.mark.analysis


def _rules(findings):
    return {f.rule for f in findings}


def _fake_prog(name, **kw):
    base = dict(name=name, num_scalar_prefetch=0, prefetch_length=None,
                prefetch_bound=None, scratch_shapes=[], scratch_bytes=0,
                extra_vmem_bytes=0, accum_axes={})
    base.update(kw)
    return base


# ==================================================================
# Layer 1: the real kernel registry passes, seeded violations fail
# ==================================================================

def test_registry_covers_every_pallas_kernel():
    """Every pallas_call site in the kernels package must be built
    from a registered program (the registry IS the audit surface)."""
    assert set(pa.registry()) == {
        "momentum_dot", "mwu_update", "momentum_dot_packed",
        "mwu_update_packed", "fwht"}


def test_full_sweep_clean():
    """All registered kernels x all serving rungs x both dry-run mesh
    client shapes x adversarial prefetch vectors: zero findings."""
    records, findings = pa.audit_all()
    assert findings == []
    # the sweep really covers both dry-run meshes and all five kernels
    cases = " | ".join(r["case"] for r in records)
    assert "k=256" in cases and "k=512" in cases
    assert {r["kernel"] for r in records} == set(pa.registry())
    # packed kernels really get the adversarial idx treatment
    assert any(r["idx_variants"] == 5 for r in records)


def test_seeded_out_of_bounds_index_map_block_001():
    prog = _fake_prog(
        "bad_block", grid=(4,),
        in_shapes=[(512,)],
        in_specs=[pl.BlockSpec((128,), lambda i: (i + 1,))],
        out_shapes=[(512,)],
        out_specs=[pl.BlockSpec((128,), lambda i: (i,))])
    assert _rules(pa.audit_program(prog, case="seed")) == {"BLOCK-001"}


def test_seeded_prefetch_out_of_bounds_block_001():
    """An off-by-one on the scalar-prefetched row index is only
    reachable when idx contains d-1 -- exactly what the adversarial
    vectors inject."""
    prog = _fake_prog(
        "bad_prefetch", grid=(2, 4), num_scalar_prefetch=1,
        prefetch_length=4, prefetch_bound=16,
        in_shapes=[(16, 256)],
        in_specs=[pl.BlockSpec((1, 128),
                               lambda i, j, idx: (idx[j] + 1, i))],
        out_shapes=[(2, 4)],
        out_specs=[pl.BlockSpec((1, 1), lambda i, j, idx: (i, j))])
    findings = pa.audit_program(prog, case="seed")
    assert "BLOCK-001" in _rules(findings)
    assert any("idx=" in f.detail for f in findings)


def test_seeded_uncovered_output_cover_001():
    prog = _fake_prog(
        "bad_cover", grid=(4,),
        in_shapes=[(512,)],
        in_specs=[pl.BlockSpec((128,), lambda i: (i,))],
        out_shapes=[(1024,)],        # twice the grid's reach
        out_specs=[pl.BlockSpec((128,), lambda i: (i,))])
    assert "COVER-001" in _rules(pa.audit_program(prog, case="seed"))


def test_seeded_racing_output_blockspec_race_001():
    """A packed-style (i,)-only output map revisited along grid axis 1
    WITHOUT declaring accumulation is a write-write race."""
    prog = _fake_prog(
        "bad_race", grid=(4, 8),
        in_shapes=[(512,)],
        in_specs=[pl.BlockSpec((128,), lambda i, j: (i,))],
        out_shapes=[(512,)],
        out_specs=[pl.BlockSpec((128,), lambda i, j: (i,))])
    assert _rules(pa.audit_program(prog, case="seed")) == {"RACE-001"}


def test_real_packed_accumulation_is_not_a_race():
    """mwu_update_packed revisits every output along the b-walk; with
    its declared accum_axes it must pass, and stripping the
    declaration must turn exactly that revisit into RACE-001."""
    from repro.kernels.saddle_update import mwu_update_packed_program
    prog = mwu_update_packed_program(n_pad=2048, d=32, b=8, tile=1024,
                                     num_slots=2)
    assert pa.audit_program(prog, case="real") == []
    tampered = dict(prog, accum_axes={})
    assert _rules(pa.audit_program(tampered, case="tampered")) == \
        {"RACE-001"}


# What the chip's compiler refused before the kernels moved to the
# row-tile layout: a one-row gather block of the (d, n_pad) operand,
# (1, 1)/(1, 4) per-tile partial outputs, and rank-1 (1,) outputs.
# Interpret mode runs all of them; TILE-001 must refuse each.
_TILE_SEEDS = {
    "x_t_row_gather": ((1, 1024), (256, 4096)),
    "squeezed_row_gather": ((None, 1024), (256, 4096)),
    "scalar_partial": ((1, 1), (4, 128)),
    "class_partials": ((1, 4), (4, 4)),
    "rank1_partial": ((1,), (4,)),
    "rank1_unaligned": ((200,), (1000,)),
    "sublane_unaligned": ((12, 128), (48, 128)),
}


@pytest.mark.parametrize("seed", sorted(_TILE_SEEDS))
def test_seeded_untiled_block_tile_001(seed):
    block, full = _TILE_SEEDS[seed]
    zeros = (0,) * len(full)
    prog = _fake_prog(
        seed, grid=(1,),
        in_shapes=[full], in_specs=[pl.BlockSpec(block, lambda i: zeros)],
        out_shapes=[(1024,)],
        out_specs=[pl.BlockSpec((1024,), lambda i: (0,))])
    assert "TILE-001" in _rules(pa.audit_program(prog, case="seed"))


@pytest.mark.parametrize("block,full,ok", [
    ((8, 128), (64, 1024), True),
    ((1, 512), (1, 4096), True),          # second-minor equals the dim
    ((None, 16, 128), (4, 16, 128), True),  # leading dims are free
    ((4096,), (4096,), True),
    ((256,), (4096,), True),
    ((1, 128), (8, 128), False),
    ((8, 64), (8, 1024), False),
])
def test_tiling_rule_matches_mosaic(block, full, ok):
    """The rule itself, on blocks either side of Mosaic's boundary."""
    dims = tuple(1 if b is None else b for b in block)
    assert pa.tiling_ok(dims, full) is ok


def test_whole_array_smem_block_is_exempt_from_tile_001():
    from jax.experimental.pallas import tpu as pltpu
    prog = _fake_prog(
        "smem_scalars", grid=(4,),
        in_shapes=[(3,), (512,)],
        in_specs=[pl.BlockSpec((3,), lambda i: (0,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((128,), lambda i: (i,))],
        out_shapes=[(512,)],
        out_specs=[pl.BlockSpec((128,), lambda i: (i,))])
    assert pa.audit_program(prog, case="smem") == []


def test_seeded_oversized_block_vmem_001():
    spec = pl.BlockSpec((4096, 4096), lambda i: (0, 0))
    prog = _fake_prog(
        "bad_vmem", grid=(1,),
        in_shapes=[(4096, 4096)], in_specs=[spec],
        out_shapes=[(4096, 4096)], out_specs=[spec])
    assert _rules(pa.audit_program(prog, case="seed")) == {"VMEM-001"}


def test_partial_race_group_is_flagged():
    """A revisit group SMALLER than the declared accumulation extent
    (output touched by only some j) is still a finding -- declared
    accumulation must be exact, not a blanket waiver."""
    prog = _fake_prog(
        "bad_partial", grid=(2, 4),
        in_shapes=[(256,)],
        in_specs=[pl.BlockSpec((128,), lambda i, j: (i,))],
        out_shapes=[(8,)],
        # grid point (i, j) -> block 2i + (j & 1): each block revisited
        # only twice, not the declared 4-wide j extent
        out_specs=[pl.BlockSpec((1,),
                                lambda i, j: (2 * i + (j % 2),))],
        accum_axes={0: (1,)})
    assert "RACE-001" in _rules(pa.audit_program(prog, case="seed"))


# ==================================================================
# Layer 2 rules, each fed a seeded violation
# ==================================================================

@pytest.mark.filterwarnings(
    "ignore:Some donated buffers were not usable")
def test_dropped_donation_flagged_donate_001():
    """A donated buffer whose shape cannot alias the output loses its
    input_output_alias entry -- the exact regression DONATE-001 exists
    to catch."""
    fn = jax.jit(lambda x: x[:1] + 1.0, donate_argnums=0)
    hlo = fn.lower(
        jax.ShapeDtypeStruct((8,), jnp.float32)).compile().as_text()
    findings = hlo_lint.check_donation(hlo, "seed", 1)
    assert [f.rule for f in findings] == ["DONATE-001"]


def test_surviving_donation_passes_donate_001():
    fn = jax.jit(lambda x: x + 1.0, donate_argnums=0)
    hlo = fn.lower(
        jax.ShapeDtypeStruct((8,), jnp.float32)).compile().as_text()
    assert hlo_lint.donated_params(hlo) == {0}
    assert hlo_lint.check_donation(hlo, "seed", 1) == []


_SEED_HLO = """\
HloModule seed, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%body (p.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.1 = (s32[], f32[8]) parameter(0)
  %tok = token[] after-all()
  %of = token[] outfeed((s32[], f32[8]) %p.1, token[] %tok)
  ROOT %r.1 = (s32[], f32[8]) tuple()
}

%cond (p.2: (s32[], f32[8])) -> pred[] {
  %p.2 = (s32[], f32[8]) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (arg: f32[8]) -> f32[8] {
  %arg = f32[8]{0} parameter(0)
  %init = (s32[], f32[8]) tuple()
  %w = (s32[], f32[8]) while((s32[], f32[8]) %init), \
condition=%cond, body=%body
  %wide = f64[8]{0} convert(f32[8]{0} %arg)
  ROOT %out = f32[8]{0} convert(f64[8]{0} %wide)
}
"""


def test_injected_f64_op_flagged_dtype_001():
    findings = hlo_lint.check_dtype(_SEED_HLO, "seed")
    assert [f.rule for f in findings] == ["DTYPE-001"]
    assert "f64" in findings[0].detail


def test_outfeed_in_while_body_flagged_host_001():
    findings = hlo_lint.check_host(_SEED_HLO, "seed")
    assert [f.rule for f in findings] == ["HOST-001"]
    assert "outfeed" in findings[0].detail


def test_clean_hlo_passes_dtype_and_host():
    fn = jax.jit(lambda x: x * 2.0)
    hlo = fn.lower(
        jax.ShapeDtypeStruct((8,), jnp.float32)).compile().as_text()
    assert hlo_lint.check_dtype(hlo, "clean") == []
    assert hlo_lint.check_host(hlo, "clean") == []
    assert hlo_lint.check_comm_serial(hlo, "clean") == []


def test_collective_in_serial_target_flagged_comm_001():
    hlo = _SEED_HLO.replace(
        "%tok = token[] after-all()",
        "%ar = f32[8]{0} all-reduce(f32[8]{0} %arg), to_apply=%cond")
    findings = hlo_lint.check_comm_serial(hlo, "seed")
    assert [f.rule for f in findings] == ["COMM-001"]


def test_lost_static_trip_flagged_trip_001():
    """The seed module's while has no known_trip_count: expecting a
    static chunk scan must fail, and so must its dynamic-while count
    when the design allows none."""
    findings = hlo_lint.check_trips(_SEED_HLO, "seed",
                                    static_trips=(4,),
                                    max_dynamic_whiles=0)
    assert [f.rule for f in findings] == ["TRIP-001", "TRIP-001"]
    assert hlo_lint.check_trips(_SEED_HLO, "seed", static_trips=(),
                                max_dynamic_whiles=1) == []


def test_suppressions_require_justification():
    f = hlo_lint.Finding("DTYPE-001", "t", "seeded")
    with pytest.raises(ValueError, match="justification"):
        hlo_lint.apply_suppressions(
            [f], (hlo_lint.Suppression("DTYPE-001", "t", "  "),))
    live, waived = hlo_lint.apply_suppressions(
        [f], (hlo_lint.Suppression("DTYPE-001", "t", "known, tracked"),))
    assert live == [] and len(waived) == 1
    assert waived[0]["justification"] == "known, tracked"
    # a non-matching suppression must not eat the finding
    live, _ = hlo_lint.apply_suppressions(
        [f], (hlo_lint.Suppression("HOST-001", "t", "other rule"),))
    assert live == [f]


# ==================================================================
# hlo_analysis shape parsing (the substrate the rules stand on)
# ==================================================================

def test_shape_bytes_tuple_shapes():
    assert ha._shape_bytes("f32[4,2]") == 32
    assert ha._shape_bytes("(f32[2], s32[4])") == 8 + 16
    assert ha._shape_bytes("(f32[128]{0}, token[])") == 512


def test_shape_bytes_zero_dim_and_pred():
    assert ha._shape_bytes("f32[]") == 4          # scalar: one element
    assert ha._shape_elements("f32[]") == 1
    assert ha._shape_bytes("pred[8]") == 8
    assert ha._shape_bytes("bf16[2,3]") == 12


def test_unknown_dtype_is_an_error_not_a_skip():
    with pytest.raises(ValueError, match="unknown HLO dtype"):
        ha._shape_bytes("f128[4]")
    with pytest.raises(ValueError, match="unknown HLO dtype"):
        ha._shape_elements("(f32[2], f128[4])")


def test_fp8_dtypes_counted():
    assert ha._shape_bytes("f8e4m3fn[16]") == 16
    assert ha._shape_bytes("f8e5m2[16]") == 16


# ==================================================================
# The gate itself (compiles the hot paths: slow tier)
# ==================================================================

@pytest.mark.slow
def test_lint_default_targets_clean():
    """In-process lint of every target the current device count can
    lower (the k=8 sharded runner needs forced host devices, which
    only the subprocess gate -- run.py sets XLA_FLAGS before jax
    imports -- can provide; jax pins the count at first init)."""
    targets = [t for t in hlo_lint.default_targets()
               if "k=8" not in t.name or jax.device_count() >= 8]
    assert len(targets) >= 4
    records, findings = hlo_lint.lint_all(targets)
    assert findings == []
    assert [r["target"] for r in records] == [t.name for t in targets]


@pytest.mark.slow
def test_gate_subprocess_green(tmp_path):
    """The CI entry point end to end: exit 0, JSON report written,
    zero unsuppressed findings."""
    out = tmp_path / "BENCH_analysis.json"
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + (
        ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.run",
         "--json", str(out)],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(out.read_text())
    assert report["unsuppressed_count"] == 0
    assert len(report["kernel_cases"]) > 100
    assert len(report["hlo_targets"]) == 9
