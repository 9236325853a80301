"""Layer 1: static audit of every Pallas kernel program in the repo.

The kernel modules (:mod:`repro.kernels.saddle_update`,
:mod:`repro.kernels.fwht`) build their ``pl.pallas_call`` launches
from ``*_program`` dicts; :func:`registry` maps kernel names to those
SAME builders, so the auditor evaluates the launched BlockSpecs, not a
copy.  For every registered shape case (:func:`audit_cases` -- the
serving bucket rungs plus the per-client dry-run shard shapes of both
production meshes) the auditor CONCRETELY evaluates each index map at
every grid point -- for scalar-prefetched kernels under a family of
adversarial index vectors spanning ``[0, d)`` -- and checks:

BLOCK-001  every selected block lies inside its operand/result shape
COVER-001  every output block is written by at least one grid point
RACE-001   an output block revisited by multiple grid points is a
           declared accumulation (``accum_axes``): the revisit group
           spans exactly the accumulation axes and is constant along
           every other grid axis; anything else is a write-write race
           on TPU's revisit-flush output semantics
VMEM-001   double-buffered blocks + scratch + kernel temporaries fit
           the 16 MiB per-core VMEM budget at 4 bytes/element
TILE-001   every block satisfies Mosaic's tiling rule: the last two
           block dims are divisible by (8, 128) or equal the operand's
           dims, and a rank-1 block is a multiple of 128 or the whole
           length (squeezed dims count as 1; a whole-array SMEM block is
           exempt, as in the TPU lowering).  Interpret mode accepts any
           block; the chip's compiler refuses these.

Zero findings over :func:`audit_cases` is a CI gate
(``python -m repro.analysis.run``; see scripts/ci.sh).
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple

import numpy as np

VMEM_BUDGET = 16 * 1024 * 1024     # bytes of VMEM per TensorCore
ELEM_BYTES = 4                     # f32; upper bound for bf16 operands

#: serving bucket rungs: preprocess.bucket_length pads every fit() to
#: 128 * 2^k, so these are exactly the n_pad values the slot engine
#: can launch kernels at, up to the service's default max_points (2^20).
SERVING_RUNGS = tuple(128 * 2 ** k for k in range(14))

#: the slot counts a packed kernel is launched at: a solo solve (S=1)
#: and the service's default lane group (S=8)
SLOT_COUNTS = (1, 8)


class Finding(NamedTuple):
    rule: str          # BLOCK-001 / COVER-001 / RACE-001 / VMEM-001 /
                       # TILE-001
    kernel: str
    case: str
    detail: str


class AuditCase(NamedTuple):
    kernel: str        # registry key
    case: str          # human-readable shape label
    kwargs: dict       # builder kwargs


def registry() -> dict[str, Callable[..., dict]]:
    """Kernel name -> program builder, covering every pl.pallas_call
    in the repo (grep for ``pallas_call`` when adding a kernel)."""
    from repro.kernels import fwht, saddle_update

    return {
        "momentum_dot": saddle_update.momentum_dot_program,
        "mwu_update": saddle_update.mwu_update_program,
        "momentum_dot_packed": saddle_update.momentum_dot_packed_program,
        "mwu_update_packed": saddle_update.mwu_update_packed_program,
        "fwht": fwht.fwht_program,
    }


# ------------------------------------------------------------- evaluation

def _block_dims(spec) -> tuple[int, ...]:
    """Block shape with squeezed (``None``) dims counted as 1."""
    return tuple(1 if b is None or not isinstance(b, int) else b
                 for b in spec.block_shape)


def _in_smem(spec) -> bool:
    from jax.experimental.pallas import tpu as pltpu
    return spec.memory_space == pltpu.SMEM


def _grid_points(grid: tuple[int, ...]) -> list[np.ndarray]:
    """Flattened coordinate arrays, one (G,) array per grid axis, in
    pallas iteration order (last axis fastest)."""
    mesh = np.meshgrid(*[np.arange(g, dtype=np.int64) for g in grid],
                       indexing="ij")
    return [m.reshape(-1) for m in mesh]


def _eval_index_map(spec, coords: list[np.ndarray],
                    idx: np.ndarray | None) -> np.ndarray:
    """Evaluate a BlockSpec index map at every grid point at once
    (index maps are arithmetic over the grid coordinates, so they
    vectorize over numpy arrays).  Returns (G, block_rank) block
    indices."""
    args = list(coords)
    if idx is not None:
        args.append(idx)
    res = spec.index_map(*args)
    if not isinstance(res, tuple):
        res = (res,)
    g = coords[0].shape[0] if coords else 1
    comps = [np.broadcast_to(np.asarray(c, dtype=np.int64), (g,))
             for c in res]
    return np.stack(comps, axis=1)


def _idx_variants(prog: dict) -> list[tuple[str, np.ndarray | None]]:
    """Adversarial scalar-prefetch vectors: every entry in [0, d),
    exercising the extremes and non-monotone permutation-ish patterns
    of the sampled coordinate block."""
    if not prog["num_scalar_prefetch"]:
        return [("", None)]
    b, d = prog["prefetch_length"], prog["prefetch_bound"]
    ar = np.arange(b, dtype=np.int64)
    return [
        ("idx=zeros", np.zeros(b, dtype=np.int64)),
        ("idx=max", np.full(b, d - 1, dtype=np.int64)),
        ("idx=ramp", ar % d),
        ("idx=reversed", (d - 1 - ar) % d),
        ("idx=strided", (ar * 37 + d // 2) % d),
    ]


def _check_blocks(prog, coords, idx, variant, case, findings) -> None:
    for role, specs, fulls in (
            ("in", prog["in_specs"], prog["in_shapes"]),
            ("out", prog["out_specs"], prog["out_shapes"])):
        for pos, (spec, full) in enumerate(zip(specs, fulls)):
            block = _block_dims(spec)
            binds = _eval_index_map(spec, coords, idx)
            if binds.shape[1] != len(block) or len(block) != len(full):
                findings.append(Finding(
                    "BLOCK-001", prog["name"], case,
                    f"{role}[{pos}]{variant}: index map rank "
                    f"{binds.shape[1]} vs block {block} vs shape {full}"))
                continue
            off = binds * np.asarray(block, dtype=np.int64)
            over = (off < 0) | (off + np.asarray(block) >
                                np.asarray(full, dtype=np.int64))
            if over.any():
                g = int(np.flatnonzero(over.any(axis=1))[0])
                findings.append(Finding(
                    "BLOCK-001", prog["name"], case,
                    f"{role}[{pos}]{variant}: grid point "
                    f"{tuple(int(c[g]) for c in coords)} selects block "
                    f"{tuple(int(v) for v in binds[g])} x {block}, "
                    f"outside shape {full}"))


def _check_outputs(prog, coords, idx, variant, case, findings) -> None:
    grid = prog["grid"]
    for pos, (spec, full) in enumerate(zip(prog["out_specs"],
                                           prog["out_shapes"])):
        block = _block_dims(spec)
        if len(block) != len(full):
            continue                       # already a BLOCK-001
        binds = _eval_index_map(spec, coords, idx)
        space = tuple(-(-f // b) for f, b in zip(full, block))

        # COVER-001: every output block written at least once
        seen = np.zeros(space, dtype=bool)
        inb = ((binds >= 0) &
               (binds < np.asarray(space, dtype=np.int64))).all(axis=1)
        if inb.any():
            seen[tuple(binds[inb].T)] = True
        if not seen.all():
            miss = tuple(int(v) for v in np.argwhere(~seen)[0])
            findings.append(Finding(
                "COVER-001", prog["name"], case,
                f"out[{pos}]{variant}: output block {miss} of {space} "
                "is never written (stale garbage in the result)"))

        # RACE-001: multi-writer blocks must be declared accumulation
        uniq, inverse, counts = np.unique(
            binds, axis=0, return_inverse=True, return_counts=True)
        if counts.max(initial=0) <= 1:
            continue
        accum = tuple(prog["accum_axes"].get(pos, ()))
        expect = int(math.prod(grid[a] for a in accum)) if accum else 1
        multi = counts > 1
        bad = multi & (counts != expect)
        reason = (f"group size != accumulation extent {expect}"
                  if bad.any() else "")
        if not bad.any():
            # the revisit group must be constant along every
            # non-accumulation grid axis (same tile, walked only
            # along the declared axes -> consecutive revisits)
            for ax in range(len(grid)):
                if ax in accum:
                    continue
                lo = np.full(len(uniq), np.iinfo(np.int64).max)
                hi = np.full(len(uniq), np.iinfo(np.int64).min)
                np.minimum.at(lo, inverse, coords[ax])
                np.maximum.at(hi, inverse, coords[ax])
                varies = multi & (lo != hi)
                if varies.any():
                    bad = varies
                    reason = f"revisit group varies along grid axis {ax}"
                    break
        if bad.any():
            blk = tuple(int(v) for v in uniq[np.flatnonzero(bad)[0]])
            n_writers = int(counts[np.flatnonzero(bad)[0]])
            findings.append(Finding(
                "RACE-001", prog["name"], case,
                f"out[{pos}]{variant}: block {blk} written by "
                f"{n_writers} grid points but {reason} "
                f"(accum_axes={accum}) -- write-write race"))


def _check_vmem(prog, case, findings) -> None:
    block_bytes = sum(
        int(math.prod(_block_dims(spec))) * ELEM_BYTES
        for spec in (*prog["in_specs"], *prog["out_specs"])
        if not _in_smem(spec))
    total = (2 * block_bytes                     # double-buffered DMA
             + prog["scratch_bytes"] + prog["extra_vmem_bytes"])
    if total > VMEM_BUDGET:
        findings.append(Finding(
            "VMEM-001", prog["name"], case,
            f"per-grid-point VMEM {total} B (2x{block_bytes} blocks + "
            f"{prog['scratch_bytes']} scratch + "
            f"{prog['extra_vmem_bytes']} temps) exceeds "
            f"{VMEM_BUDGET} B budget"))


def tiling_ok(block: tuple[int, ...], full: tuple[int, ...]) -> bool:
    """Mosaic's block-shape rule for one (f32) block of ``full``."""
    if len(block) == 1:
        return block[0] == full[0] or block[0] % 128 == 0
    (b1, b0), (a1, a0) = block[-2:], full[-2:]
    return (b0 == a0 or b0 % 128 == 0) and (b1 == a1 or b1 % 8 == 0)


def _check_tiling(prog, case, findings) -> None:
    for role, specs, fulls in (
            ("in", prog["in_specs"], prog["in_shapes"]),
            ("out", prog["out_specs"], prog["out_shapes"])):
        for pos, (spec, full) in enumerate(zip(specs, fulls)):
            block, full = _block_dims(spec), tuple(full)
            if _in_smem(spec) and block == full:
                continue
            if len(block) != len(full) or tiling_ok(block, full):
                continue          # a rank mismatch is already BLOCK-001
            findings.append(Finding(
                "TILE-001", prog["name"], case,
                f"{role}[{pos}]: block {tuple(spec.block_shape)} of "
                f"{full}: the last two dims must be divisible by "
                "(8, 128) or equal the operand's (a rank-1 block: a "
                "multiple of 128 or the whole length)"))


def audit_program(prog: dict, *, case: str = "") -> list[Finding]:
    """All five checks over one concrete kernel program."""
    findings: list[Finding] = []
    coords = _grid_points(prog["grid"])
    for variant, idx in _idx_variants(prog):
        tag = f" {variant}" if variant else ""
        if idx is not None and (
                (idx < 0).any() or (idx >= prog["prefetch_bound"]).any()):
            raise ValueError("adversarial idx escapes prefetch_bound")
        _check_blocks(prog, coords, idx, tag, case, findings)
        _check_outputs(prog, coords, idx, tag, case, findings)
    _check_vmem(prog, case, findings)
    _check_tiling(prog, case, findings)
    return findings


# ------------------------------------------------------------- case sweep

def _packed_bs(d: int) -> tuple[int, ...]:
    return tuple(dict.fromkeys((1, 8, min(128, d))))


def audit_cases(*, dryrun_mesh_sizes: tuple[int, ...] = (256, 512),
                ) -> list[AuditCase]:
    """The full shape matrix the gate proves clean: every serving
    bucket rung (times the block sizes the engines launch), the
    per-client dry-run shard shapes of both production meshes, and the
    preprocessing FWHT tiles."""
    from repro.kernels.fwht import auto_tile_n
    from repro.kernels.saddle_update import UNPACKED_TILE, _unpacked_tile
    from repro.launch.specs import (SADDLE_DSVC_SHAPES,
                                    saddle_dsvc_client_shape)

    cases: list[AuditCase] = []
    for n_pad in SERVING_RUNGS:
        tile = _unpacked_tile(n_pad, UNPACKED_TILE)
        for b in (1, 8, 128):
            kw = dict(n_pad=n_pad, b=b, tile=tile)
            lbl = f"rung n_pad={n_pad} b={b} tile={tile}"
            cases.append(AuditCase("momentum_dot", lbl, dict(kw)))
            cases.append(AuditCase("mwu_update", lbl, dict(kw)))
        for d in (64, 256):
            for b in _packed_bs(d):
                for s in SLOT_COUNTS:
                    kw = dict(n_pad=n_pad, d=d, b=b, num_slots=s)
                    lbl = f"rung n_pad={n_pad} d={d} b={b} S={s}"
                    cases.append(AuditCase("momentum_dot_packed", lbl,
                                           dict(kw)))
                    cases.append(AuditCase("mwu_update_packed", lbl,
                                           dict(kw)))
    for k in dryrun_mesh_sizes:
        for shape in SADDLE_DSVC_SHAPES.values():
            cs = saddle_dsvc_client_shape(shape, k)
            kw = dict(n_pad=cs["n_pad"], d=cs["d"], b=cs["b"])
            lbl = (f"dryrun {shape.name} k={k} n_pad={cs['n_pad']} "
                   f"d={cs['d']} b={cs['b']}")
            cases.append(AuditCase("momentum_dot_packed", lbl, dict(kw)))
            cases.append(AuditCase("mwu_update_packed", lbl, dict(kw)))
    for n in (128, 1024, 16384):
        for d in (32, 256, 1024):
            tile_n = min(auto_tile_n(n, d), n)
            cases.append(AuditCase(
                "fwht", f"fwht n={n} d={d} tile_n={tile_n}",
                dict(n_pad=n, d=d, tile_n=tile_n)))
    return cases


def audit_all(cases: list[AuditCase] | None = None,
              ) -> tuple[list[dict], list[Finding]]:
    """Run the full sweep.  Returns (per-case records, findings)."""
    reg = registry()
    if cases is None:
        cases = audit_cases()
    records: list[dict] = []
    findings: list[Finding] = []
    for c in cases:
        prog = reg[c.kernel](**c.kwargs)
        fs = audit_program(prog, case=c.case)
        findings.extend(fs)
        records.append({
            "kernel": c.kernel, "case": c.case,
            "grid": list(prog["grid"]),
            "idx_variants": len(_idx_variants(prog)),
            "findings": len(fs),
        })
    return records, findings
