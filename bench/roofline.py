"""The least work of a solver step, and the chip's peaks.

The paper's block step (Algorithm 2 with the nu projection) at n points
and a block of B coordinates has to read the sampled (B, n) block of the
points twice: once for the momentum product ``delta = X_B lam_mom`` and,
after the w update that needs all of delta, once for the dual update
``dv = dw X_B``.  A (B, n) block of a large fit does not fit in on-chip
memory, so neither read can be saved.  Beside the block, the step reads
and writes per-point vectors of 4-byte values:

* momentum pass: read lam, lam_prev and the class signs (3 vectors);
* dual (MWU) pass: read lam, u and the signs, write the new lam and u
  (5 vectors);
* projection onto the capped simplex: read and write lam once (2).

and gathers and scatters B entries of w.  Padding points are not work:
``n`` is the true point count, so a smaller pad shows as a higher share.
"""

from __future__ import annotations

import json
import os

VECTOR_PASSES = 3 + 5 + 2         # per-point 4-byte vectors per step
VECTOR_BYTES = 4

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def step_bytes(n: int, d: int, block: int, steps: int,
               itemsize: int) -> int:
    """Least HBM bytes of ``steps`` block steps at n points, d
    coordinates, block size ``block``, with the points held in
    ``itemsize``-byte values."""
    if not 1 <= block <= d:
        raise ValueError(f"block {block} outside [1, d={d}]")
    per_step = (2 * block * n * itemsize
                + VECTOR_PASSES * n * VECTOR_BYTES
                + 2 * block * VECTOR_BYTES)
    return steps * per_step


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown
    kind is an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return table[device_kind]
