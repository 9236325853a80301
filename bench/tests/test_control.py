"""The control: the plain reference put in the program's place and
computed in bfloat16 fails the cell's limit, while in float32 it meets
it, as the program does.  At sizes a CPU test holds, with the cells'
widths and request settings: the solo configuration and the meshed one
(the same problem and solver settings) at 2^13 + 2^13 points x 256
(B = 128), the service configuration at a small
phishing-like problem (B = 1, the gap stop at 0.05).  The chip readings
at the cells' own sizes are in PERF.md."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, load, reference

BENCH = os.path.join(os.path.dirname(__file__), "..")
CASES = {  # config, cell whose limit applies, class sizes and d
    "solo": ("dense_1m_nu", "solo_nu_1m", 1 << 13, 1 << 13, 256),
    "service": ("libsvm_tenants", "libsvm_steady", 300, 240, 12),
    # the meshed cell solves the solo cell's problem; its control is the
    # same single-device reference
    "mesh": ("dense_1m_nu_mesh4", "mesh_points_1m_x8", 1 << 13, 1 << 13,
             256),
}


def _case(name):
    cfg_name, cell, n1, n2, d = CASES[name]
    with open(os.path.join(BENCH, "configs", f"{cfg_name}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "limits", f"{cell}.json")) as f:
        limit = json.load(f)["gap"]
    x, y = data.problem(2 ** 33 + 7, n1, n2, d)
    return cfg, limit, (x, y, load.nu_of(cfg["alpha"], n1, n2))


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    return _case(request.param)


@pytest.mark.parametrize("dtype,fails", [("bfloat16", True),
                                         ("float32", False)])
def test_reference_against_the_limit(case, dtype, fails):
    cfg, limit, (x, y, nu) = case
    w, b, obj, _ = reference.solve(
        x, y, nu, eps=cfg["eps"], beta=cfg["beta"],
        block=cfg["block_size"], seed=11, dtype=getattr(jnp, dtype),
        gap_tol=cfg.get("gap_tol", 0.0))
    gap = reference.certificate(x, y, w, b, obj, nu)
    assert (gap > limit) == fails, (dtype, gap, limit)


def test_program_meets_the_limit():
    from repro.core.svm import SaddleNuSVC
    cfg, limit, (x, y, nu) = _case("solo")
    m = SaddleNuSVC(alpha=cfg["alpha"], block_size=cfg["block_size"],
                    seed=3).fit(x, y)
    assert reference.certificate(x, y, m.w_, m.b_, m.objective_, nu) \
        <= limit


def test_certificate_is_zero_only_at_the_optimum():
    cfg, _, (x, y, nu) = _case("solo")
    w, b, obj, _ = reference.solve(x, y, nu, eps=cfg["eps"],
                                   beta=cfg["beta"], block=128, seed=11)
    g = reference.certificate(x, y, w, b, obj, nu)
    assert reference.certificate(x, y, w * 1.01, b, obj, nu) > g
    assert reference.certificate(x, y, w, b, obj * 1.01, nu) > g
    assert reference.certificate(x, y, w, b + 0.01 * obj, obj, nu) > g
    assert reference.certificate(x, y, -w, -b, obj, nu) == float("inf")


def test_objective_is_held_to_the_direction():
    """An answer that states the objective its own support values ask
    for reads a gap of 0; the length term still catches it."""
    cfg, limit, (x, y, nu) = _case("solo")
    w, b, obj, _ = reference.solve(x, y, nu, eps=cfg["eps"],
                                   beta=cfg["beta"], block=128, seed=11)
    s = reference.scores(x, w)
    scale = reference.unit_scale(x)
    w_sq = float(w @ w)
    gap, off, length = reference.judge_parts(s, y, b, obj, nu, w_sq, scale)
    assert length < 1e-4 < limit           # a sound answer: rounding
    # a wrong direction with the objective and offset that fit it
    turned = w + 0.5 * np.linalg.norm(w) * np.roll(w, 1) / np.linalg.norm(w)
    s2 = reference.scores(x, turned)
    h_p = reference.capped_min(s2[y > 0], nu)
    h_q = -reference.capped_min(-s2[y < 0], nu)
    parts = reference.judge_parts(s2, y, 0.5 * (h_p + h_q),
                                  0.5 * (h_p - h_q), nu,
                                  float(turned @ turned), scale)
    assert parts[0] < 1e-12 and parts[1] < 1e-12
    assert parts[2] > limit
