"""The whole run after the look for a chip, with the timed path broken
underneath: ``correct`` has to come out false for each fault a cell can
have.  A one-chip cell has no exchange between chips, so that fault has
no place here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests import tiny

CELLS = ["solo_nu_1m", "libsvm_steady", "libsvm_overload",
         "mesh_points_1m_x8"]


def _state_unchanged(monkeypatch):
    """Every solver step returns the state it was given (t still counts,
    so budgets and stop rules run as before)."""
    from repro.core import engine
    monkeypatch.setattr(engine, "_step_packed_core",
                        lambda state, *a, **k: state._replace(t=state.t + 1))
    jax.clear_caches()


def _half_left_out(monkeypatch):
    """The second half of each class is packed as padding: the solve runs
    on the other half, its normalizers the mean over the rest."""
    from repro.core import preprocess as pp
    pack = pp._pack

    def half(xp, xm, n_pad):
        x_t, sign = pack(xp, xm, n_pad)
        n1, n2 = xp.shape[0], xm.shape[0]
        keep = np.ones(n_pad, np.float32)
        keep[n1 // 2:n1] = 0.0
        keep[n1 + n2 // 2:n1 + n2] = 0.0
        return x_t, sign * keep
    monkeypatch.setattr(pp, "_pack", half)
    jax.clear_caches()


def _answer_altered(monkeypatch):
    """The hyperplane comes back with its sign flipped where it is
    produced (the recovery of w from the duals)."""
    from repro.core import svm
    recover = svm.recover_hyperplane

    def flipped(*a, **k):
        w, b, obj, margin, w_t = recover(*a, **k)
        return -w, -b, obj, margin, w_t
    monkeypatch.setattr(svm, "recover_hyperplane", flipped)


def _objective_fitted(monkeypatch):
    """A wrong direction comes back with the objective and offset that
    make its duality gap read 0: ``P = (h_P - h_Q) / 2`` and ``b`` the
    midpoint, worked out on the program's own points."""
    from repro.core import preprocess as pp
    from repro.core import svm

    from bench import load, reference
    recover = svm.recover_hyperplane

    def fitted(pre, eta, xi, xp_t, xm_t):
        w, b, obj, margin, w_t = recover(pre, eta, xi, xp_t, xm_t)
        w_t = np.asarray(w_t, np.float64)
        turn = np.roll(w_t, 1)
        w_t = w_t + 0.5 * np.linalg.norm(w_t) * turn / np.linalg.norm(turn)
        n1, n2 = xp_t.shape[0], xm_t.shape[0]
        nu = load.nu_of(0.8, n1, n2)
        h_p = reference.capped_min(np.asarray(xp_t, np.float64) @ w_t, nu)
        h_q = -reference.capped_min(-(np.asarray(xm_t, np.float64) @ w_t),
                                    nu)
        w = np.asarray(pp.recover_direction(
            jnp.asarray(w_t[:pre.signs.shape[0]], jnp.float32), pre))
        return w, 0.5 * (h_p + h_q), 0.5 * (h_p - h_q), margin, w_t
    monkeypatch.setattr(svm, "recover_hyperplane", fitted)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_left_out": _half_left_out,
          "answer_altered": _answer_altered,
          "objective_fitted": _objective_fitted}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_makes_the_run_incorrect(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    try:
        res = tiny.run(cell)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not res["correct"], res["checks"]
    # it is the reference that catches it, on answers that did come
    gap = res["checks"]["gap_max"]
    assert res["attempted"] > 0 and gap["value"] > gap["limit"]
