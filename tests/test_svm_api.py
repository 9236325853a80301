"""High-level SaddleSVC / SaddleNuSVC behaviour (fit/predict/b offset)."""

import jax
import numpy as np
import pytest

from repro.core import engine
from repro.core.svm import SaddleNuSVC, SaddleSVC, split_classes
from repro.data import synthetic
from repro.kernels import resolve_use_kernels


def test_hard_margin_separable(blobs_separable):
    ds = blobs_separable
    clf = SaddleSVC(eps=1e-3, beta=0.1, num_iters=8000).fit(ds.x, ds.y)
    assert clf.score(ds.x, ds.y) >= 0.99
    assert clf.margin_ > 0


def test_offset_bisects_closest_points(blobs_separable):
    """Footnote 2: b = w.(A eta + B xi)/2 -- the decision boundary sits
    midway between the two closest (weighted) hull points."""
    ds = blobs_separable
    clf = SaddleSVC(eps=1e-3, beta=0.1, num_iters=8000).fit(ds.x, ds.y)
    xp = ds.x[ds.y > 0]
    xm = ds.x[ds.y < 0]
    p_near = clf.eta_ @ xp
    q_near = clf.xi_ @ xm
    fp = p_near @ clf.w_ - clf.b_
    fm = q_near @ clf.w_ - clf.b_
    np.testing.assert_allclose(fp, -fm, rtol=0.05, atol=1e-4)
    assert fp > 0 > fm


def test_nu_svm_overlapping(blobs_overlapping):
    ds = blobs_overlapping
    clf = SaddleNuSVC(alpha=0.85, eps=1e-3, beta=0.1,
                      num_iters=6000).fit(ds.x, ds.y)
    # gap=0.4/spread=0.5 blobs overlap heavily; Bayes accuracy ~0.78
    assert clf.score(ds.x, ds.y) >= 0.7
    nu = 1.0 / (0.85 * min((ds.y > 0).sum(), (ds.y < 0).sum()))
    assert clf.eta_.max() <= nu + 1e-5


def test_generalization(blobs_separable):
    tr, te = blobs_separable.split(test_frac=0.25, seed=3)
    clf = SaddleSVC(eps=1e-3, beta=0.1, num_iters=6000).fit(tr.x, tr.y)
    assert clf.score(te.x, te.y) >= 0.95


def test_explicit_nu():
    from repro.data import synthetic
    ds = synthetic.blobs(30, 30, 8, gap=0.5, spread=0.4, seed=7)
    clf = SaddleNuSVC(nu=0.1, num_iters=3000).fit(ds.x, ds.y)
    assert clf.eta_.max() <= 0.1 + 1e-5


def test_single_class_y_fails_fast():
    """A single-class y must raise a clear ValueError up front, not a
    shape blow-up inside pack_points."""
    x = np.random.default_rng(0).normal(size=(20, 4)).astype(np.float32)
    with pytest.raises(ValueError, match="both classes"):
        split_classes(x, np.ones(20))
    with pytest.raises(ValueError, match="both classes"):
        SaddleSVC(num_iters=10).fit(x, -np.ones(20))


def test_use_kernels_plumbed_through_fit(blobs_separable):
    """fit(use_kernels=True) must reach the Pallas backend and agree
    with the jnp backend (the engines are parity-tested; here we pin
    that the FRONT END actually forwards the flag)."""
    ds = blobs_separable
    a = SaddleSVC(num_iters=400, seed=3).fit(ds.x, ds.y)
    b = SaddleSVC(num_iters=400, seed=3, use_kernels=True).fit(ds.x, ds.y)
    np.testing.assert_allclose(a.w_, b.w_, atol=1e-5)
    np.testing.assert_allclose(a.b_, b.b_, atol=1e-5)


@pytest.mark.parametrize("platform,want", [("cpu", False), ("gpu", False),
                                           ("tpu", True)])
def test_use_kernels_default_follows_platform(monkeypatch, platform, want):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert resolve_use_kernels(None) is want


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("flag", [False, True])
def test_explicit_use_kernels_wins(monkeypatch, platform, flag):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert resolve_use_kernels(flag) is flag


class _Dispatched(Exception):
    pass


@pytest.mark.parametrize("platform,flag,backend", [
    ("tpu", None, "pallas"), ("cpu", None, "jnp"),
    ("tpu", False, "jnp"), ("cpu", True, "pallas")])
def test_fit_dispatches_resolved_backend(monkeypatch, platform, flag,
                                         backend):
    """The fit hands ``run_solve_slots`` the backend that the platform
    rule (or the explicit flag) chose; the solve stops at that call."""
    seen = []

    def run_solve_slots(*args, **kw):
        seen.append(kw["backend"])
        raise _Dispatched

    monkeypatch.setattr(engine, "run_solve_slots", run_solve_slots)
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    ds = synthetic.blobs(20, 24, 8, gap=0.5, spread=0.4, seed=5)
    with pytest.raises(_Dispatched):
        SaddleNuSVC(num_iters=64, use_kernels=flag).fit(ds.x, ds.y)
    assert seen == [backend]


def test_default_fit_matches_jnp_off_the_chip():
    """Off a TPU the default backend is jnp: a default-constructed fit
    is the ``use_kernels=False`` fit, bit for bit."""
    assert jax.default_backend() != "tpu"
    ds = synthetic.blobs(40, 36, 8, gap=0.4, spread=0.5, seed=11)
    kw = dict(alpha=0.85, num_iters=512, block_size=4, seed=2)
    a = SaddleNuSVC(**kw).fit(ds.x, ds.y)
    b = SaddleNuSVC(use_kernels=False, **kw).fit(ds.x, ds.y)
    np.testing.assert_array_equal(a.w_, b.w_)
    assert a.b_ == b.b_
    assert a.history_ == b.history_
    np.testing.assert_array_equal(a.eta_, b.eta_)
