"""Scikit-learn-style front end for Saddle-SVC.

``SaddleSVC``    -- hard-margin SVM (HM-Saddle).
``SaddleNuSVC``  -- nu-SVM (nu-Saddle).

Both run Algorithm 1 (pre-processing) + Algorithm 2 (the saddle solver)
and expose ``w_``, ``b_`` in the ORIGINAL input space.  The offset uses
the paper's footnote 2: b* = w*^T (A eta* + B xi*) / 2.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import preprocess as pp
from repro.core import saddle
from repro.utils.spans import span


def split_classes(x: np.ndarray, y: np.ndarray):
    """Split (x, y in {+-1}) into the P (+1) and Q (-1) point matrices.

    Fails fast on a single-class ``y``: the saddle problem is defined
    between TWO convex hulls, and an empty class would otherwise
    surface as an opaque shape error deep inside ``pack_points``.
    """
    x = np.asarray(x, np.float32)
    y = np.asarray(y)
    xp, xm = x[y > 0], x[y < 0]
    if len(xp) == 0 or len(xm) == 0:
        raise ValueError(
            "y must contain both classes (+1 and -1): got "
            f"{len(xp)} positive and {len(xm)} negative points "
            f"(labels seen: {np.unique(y).tolist()})")
    return xp, xm


def recover_hyperplane(pre: pp.Preprocessed, eta: jax.Array,
                       xi: jax.Array, xp_t: jax.Array, xm_t: jax.Array):
    """Map final dual weights to the input-space hyperplane.

    The shared recovery path of ``SaddleSVC.fit`` and the multi-tenant
    ``serve.solver_service``: the optimal direction is w = A eta - B xi
    in TRANSFORMED space, the offset is footnote 2's
    b = w.(A eta + B xi)/2, and the direction is mapped back through
    the orthonormal WD transform.  ``xp_t``/``xm_t`` may carry inert
    zero-padding columns beyond ``pre``'s dimensionality (bucketed
    solves); those coordinates of w are exactly 0 and are sliced off.

    Returns (w_orig, b, objective, margin, w_t).
    """
    a_eta = eta @ xp_t
    b_xi = xi @ xm_t
    w_t = a_eta - b_xi                     # optimal w = A eta - B xi
    b_t = jnp.dot(w_t, a_eta + b_xi) / 2.0
    w = np.asarray(pp.recover_direction(w_t[: pre.signs.shape[0]], pre))
    return (w, float(b_t), float(0.5 * jnp.sum(w_t * w_t)),
            float(jnp.linalg.norm(w_t)), w_t)


class SaddleSVC:
    """Hard-margin SVM via HM-Saddle (paper Sections 2-3).

    ``use_kernels`` picks the solver step's backend: True the Pallas
    kernels, False jax.numpy, None (the default) from the platform (the
    kernels on a TPU; see :func:`repro.kernels.resolve_use_kernels`).
    """

    nu = 0.0

    def __init__(self, eps: float = 1e-3, beta: float = 0.1,
                 num_iters: int | None = None, block_size: int = 1,
                 seed: int = 0, record_every: int | None = None,
                 use_kernels: bool | None = None):
        self.eps = eps
        self.beta = beta
        self.num_iters = num_iters
        self.block_size = block_size
        self.seed = seed
        self.record_every = record_every
        self.use_kernels = use_kernels

    def _nu_for(self, n1: int, n2: int) -> float:
        return 0.0

    def fit(self, x: np.ndarray, y: np.ndarray) -> "SaddleSVC":
        with span("svm.fit"):
            with span("svm.split"):
                xp, xm = split_classes(x, y)
            n1, n2 = len(xp), len(xm)
            key = jax.random.key(self.seed)
            k_pre, _ = jax.random.split(key)
            with span("svm.preprocess"):
                pre = pp.preprocess(xp, xm, k_pre)
            nu = self._nu_for(n1, n2)
            res = saddle.solve(
                pre.xp, pre.xm, eps=self.eps, beta=self.beta, nu=nu,
                num_iters=self.num_iters, block_size=self.block_size,
                seed=self.seed, record_every=self.record_every,
                use_kernels=self.use_kernels)
            st = res.state
            self.history_ = res.history
            # direction & offset in TRANSFORMED space, mapped back to
            # input space (recover_hyperplane folds the transform AND the
            # scale, so w_ . x == w_t . x_t pointwise and the threshold
            # carries over as-is)
            with span("svm.recover"):
                eta = jnp.exp(st.log_eta)
                xi = jnp.exp(st.log_xi)
                (self.w_, self.b_, self.objective_, self.margin_,
                 w_t) = recover_hyperplane(pre, eta, xi, pre.xp, pre.xm)
                self.eta_ = np.asarray(eta)
                self.xi_ = np.asarray(xi)
            self.state_ = st
        return self

    def decision_function(self, x: np.ndarray) -> np.ndarray:
        return np.asarray(x, np.float32) @ self.w_ - self.b_

    def predict(self, x: np.ndarray) -> np.ndarray:
        return np.where(self.decision_function(x) >= 0, 1, -1)

    def score(self, x: np.ndarray, y: np.ndarray) -> float:
        return float(np.mean(self.predict(x) == np.asarray(y)))


class SaddleNuSVC(SaddleSVC):
    """nu-SVM via nu-Saddle.  ``alpha`` parameterizes the paper's
    experiment convention nu = 1 / (alpha * min(n1, n2))."""

    def __init__(self, nu: float | None = None, alpha: float = 0.85,
                 **kw):
        super().__init__(**kw)
        self._nu = nu
        self.alpha = alpha

    def _nu_for(self, n1: int, n2: int) -> float:
        if self._nu is not None:
            return self._nu
        return 1.0 / (self.alpha * min(n1, n2))
