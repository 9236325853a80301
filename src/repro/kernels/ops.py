"""Public wrappers around the Pallas kernels.

``interpret=None`` (the default everywhere) resolves through
:func:`repro.kernels.default_interpret`: real compiled kernels when
``jax.default_backend() == "tpu"``, the Pallas interpreter otherwise
(a CPU-only host).  The resolution happens OUTSIDE the jitted kernel
impls, so the static ``interpret`` cache key is always a concrete bool.
The TPU block discipline the compiled path relies on is checked by
``repro.analysis.pallas_audit`` over the same program builders the
launches use, and by compiling for a described v5e in
``tests/test_tpu_compile.py``.

``launch_counts`` tallies pallas_call launches per wrapper at TRACE
time (one wrapper call == one kernel launch in the compiled step).
``benchmarks/kernels_bench.py`` uses it to assert the packed engine's
4 -> 2 launches-per-step reduction.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from repro.kernels import fwht as _fwht
from repro.kernels import saddle_update as _su
from repro.kernels import ref as ref  # noqa: F401  (re-exported oracle)

launch_counts: collections.Counter = collections.Counter()


def fwht(x: jax.Array, *, normalize: bool = True,
         interpret: bool | None = None) -> jax.Array:
    """Tiled Walsh--Hadamard transform (rows of (n, d), d a power of 2)."""
    launch_counts["fwht"] += 1
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None, :]
    out = _fwht.fwht_pallas(x, normalize=normalize, interpret=interpret)
    return out[0] if squeeze else out


def momentum_dot(cols, log_lam, log_prev, theta, *, interpret=None):
    launch_counts["momentum_dot"] += 1
    return _su.momentum_dot(cols, log_lam, log_prev, theta,
                            interpret=interpret)


def mwu_update(cols, log_lam, u, dw, *, sign, gamma, tau, d_eff,
               interpret=None, normalize=True):
    """Fused dual update; ``normalize=False`` returns the unnormalized
    log weights plus (m, s) normalizer partials with lse = m + log(s)
    (used by the solver engine to all-reduce across clients)."""
    launch_counts["mwu_update"] += 1
    return _su.mwu_update(cols, log_lam, u, dw,
                          jnp.asarray(sign), jnp.asarray(gamma),
                          jnp.asarray(tau), jnp.asarray(d_eff),
                          interpret=interpret, normalize=normalize)


def momentum_dot_packed(x_t, idx, log_lam, log_prev, sign, theta, *,
                        interpret=None):
    """Single-sweep signed momentum dot over the packed operand; the
    coordinate block is gathered from the column-major mirror (or its
    row-tile view) inside the kernel (scalar-prefetched indices)."""
    launch_counts["momentum_dot_packed"] += 1
    return _su.momentum_dot_packed(x_t, idx, log_lam, log_prev, sign,
                                   theta, interpret=interpret)


def mwu_update_packed(x_t, idx, log_lam, u, dw, sign, *, gamma, tau,
                      d_eff, interpret=None):
    """Single-sweep packed dual update.  Returns (log_new_unnormalized,
    u_new, m_p, s_p, m_m, s_m) with per-class lse = m + log(s)."""
    launch_counts["mwu_update_packed"] += 1
    return _su.mwu_update_packed(x_t, idx, log_lam, u, dw, sign,
                                 jnp.asarray(gamma), jnp.asarray(tau),
                                 jnp.asarray(d_eff), interpret=interpret)
