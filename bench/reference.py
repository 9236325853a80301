"""The benchmark's plain reference for a nu-SVM fit.

It imports nothing of the program.  Two parts:

* :func:`certificate` judges an answer by what it says.  A fit returns a
  hyperplane ``w`` (input space), an offset ``b`` and its objective
  ``P = ||w_t||^2 / 2`` (the squared distance between the two reduced
  convex hulls, halved, in the solver's working units; ``w . x ==
  w_t . x_t`` for every point).  For the nu-SVM the reduced hull of a
  class holds the weighted means with weights at most ``nu``, so
  ``h_P(w) = min_{eta} <w, A eta>`` is the water-filled mean of the
  smallest scores, ``h_Q(w)`` the same of the largest.  Weak duality
  gives ``D = h_P - h_Q - P <= P*`` (the Wolfe dual at w), so

      gap = |P - D| / P = |2 - (h_P - h_Q) / P|

  is 0 exactly at the optimum and grows with any error in the direction,
  the length or the objective.  The optimal offset lies midway between
  the two support values, ``b* = (h_P + h_Q) / 2``; ``offset`` is the
  distance of ``b`` from that midpoint.  The objective itself is held to
  the returned direction: the working space is the input space scaled
  into the unit ball (by ``1 / max ||x_i||``, which the reference takes
  from the data) and turned by an orthonormal transform, so
  ``||w||^2 = 2 P scale^2`` and an answer cannot state the ``P`` that
  would make a wrong direction's gap read 0.  All three are computed in
  float64 on the host from the benchmark's own data;
  :func:`judge_scores` says how they make the one compared number.

* :func:`solve` is a plain implementation of the paper's algorithm
  (Algorithm 1, then Algorithm 2 with the nu projection), used as the
  control: computed in bfloat16 in place of the program it has to fail
  the limit, and in float32 it shows that the limit is within reach.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 1 << 16          # rows per block of the float64 score pass


def scores(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """<w, x_i> for every row (w a vector or a (d, k) matrix of k
    answers), in float64, a block of rows at a time."""
    w = np.asarray(w, np.float64)
    return np.concatenate([np.asarray(x[i:i + ROWS], np.float64) @ w
                           for i in range(0, len(x), ROWS)])


def capped_min(s: np.ndarray, nu: float) -> float:
    """min over {0 <= eta_i <= nu, sum eta = 1} of <s, eta>: weight nu on
    each of the smallest scores until the mass is spent."""
    s = np.sort(s)
    k = int(np.floor(1.0 / nu + 1e-9))
    k = min(k, len(s))
    rest = 1.0 - k * nu
    val = nu * float(np.sum(s[:k]))
    if rest > 1e-12 and k < len(s):
        val += rest * float(s[k])
    return val


def unit_scale(x: np.ndarray) -> float:
    """1 / max_i ||x_i||, the scale into the unit ball (Algorithm 1)."""
    top = max(float(np.max(np.sum(np.asarray(x[i:i + ROWS], np.float64)
                                  ** 2, axis=1)))
              for i in range(0, len(x), ROWS))
    return 1.0 / np.sqrt(top)


def judge_scores(s: np.ndarray, y: np.ndarray, b: float,
                 objective: float, nu: float, w_sq: float,
                 scale: float) -> float:
    """The compared number of one answer: the largest of
    :func:`judge_parts`."""
    return max(judge_parts(s, y, b, objective, nu, w_sq, scale))


def judge_parts(s: np.ndarray, y: np.ndarray, b: float,
                objective: float, nu: float, w_sq: float,
                scale: float) -> tuple[float, float, float]:
    """The compared number of one answer, from its scores ``s = X w``,
    the squared length ``w_sq`` of its ``w`` and the data's
    :func:`unit_scale`.

    ``gap = |2 - m / P|`` with ``m = h_P - h_Q``; the offset's distance
    from the midpoint, ``|b - (h_P + h_Q) / 2|``, which for any answer
    that comes from dual weights is at most ``(2P - m) / 2``, enters as
    ``2 |b - mid| / P``: at most ``gap`` for a sound answer; and the
    objective's distance from half the direction's squared length in
    working units, ``|1 - w_sq / (2 P scale^2)|``, which is rounding for
    a sound answer.  The compared number is the largest of the three, so a
    wrong offset or a stated objective that does not belong to the
    direction shows even where the gap reads small.  Returns ``(gap, offset,
    length)``."""
    h_p = capped_min(s[y > 0], nu)
    h_q = -capped_min(-s[y < 0], nu)
    m = h_p - h_q
    p = float(objective)
    if not (np.isfinite(m) and np.isfinite(p) and np.isfinite(b)
            and np.isfinite(w_sq) and p > 0 and m > 0):
        return (float("inf"),) * 3
    gap = abs(2.0 - m / p)
    off = 2.0 * abs(float(b) - 0.5 * (h_p + h_q)) / p
    length = abs(1.0 - float(w_sq) / (2.0 * p * float(scale) ** 2))
    return gap, off, length


def certificate(x: np.ndarray, y: np.ndarray, w: np.ndarray, b: float,
                objective: float, nu: float) -> float:
    """:func:`judge_scores` of one answer ``(w, b, objective)``."""
    w = np.asarray(w, np.float64)
    return judge_scores(scores(x, w), np.asarray(y), b, objective, nu,
                        float(w @ w), unit_scale(x))


# ------------------------------------------------------------ the control
def _hadamard(d: int) -> np.ndarray:
    """The normalized d x d Walsh--Hadamard matrix (Sylvester)."""
    h = np.ones((1, 1))
    while h.shape[0] < d:
        h = np.block([[h, h], [h, -h]])
    return h / np.sqrt(d)


def _kl_cap(log_l, nu):
    """KL projection of the weights exp(log_l) onto {0 <= e <= nu,
    sum e = 1}: e_i = min(nu, c l_i) with the smallest cap set, found on
    the weights sorted in descending order."""
    l = jnp.exp(log_l - jnp.max(log_l))
    s = -jnp.sort(-l)
    tail = jnp.cumsum(s[::-1])[::-1]                # sum_{i >= k} s_i
    k = jnp.arange(s.shape[0], dtype=l.dtype)
    c = (1 - k * nu) / tail
    ok = (c * s <= nu) & (1 - k * nu > 0)
    c = c[jnp.argmax(ok)]
    e = jnp.minimum(nu, c * l)
    return jnp.log(jnp.maximum(e, jnp.finfo(l.dtype).tiny))


def _lse_norm(log_l):
    return log_l - jax.scipy.special.logsumexp(log_l)


def _support_min(sc, nu):
    """min over the capped simplex of <sc, e> (water-filling)."""
    s = jnp.sort(sc)
    wgt = jnp.clip(1 - jnp.arange(s.shape[0], dtype=s.dtype) * nu, 0, nu)
    return jnp.sum(s * wgt)


@functools.partial(jax.jit, static_argnames=(
    "block", "steps", "check_every", "dtype"))
def _saddle(p, q, key, nu, theta, sigma, tau, gamma, gap_tol, *,
            block: int, steps: int, check_every: int, dtype):
    """Algorithm 2 with the nu projection, written plainly on the two
    class matrices (rows are points)."""
    p, q = p.astype(dtype), q.astype(dtype)
    n1, n2, d = p.shape[0], q.shape[0], p.shape[1]
    cast = lambda v: jnp.asarray(v, dtype)          # noqa: E731
    nu, theta, sigma = cast(nu), cast(theta), cast(sigma)
    d_eff = d / block
    mwu_c = cast(1.0 / (gamma + d_eff / tau))
    mwu_dot = cast(d_eff / tau)
    le = jnp.full((n1,), -jnp.log(n1), dtype)
    lx = jnp.full((n2,), -jnp.log(n2), dtype)
    st = (jnp.zeros((d,), dtype), le, le, lx, lx,
          jnp.zeros((n1,), dtype), jnp.zeros((n2,), dtype))

    def step(i, st):
        w, le, le0, lx, lx0, up, uq = st
        idx = jax.random.permutation(jax.random.fold_in(key, i), d)[:block]
        pb, qb = p[:, idx], q[:, idx]
        me = jnp.exp(le) + theta * (jnp.exp(le) - jnp.exp(le0))
        mx = jnp.exp(lx) + theta * (jnp.exp(lx) - jnp.exp(lx0))
        delta = me @ pb - mx @ qb
        w_new = (w[idx] + sigma * delta) / (sigma + 1)
        dw = w_new - w[idx]
        dvp, dvq = pb @ dw, qb @ dw
        le_n = _lse_norm(mwu_c * (mwu_dot * le - (up + d_eff * dvp)))
        lx_n = _lse_norm(mwu_c * (mwu_dot * lx + (uq + d_eff * dvq)))
        return (w.at[idx].set(w_new), _kl_cap(le_n, nu), le,
                _kl_cap(lx_n, nu), lx, up + dvp, uq + dvq)

    def gap(st):
        w, le, _, lx, _, _, _ = st
        v = jnp.exp(le) @ p - jnp.exp(lx) @ q
        obj = 0.5 * jnp.dot(v, v)
        g = (_support_min(p @ w, nu) + _support_min(-(q @ w), nu)
             - 0.5 * jnp.dot(w, w))
        return obj - g <= gap_tol * obj

    def cond(c):
        t, st, done = c
        return (t < steps) & ~done

    def body(c):
        t, st, _ = c
        n = jnp.minimum(check_every, steps - t)
        st = jax.lax.fori_loop(t, t + n, step, st)
        return t + n, st, (gap_tol > 0) & gap(st)

    t, st, _ = jax.lax.while_loop(cond, body, (0, st, False))
    _, le, _, lx, _, _, _ = st
    a, c = jnp.exp(le) @ p, jnp.exp(lx) @ q
    v = a - c
    return (v.astype(jnp.float32), jnp.dot(v, a + c).astype(jnp.float32) / 2,
            (0.5 * jnp.dot(v, v)).astype(jnp.float32), t)


def solve(x: np.ndarray, y: np.ndarray, nu: float, *, eps: float,
          beta: float, block: int, seed: int, dtype=jnp.float32,
          num_iters: int | None = None, gap_tol: float = 0.0,
          check_every: int = 64):
    """(w, b, objective, steps) of the nu-SVM on (x, y) by the paper's
    Algorithm 1 (unit-ball scale, randomized Walsh--Hadamard transform)
    and Algorithm 2 with the nu projection, in ``dtype``: the reference
    put in the program's place.  ``w`` is in the input space, ``b`` and
    the objective in the solver's working units, as a fit returns them.
    The parameters are the paper's (Algorithm 1, line 4), the budget
    Theorem 6's unless given, the stop rule the relative duality gap of
    the primal iterate every ``check_every`` steps."""
    x = np.asarray(x, np.float64)
    y = np.asarray(y)
    n, d0 = x.shape
    d = 1 << max(d0 - 1, 0).bit_length()
    scale = 1.0 / np.max(np.linalg.norm(x, axis=1))
    rng = np.random.default_rng([int(seed) % (1 << 63), 3])
    signs = rng.choice([-1.0, 1.0], d)
    h = _hadamard(d)
    xt = np.zeros((n, d))
    xt[:, :d0] = x * scale
    xt = (xt * signs) @ h
    logn = np.log(max(n, 3))
    gamma = eps * beta / (2 * logn)
    qq = max(1.0, np.sqrt(logn))
    tau = 0.5 / qq * np.sqrt(d / gamma)
    sigma = 0.5 / qq * np.sqrt(d * gamma)
    theta = 1 - 1 / (d + qq * np.sqrt(d) / np.sqrt(gamma))
    if num_iters is None:
        num_iters = int(2 * (d + np.sqrt(2 * d / (eps * beta)) * logn))
    steps = max(1, num_iters // block)
    key = jax.random.key(int(rng.integers(1 << 31)))
    f32 = lambda a: jnp.asarray(a, jnp.float32)      # noqa: E731
    v, b, obj, t = _saddle(
        f32(xt[y > 0]), f32(xt[y < 0]), key, nu, theta, sigma, tau, gamma,
        gap_tol, block=block, steps=steps, check_every=check_every,
        dtype=dtype)
    v, b, obj, t = jax.device_get((v, b, obj, t))
    w = scale * signs * (h @ np.asarray(v, np.float64))
    return w[:d0], float(b), float(obj), int(t)
