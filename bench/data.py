"""Appendix-D data for the benchmark, made on the device from a seed.

A copy of the paper's non-separable generator (arXiv:1705.07252,
Appendix D): a random hyperplane H through the origin, points drawn
uniformly from the unit ball, labels by the side of H, and labels
flipped at random inside the band |<w, x>| < beta2 / 2.  The benchmark
keeps its own copy so that a change to the program's generator cannot
change the yardstick.

Each class gets an exact size (the first n1 points labelled +1 and the
first n2 labelled -1 of an oversampled draw), so every seed yields the
same shapes and the same compiled programs.  One jitted call per
problem; the arrays come back to the host as the numpy inputs a user
would hand to the entry point.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int, *salt: int) -> jax.Array:
    """A PRNG key from any whole number (a run's seed may exceed 32
    bits), folded with ``salt`` to give independent streams."""
    words = np.random.SeedSequence([int(seed) % (1 << 63), *salt])
    return jax.random.key(int(words.generate_state(1, np.uint32)[0]))


@functools.partial(jax.jit, static_argnames=("n1", "n2", "d", "beta2"))
def _draw(key, *, n1: int, n2: int, d: int, beta2: float):
    # both classes have probability ~1/2 under the symmetric ball, so an
    # oversampled draw of 2.3 * max(n1, n2) holds enough of each with
    # overwhelming probability (the shortfall is checked on the host)
    m = int(2.3 * max(n1, n2)) + 64
    k_w, k_x, k_r, k_f = jax.random.split(key, 4)
    w = jax.random.normal(k_w, (d,))
    w = w / jnp.linalg.norm(w)
    x = jax.random.normal(k_x, (m, d))
    x = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    x = x * jax.random.uniform(k_r, (m, 1)) ** (1.0 / d)
    signed = x @ w
    y = jnp.where(signed > 0, 1, -1)
    band = jnp.abs(signed) < beta2 * 0.5
    flips = jax.random.uniform(k_f, (m,)) < 0.5
    y = jnp.where(band & flips, -y, y)
    ip = jnp.nonzero(y > 0, size=n1, fill_value=-1)[0]
    im = jnp.nonzero(y < 0, size=n2, fill_value=-1)[0]
    ok = (ip[-1] >= 0) & (im[-1] >= 0)
    return jnp.concatenate([x[ip], x[im]]), ok


def problem(seed: int, n1: int, n2: int, d: int, *salt: int,
            beta2: float = 0.1):
    """(x, y): n1 points labelled +1 then n2 labelled -1, float32, on
    the host."""
    x, ok = _draw(key_of(seed, *salt), n1=n1, n2=n2, d=d, beta2=beta2)
    x, ok = jax.device_get((x, ok))
    if not ok:
        raise RuntimeError(f"class draw fell short for seed {seed}")
    y = np.concatenate([np.ones(n1, np.int64), -np.ones(n2, np.int64)])
    return np.asarray(x, np.float32), y
