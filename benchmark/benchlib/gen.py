"""Inputs made from the seed: dense SPD systems on the device.

The same seed gives the same inputs on every run.  What a traffic file
sets (sizes, pool lengths, the spectrum's shift) is read here, so that a
new mix is a new data file and no new code.
"""

from __future__ import annotations

import numpy as np


def spd_shift(n: int, radii: float) -> float:
    """Diagonal shift that makes ``(R + R^T)/2`` SPD for R standard normal:
    the symmetric part's spectrum fills a semicircle of radius sqrt(2n), so
    a shift of ``radii`` > 1 radii puts every eigenvalue in
    [radii - 1, radii + 1] radii (condition number (radii+1)/(radii-1))."""
    return radii * np.sqrt(2.0 * n)


def dense_pool_fn(n: int, nrhs: int, matrices: int, rhs_blocks: int,
                  dtype, radii: float):
    """A function of a PRNG key returning ``(As, Bs)``: a tuple of
    ``matrices`` SPD ``(n, n)`` matrices and a tuple of ``rhs_blocks``
    ``(n, nrhs)`` right-hand sides, as separate arrays so that the window
    indexes a tuple and runs no slicing program; meant to run as one jitted
    call on the device."""
    import jax
    import jax.numpy as jnp

    def make(key):
        keys = jax.random.split(key, matrices + 1)
        shift = jnp.asarray(spd_shift(n, radii), dtype) * jnp.eye(n, dtype=dtype)
        As = []
        for k in keys[:matrices]:
            r = jax.random.normal(k, (n, n), dtype)
            As.append((r + r.T) * jnp.asarray(0.5, dtype) + shift)
        b = jax.random.normal(keys[-1], (rhs_blocks, n, nrhs), dtype)
        return tuple(As), tuple(b[i] for i in range(rhs_blocks))

    return make


def device_key(seed: int, salt: int):
    import jax

    return jax.random.fold_in(jax.random.key(seed), salt)
