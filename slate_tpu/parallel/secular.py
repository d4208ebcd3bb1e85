"""Distributed secular-equation solve for the D&C merges.

Reference analogue: ``src/stedc_secular.cc`` — the reference splits the
secular roots of one merge across MPI ranks (each rank runs laed4 on its
share and the eigenvalues are allgathered).

TPU re-design: the merge's bisection (linalg/stedc.py ``_secular_bisect``)
is already *vectorized over brackets* with no cross-bracket dependencies —
each root j needs the full pole set (d, z2: O(m), replicated) but touches
only its own (pole_j, gap_j) state.  Sharding is therefore a pure
``shard_map`` over the bracket axis of the flattened (p × q) mesh: per-device
work drops from O(m²·iters) to O(m²·iters / P), and **no collectives run at
all** — the out-sharding re-assembles the root vector lazily, and the
consumer (the Loewner build + basis gemms) reads it under GSPMD.  This was
the last replicated O(m²) stage of the distributed stedc (VERDICT r3 #6).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mesh import COL_AXIS, ProcessGrid, ROW_AXIS
from ..obs import instrument

_FLAT = (ROW_AXIS, COL_AXIS)


@lru_cache(maxsize=32)
def _bisect_sharded_fn(mesh, m: int, m_pad: int, dtype_str: str):
    from ..linalg.stedc import _secular_bisect

    def fn(d, z2, rho, pole, sigma, gaps, use_lower):
        # one bracket chunk per device; d/z2 replicated (O(m) each)
        return _secular_bisect(d, z2, rho, pole, sigma, gaps, use_lower)

    rep = P(None)
    shard = P(_FLAT)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(rep, rep, P(), shard, shard, shard, shard),
        out_specs=(shard, shard, shard),
        check_vma=False))


@lru_cache(maxsize=1)
def _prep_jit():
    from ..linalg.stedc import _secular_prep

    return jax.jit(_secular_prep)


@instrument
def secular_roots_sharded(d, z2, rho, grid: ProcessGrid):
    """All m secular roots with the bisection sharded over the mesh.

    Same contract as ``linalg.stedc._secular_roots``: returns (t, s, lam).
    The prep (bracket widths + closer-pole selection, one f sweep) stays
    replicated — it is 1/_BISECT_ITERS of the work; the 90-sweep loop is
    what shards.
    """
    d = jnp.asarray(d)
    z2 = jnp.asarray(z2)
    rho = jnp.asarray(rho)
    m = d.shape[0]
    Pn = grid.size
    # the prep's f sweep MUST run jitted: eagerly, the (m, m) denominator
    # materializes as a real HBM buffer on every device — the exact memory
    # wall the fused form avoids at n=20,000 (see _secular_f)
    pole, sigma, gaps, use_lower = _prep_jit()(d, z2, rho)
    m_pad = -(-m // Pn) * Pn
    if m_pad != m:
        # padded brackets bisect against a pole far above the spectrum: every
        # denominator stays bounded away from zero and the results are sliced
        # off below
        pad = m_pad - m
        far = d[-1] + gaps[-1] + 1.0
        pole = jnp.concatenate([pole, jnp.full((pad,), far, d.dtype)])
        sigma = jnp.concatenate([sigma, jnp.ones((pad,), d.dtype)])
        gaps = jnp.concatenate([gaps, jnp.ones((pad,), d.dtype)])
        use_lower = jnp.concatenate(
            [use_lower, jnp.ones((pad,), use_lower.dtype)])
    t, s, lam = _bisect_sharded_fn(grid.mesh, m, m_pad, str(d.dtype))(
        d, z2, rho, pole, sigma, gaps, use_lower)
    return t[:m], s[:m], lam[:m]
