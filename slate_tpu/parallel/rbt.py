"""Distributed random-butterfly solver: gerbt + nopiv LU + IR over the mesh.

Reference analogue: ``src/gesv_rbt.cc:94-172`` — the grid driver that applies
a depth-d two-sided random butterfly transform (``src/gerbt.cc``: pairwise
tile exchanges between ranks), factors the transformed matrix *without
pivoting* (``src/getrf_nopiv.cc``), and refines in working precision.  This
was the last LU-family variant without a mesh path (VERDICT r3 #9).

TPU re-design:

* **Butterfly applies are elementwise mixes** of index pairs (i, i+h) with
  power-of-two strides.  On the sharded matrix the reshape/mix runs under
  GSPMD: the partner exchange the reference codes as explicit MPI tile swaps
  (gerbt.cc) is exactly what the compiler inserts for the sharded reshape —
  pairwise exchanges along the mesh axes, O(depth · n²/P) bytes moved.  The
  transform is a one-time O(depth·n²) cost next to the O(n³/P) factor.
* **Nopiv LU is the tournament pipeline minus the tournament**: same
  panel-psum / row-band-psum / masked trailing-gemm structure as
  ``_getrf_dist_fn`` (lu_dist.py) with the pivot machinery deleted — the
  point of RBT is that the transform makes pivoting statistically
  unnecessary.  Collectives per panel drop from 4 to 3 (no candidate
  all-gather), the swap gathers disappear entirely.
* **Refinement** reuses the shared distributed IR loop
  (``solvers._ir_refine_distributed``): one ``lax.while_loop``, one host
  sync per solve, sharded full-precision fallback on stall — the same
  policy as gesv_mixed (gesv_rbt.cc's refinement + fallback contract).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.exceptions import slate_assert
from .distribute import ceil_mult, lcm as _lcm
from .mesh import COL_AXIS, ProcessGrid, ROW_AXIS
from ..obs import instrument


@lru_cache(maxsize=32)
def _getrf_nopiv_dist_fn(mesh, npad: int, nb: int, dtype_str: str):
    """Jitted shard_map no-pivot LU over an npad×npad matrix (the
    _getrf_dist_fn pipeline with the tournament/swap machinery removed)."""
    from ..linalg.lu import _lu_nopiv_blocked

    p, q = mesh.shape[ROW_AXIS], mesh.shape[COL_AXIS]
    mr, mc = npad // p, npad // q
    nt = npad // nb
    assert mr % nb == 0 and mc % nb == 0

    def local_fn(A_loc):
        from .lu_dist import _lu_diag_info, _panel_tail

        pi = lax.axis_index(ROW_AXIS)
        qi = lax.axis_index(COL_AXIS)
        grow = pi * mr + jnp.arange(mr, dtype=jnp.int32)
        gcol = qi * mc + jnp.arange(mc, dtype=jnp.int32)

        def step(k, A_loc):
            k0 = (k * nb).astype(jnp.int32) if hasattr(k, "astype") else k * nb
            # panel columns [k0, k0+nb): owner mesh column psum (listBcast)
            qo = k0 // mc
            off = k0 - qo * mc
            pan = lax.dynamic_slice(A_loc, (jnp.int32(0), off), (mr, nb))
            pan = jnp.where(qi == qo, pan, jnp.zeros_like(pan))
            pan = lax.psum(pan, COL_AXIS)

            # diagonal block: nopiv blocked factor, replicated via psum —
            # the tournament + row exchange of the pivoted pipeline are the
            # only pieces missing here
            po = k0 // mr
            roff = k0 - po * mr
            blk = lax.dynamic_slice(pan, (roff, jnp.int32(0)), (nb, nb))
            blk = jnp.where(pi == po, blk, jnp.zeros_like(blk))
            blk = lax.psum(blk, ROW_AXIS)
            LUkk = _lu_nopiv_blocked(blk)

            # shared post-factor pipeline (lu_dist._panel_tail: panel L,
            # packed write, U row band, trailing gemm)
            return _panel_tail(A_loc, pan, LUkk, k0, grow, gcol, pi, qi,
                               mr, mc, nb)

        A_loc = lax.fori_loop(0, nt, step, A_loc)
        # info: first bad U diagonal (nopiv breakdown signal —
        # getrf_nopiv.cc reports the failing pivot instead of repairing it)
        return A_loc, _lu_diag_info(A_loc, grow, gcol, npad)

    spec = P(ROW_AXIS, COL_AXIS)
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=spec,
                       out_specs=(spec, P()), check_vma=False)
    return jax.jit(fn)


@instrument
def getrf_nopiv_distributed(A: jax.Array, grid: ProcessGrid, nb: int = 256,
                            trim: bool = True):
    """Distributed LU without pivoting (src/getrf_nopiv.cc over the grid).

    Returns ``(LU, info)``; info = 1-based index of the first zero U diagonal
    (breakdown), 0 on success.  Identity-tail padding to shard boundaries;
    ``trim=False`` returns the factor at its padded size (the tail is a
    factored identity) so repeated solves avoid re-padding per call.
    """
    n = A.shape[-1]
    slate_assert(A.ndim == 2 and A.shape[0] == n,
                 "getrf_nopiv_distributed expects a square matrix")
    from .solvers import _pad_spd

    nb = max(1, min(nb, n))
    unit = nb * _lcm(grid.p, grid.q)
    Ap, _ = _pad_spd(A, unit)       # identity tail: shared pad-and-mask policy
    npad = Ap.shape[-1]
    Ap = jax.device_put(Ap, grid.spec())
    LU, info = _getrf_nopiv_dist_fn(grid.mesh, npad, min(nb, npad),
                                    str(Ap.dtype))(Ap)
    info = jnp.where(info > n, jnp.int32(0), info)  # pad diag is never 0
    return (LU[:n, :n] if trim else LU), info


@lru_cache(maxsize=1)
def _transform_jit():
    from ..linalg.lu import _butterfly_apply

    def transform(x, wu, wv):
        y = _butterfly_apply(wu, x, transpose=True)
        return _butterfly_apply(wv, y.T, transpose=True).T

    return jax.jit(transform)


@instrument
def gesv_rbt_distributed(A, B, grid: ProcessGrid, depth: int = 2,
                         nb: int = 256, key=None, max_iterations: int = 30,
                         use_fallback: bool = True, tol=None):
    """Distributed solve via random butterfly transform + nopiv LU +
    refinement (src/gesv_rbt.cc:94-172 over the mesh).

    Returns ``(X, info, iters, via_rbt)`` with the gesv_rbt contract: info
    from the nopiv factor, iters from the IR loop; on IR stall (the
    transform failed to tame a pathological matrix) the sharded pivoted
    solve takes over, matching Option::UseFallbackSolver (gesv_rbt.cc
    fallback path), and ``via_rbt`` is False so callers can report which
    rung actually produced the result.
    """
    from ..linalg.lu import _butterfly_apply, rbt_generate
    from .lu_dist import gesv_distributed
    from .solvers import _ir_refine_distributed, _trsm_dist_fn

    a = jnp.asarray(A)
    b = jnp.asarray(B)
    n = a.shape[-1]
    vec = b.ndim == 1
    b2 = b[:, None] if vec else b
    key = key if key is not None else jax.random.PRNGKey(42)
    ku, kv = jax.random.split(key)
    from .solvers import _pad_spd

    np_ = ceil_mult(n, 2 ** depth)
    Wu = rbt_generate(ku, np_, depth, a.dtype)
    Wv = rbt_generate(kv, np_, depth, a.dtype)
    ap, _ = _pad_spd(a, np_ if n < np_ else 1)   # identity tail to np_
    ap = jax.device_put(ap, grid.spec())

    # two-sided transform U^T A V under GSPMD: the level mixes lower to the
    # pairwise shard exchanges the reference's gerbt.cc posts as MPI swaps
    at = _transform_jit()(ap, Wu, Wv)
    # keep the factor at its padded size: the L/U triangles are device_put
    # ONCE, and the per-iteration solves reuse the cached sharded trsm
    # programs directly — no re-pad / re-place inside the IR loop body
    LUp, info = getrf_nopiv_distributed(at, grid, nb=nb, trim=False)
    npad2 = LUp.shape[-1]
    L = jax.device_put(jnp.tril(LUp, -1) + jnp.eye(npad2, dtype=LUp.dtype),
                       grid.spec())
    U = jax.device_put(jnp.triu(LUp), grid.spec())
    solveL = _trsm_dist_fn(grid.mesh, True, False, str(LUp.dtype))
    solveU = _trsm_dist_fn(grid.mesh, False, False, str(LUp.dtype))
    nrhs = b2.shape[-1]
    cpad = ceil_mult(max(nrhs, 1), grid.q)

    def solve_lo(R):                      # R: (n, nrhs) working precision
        rp = jnp.pad(R, ((0, np_ - n), (0, cpad - nrhs)))
        y = _butterfly_apply(Wu, rp, transpose=True)
        y = jnp.pad(y, ((0, npad2 - np_), (0, 0)))  # identity tail: zeros
        z = solveL(L, y)
        w = solveU(U, z)
        x = _butterfly_apply(Wv, w[:np_], transpose=False)
        return x[:n, :nrhs]

    X, iters, ok = _ir_refine_distributed(a, b2, solve_lo, grid,
                                          max_iterations, tol=tol)
    via_rbt = bool(ok)                    # the solve's single host sync
    if use_fallback and not via_rbt:
        # rbt→partialpiv ladder (robust.LADDERS["gesv_rbt_distributed"])
        from ..utils.trace import trace_event

        trace_event("fallback", routine="gesv_rbt_distributed",
                    to="partialpiv")
        X, info = gesv_distributed(a, b2, grid, nb=nb)
    return (X[:, 0] if vec else X), info, iters, via_rbt
