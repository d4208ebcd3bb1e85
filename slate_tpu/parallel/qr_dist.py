"""Distributed communication-avoiding QR over the process grid.

Reference analogues:

* ``src/geqrf.cc:146-253`` — CAQR: Householder panel (internal_geqrf.cc) +
  triangle-triangle tree reduction over mesh rows (internal_ttqrt.cc), trailing
  update via unmqr + ttmqr.
* ``src/internal/internal_ttqrt.cc`` — the pairwise R-triangle merge tree.
* ``src/unmqr.cc`` — apply Q by replaying panel + tree tasks.
* ``src/gels_qr.cc`` — least squares through the QR path.

TPU re-design (not a translation):

- **TSQR rides one all-gather.** The reference's ttqrt builds a log(p) pairwise
  tree because MPI messages are point-to-point; on TPU the ICI all-gather is a
  hardware-scheduled ring that delivers all p candidate R triangles in one
  collective, so each shard factors the stacked (p·nb × nb) matrix redundantly
  and keeps its own coupling block — replicated compute for O(p·nb²) flops in
  exchange for zero extra latency steps (the scaling-book trade: small
  redundant compute beats serial communication rounds).
- **Panel QR via block classical Gram-Schmidt with reorthogonalization
  (BCGS2)** instead of Householder-in-place: each panel is projected twice
  against the accumulated Q (two MXU gemm pairs + psums), then TSQR'd.  CGS2's
  "twice is enough" gives O(eps) orthogonality while every operation is a
  full-width static-shape gemm — the Householder V/T replay (unmqr.cc) would
  serialize k rank-nb updates through HBM for no TPU benefit.  Q is therefore
  *explicit* (the reference reconstructs it on demand via unmqr; here
  applying Q is one sharded gemm).
- **Fixed-shape pipeline**: one ``lax.fori_loop`` over panels, O(1) program
  size (same design as lu_dist.py).
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


# ---------------------------------------------------------------------------
# 1-D tall-skinny TSQR over the flattened mesh (ttqrt tree analogue)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=32)
def _tsqr_dist_fn(mesh, dtype_str: str):
    axes = (ROW_AXIS, COL_AXIS)
    world = mesh.devices.size

    def local(a):
        # leaf QR on my row shard (internal_geqrf panel analogue)
        q_leaf, r_leaf = lax.linalg.qr(a, full_matrices=False)
        # one-round tree: all-gather the p R-triangles, stacked QR everywhere
        Rs = lax.all_gather(r_leaf, axes, tiled=True)      # (world*n, n)
        q_stack, R = lax.linalg.qr(Rs, full_matrices=False)
        n = a.shape[-1]
        w = lax.axis_index(axes[0]) * mesh.shape[COL_AXIS] + lax.axis_index(axes[1])
        coupling = lax.dynamic_slice(
            q_stack, (w.astype(jnp.int32) * n, jnp.int32(0)), (n, n))
        Q = jnp.matmul(q_leaf, coupling, precision=lax.Precision.HIGHEST)
        return Q, R

    spec = P((ROW_AXIS, COL_AXIS), None)
    fn = jax.shard_map(local, mesh=mesh, in_specs=spec,
                       out_specs=(spec, P(None, None)), check_vma=False)
    return jax.jit(fn)


@instrument
def tsqr_distributed(A: jax.Array, grid: ProcessGrid):
    """Tall-skinny QR by tree reduction over the whole mesh (ttqrt analogue).

    A is 1-D row-sharded over all devices; returns ``(Q row-sharded, R
    replicated)`` with Q explicit reduced m×n.  Unconditionally stable
    (Householder leaves + Householder merge), unlike the Gram-based CholQR —
    this is the reference's MethodCholQR-vs-QR distinction (gels.cc dispatch).
    """
    from .distribute import pad2d

    m, n = A.shape[-2:]
    world = grid.size
    slate_assert(m >= n, "tsqr expects a tall matrix")
    # every shard needs at least n rows for a well-shaped leaf
    unit = world * max(n, 1)
    mpad = ceil_mult(m, unit)
    Ap = jnp.pad(A, ((0, mpad - m), (0, 0))) if mpad != m else A
    Ap = jax.device_put(Ap, grid.row_spec())
    Q, R = _tsqr_dist_fn(grid.mesh, str(Ap.dtype))(Ap)
    return (Q[:m] if mpad != m else Q), R


@instrument
def unmqr_distributed(Q: jax.Array, C: jax.Array, grid: ProcessGrid,
                      trans: bool = True):
    """Apply the explicit distributed Q (or Q^H) to C: one sharded gemm
    (src/unmqr.cc collapses — Q is explicit here, see module docstring)."""
    Qs = jax.device_put(Q, grid.row_spec())
    Cs = jax.device_put(C, grid.row_spec() if not trans else grid.replicated())

    @jax.jit
    def apply(Qs, Cs):
        op = jnp.conj(Qs.T) if trans else Qs
        return jnp.matmul(op, Cs, precision=lax.Precision.HIGHEST)

    return apply(Qs, Cs)


@instrument
def gels_qr_distributed(A: jax.Array, B: jax.Array, grid: ProcessGrid):
    """Overdetermined least squares via distributed TSQR (src/gels_qr.cc):
    X = R^{-1} (Q^H B).  The QR path survives ill-conditioned panels where
    CholQR's Gram matrix goes numerically indefinite."""
    Q, R = tsqr_distributed(A, grid)
    QhB = unmqr_distributed(Q, B, grid, trans=True)
    return lax.linalg.triangular_solve(R, QhB, left_side=True, lower=False)


# ---------------------------------------------------------------------------
# 2-D blocked CAQR (geqrf over the (p, q) mesh)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=32)
def _geqrf_dist_fn(mesh, mpad: int, npad: int, nb: int, dtype_str: str):
    p, q = mesh.shape[ROW_AXIS], mesh.shape[COL_AXIS]
    mr, mc = mpad // p, npad // q
    nt = npad // nb
    assert mr % nb == 0 and mc % nb == 0

    def local_fn(A_loc):
        pi = lax.axis_index(ROW_AXIS)
        qi = lax.axis_index(COL_AXIS)
        grow = pi * mr + jnp.arange(mr, dtype=jnp.int32)
        gcol = qi * mc + jnp.arange(mc, dtype=jnp.int32)
        prec = lax.Precision.HIGHEST

        def project(Q_loc, Pn, k0):
            """One BCGS projection pass: coefficients W (my Q columns) and the
            projection-subtracted panel; cols ≥ k0 of Q are masked out."""
            Qm = jnp.where((gcol < k0)[None, :], Q_loc, jnp.zeros_like(Q_loc))
            W = lax.psum(jnp.matmul(jnp.conj(Qm.T), Pn, precision=prec),
                         ROW_AXIS)                         # (mc, nb) my coeffs
            proj = lax.psum(jnp.matmul(Qm, W, precision=prec), COL_AXIS)
            return W, Pn - proj

        def step(k, carry):
            A_loc, Q_loc, R_loc = carry
            k0 = (k * nb).astype(jnp.int32)
            qo = k0 // mc
            off = k0 - qo * mc

            # panel columns [k0, k0+nb) of the ORIGINAL A (left-looking)
            pan = lax.dynamic_slice(A_loc, (jnp.int32(0), off), (mr, nb))
            pan = jnp.where(qi == qo, pan, jnp.zeros_like(pan))
            pan = lax.psum(pan, COL_AXIS)

            # BCGS2: project against accumulated Q twice ("twice is enough")
            W1, P1 = project(Q_loc, pan, k0)
            W2, P2 = project(Q_loc, P1, k0)

            # TSQR of the projected panel over the p axis
            q_leaf, r_leaf = lax.linalg.qr(P2, full_matrices=False)
            Rs = lax.all_gather(r_leaf, ROW_AXIS, tiled=True)   # (p*nb, nb)
            q_stack, Rkk = lax.linalg.qr(Rs, full_matrices=False)
            coupling = lax.dynamic_slice(
                q_stack, (pi.astype(jnp.int32) * nb, jnp.int32(0)), (nb, nb))
            Qk = jnp.matmul(q_leaf, coupling, precision=prec)   # (mr, nb)

            # write Qk into Q columns [k0, k0+nb) (owner mesh column)
            newQ = lax.dynamic_update_slice(Q_loc, Qk, (jnp.int32(0), off))
            Q_loc = jnp.where(qi == qo, newQ, Q_loc)

            # assemble the R column block: rows < k0 get W1 + W2 (indexed by my
            # Q columns → global rows gcol), rows [k0, k0+nb) get Rkk
            W = jnp.where((gcol < k0)[:, None], W1 + W2,
                          jnp.zeros_like(W1))                   # (mc, nb)
            Rcol = jnp.zeros((mpad, nb), A_loc.dtype).at[gcol].set(W)
            Rcol = jnp.where(pi == 0, Rcol, jnp.zeros_like(Rcol))
            Rcol = lax.dynamic_update_slice(
                Rcol, jnp.where((pi == 0) & (qi == 0), Rkk,
                                jnp.zeros_like(Rkk)), (k0, jnp.int32(0)))
            Rcol = lax.psum(lax.psum(Rcol, ROW_AXIS), COL_AXIS)
            my_rows = lax.dynamic_slice(Rcol, (pi.astype(jnp.int32) * mr,
                                               jnp.int32(0)), (mr, nb))
            newR = lax.dynamic_update_slice(R_loc, my_rows, (jnp.int32(0), off))
            R_loc = jnp.where(qi == qo, newR, R_loc)
            return A_loc, Q_loc, R_loc

        Q0 = jnp.zeros_like(A_loc)
        R0 = jnp.zeros_like(A_loc)
        _, Q_loc, R_loc = lax.fori_loop(0, nt, step, (A_loc, Q0, R0))
        return Q_loc, R_loc

    spec = P(ROW_AXIS, COL_AXIS)
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=spec,
                       out_specs=(spec, spec), check_vma=False)
    return jax.jit(fn)


@instrument
def geqrf_distributed(A: jax.Array, grid: ProcessGrid, nb: int = 256):
    """Distributed blocked CAQR of a general m×n matrix (m ≥ n) over the
    (p, q) mesh (src/geqrf.cc:146-253 analogue; BCGS2 + TSQR panels).

    Returns ``(Q, R)``: Q explicit reduced (m×n, sharded), R (n×n, taken from
    the sharded upper block).
    """
    m, n = A.shape[-2:]
    slate_assert(m >= n, "geqrf_distributed expects m >= n")
    nb = max(1, min(nb, n))  # keep the pad unit proportional to the problem
    npad = ceil_mult(n, nb * grid.q)
    runit = nb * grid.p
    # rows must fit both the real matrix and the unit-column pad block
    mpad = ceil_mult(max(m + (npad - n), npad), runit)
    Ap = jnp.zeros((mpad, npad), A.dtype)
    Ap = Ap.at[:m, :n].set(A)
    if npad > n:
        # unit columns in the padding keep every panel full rank; they come
        # after the real columns so R[:n, :n] and Q[:, :n] are unaffected
        idx = jnp.arange(npad - n)
        Ap = Ap.at[m + idx, n + idx].set(1)
    Ap = jax.device_put(Ap, grid.spec())
    Q, R = _geqrf_dist_fn(grid.mesh, mpad, npad, min(nb, npad),
                          str(Ap.dtype))(Ap)
    return Q[:m, :n], R[:n, :n]


@instrument
def gels_caqr_distributed(A: jax.Array, B: jax.Array, grid: ProcessGrid,
                          nb: int = 256):
    """Least squares through the 2-D CAQR (general overdetermined A)."""
    Q, R = geqrf_distributed(A, grid, nb=nb)
    QhB = jnp.matmul(jnp.conj(Q.T), B, precision=lax.Precision.HIGHEST)
    return lax.linalg.triangular_solve(R, QhB, left_side=True, lower=False)


@instrument
def gelqf_distributed(A: jax.Array, grid: ProcessGrid, nb: int = 256):
    """Distributed LQ factorization A = L Q over the mesh (src/gelqf.cc).

    Like the single-device ``linalg.qr.gelqf``, LQ is CAQR of A^H: A^H = Q1 R1
    gives A = R1^H Q1^H — the transpose is one resharding device_put, and the
    factorization itself is the 2-D BCGS2+TSQR pipeline (``geqrf_distributed``)
    the reference's gelqf.cc mirrors with its own ttlqt trees.  Returns
    ``(L, Q)``: L (m×m lower, for m ≤ n), Q (m×n with orthonormal rows).
    """
    m, n = A.shape[-2:]
    slate_assert(n >= m, "gelqf_distributed expects a wide matrix (m <= n)")
    Q1, R1 = geqrf_distributed(jnp.conj(A.T), grid, nb=nb)
    return jnp.conj(R1.T), jnp.conj(Q1.T)


@instrument
def unmlq_distributed(Q: jax.Array, C: jax.Array, grid: ProcessGrid,
                      conj_trans: bool = False) -> jax.Array:
    """Apply the LQ factor's Q (rows orthonormal) to C from the left over the
    mesh (src/unmlq.cc): op(Q) @ C as one SUMMA gemm — with Q explicit, the
    compact-WY replay the reference schedules collapses into the sharded
    product."""
    from .summa import gemm_padded

    Qop = jnp.conj(Q.T) if conj_trans else Q
    return gemm_padded(Qop, C, grid)


@instrument
def gels_lq_distributed(A: jax.Array, B: jax.Array, grid: ProcessGrid,
                        nb: int = 256) -> jax.Array:
    """Minimum-norm solution of the underdetermined system A X = B over the
    mesh (src/gels.cc wide branch): A = L Q, X = Q^H L^{-1} B — sharded
    triangular solve + SUMMA back-multiply."""
    from .solvers import trsm_distributed

    L, Q = gelqf_distributed(A, grid, nb=nb)
    Y = trsm_distributed(L, B, grid, lower=True, conj_trans=False)
    return unmlq_distributed(Q, Y, grid, conj_trans=True)
