"""Distributed Hermitian-indefinite (Aasen) factorization over the mesh.

Reference analogues: ``src/hetrf.cc`` (642 LoC: communication-avoiding Aasen
over the grid — panel LU on the Schur-complement column, band T assembly,
two-sided pivoting), ``src/hetrs.cc`` (L sweep + banded-T solve + L^H sweep),
``src/hesv.cc``.

TPU re-design (not a translation):

- **1-D row-block layout over the flattened mesh** (the TSLU layout,
  ``lu_dist._getrf_tall_fn``): every device owns all columns of its row
  block, so Aasen's H-column gemm — the flops-dominant step — is a fully
  local (n/P × n)·(n × nb) MXU gemm with *zero* communication; only the
  nb-row block extractions (masked psum), the H-column all-gather, and the
  tournament candidate all-gather touch the interconnect per panel.
- **Tournament panel pivoting.**  The reference's hetrf panel is a
  partial-pivoted LU over grid tiles; here the Schur panel reuses the CALU
  tournament (one candidate all-gather + one stacked LU — the
  communication-avoiding shape, SURVEY §7 hard-part 1).
- **Two-sided dirty exchange.**  The symmetric permutation moves ≤ 2nb rows
  (one masked psum) and ≤ 2nb columns (purely local gathers — columns are
  resident), instead of the reference's MPI pairwise row+column swaps.
- **ONE ``lax.fori_loop``** over panels: O(1) program size (the
  single-device path unrolls panels at trace time; the reference unrolls an
  OpenMP task graph).

T is returned in compact lower band form (bandwidth nb) and factored by the
distributed band LU, so ``hetrs_distributed`` solves ride
``band_dist.gbtrs_distributed`` + the sharded unit-lower sweeps.
"""

from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.exceptions import slate_assert
from .band_dist import (BandLUDist, dense_to_band_general, gbtrf_distributed,
                        gbtrs_distributed)
from .distribute import ceil_mult
from .mesh import COL_AXIS, ProcessGrid, ROW_AXIS
from .pivot import (exchange_rows as _exchange_rows,
                    extract_rows as _extract_rows,
                    step_permutation, tournament_piv)
from ..obs import instrument

AX = (ROW_AXIS, COL_AXIS)


class HermitianFactorsDist(NamedTuple):
    """Distributed Aasen bundle P A P^H = L T L^H (hetrf.cc output shape)."""
    L: jax.Array         # (n, n) unit lower triangular (sharded rows)
    Tband: jax.Array     # T in LAPACK-gb layout (3nb+1, n): row j holds
                         # diagonal j - 2nb, i.e. dense_to_band_general(
                         # T, nb, nb, extra=nb); the diagonal is row 2nb
    T_fac: BandLUDist    # distributed band LU of T
    perm: jax.Array      # (n,)
    nb: int


@lru_cache(maxsize=32)
def _hetrf_dist_fn(mesh, npad: int, nb: int, dtype_str: str):
    nprocs = mesh.shape[ROW_AXIS] * mesh.shape[COL_AXIS]
    mr = npad // nprocs
    N = npad // nb
    cplx = dtype_str.startswith("complex")

    def conj_t(x):
        return jnp.conj(jnp.swapaxes(x, -1, -2)) if cplx else \
            jnp.swapaxes(x, -1, -2)

    def local_fn(A_loc):                     # (mr, npad)
        ri = lax.axis_index(AX)
        grow = ri * mr + jnp.arange(mr, dtype=jnp.int32)
        gcol = jnp.arange(npad, dtype=jnp.int32)

        def extract_rows(X_loc, r0, cnt):
            """Replicated (cnt, npad) block of rows [r0, r0+cnt)."""
            S = r0 + jnp.arange(cnt, dtype=jnp.int32)
            return _extract_rows(X_loc, S, ri, mr, AX)

        def step(j, carry):
            A_loc, L_loc, T_loc, perm = carry
            j0 = (j * nb).astype(jnp.int32) if hasattr(j, "astype") else j * nb
            j1 = j0 + nb

            # ---- H-column: Hcol = T[:, :j1+nb] @ L[j0:j1, :j1+nb]^H,
            # rows < j0 meaningful.  T band => cols beyond j1+nb are zero in
            # the needed rows; local gemm over my rows, then gather.
            Lj = extract_rows(L_loc, j0, nb)             # (nb, npad)
            cmask = (gcol < j1 + nb)
            Hcol_loc = jnp.matmul(
                jnp.where(cmask[None, :], T_loc, jnp.zeros_like(T_loc)),
                conj_t(jnp.where(cmask[None, :], Lj, jnp.zeros_like(Lj))),
                precision=lax.Precision.HIGHEST)         # (mr, nb)
            Hcol_loc = jnp.where((grow < j0)[:, None], Hcol_loc,
                                 jnp.zeros_like(Hcol_loc))
            Hcol = lax.all_gather(Hcol_loc, AX).reshape(npad, nb)

            # ---- diagonal identities (replicated small blocks)
            Arow = extract_rows(A_loc, j0, nb)           # (nb, npad)
            Ajj = lax.dynamic_slice(Arow, (jnp.int32(0), j0), (nb, nb))
            Ljj = lax.dynamic_slice(Lj, (jnp.int32(0), j0), (nb, nb))
            pmask = (gcol < j0)
            LH = jnp.matmul(jnp.where(pmask[None, :], Lj, jnp.zeros_like(Lj)),
                            Hcol, precision=lax.Precision.HIGHEST)
            LjjHjj = Ajj - LH
            Hjj = lax.linalg.triangular_solve(Ljj, LjjHjj, left_side=True,
                                              lower=True, unit_diagonal=True)
            Trow = extract_rows(T_loc, j0, nb)           # (nb, npad)
            start_prev = jnp.maximum(j0 - nb, 0)
            Tprev = lax.dynamic_slice(Trow, (jnp.int32(0), start_prev),
                                      (nb, nb))
            Lprev = lax.dynamic_slice(Lj, (jnp.int32(0), start_prev), (nb, nb))
            rhs = Hjj - jnp.where(j0 > 0, jnp.matmul(
                Tprev, conj_t(Lprev), precision=lax.Precision.HIGHEST),
                jnp.zeros((nb, nb), Hjj.dtype))
            Tjj = lax.linalg.triangular_solve(
                Ljj, rhs, left_side=False, lower=True, unit_diagonal=True,
                conjugate_a=cplx, transpose_a=True)
            Tjj = (Tjj + jnp.conj(Tjj.T)) / 2 if cplx else (Tjj + Tjj.T) / 2
            # write T[j0:j1, j0:j1]
            dstT = j0 + jnp.arange(nb, dtype=jnp.int32) - ri * mr
            dstT = jnp.where((dstT >= 0) & (dstT < mr), dstT, mr)
            Tnew = jnp.zeros((nb, npad), T_loc.dtype)
            Tnew = lax.dynamic_update_slice(Tnew, Tjj, (jnp.int32(0), j0))
            keep = lax.dynamic_update_slice(
                jnp.zeros((nb, npad), jnp.bool_),
                jnp.ones((nb, nb), jnp.bool_), (jnp.int32(0), j0))
            Trows_cur = T_loc[jnp.clip(dstT, 0, mr - 1)]
            T_loc = T_loc.at[dstT].set(
                jnp.where(keep, Tnew, Trows_cur), mode="drop")

            # ---- Schur panel W = A[:, j0:j1] - L[:, :j0] Hcol - L[:, j0:j1] Hjj
            # (rows >= j1 meaningful)
            Acol = lax.dynamic_slice(A_loc, (jnp.int32(0), j0), (mr, nb))
            Lpre = jnp.where(pmask[None, :], L_loc, jnp.zeros_like(L_loc))
            W = Acol - jnp.matmul(Lpre, Hcol, precision=lax.Precision.HIGHEST)
            Lcur = lax.dynamic_slice(L_loc, (jnp.int32(0), j0), (mr, nb))
            W = W - jnp.matmul(Lcur, Hjj, precision=lax.Precision.HIGHEST)

            # ---- tournament panel LU over rows >= j1 (shared machinery,
            # pivot.py; CALU round)
            piv = tournament_piv(W, grow, j1, nb, nprocs, AX)
            safe = j1 < npad        # final iteration has no trailing panel
            iota = jnp.arange(npad, dtype=jnp.int32)
            stepperm = jnp.where(safe, step_permutation(piv, j1, npad, nb),
                                 iota)
            perm = perm[stepperm]

            # dirty sets
            S = jnp.concatenate([j1 + jnp.arange(nb, dtype=jnp.int32), piv])
            src = stepperm[jnp.clip(S, 0, npad - 1)]

            def exchange_rows(X_loc):
                return _exchange_rows(X_loc, S, src, ri, mr, AX)

            # two-sided on A: rows (psum) then columns (local gather)
            A_loc = exchange_rows(A_loc)
            A_loc = A_loc.at[:, S].set(A_loc[:, jnp.clip(src, 0, npad - 1)],
                                       mode="drop")
            # L rows move only inside cols [nb, j1) — swap then re-mask
            Lsw = exchange_rows(L_loc)
            lmask = (gcol >= nb) & (gcol < j1)
            L_loc = jnp.where(lmask[None, :], Lsw, L_loc)
            # W rows follow the same permutation
            W = exchange_rows(W)

            # ---- factor the swapped panel block
            blk = extract_rows(W, j1, nb)
            blk = lax.dynamic_slice(blk, (jnp.int32(0), jnp.int32(0)),
                                    (nb, nb))
            LUkk, _, blkperm = lax.linalg.lu(blk)
            # guard the final iteration (j1 >= npad): identity block
            LUkk = jnp.where(safe, LUkk, jnp.eye(nb, dtype=LUkk.dtype))
            blkperm = jnp.where(safe, blkperm,
                                jnp.arange(nb, dtype=blkperm.dtype))
            # fold intra-block pivots (rows j1..j1+nb): perm, A rows+cols,
            # L masked cols, W rows
            seg = jnp.take(perm, jnp.clip(j1 + blkperm, 0, npad - 1))
            perm = lax.dynamic_update_slice(
                perm, jnp.where(safe, seg,
                                lax.dynamic_slice(perm, (jnp.int32(
                                    jnp.minimum(j1, npad - nb)),), (nb,))),
                (jnp.minimum(j1, npad - nb),))

            Sb = j1 + jnp.arange(nb, dtype=jnp.int32)
            srcb = jnp.clip(j1 + blkperm, 0, npad - 1)

            def reorder_block_rows(X_loc):
                return _exchange_rows(X_loc, Sb, srcb, ri, mr, AX)

            A_loc = reorder_block_rows(A_loc)
            A_loc = A_loc.at[:, Sb].set(A_loc[:, srcb], mode="drop")
            Lsw = reorder_block_rows(L_loc)
            L_loc = jnp.where(lmask[None, :], Lsw, L_loc)
            W = reorder_block_rows(W)

            # ---- L panel and T sub/super blocks
            Up = jnp.triu(LUkk)
            Lblock = jnp.tril(LUkk, -1) + jnp.eye(nb, dtype=LUkk.dtype)
            # rows below j1+nb: X = W · Up^{-1}
            # guard singular Up (pad tail): unit diagonal floor
            dU = jnp.abs(jnp.diagonal(Up))
            Up_safe = Up + jnp.diag(jnp.where(dU > 0, 0.0, 1.0).astype(
                Up.dtype))
            X = lax.linalg.triangular_solve(Up_safe, W, left_side=False,
                                            lower=False)
            belowb = grow >= (j1 + nb)
            in_blk = (grow >= j1) & (grow < j1 + nb)
            Lpan_loc = jnp.where(belowb[:, None], X,
                                 jnp.zeros_like(X))
            # block rows get the unit-lower Lblock
            Lblk_rows = lax.dynamic_update_slice(
                jnp.zeros((mr, nb), X.dtype), Lblock,
                (jnp.clip(j1 - ri * mr, 0, mr), jnp.int32(0)))
            Lblk_rows = jnp.where(in_blk[:, None], Lblk_rows,
                                  jnp.zeros_like(Lblk_rows))
            Lpan_loc = Lpan_loc + Lblk_rows
            # write L[:, j1:j1+nb] where rows >= j1 (cond: only if safe)
            cur = lax.dynamic_slice(
                L_loc, (jnp.int32(0), jnp.minimum(j1, npad - nb)), (mr, nb))
            put = jnp.where(jnp.logical_and(safe, in_blk | belowb)[:, None],
                            Lpan_loc, cur)
            L_loc = lax.dynamic_update_slice(
                L_loc, put, (jnp.int32(0), jnp.minimum(j1, npad - nb)))

            # T[j1][j0] = Up (L[j0:j1,j0:j1]^H)^{-1}; Ljj unchanged by swaps
            Tj1j = lax.linalg.triangular_solve(
                Ljj, Up, left_side=False, lower=True, unit_diagonal=True,
                conjugate_a=cplx, transpose_a=True)
            Tj1j = jnp.where(safe, Tj1j, jnp.zeros_like(Tj1j))
            # write T[j1:j1+nb, j0:j1] and its Hermitian mirror
            dstT2 = Sb - ri * mr
            dstT2 = jnp.where((dstT2 >= 0) & (dstT2 < mr), dstT2, mr)
            rows_cur = T_loc[jnp.clip(dstT2, 0, mr - 1)]
            block_row = lax.dynamic_update_slice(
                jnp.zeros((nb, npad), T_loc.dtype), Tj1j, (jnp.int32(0), j0))
            keep2 = lax.dynamic_update_slice(
                jnp.zeros((nb, npad), jnp.bool_),
                jnp.ones((nb, nb), jnp.bool_), (jnp.int32(0), j0))
            T_loc = T_loc.at[dstT2].set(
                jnp.where(keep2, block_row, rows_cur), mode="drop")
            # mirror: T[j0:j1, j1:j1+nb] = Tj1j^H
            mirror = lax.dynamic_update_slice(
                jnp.zeros((nb, npad), T_loc.dtype), conj_t(Tj1j),
                (jnp.int32(0), jnp.minimum(j1, npad - nb)))
            keep3 = lax.dynamic_update_slice(
                jnp.zeros((nb, npad), jnp.bool_),
                jnp.ones((nb, nb), jnp.bool_),
                (jnp.int32(0), jnp.minimum(j1, npad - nb)))
            keep3 = keep3 & safe
            rows_cur2 = T_loc[jnp.clip(dstT, 0, mr - 1)]
            T_loc = T_loc.at[dstT].set(
                jnp.where(keep3, mirror, rows_cur2), mode="drop")

            return A_loc, L_loc, T_loc, perm

        eyer = (grow[:, None] == gcol[None, :]).astype(A_loc.dtype)
        L0 = eyer
        T0 = jnp.zeros_like(A_loc)
        perm0 = jnp.arange(npad, dtype=jnp.int32)
        A_loc, L_loc, T_loc, perm = lax.fori_loop(
            0, N, step, (A_loc, L0, T0, perm0))
        return L_loc, T_loc, perm

    spec = P(AX, None)
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=spec,
                       out_specs=(spec, spec, P(None)), check_vma=False)
    return jax.jit(fn)


@instrument
def hetrf_distributed(A: jax.Array, grid: ProcessGrid, nb: int = 256):
    """Distributed Aasen factorization P A P^H = L T L^H (src/hetrf.cc).

    Returns ``(HermitianFactorsDist, info)``; T comes back as a compact
    general band (bandwidth nb each side) already factored by the
    distributed band LU, so solves never refactor.
    """
    slate_assert(A.ndim == 2 and A.shape[-1] == A.shape[-2],
                 "hetrf_distributed expects a square Hermitian matrix")
    n = A.shape[-1]
    nb = max(1, min(nb, n))
    nprocs = grid.p * grid.q
    unit = nb * nprocs
    npad = ceil_mult(n, unit)
    if npad > n:
        Ap = jnp.zeros((npad, npad), A.dtype)
        Ap = Ap.at[:n, :n].set(A)
        idx = jnp.arange(n, npad)
        Ap = Ap.at[idx, idx].set(1)
    else:
        Ap = A
    Ap = jax.device_put(Ap, jax.sharding.NamedSharding(grid.mesh,
                                                       P(AX, None)))
    L, T, perm = _hetrf_dist_fn(grid.mesh, npad, nb, str(Ap.dtype))(Ap)
    L = L[:n, :n]
    T = T[:n, :n]
    perm = perm[:n]
    Tband = dense_to_band_general(T, nb, nb, extra=nb)
    T_fac, info = gbtrf_distributed(Tband, grid, nb, nb, nb=nb)
    return HermitianFactorsDist(L=L, Tband=Tband, T_fac=T_fac, perm=perm,
                                nb=nb), info


@instrument
def hetrs_distributed(fac: HermitianFactorsDist, B: jax.Array,
                      grid: ProcessGrid) -> jax.Array:
    """Distributed Aasen solve (src/hetrs.cc): permute, unit-lower sweep,
    banded-T solve, unit-lower^H sweep, un-permute — all on mesh kernels."""
    from .solvers import trsm_distributed

    vec = B.ndim == 1
    b = B[:, None] if vec else B
    y = jnp.take(b, fac.perm, axis=0)
    n = fac.L.shape[-1]
    idx = jnp.arange(n)
    Lu = jnp.tril(fac.L, -1).at[idx, idx].set(1)
    y = trsm_distributed(Lu, y, grid, lower=True, conj_trans=False)
    z = gbtrs_distributed(fac.T_fac, y, grid)
    x = trsm_distributed(Lu, z, grid, lower=True, conj_trans=True)
    x = jnp.zeros_like(x).at[fac.perm].set(x)
    return x[:, 0] if vec else x


@instrument
def hesv_distributed(A: jax.Array, B: jax.Array, grid: ProcessGrid,
                     nb: int = 256):
    """Distributed Hermitian-indefinite solve (src/hesv.cc = hetrf + hetrs)."""
    fac, info = hetrf_distributed(A, grid, nb=nb)
    return hetrs_distributed(fac, B, grid), info
