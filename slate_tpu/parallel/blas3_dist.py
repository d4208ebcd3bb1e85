"""Distributed symmetric/Hermitian/triangular BLAS-3 over the process grid.

Reference analogues (SURVEY.md §2.2, §2.4): the distributed BLAS-3 drivers
``src/herk.cc`` / ``src/her2k.cc`` / ``src/syrk.cc`` / ``src/syr2k.cc`` (rank-k
updates of one stored triangle), ``src/hemm*.cc`` / ``src/symm.cc`` (symmetric
multiply), and ``src/trmm.cc`` (triangular multiply), each a task DAG of panel
broadcasts + batched tile gemms.

TPU re-design, two shapes:

* **Rank-k updates** (herk/her2k/syrk/syr2k) are written with *explicit*
  collectives inside ``shard_map``: the k-panel is all-gathered along both mesh
  axes — the reference's ``listBcastMT`` of the panel to its row *and* column
  owners (potrf.cc:122-132) collapsed into two ICI all-gathers — and every
  device then updates its local C block with one dense MXU matmul.  The
  triangle is enforced with an index mask on the local block (global row/col
  indices reconstructed from the mesh coordinates), so the untouched triangle
  passes through exactly as the reference's one-triangle update does.

* **hemm/symm/trmm** reconstruct the implied full operand from the stored
  triangle under ``jit`` with sharded operands (a masked add + transpose, which
  GSPMD turns into the mesh all-to-all) and run one sharded matmul — the
  structure lives in masks, the FLOPs stay on the MXU (SURVEY.md §2.5 mapping).

All entry points accept ragged shapes: operands are zero-padded to
grid-divisible sizes (zero rows/cols leave every product unchanged) and the
result is sliced back.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.exceptions import slate_assert
from .distribute import lcm, pad2d
from .mesh import COL_AXIS, ProcessGrid, ROW_AXIS
from ..obs import instrument

_PREC = lax.Precision.HIGHEST


def _tri_mask(n_loc_p, n_loc_q, lower: bool, strict: bool = False):
    """Local-block mask of the stored triangle, from global indices."""
    i = lax.axis_index(ROW_AXIS)
    j = lax.axis_index(COL_AXIS)
    rows = i * n_loc_p + jnp.arange(n_loc_p)[:, None]
    cols = j * n_loc_q + jnp.arange(n_loc_q)[None, :]
    if lower:
        return rows > cols if strict else rows >= cols
    return rows < cols if strict else rows <= cols


def _col_block(a_row, n, q):
    """From the row-gathered panel (n/p, k), produce this device's *column*
    block (n/q, k): gather the rest of the rows along p, slice at the q
    coordinate.  Two all-gathers total = the reference's panel bcast to row and
    column owners."""
    a_all = lax.all_gather(a_row, ROW_AXIS, axis=0, tiled=True)  # (n, k)
    j = lax.axis_index(COL_AXIS)
    return lax.dynamic_slice_in_dim(a_all, j * (n // q), n // q, axis=0)


@lru_cache(maxsize=8)
def _axpby_fn(mesh):
    spec = NamedSharding(mesh, P(ROW_AXIS, COL_AXIS))

    def fn(alpha, x, beta, y):
        return lax.with_sharding_constraint(alpha * x + beta * y, spec)

    return jax.jit(fn, in_shardings=(None, spec, None, spec),
                   out_shardings=spec)


@lru_cache(maxsize=64)
def _rank_k_fn(mesh, n: int, lower: bool, herm: bool, two: bool):
    p = mesh.shape[ROW_AXIS]
    q = mesh.shape[COL_AXIS]

    def ct(x):
        return jnp.conj(x.T) if herm else x.T

    def local(a, b, c, alpha, beta):
        a_row = lax.all_gather(a, COL_AXIS, axis=1, tiled=True)   # (n/p, k)
        b_row = lax.all_gather(b, COL_AXIS, axis=1, tiled=True)
        b_col = _col_block(b_row, n, q)                            # (n/q, k)
        upd = jnp.matmul(a_row, ct(b_col), precision=_PREC)
        if two:
            a_col = _col_block(a_row, n, q)
            alpha2 = jnp.conj(alpha) if herm else alpha
            upd = alpha * upd + alpha2 * jnp.matmul(
                b_row, ct(a_col), precision=_PREC)
        else:
            upd = alpha * upd
        if herm and jnp.issubdtype(c.dtype, jnp.complexfloating):
            # her*k semantics: the Hermitian diagonal is real — drop any
            # imaginary part of C's diagonal before beta scales it (the
            # reference's herk does the same on the diagonal tiles)
            i = lax.axis_index(ROW_AXIS)
            j = lax.axis_index(COL_AXIS)
            rows = i * (n // p) + jnp.arange(n // p)[:, None]
            cols = j * (n // q) + jnp.arange(n // q)[None, :]
            c = jnp.where(rows == cols, c.real.astype(c.dtype), c)
        mask = _tri_mask(n // p, n // q, lower)
        return jnp.where(mask, upd + beta * c, c)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(ROW_AXIS, COL_AXIS), P(ROW_AXIS, COL_AXIS),
                  P(ROW_AXIS, COL_AXIS), P(), P()),
        out_specs=P(ROW_AXIS, COL_AXIS))
    return jax.jit(fn)


def _run_rank_k(alpha, A, B, beta, C, grid, lower, herm, two):
    n, k = A.shape[-2:]
    slate_assert(B.shape == A.shape, "rank-k operands must have equal shapes")
    slate_assert(C.shape[-2:] == (n, n), f"C must be {n}x{n}")
    unit = lcm(grid.p, grid.q)
    Ap = pad2d(A, unit, grid.q)
    Bp = Ap if B is A else pad2d(B, unit, grid.q)
    Cp = pad2d(C, unit, unit)
    npad = Cp.shape[-1]
    spec = grid.spec()
    Ap = jax.device_put(Ap, spec)
    Bp = Ap if B is A else jax.device_put(Bp, spec)
    Cp = jax.device_put(Cp, spec)
    dt = Cp.dtype
    out = _rank_k_fn(grid.mesh, npad, lower, herm, two)(
        Ap, Bp, Cp, jnp.asarray(alpha, dt), jnp.asarray(beta, dt))
    return out[:n, :n] if npad != n else out


@instrument
def herk_distributed(alpha, A, beta, C, grid: ProcessGrid,
                     uplo: str = "lower") -> jax.Array:
    """C_uplo = alpha A A^H + beta C_uplo, C sharded (p, q) (src/herk.cc).
    The opposite triangle of C passes through untouched."""
    return _run_rank_k(alpha, A, A, beta, C, grid, uplo == "lower",
                       herm=True, two=False)


@instrument
def syrk_distributed(alpha, A, beta, C, grid: ProcessGrid,
                     uplo: str = "lower") -> jax.Array:
    """C_uplo = alpha A A^T + beta C_uplo (src/syrk.cc)."""
    return _run_rank_k(alpha, A, A, beta, C, grid, uplo == "lower",
                       herm=False, two=False)


@instrument
def her2k_distributed(alpha, A, B, beta, C, grid: ProcessGrid,
                      uplo: str = "lower") -> jax.Array:
    """C_uplo = alpha A B^H + conj(alpha) B A^H + beta C_uplo (src/her2k.cc)."""
    return _run_rank_k(alpha, A, B, beta, C, grid, uplo == "lower",
                       herm=True, two=True)


@instrument
def syr2k_distributed(alpha, A, B, beta, C, grid: ProcessGrid,
                      uplo: str = "lower") -> jax.Array:
    """C_uplo = alpha (A B^T + B A^T) + beta C_uplo (src/syr2k.cc)."""
    return _run_rank_k(alpha, A, B, beta, C, grid, uplo == "lower",
                       herm=False, two=True)


# ---------------------------------------------------------------------------
# hemm / symm / trmm — masked sharded matmuls
# ---------------------------------------------------------------------------


@lru_cache(maxsize=64)
def _hemm_fn(mesh, left: bool, lower: bool, herm: bool):
    spec = NamedSharding(mesh, P(ROW_AXIS, COL_AXIS))

    def fn(a, b, c, alpha, beta):
        from ..core.matrix import tri_to_full

        full = tri_to_full(a, lower, herm)
        prod = (jnp.matmul(full, b, precision=_PREC) if left
                else jnp.matmul(b, full, precision=_PREC))
        out = alpha * prod + beta * c
        return lax.with_sharding_constraint(out, spec)

    return jax.jit(fn, in_shardings=(spec, spec, spec, None, None),
                   out_shardings=spec)


@instrument
def hemm_distributed(side, alpha, A, B, beta, C, grid: ProcessGrid,
                     uplo: str = "lower", herm: bool = True) -> jax.Array:
    """C = alpha A B + beta C (side=left) or alpha B A + beta C (side=right),
    with A Hermitian/symmetric stored in one triangle (src/hemm.cc, src/symm.cc)."""
    left = str(side).lower().startswith("l")
    slate_assert(A.shape[-1] == A.shape[-2], "hemm operand A must be square")
    slate_assert(A.shape[-1] == (C.shape[-2] if left else C.shape[-1]),
                 f"side={side!r} needs A of order "
                 f"{C.shape[-2] if left else C.shape[-1]}, got {A.shape[-1]}")
    m, n = C.shape[-2:]
    unit = lcm(grid.p, grid.q)
    Ap = pad2d(A, unit, unit)
    Bp = pad2d(B, unit, unit)
    Cp = pad2d(C, unit, unit)
    spec = grid.spec()
    Ap, Bp, Cp = (jax.device_put(x, spec) for x in (Ap, Bp, Cp))
    dt = Cp.dtype
    out = _hemm_fn(grid.mesh, left, uplo == "lower", herm)(
        Ap, Bp, Cp, jnp.asarray(alpha, dt), jnp.asarray(beta, dt))
    return out[:m, :n] if out.shape[-2:] != (m, n) else out


@instrument
def symm_distributed(side, alpha, A, B, beta, C, grid: ProcessGrid,
                     uplo: str = "lower") -> jax.Array:
    return hemm_distributed(side, alpha, A, B, beta, C, grid, uplo, herm=False)


@lru_cache(maxsize=64)
def _trmm_fn(mesh, left: bool, lower: bool, trans: bool, unit_diag: bool):
    spec = NamedSharding(mesh, P(ROW_AXIS, COL_AXIS))

    def fn(a, b, alpha):
        tri = jnp.tril(a) if lower else jnp.triu(a)
        if unit_diag:
            idx = jnp.arange(a.shape[0])
            tri = tri.at[idx, idx].set(1)
        if trans:
            tri = jnp.conj(tri.T)
        prod = (jnp.matmul(tri, b, precision=_PREC) if left
                else jnp.matmul(b, tri, precision=_PREC))
        return lax.with_sharding_constraint(alpha * prod, spec)

    return jax.jit(fn, in_shardings=(spec, spec, None), out_shardings=spec)


@instrument
def gbmm_distributed(alpha, A, B, beta, C, grid: ProcessGrid,
                     kl: int, ku: int) -> jax.Array:
    """C = alpha A B + beta C with A a general band matrix (src/gbmm.cc over
    the grid).  The band structure is a mask — zeros outside the band keep
    every shard's matmul dense on the MXU (SURVEY.md §2.5 mapping) — and the
    product rides the SUMMA all-gather gemm."""
    from ..linalg.band import _band_mask
    from .summa import gemm_allgather

    m, k = A.shape[-2:]
    n = B.shape[-1]
    slate_assert(B.shape[-2] == k, f"gbmm inner dims {k} != {B.shape[-2]}")
    slate_assert(C.shape[-2:] == (m, n), f"gbmm C must be {m}x{n}")
    Am = A * _band_mask(m, k, kl, ku, A.dtype)
    kmult = lcm(grid.p, grid.q)
    Ap = pad2d(Am, grid.p, kmult)
    Bp = pad2d(B, kmult, grid.q)
    prod = gemm_allgather(Ap, Bp, grid)          # sharded, padded (mp, np)
    # fold the axpy into a sharded program so the result keeps the grid
    # sharding like every other *_distributed entry point (C is padded to the
    # product's shape and placed on the grid first)
    Cp = jax.device_put(
        jnp.pad(C, ((0, prod.shape[-2] - m), (0, prod.shape[-1] - n))),
        grid.spec())
    dt = Cp.dtype
    out = _axpby_fn(grid.mesh)(jnp.asarray(alpha, dt), prod,
                               jnp.asarray(beta, dt), Cp)
    return out[:m, :n] if out.shape[-2:] != (m, n) else out


@instrument
def hbmm_distributed(alpha, A, B, beta, C, grid: ProcessGrid,
                     kd: int, uplo: str = "lower",
                     side: str = "left") -> jax.Array:
    """C = alpha A B + beta C (side=left) or alpha B A + beta C (side=right)
    with A Hermitian band, one triangle stored (src/hbmm.cc over the grid;
    the reference's Side parameter, slate.hh:215)."""
    from ..linalg.band import _band_mask

    n = A.shape[-1]
    lower = uplo == "lower"
    tri = A * _band_mask(n, n, kd if lower else 0, 0 if lower else kd, A.dtype)
    # the hemm kernel reconstructs the full Hermitian operand from the stored
    # (band-masked) triangle in-trace
    return hemm_distributed(side, alpha, tri, B, beta, C, grid, uplo=uplo)


@instrument
def trmm_distributed(side, alpha, A, B, grid: ProcessGrid,
                     uplo: str = "lower", conj_trans: bool = False,
                     unit_diag: bool = False) -> jax.Array:
    """B = alpha op(A) B (side=left) or alpha B op(A) (side=right) with A
    triangular (src/trmm.cc).  Zero-padding keeps the padded triangle inert."""
    left = str(side).lower().startswith("l")
    m, n = B.shape[-2:]
    unit = lcm(grid.p, grid.q)
    Ap = pad2d(A, unit, unit)
    Bp = pad2d(B, unit, unit)
    spec = grid.spec()
    Ap = jax.device_put(Ap, spec)
    Bp = jax.device_put(Bp, spec)
    out = _trmm_fn(grid.mesh, left, uplo == "lower", conj_trans, unit_diag)(
        Ap, Bp, jnp.asarray(alpha, Bp.dtype))
    return out[:m, :n] if out.shape[-2:] != (m, n) else out
