"""Public parallel BLAS-3 and auxiliary drivers — the L5 API.

Reference analogue: the BLAS-3 and Aux rows of the driver inventory (SURVEY.md §2.4):
``src/{gemm,gemmA,gemmC,hemm,symm,herk,her2k,syrk,syr2k,trmm,trsm}.cc`` and
``src/{add,copy,scale,scale_row_col,set,norm,colNorms}.cc``, declared in
``include/slate/slate.hh``.

Drivers accept Matrix wrappers (using their op/uplo/diag flags, like the reference's
typed-matrix dispatch) or raw arrays with explicit keywords.  Each mutates its output
wrapper in place (functional rebind) *and* returns the new array, so both the
reference's in-place style and JAX's functional style work.

Method dispatch: ``select_algo`` mirrors src/gemm.cc:12-24 — on a single device all
stationary variants lower to the same fused XLA matmul (stationarity is a communication
concept), so the choice only matters on a distributed mesh where MethodGemm.SUMMA
routes to the shard_map pipeline (parallel/summa.py).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .core.exceptions import SlateError, slate_assert
from .core.matrix import (BaseBandMatrix, BaseMatrix, BaseTrapezoidMatrix,
                          HermitianMatrix, SymmetricMatrix, as_array, write_back)
from .core.types import (Diag, MethodGemm, MethodTrsm, Norm, NormScope,
                         Options, Side, Uplo)
from .ops import blas3, elementwise, norms as norm_ops


def _uplo_of(A, uplo) -> Uplo:
    if uplo is not None:
        return Uplo.from_string(uplo)
    if isinstance(A, (BaseTrapezoidMatrix, BaseBandMatrix)) and A.uplo != Uplo.General:
        return A.uplo
    raise SlateError("uplo required (pass a triangular/symmetric matrix or uplo=...)")


def _diag_of(A, diag) -> Diag:
    if diag is not None:
        return Diag.from_string(diag)
    return getattr(A, "diag", Diag.NonUnit)


def select_algo_gemm(A, B, C, opts: Options) -> MethodGemm:
    """Pick a gemm variant (src/gemm.cc:12-24 select_algo).

    The reference picks stationary-C when B has >= 2 block columns, else stationary-A.
    On one device both are the same XLA matmul; the distinction is kept so distributed
    callers can follow the same heuristic.
    """
    if opts.method_gemm != MethodGemm.Auto:
        return opts.method_gemm
    B_nt = B.nt if isinstance(B, BaseMatrix) else 2
    return MethodGemm.C if B_nt >= 2 else MethodGemm.A


def gemm(alpha, A, B, beta, C, opts=None):
    """C = alpha op(A) op(B) + beta C (src/gemm.cc:87)."""
    from .core.matrix import distribution_grid

    opts = Options.make(opts)
    grid = distribution_grid(A, B, C)
    if opts.f64_emulation:
        # double-precision-class result on f64-less hardware (exact Ozaki
        # bf16 splitting + double-f32 accumulation, ops/f64emu.py); the
        # whole alpha/beta combination happens inside the compensated
        # accumulator so residual-style calls keep their accuracy
        if grid is not None:
            raise SlateError("f64_emulation gemm is single-device; detach "
                             "the grid or pre-gather the operands")
        from .ops.f64emu import gemm_f64emu

        out = gemm_f64emu(as_array(A), as_array(B), alpha=alpha, beta=beta,
                          C=as_array(C))
        return write_back(C, out)
    if grid is not None:
        # wrappers bound to a >1-device grid run the SUMMA pipeline over it
        # (scalapack_gemm.cc builds on the BLACS grid the same way)
        from .parallel import summa

        return write_back(C, summa.summa_gemm(alpha, A, B, beta, C, opts,
                                              grid=grid))
    method = select_algo_gemm(A, B, C, opts)
    if method == MethodGemm.SUMMA:
        # explicit shard_map pipeline; requires distributed wrappers
        from .parallel import summa

        out = summa.summa_gemm(alpha, A, B, beta, C, opts)
    else:
        # stationary-A/C both lower to one fused MXU matmul on a single array;
        # stationarity is a communication-layout concept handled by the sharding
        out = blas3.gemm(alpha, as_array(A), as_array(B), beta, as_array(C))
    return write_back(C, out)


def gemmA(alpha, A, B, beta, C, opts=None):
    """Stationary-A gemm (src/gemmA.cc): A's tiles stay put, partial C
    products are reduced to C's owners — the reference's pick for a B with
    one block column (select_algo, src/gemm.cc:12-24).  On one device the
    stationarity distinction is a communication layout, not a kernel: both
    variants are the same fused MXU matmul."""
    from dataclasses import replace

    opts = replace(Options.make(opts), method_gemm=MethodGemm.A)
    return gemm(alpha, A, B, beta, C, opts)


def gemmC(alpha, A, B, beta, C, opts=None):
    """Stationary-C gemm (src/gemmC.cc): C never moves, A panels are
    broadcast — the wide-B default."""
    from dataclasses import replace

    opts = replace(Options.make(opts), method_gemm=MethodGemm.C)
    return gemm(alpha, A, B, beta, C, opts)


def symm(side, alpha, A, B, beta, C, opts=None, uplo=None):
    """C = alpha A B + beta C, A symmetric (src/symm.cc)."""
    out = blas3.symm(side, alpha, as_array(A), _uplo_of(A, uplo),
                     as_array(B), beta, as_array(C))
    return write_back(C, out)


def hemm(side, alpha, A, B, beta, C, opts=None, uplo=None):
    """Hermitian symm (src/hemm.cc, hemmA/hemmC variants)."""
    out = blas3.hemm(side, alpha, as_array(A), _uplo_of(A, uplo),
                     as_array(B), beta, as_array(C))
    return write_back(C, out)


def hemmA(side, alpha, A, B, beta, C, opts=None, uplo=None):
    """Stationary-A Hermitian multiply (src/hemmA.cc); see gemmA for the
    stationarity semantics on TPU."""
    return hemm(side, alpha, A, B, beta, C, opts=opts, uplo=uplo)


def hemmC(side, alpha, A, B, beta, C, opts=None, uplo=None):
    """Stationary-C Hermitian multiply (src/hemmC.cc)."""
    return hemm(side, alpha, A, B, beta, C, opts=opts, uplo=uplo)


def syrk(alpha, A, beta, C, opts=None, uplo=None):
    """C = alpha A A^T + beta C on the stored triangle (src/syrk.cc)."""
    out = blas3.syrk(alpha, as_array(A), beta, as_array(C), _uplo_of(C, uplo))
    return write_back(C, out)


def herk(alpha, A, beta, C, opts=None, uplo=None):
    """C = alpha A A^H + beta C, alpha/beta real (src/herk.cc)."""
    out = blas3.herk(alpha, as_array(A), beta, as_array(C), _uplo_of(C, uplo))
    return write_back(C, out)


def syr2k(alpha, A, B, beta, C, opts=None, uplo=None):
    out = blas3.syr2k(alpha, as_array(A), as_array(B), beta, as_array(C),
                      _uplo_of(C, uplo))
    return write_back(C, out)


def her2k(alpha, A, B, beta, C, opts=None, uplo=None):
    out = blas3.her2k(alpha, as_array(A), as_array(B), beta, as_array(C),
                      _uplo_of(C, uplo))
    return write_back(C, out)


def trmm(side, alpha, A, B, opts=None, uplo=None, diag=None):
    """B = alpha op(T) B / alpha B op(T) (src/trmm.cc; work::trmm body)."""
    out = blas3.trmm(side, _uplo_of(A, uplo), _diag_of(A, diag),
                     alpha, as_array(A), as_array(B))
    return write_back(B, out)


def select_algo_trsm(A, B, opts: Options) -> MethodTrsm:
    """Pick a trsm variant (src/trsm.cc:11-23 select_algo).

    The reference picks stationary-A when B has a single block column (a
    narrow right-hand side: moving nb×nrhs X blocks is cheaper than moving
    A's panels), else stationary-B.  On one device both lower to the same
    XLA TriangularSolve; on a grid they are genuinely different dataflows
    (parallel/solvers.py trsmA_distributed vs trsm_distributed)."""
    if opts.method_trsm != MethodTrsm.Auto:
        return opts.method_trsm
    B_nt = B.nt if isinstance(B, BaseMatrix) else 2
    return MethodTrsm.A if B_nt < 2 else MethodTrsm.B


def _trsm_dispatch(method, side, alpha, A, B, opts, uplo, diag):
    from .core.matrix import distribution_grid

    grid = distribution_grid(A, B)
    if grid is None:
        # one device: stationarity is a communication concept; both methods
        # are the same blocked TriangularSolve
        out = blas3.trsm(side, _uplo_of(A, uplo), _diag_of(A, diag),
                         alpha, as_array(A), as_array(B))
        return write_back(B, out)
    from .parallel.solvers import trsmA_distributed, trsm_distributed

    u = _uplo_of(A, uplo)
    d = _diag_of(A, diag)
    a, b = as_array(A), as_array(B)
    s = Side.from_string(side)
    if s == Side.Right:
        # X op(A) = alpha B  <=>  op(A)^T X^T = alpha B^T: reuse the left
        # sweeps on transposed operands (work_trsmA.cc:79-89 does the same)
        a, b = a.T, jnp.swapaxes(b, -1, -2)
        u = Uplo.Upper if u == Uplo.Lower else Uplo.Lower
    lower = u == Uplo.Lower
    if method == MethodTrsm.A:
        out = trsmA_distributed(a, jnp.asarray(alpha, b.dtype) * b, grid,
                                lower=lower, unit_diag=(d == Diag.Unit))
    else:
        if d == Diag.Unit:
            # stationary-B's fused TriangularSolve has no unit flag here:
            # make the implicit unit diagonal explicit instead
            idx = jnp.arange(a.shape[-1])
            a = a.at[idx, idx].set(jnp.asarray(1.0, a.dtype))
        out = trsm_distributed(a, jnp.asarray(alpha, b.dtype) * b, grid,
                               lower=lower)
    if s == Side.Right:
        out = jnp.swapaxes(out, -1, -2)
    return write_back(B, out)


def trsm(side, alpha, A, B, opts=None, uplo=None, diag=None):
    """Solve op(T) X = alpha B in place of B (src/trsm.cc; work::trsm,
    work_trsm.cc:54-387 — the lookahead task DAG collapses into XLA's blocked
    TriangularSolve on TPU).  Grid-bound operands dispatch between the
    stationary-A and stationary-B distributed dataflows via select_algo."""
    opts = Options.make(opts)
    return _trsm_dispatch(select_algo_trsm(A, B, opts), side, alpha, A, B,
                          opts, uplo, diag)


def trsmA(side, alpha, A, B, opts=None, uplo=None, diag=None):
    """Stationary-A triangular solve (src/trsmA.cc): A's tiles stay put, the
    narrow B moves.  Explicit-method entry; trsm's select_algo picks this
    form automatically when B has one block column."""
    opts = Options.make(opts)
    return _trsm_dispatch(MethodTrsm.A, side, alpha, A, B, opts, uplo, diag)


def trsmB(side, alpha, A, B, opts=None, uplo=None, diag=None):
    """Stationary-B triangular solve (src/trsmB.cc): B's tiles stay put, A's
    panels are broadcast — the default for wide right-hand sides."""
    opts = Options.make(opts)
    return _trsm_dispatch(MethodTrsm.B, side, alpha, A, B, opts, uplo, diag)


# ---------------------------------------------------------------------------
# Aux drivers (add/copy/scale/set/norm)
# ---------------------------------------------------------------------------


def add(alpha, A, beta, B, opts=None):
    """B = alpha A + beta B (src/add.cc; tzadd for trapezoid operands)."""
    if isinstance(B, BaseTrapezoidMatrix):
        out = elementwise.tzadd(B.uplo, alpha, as_array(A), beta, as_array(B))
    else:
        out = elementwise.geadd(alpha, as_array(A), beta, as_array(B))
    return write_back(B, out)


def copy(A, B, opts=None):
    """B = A with dtype conversion (src/copy.cc; device_gecopy.cu)."""
    if isinstance(B, BaseTrapezoidMatrix):
        out = elementwise.tzcopy(B.uplo, as_array(A), as_array(B))
    else:
        out = elementwise.gecopy(as_array(A), as_array(B).dtype)
    return write_back(B, out)


def scale(numer, denom, A, opts=None):
    """A *= numer/denom (src/scale.cc)."""
    if isinstance(A, BaseTrapezoidMatrix):
        out = elementwise.tzscale(A.uplo, numer, denom, as_array(A))
    else:
        out = elementwise.gescale(numer, denom, as_array(A))
    return write_back(A, out)


def scale_row_col(R, C, A, opts=None):
    """A = diag(R) A diag(C) equilibration (src/scale_row_col.cc)."""
    out = elementwise.gescale_row_col(jnp.asarray(R), jnp.asarray(C), as_array(A))
    return write_back(A, out)


def set(offdiag_value, diag_value, A, opts=None):  # noqa: A001 - reference name
    """Set entries to constants (src/set.cc; geset/tzset kernels)."""
    if isinstance(A, BaseTrapezoidMatrix):
        out = elementwise.tzset(A.uplo, offdiag_value, diag_value, as_array(A))
    else:
        out = elementwise.geset(offdiag_value, diag_value, as_array(A))
    return write_back(A, out)


def set_from_function(value, A, opts=None):
    """Set entries A[i, j] = value(i, j) (src/set_lambdas.cc).

    TPU re-design: the reference evaluates a per-entry host lambda inside
    each tile task; here ``value`` receives broadcastable global index arrays
    (I of shape (m, 1), J of shape (1, n)) and is evaluated once, vectorized
    — jnp-traceable functions stay on device, numpy functions work too."""
    a = as_array(A)
    m, n = a.shape[-2:]
    I = jnp.arange(m)[:, None]
    J = jnp.arange(n)[None, :]
    vals = jnp.broadcast_to(jnp.asarray(value(I, J), dtype=a.dtype), a.shape)
    if isinstance(A, BaseTrapezoidMatrix):
        # only the stored triangle is set; the off-triangle of shared storage
        # passes through untouched (same contract as set()/tzset)
        from .core.types import Uplo

        mask = (I >= J) if A.uplo == Uplo.Lower else (I <= J)
        vals = jnp.where(mask, vals, a)
    return write_back(A, vals)


set_lambdas = set_from_function   # reference driver name (src/set_lambdas.cc)


def norm(norm_kind, A, opts=None, scope=NormScope.Matrix, uplo=None, diag=None):
    """Matrix norm dispatched on matrix type (src/norm.cc).

    General -> genorm, symmetric/Hermitian -> synorm/henorm, triangular -> trnorm,
    band -> gbnorm/hbnorm (internal_*norm.cc family).
    """
    from .core.matrix import distribution_grid
    from .core.types import Norm

    a = as_array(A)
    grid = distribution_grid(A)
    kind = Norm.from_string(norm_kind)
    the_scope = NormScope.from_string(scope)
    if (grid is not None and a.ndim == 2
            and kind in (Norm.Max, Norm.One, Norm.Inf, Norm.Fro)):
        # wrapper bound to a >1-device grid: sharded masked reduction.
        # Band and unit-diagonal triangles keep the local masked kernels.
        from .parallel import col_norms_distributed, norm_distributed

        general = not isinstance(A, (BaseTrapezoidMatrix, BaseBandMatrix))
        if the_scope == NormScope.Columns and general and kind == Norm.Max:
            return col_norms_distributed(a, grid)
        if the_scope == NormScope.Matrix:
            if isinstance(A, (HermitianMatrix, SymmetricMatrix)):
                return norm_distributed(kind, A.full_array(), grid)
            if (isinstance(A, BaseTrapezoidMatrix)
                    and _diag_of(A, diag) != Diag.Unit):
                return norm_distributed(kind, a, grid, uplo=str(A.uplo.value))
            if general:
                return norm_distributed(kind, a, grid)
    if isinstance(A, HermitianMatrix):
        return norm_ops.henorm(norm_kind, A.uplo, a)
    if isinstance(A, SymmetricMatrix):
        return norm_ops.synorm(norm_kind, A.uplo, a)
    if isinstance(A, BaseTrapezoidMatrix):
        return norm_ops.trnorm(norm_kind, A.uplo, A.diag, a)
    if isinstance(A, BaseBandMatrix):
        from .core.matrix import HermitianBandMatrix
        if isinstance(A, HermitianBandMatrix):
            return norm_ops.hbnorm(norm_kind, A.uplo, A.kd, a)
        # TriangularBandMatrix's (kl, ku) already encode triangle ∩ band exactly
        return norm_ops.gbnorm(norm_kind, A.kl, A.ku, a)
    return norm_ops.genorm(norm_kind, a, scope)


def col_norms(norm_kind, A, opts=None):
    """Per-column max norms (src/colNorms.cc; Norm.Max only, like the reference)."""
    return norm_ops.genorm(norm_kind, as_array(A), NormScope.Columns)
