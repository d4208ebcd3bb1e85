"""slate_tpu — a TPU-native distributed dense linear algebra framework.

A brand-new JAX/XLA/Pallas re-design with the capabilities of SLATE (Software for Linear
Algebra Targeting Exascale): tiled distributed matrices with pluggable 2D block-cyclic
layouts, parallel BLAS-3, linear solvers (Cholesky / LU variants / mixed-precision
iterative refinement / band), least squares (QR, CholQR), and eigenvalue/SVD drivers —
where a ``jax.sharding.Mesh`` over a TPU pod slice replaces the reference's MPI process
grid, ICI collectives replace tile broadcasts, and XLA/Pallas kernels replace
cuBLAS/CUDA (see SURVEY.md for the layer-by-layer mapping).

Public API mirrors ``include/slate/slate.hh`` (~92 routines) with snake_case names; the
verb-style convenience layer mirroring ``include/slate/simplified_api.hh`` lives in
:mod:`slate_tpu.simplified`.
"""

from .core import (BandMatrix, BaseMatrix, ConvergenceError,
                   DeadlineExceededError, Diag, GridOrder,
                   HermitianBandMatrix, HermitianMatrix, Layout, Matrix,
                   MethodCholQR, MethodEig, MethodGels, MethodGemm, MethodHemm,
                   MethodLU, MethodSVD, MethodTrsm, Norm, NormScope,
                   NumericalError, Op, Options, QueueOverloadError, Side,
                   SingularMatrixError, SlateError, SymmetricMatrix, Target,
                   TileKind, TrapezoidMatrix, TriangularBandMatrix,
                   TriangularMatrix, Uplo, func)

from .blas import (add, col_norms, copy, gemm, gemmA, gemmC, hemm, hemmA,
                   hemmC, her2k, herk, norm, scale, scale_row_col, set,
                   set_from_function, set_lambdas, symm, syr2k, syrk, trmm,
                   trsm, trsmA, trsmB)
from .linalg import (bdsqr, cholqr, gbmm, gbsv, gbtrf, gbtrs, ge2tb, ge2tb_band, gecondest,
                     gelqf, gels, gels_cholqr, gels_qr, geqrf, gerbt, gesv,
                     gesv_mixed, gesv_mixed_gmres, gesv_nopiv, gesv_rbt, getrf,
                     getrf_nopiv, getrf_tntpiv, getri, getri_oop, getrs,
                     getrs_nopiv, hb2st, hbmm, he2hb, he2hb_q, heev,
                     heev_range, eig_count, hegst, hegv_range,
                     hegv, hesv, hetrf, hetrs, norm1est, pbsv, pbtrf, pbtrs,
                     pocondest, posv, posv_mixed, posv_mixed_gmres, potrf, potri,
                     potrs, stedc, stedc_deflate, stedc_merge, stedc_secular,
                     stedc_solve, stedc_sort, stedc_z_vector, stein, steqr,
                     steqr2, sterf, sterf_bisect, svd, svd_range, svd_vals,
                     syev, sygst,
                     sygv, sysv, sytrf,
                     sytrs, tb2bd, tbsm, tbsm_pivots, tbsmPivots, trcondest,
                     trtri, trtrm, unmbr_ge2tb,
                     unmbr_tb2bd, unmlq, unmqr, unmtr_hb2st, unmtr_he2hb)
from . import robust
from .robust import (FaultPlan, FaultSpec, RetryPolicy, SolveReport,
                     reduce_info)
from . import serve
from .serve import gels_batched, gesv_batched, posv_batched
from . import simplified
from . import matgen
from . import native
from .utils import debug, load_matrix, print_matrix, save_matrix, trace
from .matgen import generate_matrix
from .ops.f64emu import gemm_f64emu, gesv_f64ir, posv_f64ir
from . import lapack_api
from . import scalapack_api
from . import parallel

__version__ = "0.2.0"
VERSION = 2026_07_00   # yyyymmrr, the reference's integer form (version.cc)


def version() -> int:
    """Library version as the reference's yyyymmrr integer
    (src/version.cc: slate::version())."""
    return VERSION


def id() -> str:  # noqa: A001 - reference name (slate::id)
    """Git commit hash of this build, or "unknown" (src/version.cc: slate::id())."""
    import os
    import subprocess

    try:
        pkg = os.path.realpath(__path__[0])
        # an installed copy may sit under an unrelated enclosing repo — only
        # report a hash when the repo actually *tracks* this package
        tracked = subprocess.run(
            ["git", "ls-files", "--error-unmatch", pkg], capture_output=True,
            text=True, timeout=5, cwd=pkg)
        if tracked.returncode != 0:
            return "unknown"
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=5, cwd=pkg).stdout.strip() or "unknown"
    # slate-lint: disable=SLT501 -- git metadata probe: the block runs only
    # subprocess/os calls, the NumericalError taxonomy cannot arise here
    except Exception:
        return "unknown"
