"""Cholesky family: potrf / potrs / posv / trtri / trtrm / potri / posv_mixed.

Reference analogue: ``src/potrf.cc:22-281`` (the canonical lookahead task-DAG driver,
SURVEY.md §3.1), ``src/{potrs,posv,potri,trtri,trtrm,posv_mixed}.cc`` and the panel
kernel ``src/internal/internal_potrf.cc``.

TPU re-design of the potrf pipeline:

* The reference runs an OpenMP task DAG: factor diagonal tile -> MPI-bcast panel ->
  batched trsm -> batched herk trailing update, with lookahead columns prioritized
  (potrf.cc:84-195).  On TPU the same right-looking blocked recurrence is expressed as
  a *software-pipelined XLA program*: a Python-unrolled loop over block columns (static
  shapes per step, every matmul MXU-shaped), with no dynamic task runtime — XLA's async
  scheduler overlaps the (sharded) panel collectives with the trailing update, which is
  exactly what the lookahead machinery hand-builds in OpenMP.
* The panel factor (internal_potrf.cc -> lapack::potrf on one tile) is
  ``lax.linalg.cholesky`` on the nb x nb diagonal block; the panel trsm is XLA's native
  blocked TriangularSolve; the trailing herk is one fused matmul per step.
* ``Target.XLA`` routes the whole factorization to ``lax.linalg.cholesky`` — the
  analogue of calling the vendor library on a single tile when the matrix fits one
  device.  ``Target.Tiled`` (default for distributed or when nb is specified) runs the
  blocked recurrence above; it is the path that honors Options.block_size and shards
  over a mesh.

Non-SPD detection: the reference reduces an ``info`` code across ranks
(internal_reduce_info.cc, potrf.cc:208).  Here ``info`` is computed functionally from
the factor's diagonal (NaN or <= 0 -> first failing global index + 1, LAPACK-style).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax

from ..core.exceptions import SlateError
from ..core.matrix import (BaseMatrix, HermitianMatrix, SymmetricMatrix, as_array,
                           distribution_grid, write_back)
from ..core.types import Options, Target, Uplo
from ..ops import blas3
from ..robust import (RetryPolicy, Rung, SolveReport, first_bad_index, inject,
                      run_ladder)
from ..utils.trace import trace_block, trace_event
from ..obs import instrument


def _full_spd(A, uplo) -> jax.Array:
    """Materialize the full Hermitian matrix from a half-stored wrapper or array."""
    if isinstance(A, (HermitianMatrix, SymmetricMatrix)):
        return A.full_array()
    a = as_array(A)
    if uplo is None:
        return a  # trust caller: already full
    uplo = Uplo.from_string(uplo)
    if uplo == Uplo.Lower:
        strict = jnp.tril(a, -1)
    else:
        strict = jnp.triu(a, 1)
    other = jnp.conj(jnp.swapaxes(strict, -1, -2)) if jnp.iscomplexobj(a) \
        else jnp.swapaxes(strict, -1, -2)
    idx = jnp.arange(a.shape[-1])
    diag = jnp.diagonal(a, axis1=-2, axis2=-1)
    if jnp.iscomplexobj(a):
        diag = jnp.real(diag).astype(a.dtype)
    return (strict + other).at[..., idx, idx].set(diag)


def _stored_lower(A, uplo: Uplo) -> jax.Array:
    """The Hermitian matrix stored in the ``uplo`` triangle of ``A``, with its
    lower triangle valid and its strict upper triangle left as it is: the
    stored array itself for Lower, one conjugate transpose for Upper.  A
    complex diagonal is real-cast (zpotrf ignores its imaginary part)."""
    a = as_array(A)
    if uplo == Uplo.Upper:
        a = jnp.conj(jnp.swapaxes(a, -1, -2))
    if jnp.iscomplexobj(a):
        idx = jnp.arange(a.shape[-1])
        diag = jnp.real(jnp.diagonal(a, axis1=-2, axis2=-1)).astype(a.dtype)
        a = a.at[..., idx, idx].set(diag)
    return a


def _chol_info(L) -> jax.Array:
    """LAPACK-style info from a lower factor: 0 if SPD, else 1-based index of the
    first non-positive/NaN pivot — the shared info kernel
    (robust.first_bad_index, reference reduce_info semantics)."""
    d = jnp.real(jnp.diagonal(L, axis1=-2, axis2=-1))
    return first_bad_index(jnp.isnan(d) | (d <= 0))


def _host_chol_info(a, nb: int = 256) -> int:
    """Exact 1-based first-failing-pivot index, found by a host-side blocked
    factorization.  Runs only on the (exceptional) non-SPD path, because XLA's
    Cholesky NaN-fills the whole output and loses the index the reference reports
    via its per-tile info codes (potrf.cc:208)."""
    import numpy as np

    a = np.array(a, copy=True)
    n = a.shape[-1]
    for k0 in range(0, n, nb):
        k1 = min(k0 + nb, n)
        blk = a[k0:k1, k0:k1]
        try:
            Lkk = np.linalg.cholesky(blk)
        except np.linalg.LinAlgError:
            # scalar scan inside the failing block
            for j in range(k1 - k0):
                d = blk[j, j] - np.real(np.dot(blk[j, :j], np.conj(blk[j, :j])))
                if not (d > 0) or np.isnan(d):
                    return k0 + j + 1
                blk[j, j] = np.sqrt(d)
                if j + 1 < k1 - k0:
                    blk[j+1:, j] = (blk[j+1:, j]
                                    - blk[j+1:, :j] @ np.conj(blk[j, :j])) / blk[j, j]
            return k1  # shouldn't happen
        if k1 < n:
            # pan = A21 · Lkk^{-H}  (pan^H = Lkk^{-1} · A21^H)
            pan = np.linalg.solve(Lkk, a[k1:, k0:k1].conj().T).conj().T
            a[k1:, k1:] -= pan @ np.conj(pan.T)
            a[k1:, k0:k1] = pan
    return 0


_CHOL_BASE = 256


def _chol_blocked(a):
    """Recursive blocked Cholesky of one diagonal block: factor the leading
    half, one triangular solve, one Schur-complement MXU gemm, recurse.  XLA's
    fused Cholesky serializes its internal panel recursion and crawls on large
    blocks (round-6 chip evidence); the fused op runs only at the <=256 base."""
    n = a.shape[-1]
    if n <= _CHOL_BASE:
        # lower-triangle-only reference (XLA Cholesky ignores the upper
        # triangle): callers may hand in blocks whose upper triangle is
        # stale because the trailing updates maintain only the lower half
        return lax.linalg.cholesky(a, symmetrize_input=False)
    h = n // 2
    a11, a21, a22 = a[..., :h, :h], a[..., h:, :h], a[..., h:, h:]
    l11 = _chol_blocked(a11)
    l21 = lax.linalg.triangular_solve(l11, a21, left_side=False, lower=True,
                                      conjugate_a=True, transpose_a=True)
    s = a22 - jnp.matmul(l21, jnp.conj(jnp.swapaxes(l21, -1, -2)),
                         precision=lax.Precision.HIGHEST)
    l22 = _chol_blocked(s)
    zeros = jnp.zeros(a.shape[:-2] + (h, n - h), a.dtype)
    return jnp.concatenate(
        [jnp.concatenate([l11, zeros], axis=-1),
         jnp.concatenate([l21, l22], axis=-1)], axis=-2)


@lru_cache(maxsize=32)
def _potrf_tiled_fn(n: int, nb: int, dtype_str: str, inv_trsm: bool = False):
    """Build + jit the blocked right-looking factorization for static (n, nb).

    ``inv_trsm``: replace the panel TriangularSolve with an explicit
    inverse-apply — Linv = Lkk^{-1} once per step (one nb-wide solve), then
    panel = A21 · Linv^H as a full-rate MXU gemm.  TriangularSolve's internal
    blocking serializes against the MXU at large nb; the inverse-apply trades
    ~cond(Lkk)² local error amplification (fine for the f32 bench envelope)
    for pure gemm throughput — the classical GPU-library trsm trick, selected
    via ``Options.trsm_via_inverse`` (bench.py's potrf child maps the
    ``BENCH_POTRF_INVTRSM=1`` sweep env var onto it)."""

    nt = -(-n // nb)

    def fn(Af):
        L = Af
        for k in range(nt):
            k0, k1 = k * nb, min((k + 1) * nb, n)
            # panel factor (≅ internal::potrf on the diagonal tile, potrf.cc:96-102)
            Akk = L[k0:k1, k0:k1]
            Lkk = _chol_blocked(Akk)
            L = L.at[k0:k1, k0:k1].set(Lkk)
            if k1 < n:
                # panel trsm (≅ internal::trsm over the panel, potrf.cc:115-119);
                # the panel "broadcast" (tileBcast, potrf.cc:109) is implicit: XLA
                # inserts the all-gather when the operands are sharded.
                if inv_trsm:
                    eye_b = jnp.eye(k1 - k0, dtype=L.dtype)
                    Linv = lax.linalg.triangular_solve(
                        Lkk, eye_b, left_side=True, lower=True)
                    panel = jnp.matmul(L[k1:n, k0:k1],
                                       jnp.conj(Linv.T),
                                       precision=lax.Precision.HIGHEST)
                else:
                    panel = lax.linalg.triangular_solve(
                        Lkk, L[k1:n, k0:k1], left_side=False, lower=True,
                        conjugate_a=True, transpose_a=True)
                L = L.at[k1:n, k0:k1].set(panel)
                # trailing update (≅ internal::herk, potrf.cc:136-148 — the hot
                # loop).  Blocked herk: one trapezoidal gemm per block-column
                # group on/below the diagonal instead of the full panel·panelᴴ
                # square — flop factor (1 + 1/S)/2 of the square at S groups,
                # i.e. 0.56x at the S=8 cap (exact halving when few columns
                # remain).  S is capped so the unrolled program stays O(8·nt)
                # ops, and beyond the same nt=32 unroll bound solvers.py caps
                # at, S=1 degenerates to the single full-square update (whose
                # Hermitian add keeps both triangles valid, as before).  Only
                # the lower triangle of the trailing block is maintained;
                # every later read (diagonal-block Cholesky, sub-diagonal
                # panels) references the lower half only (_chol_blocked
                # factors with symmetrize_input=False).
                rem = nt - (k + 1)
                S = min(rem, 8) if nt <= 32 else 1
                for i in range(S):
                    jb0 = k + 1 + (i * rem) // S
                    jb1 = k + 1 + ((i + 1) * rem) // S
                    j0, j1 = jb0 * nb, min(jb1 * nb, n)
                    s = j0 - k1
                    upd = jnp.matmul(panel[s:, :],
                                     jnp.conj(panel[s:j1 - k1, :].T),
                                     precision=lax.Precision.HIGHEST)
                    L = L.at[j0:n, j0:j1].add(-upd)
        return jnp.tril(L)

    return jax.jit(fn)


@instrument
def potrf(A, opts=None, uplo=None):
    """Cholesky factorization A = L L^H (src/potrf.cc:262-281 dispatch shape).

    Returns ``(L, info)``; writes the factor back into the stored triangle of ``A`` if
    it is a Matrix wrapper.  ``uplo=Upper`` returns/stores U with A = U^H U.
    """
    opts = Options.make(opts)
    the_uplo = uplo or (A.uplo if isinstance(A, BaseMatrix) and A.uplo != Uplo.General
                        else Uplo.Lower)
    the_uplo = Uplo.from_string(the_uplo)
    herm = isinstance(A, (HermitianMatrix, SymmetricMatrix))
    src_uplo = A.uplo if herm else the_uplo
    grid = distribution_grid(A)
    # one chip factors the stored triangle where it lies (both factorizations
    # read the lower half only); the sharded factorization, and a complex
    # symmetric wrapper, which is not Hermitian, take the full matrix
    full = grid is not None or (isinstance(A, SymmetricMatrix)
                                and jnp.iscomplexobj(as_array(A)))
    # the phases' scopes name the compiled operations (potrf/prep, ...)
    with trace_block("prep"):
        if full:
            Af = _full_spd(A, None if herm else the_uplo)
        else:
            Af = _stored_lower(A, src_uplo)
    Af = inject("potrf", Af)
    n = Af.shape[-1]
    target = opts.target
    if target == Target.Auto:
        target = Target.XLA  # single fused factorization; Tiled for distributed runs

    source = "full" if full else ("stored_lower" if src_uplo == Uplo.Lower
                                  else "transposed_upper")
    with trace_block("factor", n=n, nb=opts.block_size, target=str(target),
                     input=source):
        if grid is not None:
            # the wrapper carries a >1-device process grid: run the sharded
            # factorization over it (reference: distribution installed at
            # construction is consumed by every driver)
            from ..parallel import potrf_distributed

            L = potrf_distributed(Af, grid, nb=min(opts.block_size, n),
                                  lookahead=opts.lookahead)
        elif target == Target.XLA:
            L = lax.linalg.cholesky(Af, symmetrize_input=full)
        else:
            L = _potrf_tiled_fn(n, min(opts.block_size, n), str(Af.dtype),
                                inv_trsm=opts.trsm_via_inverse)(Af)
    if grid is None and target == Target.XLA:
        # XLA's Cholesky leaves the strict upper triangle unspecified
        with trace_block("mask"):
            L = jnp.tril(L)
    with trace_block("info"):
        info = _chol_info(L)
    if opts.exact_info and int(info) != 0:
        # opt-in host refinement: XLA's Cholesky NaN-fills the whole factor, so
        # the exact first-failing-pivot index needs a host pass.  Off by
        # default — the int() is a device→host sync on every call (hot-path
        # hazard), and potrf stays fully jittable without it.
        info = jnp.int32(_host_chol_info(Af))

    with trace_block("store"):
        out = L if the_uplo == Uplo.Lower else jnp.conj(L.T)
        if isinstance(A, BaseMatrix):
            # store only into the stored triangle, leave the rest untouched
            stored = as_array(A)
            mask = jnp.tril(jnp.ones_like(stored, dtype=bool)) \
                if the_uplo == Uplo.Lower \
                else jnp.triu(jnp.ones_like(stored, dtype=bool))
            write_back(A, jnp.where(mask, out, stored))
    return out, info


def posv_core(a, b):
    """Pure single-matrix posv kernel: fused Cholesky + the two triangular
    sweeps — no wrappers, injection, tracing, or host syncs.  Expects the
    *full* Hermitian matrix (the serving layer hands in dense operands, not
    half-stored wrappers).  vmap-compatible: :mod:`slate_tpu.serve` maps this
    over a leading batch axis.  Returns ``(x, info)`` with the per-matrix
    LAPACK info from the factor diagonal."""
    L = lax.linalg.cholesky(a, symmetrize_input=False)
    info = _chol_info(L)
    y = lax.linalg.triangular_solve(L, b, left_side=True, lower=True)
    x = lax.linalg.triangular_solve(L, y, left_side=True, lower=True,
                                    conjugate_a=True, transpose_a=True)
    return x, info


def potrs(A, B, opts=None, uplo=None):
    """Solve A X = B given the Cholesky factor (src/potrs.cc: two work::trsm calls)."""
    opts = Options.make(opts)
    the_uplo = Uplo.from_string(uplo or (A.uplo if isinstance(A, BaseMatrix)
                                         and A.uplo != Uplo.General else Uplo.Lower))
    grid = distribution_grid(A, B)
    span = {} if grid is None else {"grid": f"{grid.p}x{grid.q}"}
    # the phases' scopes name the compiled operations (potrs/prep, ...)
    with trace_block("potrs", **span):
        with trace_block("prep"):
            F = as_array(A)
            L = jnp.tril(F) if the_uplo == Uplo.Lower else jnp.conj(jnp.triu(F).T)
        b = as_array(B)
        if grid is not None:
            # grid-bound operands: the stationary-A sweeps (trsmA.cc; the
            # factor never moves, nb x nrhs blocks of X travel).  XLA's own
            # transposed TriangularSolve over a 2x2-sharded factor needs 17.6
            # GB per device at n=16384 (described-v5e compile), more than the
            # chip holds.
            from ..parallel.solvers import trsmA_distributed

            with trace_block("forward"):
                y = trsmA_distributed(L, b, grid, lower=True)
            with trace_block("backward"):
                x = trsmA_distributed(L, y, grid, lower=True, conj_trans=True)
        else:
            with trace_block("forward"):
                y = lax.linalg.triangular_solve(L, b, left_side=True, lower=True)
            with trace_block("backward"):
                x = lax.linalg.triangular_solve(L, y, left_side=True, lower=True,
                                                conjugate_a=True, transpose_a=True)
        with trace_block("store"):
            return write_back(B, x)


@instrument
def posv(A, B, opts=None, uplo=None):
    """Solve SPD system A X = B (src/posv.cc = potrf + potrs).

    Returns (X, info); with ``Options(solve_report=True)``,
    (X, info, SolveReport)."""
    opts = Options.make(opts)
    L, info = potrf(A, opts, uplo)
    X = potrs(L if not isinstance(A, BaseMatrix) else A, B, opts,
              uplo=uplo or (A.uplo if isinstance(A, BaseMatrix)
                            and A.uplo != Uplo.General else "lower"))
    if opts.solve_report:
        report = SolveReport(routine="posv", info=int(info),
                             precision_used=str(as_array(L).dtype),
                             fallback_chain=("cholesky",)).finalize()
        report.recovered = report.info == 0
        return X, info, report
    return X, info


def trtri(A, opts=None, uplo=None, diag=None):
    """Triangular inverse (src/trtri.cc).

    The reference runs a blocked in-place algorithm; on TPU a TriangularSolve against
    the identity is the same blocked computation executed by one fused XLA op.
    """
    from ..blas import _diag_of  # local import to avoid cycle
    the_uplo = _default_uplo(A, uplo)
    the_diag = _diag_of(A, diag)
    a = as_array(A)
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=a.dtype)
    with trace_block("trtri", n=n):
        inv = lax.linalg.triangular_solve(
            a, eye, left_side=True, lower=(the_uplo == Uplo.Lower),
            unit_diagonal=(the_diag.value == "unit"))
    tri = jnp.tril if the_uplo == Uplo.Lower else jnp.triu
    return _write_triangle(A, tri(inv), the_uplo)


def trtrm(A, opts=None, uplo=None):
    """Triangular-triangular multiply L^H L (or U U^H) producing a Hermitian result in
    the stored triangle — the second half of potri (src/trtrm.cc)."""
    the_uplo = _default_uplo(A, uplo)
    a = as_array(A)
    if the_uplo == Uplo.Lower:
        L = jnp.tril(a)
        out = jnp.matmul(jnp.conj(L.T), L, precision=lax.Precision.HIGHEST)
        res = jnp.tril(out)
    else:
        U = jnp.triu(a)
        out = jnp.matmul(U, jnp.conj(U.T), precision=lax.Precision.HIGHEST)
        res = jnp.triu(out)
    return _write_triangle(A, res, the_uplo)


def _default_uplo(A, uplo) -> Uplo:
    """Resolve uplo like the sibling drivers: wrapper flag, else Lower."""
    return Uplo.from_string(uplo or (A.uplo if isinstance(A, BaseMatrix)
                                     and A.uplo != Uplo.General else Uplo.Lower))


def _write_triangle(A, tri_result, uplo: Uplo):
    """Write a triangular result into only the stored triangle of a wrapper,
    preserving the unstored triangle (matches potrf's write-back discipline)."""
    if not isinstance(A, BaseMatrix):
        return tri_result
    stored = as_array(A)
    mask = jnp.tril(jnp.ones_like(stored, dtype=bool)) if uplo == Uplo.Lower \
        else jnp.triu(jnp.ones_like(stored, dtype=bool))
    write_back(A, jnp.where(mask, tri_result, stored))
    return tri_result


@instrument
def potri(A, opts=None, uplo=None):
    """SPD inverse from the Cholesky factor: A^{-1} = L^{-H} L^{-1}
    (src/potri.cc = trtri + trtrm)."""
    the_uplo = _default_uplo(A, uplo)
    Linv = trtri(A, opts, uplo=the_uplo, diag="nonunit")
    return trtrm(A if isinstance(A, BaseMatrix) else Linv, opts, uplo=the_uplo)


# ---------------------------------------------------------------------------
# Mixed-precision iterative refinement (src/posv_mixed.cc, gesv_mixed.cc:23-40)
# ---------------------------------------------------------------------------


def _lower_precision(dtype):
    """The reference factors f64 systems in f32 (gesv_mixed): f64->f32, c128->c64.

    f32 has no lower rung: XLA's LU/Cholesky do not accept bfloat16 operands (the
    MXU already uses bf16 multipliers inside f32 matmuls), so f32 inputs fall back
    to the plain full-precision solve."""
    mapping = {
        jnp.dtype(jnp.float64): jnp.float32,
        jnp.dtype(jnp.complex128): jnp.complex64,
    }
    return mapping.get(jnp.dtype(dtype))


def _ir_solve(Af, b, solve_lo, opts: Options):
    """Generic iterative-refinement loop shared by posv_mixed/gesv_mixed
    (gesv_mixed.cc iterative loop): solve in low precision, refine the residual in
    working precision, stop on ||r|| <= ||x|| * ||A|| * sqrt(n) * eps."""
    n = Af.shape[-1]
    eps = jnp.finfo(Af.dtype).eps if jnp.issubdtype(Af.dtype, jnp.floating) else \
        jnp.finfo(jnp.float64 if Af.dtype == jnp.complex128 else jnp.float32).eps
    tol = opts.tolerance if opts.tolerance is not None else float(eps) * (n ** 0.5)
    anorm = jnp.max(jnp.sum(jnp.abs(Af), axis=-1))  # inf-norm

    x0 = solve_lo(b).astype(b.dtype)

    def cond(state):
        x, it, converged = state
        return (~converged) & (it < opts.max_iterations)

    def body(state):
        x, it, _ = state
        r = b - jnp.matmul(Af, x, precision=lax.Precision.HIGHEST)
        dx = solve_lo(r).astype(b.dtype)
        x = x + dx
        rnorm = jnp.max(jnp.abs(b - jnp.matmul(Af, x, precision=lax.Precision.HIGHEST)))
        xnorm = jnp.max(jnp.abs(x))
        converged = rnorm <= tol * anorm * xnorm
        return x, it + 1, converged

    r0 = b - jnp.matmul(Af, x0, precision=lax.Precision.HIGHEST)
    conv0 = jnp.max(jnp.abs(r0)) <= tol * anorm * jnp.max(jnp.abs(x0))
    x, iters, converged = lax.while_loop(cond, body, (x0, jnp.int32(0), conv0))
    return x, iters, converged


@instrument
def posv_mixed(A, B, opts=None, uplo=None):
    """SPD solve: low-precision factor + working-precision refinement
    (src/posv_mixed.cc), run as the declared mixed→full escalation ladder
    (robust.LADDERS["posv_mixed"]; Option::UseFallbackSolver gates the second
    rung, gesv_mixed.cc:93-96).

    Returns (X, info, iters); with ``Options(solve_report=True)``,
    (X, info, iters, SolveReport).
    """
    opts = Options.make(opts)
    the_uplo = uplo or (A.uplo if isinstance(A, BaseMatrix) and A.uplo != Uplo.General
                        else Uplo.Lower)
    Af0 = _full_spd(A, None if isinstance(A, (HermitianMatrix, SymmetricMatrix))
                    else the_uplo)
    # pristine snapshot: each rung re-enters the input injection site, so a
    # call_index=0 input fault is transient under escalation — the full-
    # precision rung recovers from intact data, never a corrupted copy
    b = as_array(B)
    plain = opts.replace(solve_report=False)
    lo = opts.factor_precision or _lower_precision(Af0.dtype)
    report = SolveReport(routine="posv_mixed") if opts.solve_report else None
    if lo is None:
        Af = inject("posv_mixed", Af0)
        if Af is Af0 and isinstance(A, BaseMatrix):
            # no fault fired → original wrapper through posv, keeping its
            # in-place L-factor write-back (pre-ladder contract)
            X, info = posv(A, b, plain, uplo)
        else:
            X, info = posv(Af, b, plain, "lower")
        X = write_back(B, as_array(X))
        if report is not None:
            report.record_rung("full")
            report.info, report.precision_used = int(info), str(Af0.dtype)
            report.recovered = report.info == 0
            return X, info, jnp.int32(0), report.finalize()
        return X, info, jnp.int32(0)

    state = {"iters": jnp.int32(0)}

    def mixed_rung():
        Af = inject("posv_mixed", Af0)
        with trace_block("posv_mixed", lo=str(lo)):
            L_lo = lax.linalg.cholesky(Af.astype(lo))
            L_lo = inject("posv_mixed", L_lo, point="factor")
            info = _chol_info(L_lo)

            def solve_lo(rhs):
                y = lax.linalg.triangular_solve(L_lo, rhs.astype(lo),
                                                left_side=True, lower=True)
                return lax.linalg.triangular_solve(L_lo, y, left_side=True,
                                                   lower=True, conjugate_a=True,
                                                   transpose_a=True)

            x, iters, converged = _ir_solve(Af, b, solve_lo, opts)
        state["iters"] = iters
        return (x, info), bool(converged)

    def full_rung():
        Af = inject("posv_mixed", Af0)
        if Af is Af0 and isinstance(A, BaseMatrix):
            # no fault fired → original wrapper through posv, preserving its
            # in-place L-factor write-back (the mixed rung never touched it)
            X, info = posv(A, b, plain, uplo)
        else:
            X, info = posv(Af, b, plain, "lower")   # full-precision fallback
        return (as_array(X), info), bool(info == 0)

    rungs = [Rung("mixed", mixed_rung)]
    if opts.use_fallback_solver:
        rungs.append(Rung("full", full_rung))
    x, info = run_ladder("posv_mixed", rungs,
                         RetryPolicy.from_options(opts, "posv_mixed"), report)
    X = write_back(B, x)
    if report is not None:
        report.info = int(info)
        report.iters = int(state["iters"])
        report.precision_used = (str(jnp.dtype(lo)) if report.fallback_chain
                                 == ("mixed",) else str(Af0.dtype))
        return X, info, state["iters"], report.finalize()
    return X, info, state["iters"]


@instrument
def posv_mixed_gmres(A, B, opts=None, uplo=None):
    """SPD GMRES-IR: FGMRES in working precision, right-preconditioned by the
    low-precision Cholesky solve (src/posv_mixed_gmres.cc; single RHS like the
    reference). Returns (X, info, iters)."""
    from .lu import _gmres_ir, _require_single_rhs

    opts = Options.make(opts)
    the_uplo = uplo or (A.uplo if isinstance(A, BaseMatrix) and A.uplo != Uplo.General
                        else Uplo.Lower)
    Af = _full_spd(A, None if isinstance(A, (HermitianMatrix, SymmetricMatrix))
                   else the_uplo)
    b = as_array(B)
    _require_single_rhs(b, "posv_mixed_gmres")
    lo = opts.factor_precision or _lower_precision(Af.dtype)
    if lo is None:
        # solve_report stays off here: posv would otherwise append a report
        # and break this 2-way unpack (posv_mixed_gmres has no report form)
        X, info = posv(A, B, opts.replace(solve_report=False), uplo)
        return X, info, jnp.int32(0)

    with trace_block("posv_mixed_gmres", lo=str(lo)):
        L_lo = lax.linalg.cholesky(Af.astype(lo))
        info = _chol_info(L_lo)

        def precond(r):
            y = lax.linalg.triangular_solve(L_lo, r.astype(lo)[:, None],
                                            left_side=True, lower=True)
            z = lax.linalg.triangular_solve(L_lo, y, left_side=True, lower=True,
                                            conjugate_a=True, transpose_a=True)
            return z[:, 0].astype(b.dtype)

        def matvec(x):
            return jnp.matmul(Af, x, precision=lax.Precision.HIGHEST)

        x_out, restarts, converged = _gmres_ir(matvec, precond, b, opts,
                                               "posv_mixed_gmres")

    if opts.use_fallback_solver and not converged:
        # mixed_gmres→full ladder (robust.LADDERS), open-coded like
        # gesv_mixed_gmres; the event keeps the escalation traceable
        trace_event("fallback", routine="posv_mixed_gmres", to="full")
        X, info = posv(A, B, opts.replace(solve_report=False), uplo)
        return X, info, jnp.int32(-1)
    return write_back(B, x_out), info, jnp.int32(restarts)
