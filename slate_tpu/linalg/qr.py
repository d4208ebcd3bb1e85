"""QR/LQ factorizations and least squares: geqrf / gelqf / unmqr / unmlq / tsqr (CAQR)
/ cholqr / gels.

Reference analogue (SURVEY.md §2.4 QR/LS row): ``src/geqrf.cc`` (CAQR: multithreaded
Householder panel internal_geqrf.cc + triangle-triangle tree reduction
internal_ttqrt.cc), ``src/gelqf.cc``, ``src/{unmqr,unmlq}.cc``, ``src/cholqr.cc``,
``src/{gels,gels_qr,gels_cholqr}.cc``; ``TriangularFactors`` is the reference's
``vector<Matrix>`` of block-reflector T factors (slate.hh:857).

TPU re-design:

* **Panel QR** is ``jnp.linalg.qr(mode='raw')`` — XLA's native Householder
  factorization returning the packed V + tau form (the per-tile geqrf of
  Tile_geqrf.hh).
* **Block reflector T** (the reference accumulates it column-by-column in the panel
  loop, internal_geqrf.cc:79-124) is computed *in closed form*: with V the unit lower
  trapezoid and S = V^H V, orthogonality of Q = I - V T V^H forces
  T^{-1} + T^{-H} = S, so ``T = inv(triu(S, 1) + diag(1/tau))`` — one gemm plus one
  k x k triangular solve, fully MXU-parallel instead of a length-k recurrence.
* **Applying Q** (unmqr/unmlq; reference replays the panel+tree tasks in reverse,
  unmqr.cc + internal_ttmqr.cc) is three gemms: Q^H C = C - V (T^H (V^H C)).
* **TSQR/CAQR tree** (ttqrt's triangle-triangle reduction over mesh rows) is
  ``tsqr``: leaf QRs over row blocks + a binary tree of stacked-R QRs; the Q factor
  is reconstructed down the tree.  This is the communication-avoiding shape that
  rides a mesh axis all-gather (distributed form lives in parallel/).
* **CholQR** (cholqr.cc; MethodCholQR Herk/Gemm variants for the Gram matrix) with
  the CholeskyQR2 re-orthogonalization pass and a shifted retry when the Gram matrix
  is numerically indefinite (the reference falls back to QR inside gels_cholqr).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.exceptions import SlateError
from ..core.matrix import BaseMatrix, as_array, distribution_grid, write_back
from ..core.types import MethodGels, Op, Options, Side
from ..robust import inject
from ..utils.trace import trace_block
from ..ops.blas3 import gram
from .chol import _chol_blocked, _chol_info
from ..obs import instrument


@dataclasses.dataclass
class TriangularFactors:
    """Block-Householder factors (reference TriangularFactors, slate.hh:857):
    ``packed`` holds R in the upper triangle and the reflector columns V below the
    diagonal (LAPACK geqrf layout); ``tau`` the reflector scalars; ``T`` the k x k
    block-reflector triangle."""

    packed: jax.Array   # (m, k)
    tau: jax.Array      # (k,)
    T: jax.Array        # (k, k) upper triangular

    @property
    def m(self):
        return self.packed.shape[-2]

    @property
    def k(self):
        return self.tau.shape[-1]

    def V(self) -> jax.Array:
        """Unit lower-trapezoid reflector matrix."""
        k = self.k
        V = jnp.tril(self.packed, -1)[..., :, :k]
        idx = jnp.arange(k)
        return V.at[..., idx, idx].set(jnp.ones((), self.packed.dtype))

    def Q(self, full: bool = False) -> jax.Array:
        """Materialize the (reduced) orthogonal factor via householder_product."""
        if not full:
            return lax.linalg.householder_product(self.packed, self.tau)
        m, k = self.m, self.k
        pad = jnp.zeros((m, m - k), dtype=self.packed.dtype)
        packed_f = jnp.concatenate([self.packed, pad], axis=-1)
        tau_f = jnp.concatenate([self.tau, jnp.zeros((m - k,), self.tau.dtype)])
        return lax.linalg.householder_product(packed_f, tau_f)

    def R(self) -> jax.Array:
        return jnp.triu(self.packed[..., : self.k, :])


def _block_T(V, tau):
    """Closed-form block-reflector triangle: T = inv(triu(S,1) + diag(1/tau)),
    S = V^H V (see module docstring)."""
    S = jnp.matmul(jnp.conj(jnp.swapaxes(V, -1, -2)), V,
                   precision=lax.Precision.HIGHEST)
    k = tau.shape[-1]
    inv_tau = jnp.where(tau == 0, jnp.inf, 1.0 / tau)
    Tinv = jnp.triu(S, 1) + jnp.zeros_like(S).at[..., jnp.arange(k), jnp.arange(k)
                                                 ].set(inv_tau)
    eye = jnp.eye(k, dtype=V.dtype)
    T = lax.linalg.triangular_solve(Tinv, eye, left_side=True, lower=False)
    # zero columns where tau == 0 (identity reflectors contribute nothing)
    return jnp.where(tau[..., None, :] == 0, 0, T)


@instrument
def geqrf(A, opts=None):
    """QR factorization A = Q R (src/geqrf.cc). Returns TriangularFactors; writes the
    packed factor back into a Matrix wrapper (R in the upper triangle, V below)."""
    opts = Options.make(opts)
    a = inject("geqrf", as_array(A))
    m, n = a.shape[-2:]
    k = min(m, n)
    with trace_block("geqrf", m=m, n=n):
        h, tau = jnp.linalg.qr(a, mode="raw")
        packed = jnp.swapaxes(h, -1, -2)  # numpy raw convention is transposed
        fac = TriangularFactors(packed=packed[..., :, :], tau=tau,
                                T=None)  # type: ignore[arg-type]
        V = jnp.tril(packed[..., :, :k], -1).at[..., jnp.arange(k), jnp.arange(k)
                                                ].set(jnp.ones((), a.dtype))
        fac.T = _block_T(V, tau)
    write_back(A, packed) if isinstance(A, BaseMatrix) else None
    return fac


@instrument
def gelqf(A, opts=None):
    """LQ factorization A = L Q (src/gelqf.cc) via QR of A^H: A^H = Q1 R1 =>
    A = R1^H Q1^H. Returns TriangularFactors of A^H."""
    a = as_array(A)
    fac = geqrf(jnp.conj(jnp.swapaxes(a, -1, -2)), opts)
    if isinstance(A, BaseMatrix):
        write_back(A, jnp.conj(jnp.swapaxes(fac.packed, -1, -2)))
    return fac


def unmqr(side, op, factors: TriangularFactors, C, opts=None):
    """Multiply by Q from geqrf (src/unmqr.cc): C := op(Q) C or C op(Q) using the
    compact WY form, Q = I - V T V^H."""
    side = Side.from_string(side)
    op = Op.from_string(op)
    V = factors.V()
    T = factors.T
    c = as_array(C)
    if op == Op.Trans and jnp.iscomplexobj(c):
        # LAPACK unmqr likewise rejects plain transpose for complex factors
        raise SlateError("unmqr: Op.Trans unsupported for complex; use ConjTrans")
    Tm = T if op == Op.NoTrans else jnp.conj(jnp.swapaxes(T, -1, -2))
    with trace_block("unmqr"):
        if side == Side.Left:
            # op(Q) C = C - V op(T) (V^H C)
            W = jnp.matmul(jnp.conj(jnp.swapaxes(V, -1, -2)), c,
                           precision=lax.Precision.HIGHEST)
            out = c - jnp.matmul(V, jnp.matmul(Tm, W),
                                 precision=lax.Precision.HIGHEST)
        else:
            # C op(Q) = C - (C V) op(T) V^H
            W = jnp.matmul(c, V, precision=lax.Precision.HIGHEST)
            out = c - jnp.matmul(jnp.matmul(W, Tm),
                                 jnp.conj(jnp.swapaxes(V, -1, -2)),
                                 precision=lax.Precision.HIGHEST)
    return write_back(C, out)


def unmlq(side, op, factors: TriangularFactors, C, opts=None):
    """Multiply by Q from gelqf (src/unmlq.cc). With A = L Q, Q = Q1^H where Q1 is
    the QR factor of A^H, so op(Q) flips the op on Q1."""
    op = Op.from_string(op)
    if op == Op.Trans and jnp.iscomplexobj(factors.packed):
        raise SlateError("unmlq: Op.Trans unsupported for complex; use ConjTrans")
    flip = {Op.NoTrans: Op.ConjTrans, Op.ConjTrans: Op.NoTrans,
            Op.Trans: Op.NoTrans}[op]
    return unmqr(side, flip, factors, C, opts)


# ---------------------------------------------------------------------------
# TSQR / CAQR tree
# ---------------------------------------------------------------------------


def tsqr(a, row_blocks: int = 0, nb: int = 1024):
    """Tall-skinny QR by binary tree reduction (the CAQR pattern of
    internal_ttqrt.cc: leaf QRs + pairwise triangle-triangle QRs up the tree).

    Returns (Q, R) with Q explicit reduced (m x n).  The distributed version runs the
    same tree over a mesh axis (parallel/).
    """
    m, n = a.shape[-2:]
    if row_blocks <= 0:
        row_blocks = max(1, min(m // max(n, 1), -(-m // nb)))
    if row_blocks <= 1 or m < 2 * n:
        return lax.linalg.qr(a, full_matrices=False)

    # split into row blocks (pad to equal size)
    bs = -(-m // row_blocks)
    pad = bs * row_blocks - m
    ap = jnp.pad(a, ((0, pad), (0, 0)))
    blocks = ap.reshape(row_blocks, bs, n)
    # leaf QRs, batched
    Qs, Rs = lax.linalg.qr(blocks, full_matrices=False)
    levels = [Qs]  # per-level Q stacks
    while Rs.shape[0] > 1:
        nblk = Rs.shape[0]
        if nblk % 2 == 1:
            Rs = jnp.concatenate([Rs, jnp.zeros((1, n, n), Rs.dtype)], axis=0)
            nblk += 1
        paired = Rs.reshape(nblk // 2, 2 * n, n)
        Qp, Rs = lax.linalg.qr(paired, full_matrices=False)
        levels.append(Qp)
    R = Rs[0]
    # reconstruct Q down the tree: start from the root's identity coupling
    Qacc = jnp.eye(n, dtype=a.dtype)[None]          # (1, n, n)
    for Qp in reversed(levels[1:]):
        npair = Qp.shape[0]
        # each pair contributes two n-row slices of Q
        Qfull = jnp.matmul(Qp, Qacc[:npair])        # (npair, 2n, n)
        Qacc = Qfull.reshape(npair * 2, n, n)
    Qacc = Qacc[: levels[0].shape[0]]
    Q = jnp.matmul(levels[0], Qacc).reshape(row_blocks * bs, n)[:m]
    return Q, R


@instrument
def cholqr(A, opts=None):
    """Cholesky QR (src/cholqr.cc): R = chol(A^H A)^H upper, Q = A R^{-1}, with a
    CholeskyQR2 second pass for orthogonality and a shifted retry if the Gram matrix
    is numerically indefinite. Returns (Q, R).

    The cholqr→shifted→Householder escalation is an IN-TRACE ladder
    (``lax.cond`` chain, declared in robust.LADDERS["cholqr"]): hoisting it
    to the host would cost a sync per call, so unlike the mixed-precision
    ladders it stays inside the jitted program."""
    opts = Options.make(opts)
    a = inject("cholqr", as_array(A))
    m, n = a.shape[-2:]

    def q_from_chol(L, x):
        # Q = x · L^{-H} via inverting the small n×n triangle and one MXU gemm.
        # A right-side blocked TriangularSolve over the tall x materializes
        # O(m·n) temps per column block inside XLA — it OOMs a single chip at
        # the BASELINE 131072×4096 config — while the inverse is n×n and the
        # product is a single (m,n)·(n,n) matmul (the trtri+gemm trsm shape).
        # CholeskyQR2's second pass absorbs the extra rounding of the explicit
        # inverse.
        eye = jnp.broadcast_to(jnp.eye(n, dtype=L.dtype), L.shape)
        Linv = lax.linalg.triangular_solve(L, eye, left_side=True, lower=True)
        W = jnp.conj(jnp.swapaxes(Linv, -1, -2))    # L^{-H}, upper
        return jnp.matmul(x, W, precision=lax.Precision.HIGHEST)

    def one_pass(x):
        # herk-halved Gram + recursive blocked factor of the n x n result
        # (the fused XLA Cholesky serializes at large n)
        G = gram(x)
        L = _chol_blocked(G)
        info = _chol_info(L)
        return q_from_chol(L, x), jnp.conj(jnp.swapaxes(L, -1, -2)), info

    def shifted_pass(x):
        # shifted retry (stabilized CholeskyQR): shift Gram by ~11(mn+n^2) eps ||A||^2
        eps = jnp.finfo(x.dtype).eps
        shift = 11.0 * (m * n + n * (n + 1)) * eps * (jnp.linalg.norm(x) ** 2)
        G = gram(x) + shift * jnp.eye(n, dtype=x.dtype)
        L = _chol_blocked(G)
        return q_from_chol(L, x), jnp.conj(L.T)

    with trace_block("cholqr", m=m, n=n):
        # fully traceable (no host syncs): failure branches route through
        # lax.cond, so cholqr composes under jit/vmap and never blocks dispatch
        Q1, R1, info = one_pass(a)
        Q1, R1 = lax.cond(info != 0, lambda _: shifted_pass(a),
                          lambda _: (Q1, R1), None)
        # CholeskyQR2: re-orthogonalize
        Q2, R2, info2 = one_pass(Q1)
        R = jnp.matmul(R2, R1, precision=lax.Precision.HIGHEST)
        # rank-deficient input: the Gram route cannot recover — fall back to
        # Householder QR (the reference's MethodCholQR -> MethodGels::QR
        # fallback); lax.cond executes only the taken branch
        Q, R = lax.cond(info2 != 0,
                        lambda _: lax.linalg.qr(a, full_matrices=False),
                        lambda _: (Q2, R), None)
    return Q, R


def _gels_csne(a, b):
    """Overdetermined least squares by corrected semi-normal equations
    (Björck's CSNE — the TPU-fit form of the reference's CholQR least squares,
    src/gels_cholqr.cc): R^H R x = A^H b with R from Cholesky of the Gram
    matrix, plus one refinement step x += (R^H R)^{-1} A^H (b - A x).

    Redesign note: the reference materializes the tall Q = A R^{-1} and
    applies Q^H to B.  On TPU that right-side triangular solve over the tall
    operand is the memory hot spot (XLA materializes O(m·n) temps per column
    block — it OOMs one chip at the BASELINE 131072×4096 config), and Q is
    never needed again.  CSNE keeps the whole job as one Gram matmul plus thin
    mat-vecs — pure MXU work, O(n²) extra memory — and the corrected step
    restores the accuracy the squared condition number costs, to the same
    envelope as the reference's CholQR path (which squares cond(A) in R too).
    Rank-deficient or borderline-conditioned inputs (Cholesky of the Gram
    fails, or the solve produces non-finite values) fall back to Householder
    QR inside the jitted program (lax.cond), mirroring the MethodCholQR -> QR
    fallback — and Householder is the accurate choice exactly when the
    squared-Gram route is in trouble, so no shifted retry is attempted here.
    """
    ah = jnp.conj(jnp.swapaxes(a, -1, -2))
    # herk-halved Gram (the dominant 2mn^2 of the whole job) + recursive
    # blocked factor (the fused XLA Cholesky serializes at large n)
    G = gram(a)
    w = jnp.matmul(ah, b, precision=lax.Precision.HIGHEST)
    L = _chol_blocked(G)
    info = _chol_info(L)

    def normal_solve(rhs):
        y = lax.linalg.triangular_solve(L, rhs, left_side=True, lower=True)
        return lax.linalg.triangular_solve(L, y, left_side=True, lower=True,
                                           conjugate_a=True, transpose_a=True)

    x = normal_solve(w)
    # one corrected step (the "C" in CSNE)
    r = b - jnp.matmul(a, x, precision=lax.Precision.HIGHEST)
    x = x + normal_solve(jnp.matmul(ah, r, precision=lax.Precision.HIGHEST))

    def qr_path(_):
        Q, R = lax.linalg.qr(a, full_matrices=False)
        # this branch only runs when the Gram route failed, i.e. A may be
        # numerically rank-deficient: clamp vanishing R diagonals at
        # sqrt(eps)·max|d| so the null directions get negligible (not
        # catastrophic) weight — full-rank borderline cases (|d| ratio down
        # to ~1/cond > sqrt(eps)) are untouched
        n = R.shape[-1]
        d = jnp.diagonal(R, axis1=-2, axis2=-1)
        tol = jnp.sqrt(jnp.finfo(R.real.dtype).eps) * jnp.max(jnp.abs(d))
        small = jnp.abs(d) < tol
        dc = jnp.where(small, jnp.where(jnp.real(d) < 0, -tol, tol)
                       .astype(R.dtype), d)
        idx = jnp.arange(n)
        R = R.at[..., idx, idx].set(dc)
        y = jnp.matmul(jnp.conj(jnp.swapaxes(Q, -1, -2)), b,
                       precision=lax.Precision.HIGHEST)
        return lax.linalg.triangular_solve(R, y, left_side=True, lower=False)

    bad = (info != 0) | ~jnp.all(jnp.isfinite(x))
    return lax.cond(bad, qr_path, lambda _: x, None)


def gels_core(a, b):
    """Pure least-squares kernel — no wrappers, injection, tracing, or host
    syncs; the vmap-first core the batched serving layer maps over a leading
    batch axis.  The tall/square path is *raw* CSNE — deliberately WITHOUT
    :func:`_gels_csne`'s in-trace Householder escape: under ``vmap`` a
    ``lax.cond`` lowers to a select that executes BOTH branches for every
    batch element, so the escape would make every healthy batch pay a full
    batched Householder QR.  The escape lives in the serving layer's
    element-granular ladder instead (a failed element re-runs alone through
    the full :func:`gels` driver, escape included).  The wide path is the LQ
    minimum-norm solve expressed through QR of ``a^H``.  The branch is
    static on shape, so every element of a shape bucket traces one program.

    Returns ``(x, info)`` with x ``(n, nrhs)`` and info 0 on success,
    nonzero when the Gram Cholesky broke (its 1-based pivot index) or the
    solution is non-finite — the health verdict the escalation ladder keys
    on (least squares has no LAPACK pivot semantics beyond that).
    """
    from ..ops.blas3 import gram as _gram
    from .chol import _chol_blocked as _cb, _chol_info as _ci

    m, n = a.shape[-2:]
    if m >= n:
        ah = jnp.conj(jnp.swapaxes(a, -1, -2))
        G = _gram(a)
        L = _cb(G)
        ginfo = _ci(L)

        def normal_solve(rhs):
            y = lax.linalg.triangular_solve(L, rhs, left_side=True,
                                            lower=True)
            return lax.linalg.triangular_solve(L, y, left_side=True,
                                               lower=True, conjugate_a=True,
                                               transpose_a=True)

        x = normal_solve(jnp.matmul(ah, b, precision=lax.Precision.HIGHEST))
        r = b - jnp.matmul(a, x, precision=lax.Precision.HIGHEST)
        x = x + normal_solve(jnp.matmul(ah, r,
                                        precision=lax.Precision.HIGHEST))
    else:
        # minimum-norm via QR of a^H: a = R^H Q^H, x = Q R^{-H} b
        q, r = lax.linalg.qr(jnp.conj(jnp.swapaxes(a, -1, -2)),
                             full_matrices=False)
        y = lax.linalg.triangular_solve(r, b, left_side=True, lower=False,
                                        transpose_a=True, conjugate_a=True)
        x = jnp.matmul(q, y, precision=lax.Precision.HIGHEST)
        ginfo = jnp.int32(0)
    info = jnp.where(jnp.all(jnp.isfinite(x)), ginfo,
                     jnp.maximum(ginfo, jnp.int32(1)))
    return x, info


@instrument
def gels(A, BX, opts=None):
    """Least squares min ||A X - B|| / minimum-norm solve (src/gels.cc dispatch:
    MethodGels QR vs CholQR; src/gels_qr.cc, src/gels_cholqr.cc).

    Overdetermined (m >= n): X = R^{-1} Q^H B.  Underdetermined: minimum-norm via LQ.
    Returns the n x nrhs solution.

    Rank-deficiency note (differs from the reference): when the CholQR/CSNE
    route detects trouble (Gram Cholesky fails or the solve goes non-finite)
    it falls back to Householder QR *and clamps vanishing R diagonals* at
    sqrt(eps)·max|diag(R)|, i.e. numerically rank-deficient systems are
    regularized (null directions get negligible weight) rather than erroring.
    The reference's gels_qr/gels_cholqr make no such substitution.  Callers
    who must detect rank deficiency should check ``jnp.abs(jnp.diagonal(R))``
    from ``geqrf`` directly.
    """
    opts = Options.make(opts)
    a = as_array(A)
    b = as_array(BX)
    m, n = a.shape[-2:]
    grid = distribution_grid(A, BX)
    if grid is not None:
        # wrapper bound to a >1-device grid: ride the mesh least-squares
        # pipelines (gels.cc consumes the construction-time distribution the
        # same way).  An explicit MethodGels is honored; Auto takes the same
        # CholQR-when-very-tall heuristic as the local path.
        from ..parallel import (gels_caqr_distributed, gels_cholqr_distributed,
                                gels_lq_distributed)

        if m < n:
            X = gels_lq_distributed(a, b, grid, nb=opts.block_size)
        else:
            gmethod = opts.method_gels
            if gmethod == MethodGels.Auto:
                gmethod = MethodGels.CholQR if m >= 4 * n else MethodGels.QR
            if gmethod == MethodGels.CholQR:
                X = gels_cholqr_distributed(a, b, grid)
            else:
                X = gels_caqr_distributed(a, b, grid, nb=opts.block_size)
        return write_back(BX, X) if X.shape == b.shape else X
    method = opts.method_gels
    if method == MethodGels.Auto:
        # cholqr for very tall well-shaped panels (the reference's heuristic picks
        # cholqr when tall-skinny), qr otherwise
        method = MethodGels.CholQR if m >= 4 * n else MethodGels.QR

    with trace_block("gels", m=m, n=n, method=str(method)):
        if m >= n:
            if method == MethodGels.CholQR:
                x = _gels_csne(a, b)
            else:
                fac = geqrf(a, opts)
                y = unmqr("left", "c", fac, b)[..., :n, :]
                R = fac.R()
                x = lax.linalg.triangular_solve(R, y, left_side=True,
                                                lower=False)
        else:
            # minimum-norm: A = L Q, x = Q^H L^{-1} b
            fac = gelqf(a, opts)
            L = jnp.conj(jnp.swapaxes(fac.R(), -1, -2))   # m x m lower
            y = lax.linalg.triangular_solve(L, b, left_side=True, lower=True)
            ypad = jnp.concatenate(
                [y, jnp.zeros((n - m,) + y.shape[1:], y.dtype)], axis=0)
            x = unmqr("left", "n", fac, ypad)  # Q1 ypad = Q^H ypad
    return write_back(BX, x) if (isinstance(BX, BaseMatrix)
                                 and as_array(BX).shape == x.shape) else x


def gels_qr(A, BX, opts=None):
    """Least squares via Householder QR explicitly (src/gels_qr.cc)."""
    return gels(A, BX, Options.make(opts).replace(method_gels=MethodGels.QR))


def gels_cholqr(A, BX, opts=None):
    """Least squares via CholeskyQR explicitly (src/gels_cholqr.cc).

    See :func:`gels` for the rank-deficient fallback-and-clamp behavior of
    this path (the QR fallback regularizes vanishing R diagonals)."""
    return gels(A, BX, Options.make(opts).replace(method_gels=MethodGels.CholQR))
