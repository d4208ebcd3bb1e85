"""Distributed solvers over the process grid.

Reference analogues:

* ``src/potrf.cc:22-210`` — right-looking Cholesky with panel bcast + lookahead.
* ``src/work/work_trsm.cc:54-387`` — the shared triangular-solve task DAG.
* ``src/cholqr.cc`` + ``src/gels_cholqr.cc`` — communication-avoiding tall-skinny QR
  (gram = A^H A via listReduce tree, Cholesky of the small gram, trsm back).

TPU re-design: the factorizations keep the same blocked recurrences as the
single-device drivers (linalg/chol.py) but run them **jitted over sharded operands**:
the mesh-aware ``NamedSharding`` on inputs/outputs plus ``with_sharding_constraint``
on the trailing matrix make GSPMD insert the panel broadcast (all-gather along q) and
the symmetric-update collectives automatically — the reference's hand-built
listBcast/lookahead machinery becomes compiler-scheduled.  CholQR is written with
*explicit* collectives (``psum`` of per-shard Gram contributions inside ``shard_map``)
because its tree reduction is the whole algorithm (the reference's listReduce,
BaseMatrix.hh:2219-2258).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.exceptions import slate_assert
from ..linalg.chol import _chol_blocked
from ..ops import blas3
from ..robust import RetryPolicy, Rung, guard_shards, inject, run_ladder
from ..utils.trace import trace_event
from .mesh import COL_AXIS, ProcessGrid, ROW_AXIS
from ..obs import instrument


# ---------------------------------------------------------------------------
# Cholesky
# ---------------------------------------------------------------------------


@lru_cache(maxsize=32)
def _potrf_dist_fn(mesh, n: int, nb: int, dtype_str: str):
    spec = jax.NamedSharding(mesh, P(ROW_AXIS, COL_AXIS))
    nt = -(-n // nb)

    def fn(Af):
        L = Af
        for k in range(nt):
            k0, k1 = k * nb, min((k + 1) * nb, n)
            # panel factor on the nb×nb diagonal block — small, so GSPMD replicates
            # it (the reference also runs internal::potrf on one tile, potrf.cc:96)
            Lkk = _chol_blocked(L[k0:k1, k0:k1])
            L = L.at[k0:k1, k0:k1].set(Lkk)
            if k1 < n:
                panel = lax.linalg.triangular_solve(
                    Lkk, L[k1:n, k0:k1], left_side=False, lower=True,
                    conjugate_a=True, transpose_a=True)
                L = L.at[k1:n, k0:k1].set(panel)
                # trailing update: keeping L constrained to the (p, q) block sharding
                # makes GSPMD all-gather `panel` along the mesh axes — the tileBcast
                # of potrf.cc:109 — and run the rank-nb update shard-locally.
                upd = jnp.matmul(panel, jnp.conj(panel.T),
                                 precision=lax.Precision.HIGHEST)
                L = L.at[k1:n, k1:n].add(-upd)
                L = lax.with_sharding_constraint(L, spec)
        return jnp.tril(L)

    return jax.jit(fn, in_shardings=spec, out_shardings=spec)


# above this many panels the unrolled factorization's HLO gets expensive to
# compile (tens of seconds); the fori_loop body below keeps program size O(1)
_POTRF_UNROLL_MAX_NT = 32


@lru_cache(maxsize=32)
def _potrf_dist_loop_fn(mesh, n: int, nb: int, dtype_str: str):
    """O(1)-program-size distributed Cholesky: a lax.fori_loop whose body
    factors one panel with masked full-height operations.

    The reference's loop is O(nt) work but O(1) program (potrf.cc:84-195);
    the unrolled fn above is O(nt) program.  This body trades that for masked
    full-width updates (~3x the flops of the sliced trailing update — the
    rank-nb product runs over all n columns and the mask discards the left
    ones), which XLA still runs as dense MXU gemms; at large nt the compile
    saving dominates.
    """
    spec = jax.NamedSharding(mesh, P(ROW_AXIS, COL_AXIS))
    nt = -(-n // nb)

    def body(k, L):
        k0 = k * nb
        rows = jnp.arange(n)
        Dkk = lax.dynamic_slice(L, (k0, k0), (nb, nb))
        Lkk = _chol_blocked(Dkk)
        L = lax.dynamic_update_slice(L, Lkk, (k0, k0))
        # full-height panel solve; rows above the diagonal block are masked out
        P_ = lax.dynamic_slice(L, (0, k0), (n, nb))
        P_ = jnp.where((rows >= k0 + nb)[:, None], P_, 0)
        panel = lax.linalg.triangular_solve(
            Lkk, P_, left_side=False, lower=True,
            conjugate_a=True, transpose_a=True)
        L = lax.dynamic_update_slice(
            L, jnp.where((rows >= k0 + nb)[:, None], panel,
                         lax.dynamic_slice(L, (0, k0), (n, nb))), (0, k0))
        # masked trailing update over the full matrix (cols >= k0+nb only)
        upd = jnp.matmul(panel, jnp.conj(panel.T),
                         precision=lax.Precision.HIGHEST)
        mask = (rows >= k0 + nb)[None, :]
        L = L - jnp.where(mask, upd, 0)
        return lax.with_sharding_constraint(L, spec)

    def fn(Af):
        L = lax.fori_loop(0, nt, body, Af)
        return jnp.tril(L)

    return jax.jit(fn, in_shardings=spec, out_shardings=spec)


from .distribute import lcm as _lcm


def _pad_spd(Af: jax.Array, mult: int):
    """Pad a Hermitian matrix to a mult-divisible size with an identity tail, so the
    padded matrix stays SPD (the pad-and-mask edge policy, SURVEY.md §7 hard-part 5)."""
    from .distribute import pad2d

    n = Af.shape[-1]
    Af2 = pad2d(Af, mult, mult)
    if Af2.shape[-1] == n:
        return Af, n
    idx = jnp.arange(n, Af2.shape[-1])
    return Af2.at[idx, idx].set(1), n


@instrument
def potrf_distributed(Af: jax.Array, grid: ProcessGrid, nb: int = 256,
                      method: str = "auto",
                      lookahead: int = 1) -> jax.Array:
    """Distributed lower Cholesky of a full Hermitian array. Returns sharded L.

    method: "unroll" (O(nt) program, optimal flops), "loop" (O(1) program,
    masked updates — survives large panel counts), or "auto" which switches to
    the loop body past _POTRF_UNROLL_MAX_NT panels (the BASELINE n=16384
    nb=256 configuration is 64 panels, where unrolled compiles cost minutes).

    lookahead >= 2 routes to the explicit software pipeline
    (``pipeline.potrf_pipelined``): the next panel's column is updated first
    so its factorization overlaps the wide trailing collective — the
    reference's lookahead machinery (potrf.cc:84-195) made explicit instead
    of trusting XLA's async scheduler.  Depth-1 (the default) keeps the
    GSPMD bodies, whose single fused program XLA already overlaps.
    """
    n0 = Af.shape[-1]
    nb = max(1, min(nb, n0))
    if lookahead >= 2:
        from .pipeline import potrf_pipelined

        return potrf_pipelined(Af, grid, nb=nb)
    unit = _lcm(grid.p, grid.q)
    use_loop = method == "loop" or (
        method == "auto" and -(-n0 // nb) > _POTRF_UNROLL_MAX_NT)
    if use_loop:
        import math
        unit = unit * nb // math.gcd(unit, nb)  # the loop body needs nb | npad
    Af, n = _pad_spd(Af, unit)
    npad = Af.shape[-1]
    Af = jax.device_put(Af, grid.spec())
    make = _potrf_dist_loop_fn if use_loop else _potrf_dist_fn
    L = make(grid.mesh, npad, min(nb, npad), str(Af.dtype))(Af)
    return L[:n, :n] if npad != n else L


@lru_cache(maxsize=32)
def _trsm_dist_fn(mesh, lower: bool, trans: bool, dtype_str: str):
    spec = jax.NamedSharding(mesh, P(ROW_AXIS, COL_AXIS))

    def fn(L, B):
        return lax.linalg.triangular_solve(
            L, B, left_side=True, lower=lower,
            conjugate_a=trans, transpose_a=trans)

    return jax.jit(fn, in_shardings=(spec, spec), out_shardings=spec)


@instrument
def trsm_distributed(L: jax.Array, B: jax.Array, grid: ProcessGrid,
                     lower: bool = True, conj_trans: bool = False) -> jax.Array:
    """Distributed left triangular solve (work::trsm analogue); XLA's blocked
    TriangularSolve partitions over the sharded RHS.  Ragged shapes are padded:
    L gets an identity tail (keeps it invertible), B zero rows/cols."""
    from .distribute import pad2d

    n, nrhs = B.shape[-2:]
    mult = _lcm(grid.p, grid.q)
    Lp, _ = _pad_spd(L, mult)
    npad = Lp.shape[-1]
    Bp = pad2d(B, 1, grid.q)
    if npad > n:
        Bp = jnp.pad(Bp, ((0, npad - n), (0, 0)))
    cpad = Bp.shape[-1]
    Lp = jax.device_put(Lp, grid.spec())
    Bp = jax.device_put(Bp, grid.spec())
    X = _trsm_dist_fn(grid.mesh, lower, conj_trans, str(Lp.dtype))(Lp, Bp)
    return X[:n, :nrhs] if (npad != n or cpad != nrhs) else X


@instrument
def posv_distributed(Af: jax.Array, B: jax.Array, grid: ProcessGrid,
                     nb: int = 256) -> jax.Array:
    """Distributed SPD solve: potrf + two trsm sweeps (src/posv.cc), all sharded.

    The whole solve runs under the failed-shard guard
    (robust.guard_shards): when a fault plan simulates a dead device
    (shard_fail at the "output" point) or chaos is otherwise active, a
    non-finite result re-runs the solve from the intact input — zero extra
    host syncs on the production path."""

    def run():
        L = potrf_distributed(inject("posv_distributed", Af), grid, nb)
        Y = trsm_distributed(L, B, grid, lower=True, conj_trans=False)
        return trsm_distributed(L, Y, grid, lower=True, conj_trans=True)

    X, _ = guard_shards("posv_distributed", run, RetryPolicy(max_retries=1))
    return X


_FLAT = (ROW_AXIS, COL_AXIS)      # flattened device axis for 1-D row layouts


@lru_cache(maxsize=32)
def _trsmA_dist_fn(mesh, npad: int, nb: int, nrhs: int, lower: bool,
                   conj_trans: bool, unit_diag: bool, dtype_str: str):
    """Stationary-A triangular solve (src/trsmA.cc + work/work_trsmA.cc:1-580).

    The reference's trsmA keeps A's tiles where they live and moves the
    (narrow) B around instead — the right trade when B has a single block
    column (select_algo, src/trsm.cc:12-23).  Here: A is row-block-sharded
    on the flattened mesh and NEVER communicated; the per-step traffic is
    exactly one psum of the just-solved nb×nrhs X block (plus one more for
    the column-panel reduction in the conj-transpose sweep) — O(n·nrhs)
    total collective volume versus the O(n²)-class panel gathers of the
    stationary-B form.

    Sweep table (side=left; right is handled by the caller via transpose):
      lower/notrans  -> forward,  row-panel product (owner-local)
      lower/conjT    -> backward, column-panel psum reduction
      upper/notrans  -> backward, row-panel product (owner-local)
      upper/conjT    -> forward,  column-panel psum reduction
    """
    nproc = mesh.size
    rl = npad // nproc                       # local rows per device
    nt = npad // nb
    forward = (lower and not conj_trans) or (not lower and conj_trans)

    def local_fn(a_loc, b):                  # a_loc (rl, npad), b replicated
        me = lax.axis_index(_FLAT)

        def body(i, X):
            k = i if forward else nt - 1 - i
            k0 = k * nb
            owner = k0 // rl
            loc = k0 - owner * rl
            akk = lax.dynamic_slice(a_loc, (loc, k0), (nb, nb))
            bk = lax.dynamic_slice(b, (k0, 0), (nb, nrhs))
            if not conj_trans:
                # row-panel product: the owner holds block row k of A in
                # full, X carries zeros on unsolved rows — no communication
                # X is zero on every unsolved row (including block k), so the
                # full row-panel product is exactly the solved-part update
                row = lax.dynamic_slice(a_loc, (loc, 0), (nb, npad))
                upd = jnp.matmul(row, X, precision=lax.Precision.HIGHEST)
            else:
                # column-panel reduction: block column k of A^H is spread
                # over every device's rows — local partial + one psum
                colp = lax.dynamic_slice(a_loc, (0, k0), (rl, nb))
                Xl = lax.dynamic_slice(X, (me * rl, jnp.zeros((), me.dtype)),
                                       (rl, nrhs))
                part = jnp.matmul(jnp.conj(colp).T, Xl,
                                  precision=lax.Precision.HIGHEST)
                upd = lax.psum(part, _FLAT)
            xk = lax.linalg.triangular_solve(
                akk, bk - upd, left_side=True, lower=lower,
                transpose_a=conj_trans, conjugate_a=conj_trans,
                unit_diagonal=unit_diag)
            xk = jnp.where(me == owner, xk, jnp.zeros_like(xk))
            xk = lax.psum(xk, _FLAT)         # broadcast from the owner
            return lax.dynamic_update_slice(X, xk, (k0, 0))

        X = lax.fori_loop(0, nt, body, jnp.zeros_like(b))
        return X

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(_FLAT, None), P(None, None)),
                       out_specs=P(None, None), check_vma=False)
    return jax.jit(fn)


@instrument
def trsmA_distributed(A: jax.Array, B: jax.Array, grid: ProcessGrid,
                      lower: bool = True, conj_trans: bool = False,
                      unit_diag: bool = False) -> jax.Array:
    """Distributed left triangular solve, stationary-A dataflow
    (src/trsmA.cc).  A stays row-sharded on the mesh; only nb×nrhs X blocks
    travel.  Pads to a (nproc·nb)-aligned size with an identity tail."""
    n, nrhs = B.shape[-2:]
    nproc = grid.p * grid.q
    nb = max(32, min(256, -(-n // nproc)))
    Ap, _ = _pad_spd(A, nproc * nb)
    npad = Ap.shape[-1]
    Bp = jnp.pad(B, ((0, npad - n), (0, 0))) if npad != n else B
    X = _trsmA_dist_fn(grid.mesh, npad, nb, int(Bp.shape[-1]), bool(lower),
                       bool(conj_trans), bool(unit_diag), str(Ap.dtype))(Ap, Bp)
    return X[:n]


def _lower_dtype(dt):
    """The precision-ladder policy, shared with the single-device drivers
    (one source of truth: linalg.chol._lower_precision)."""
    from ..linalg.chol import _lower_precision

    return _lower_precision(dt)


def _ir_refine_distributed(Af, B, solve_lo, grid, max_iterations, tol=None):
    """Working-precision iterative refinement around a low-precision sharded
    solve (the gesv_mixed.cc loop over the mesh), expressed as ONE
    ``lax.while_loop``: the residual-norm convergence check rides the loop
    carry instead of a per-iteration device→host fetch, so the whole
    refinement dispatches without a single round trip (the reference's
    MPI-reduced norm per iteration has no host in the loop either).

    Returns traced ``(X, iters, ok)`` with ``ok = converged & all-finite(X)``;
    callers sync once on ``ok``.
    """
    dt = jnp.dtype(Af.dtype)
    eps = float(jnp.finfo(
        dt if jnp.issubdtype(dt, jnp.floating)
        else (jnp.float64 if dt == jnp.complex128 else jnp.float32)).eps)
    n = Af.shape[-1]
    tol = tol if tol is not None else eps * (n ** 0.5)
    anorm = jnp.max(jnp.sum(jnp.abs(Af), axis=-1))
    rdt = jnp.finfo(anorm.dtype)

    def residual(X):
        R = B - jnp.matmul(Af, X, precision=lax.Precision.HIGHEST)
        good = jnp.max(jnp.abs(R)) <= tol * anorm * jnp.maximum(
            jnp.max(jnp.abs(X)), jnp.asarray(rdt.tiny, anorm.dtype))
        return R, good

    X0 = solve_lo(B).astype(B.dtype)
    R0, good0 = residual(X0)

    def cond(carry):
        _X, _R, it, done = carry
        return (~done) & (it < max_iterations)

    def body(carry):
        X, R, it, _ = carry
        X = X + solve_lo(R).astype(B.dtype)
        R, good = residual(X)
        return X, R, it + 1, good

    X, _R, it, done = lax.while_loop(cond, body,
                                     (X0, R0, jnp.int32(0), good0))
    return X, it, done & jnp.all(jnp.isfinite(X))


@instrument
def posv_mixed_distributed(Af: jax.Array, B: jax.Array, grid: ProcessGrid,
                           nb: int = 256, max_iterations: int = 30):
    """Distributed mixed-precision SPD solve (src/posv_mixed.cc over the mesh):
    factor in the next precision down (f32 has no lower rung — XLA's Cholesky
    rejects bf16 — so f32 inputs take the plain sharded solve), refine the
    residual at working precision, escalate along the declared mixed→full
    ladder (robust.LADDERS["posv_mixed_distributed"]) when IR stalls.

    Returns (X, iters, converged_via_ir).
    """
    lo = _lower_dtype(Af.dtype)
    if lo is None:
        return posv_distributed(Af, B, grid, nb=nb), 0, True
    state = {"iters": 0}

    def mixed_rung():
        L = potrf_distributed(
            inject("posv_mixed_distributed", Af.astype(lo), point="factor"),
            grid, nb=nb)

        def solve_lo(R):
            Y = trsm_distributed(L, R.astype(lo), grid, lower=True,
                                 conj_trans=False)
            return trsm_distributed(L, Y, grid, lower=True, conj_trans=True)

        X, iters, ok = _ir_refine_distributed(Af, B, solve_lo, grid,
                                              max_iterations)
        state["iters"] = int(iters)
        return (X, True), bool(ok)        # the solve's single host sync

    def full_rung():
        return (posv_distributed(Af, B, grid, nb=nb), False), True

    X, via_ir = run_ladder("posv_mixed_distributed",
                           [Rung("mixed", mixed_rung),
                            Rung("full", full_rung)])
    return X, state["iters"], via_ir


@instrument
def posv_mixed_gmres_distributed(Af: jax.Array, B: jax.Array,
                                 grid: ProcessGrid, nb: int = 256, opts=None):
    """Distributed SPD GMRES-IR (src/posv_mixed_gmres.cc over the mesh):
    FGMRES with sharded matvecs, right-preconditioned by the low-precision
    sharded Cholesky solve.  Single-RHS like the reference.  Returns
    (X, restarts, converged); full-precision sharded fallback on stall."""
    from ..core.types import Options
    from ..linalg.lu import _gmres_ir, _require_single_rhs
    from .eig_dist import _shard

    opts = Options.make(opts)
    _require_single_rhs(B, "posv_mixed_gmres_distributed")
    vec = B.ndim == 1
    B2 = B[:, None] if vec else B       # the sharded solves need 2-D RHS

    def fallback():
        Xf = posv_distributed(Af, B2, grid, nb=nb)
        return Xf[:, 0] if vec else Xf

    lo = opts.factor_precision or _lower_dtype(Af.dtype)
    if lo is None:
        return fallback(), 0, True
    # sharding *constraints*, not device_put: GSPMD pads grid-indivisible n
    L = _shard(potrf_distributed(Af.astype(lo), grid, nb=nb), grid)
    As = _shard(Af, grid)

    def matvec(x):
        return jnp.matmul(As, x, precision=lax.Precision.HIGHEST)

    def precond(r):
        y = lax.linalg.triangular_solve(L, r.astype(lo)[:, None],
                                        left_side=True, lower=True)
        z = lax.linalg.triangular_solve(L, y, left_side=True, lower=True,
                                        conjugate_a=True, transpose_a=True)
        return z[:, 0].astype(B.dtype)

    X, restarts, converged = _gmres_ir(matvec, precond, B, opts,
                                       "posv_mixed_gmres_distributed")
    if not converged:
        if not opts.use_fallback_solver:
            return X, int(restarts), False
        trace_event("fallback", routine="posv_mixed_gmres_distributed",
                    to="full")
        return fallback(), int(restarts), False
    return X, int(restarts), True


# ---------------------------------------------------------------------------
# Tall-skinny CholQR (communication-avoiding QR)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=32)
def _cholqr_fn(mesh, precision):
    in_spec = P((ROW_AXIS, COL_AXIS), None)   # rows over the whole flattened grid
    axes = (ROW_AXIS, COL_AXIS)
    world = mesh.devices.size

    def local(a):
        # per-shard Gram contribution (herk-halved strips); psum = the
        # listReduce tree over all ranks
        g = lax.psum(blas3.gram(a, precision=precision), axes)
        Rg = jnp.conj(_chol_blocked(g).T)           # g = R^H R

        def gram_path(_):
            q = lax.linalg.triangular_solve(Rg, a, left_side=False, lower=False)
            return q, Rg

        def householder_path(_):
            # rank-deficient input: the Gram route cannot recover — fall back
            # to Householder QR on the gathered matrix (the reference's
            # MethodCholQR -> QR fallback), still inside the jitted program:
            # no host sync, lax.cond runs only the taken branch
            n = a.shape[-1]
            Af = lax.all_gather(a, axes, tiled=True)
            Qf, Rf = lax.linalg.qr(Af, full_matrices=False)
            w = lax.axis_index(axes[0]) * mesh.shape[COL_AXIS] \
                + lax.axis_index(axes[1])
            rows = a.shape[0]
            q = lax.dynamic_slice(
                Qf, (w.astype(jnp.int32) * rows, jnp.int32(0)), (rows, n))
            return q, Rf

        bad = ~jnp.all(jnp.isfinite(jnp.diagonal(Rg)))
        return lax.cond(bad, householder_path, gram_path, None)

    fn = jax.shard_map(local, mesh=mesh, in_specs=in_spec,
                       out_specs=(in_spec, P(None, None)), check_vma=False)
    return jax.jit(fn)


@instrument
def cholqr_distributed(A: jax.Array, grid: ProcessGrid,
                       precision=lax.Precision.HIGHEST):
    """Tall-skinny QR via Cholesky of the Gram matrix (src/cholqr.cc).

    A is 1D row-sharded over all devices; returns (Q row-sharded, R replicated).
    The psum of Gram contributions is the reference's listReduce tree
    (BaseMatrix.hh:2219-2258) collapsed into one ICI all-reduce.
    """
    from .distribute import pad2d

    m, n = A.shape[-2:]
    world = grid.size
    slate_assert(m >= n, "cholqr expects a tall matrix")
    Ap = pad2d(A, world, 1)  # zero rows leave the Gram unchanged
    mpad = Ap.shape[-2]
    Ap = jax.device_put(Ap, grid.row_spec())
    Q, R = _cholqr_fn(grid.mesh, precision)(Ap)
    return (Q[:m] if mpad != m else Q), R


@instrument
def gels_cholqr_distributed(A: jax.Array, B: jax.Array, grid: ProcessGrid):
    """Overdetermined least squares min ||A X - B|| via CholQR
    (src/gels_cholqr.cc): X = R^{-1} (Q^H B)."""
    Q, R = cholqr_distributed(A, grid)
    QhB = jnp.matmul(jnp.conj(Q.T), B, precision=lax.Precision.HIGHEST)
    return lax.linalg.triangular_solve(R, QhB, left_side=True, lower=False)
