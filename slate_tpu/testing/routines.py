"""Routine dispatch table + per-routine runners and numerical checks.

≅ test/test.cc:117-320 (dispatch) and the per-routine ``test_<routine>.cc`` files.
Each runner follows the reference's test strategy (SURVEY.md §4): generate inputs
with matgen, time the library call, then verify with a **residual identity that
needs no reference implementation** — gemm via the random-RHS trick
(test_gemm.cc:192-207), factorizations via reconstruction (‖A − LLᴴ‖-style), eig/svd
via ‖AZ − ZΛ‖ + orthogonality of Z.  ``--ref`` additionally times the numpy
reference on the same problem (driver._REF_FNS — the analogue of the ScaLAPACK
reference path, reported in the ref(s) column).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

from functools import lru_cache

import numpy as np

import jax.numpy as jnp

from .. import matgen
from .sweeper import DTYPES, TestResult, time_call

# filled by @_routine below: name -> {"category", "runner", "doc"}
ROUTINES: Dict[str, Dict[str, Any]] = {}


def _routine(name: str, category: str):
    def wrap(fn):
        ROUTINES[name] = {"category": category, "runner": fn, "doc": fn.__doc__ or ""}
        return fn
    return wrap


# ---------------------------------------------------------------------------
# helpers

def _phases(routine: str) -> dict:
    """Driver phase map for the tester row (--timer-level-2 analogue): the
    he2hb / chase / tridiag / back-transform attribution recorded by the last
    heev/svd call (utils.trace.record_phases).  Host-side spans — on an async
    backend they attribute dispatch, not device time; the stage-level rows
    (sterf/he2hb/hb2st) are the forced per-phase sweep surface."""
    from slate_tpu.utils.trace import last_phases, phase_report

    t = last_phases(routine)
    return phase_report(t, min_frac=0.02) if t else {}


def _grid(p):
    """ProcessGrid for a grid-swept row (tester p x q dimension, like the
    reference tester's --p/--q sweep) or None for single-device rows."""
    g = p.get("grid")
    if not g:
        return None
    return _grid_cached(tuple(g))


@lru_cache(maxsize=8)
def _grid_cached(pq):
    from slate_tpu.parallel import ProcessGrid

    return ProcessGrid(*pq)


def _eps(dtype) -> float:
    return float(np.finfo(np.dtype(dtype).char.lower()
                          if np.dtype(dtype).kind == "c" else dtype).eps)


def _tol(p) -> float:
    """Default accept threshold: 3·eps scaled by problem size^1/2 with generous
    headroom for blocked algorithms (the reference gates at 3·eps for gemm and
    looser per-routine factors elsewhere)."""
    n = max(p["m"], p["n"], p["k"])
    return 50.0 * _eps(p["dtype"]) * max(1.0, n ** 0.5)


def _gen(kind, m, n, p, **kw):
    A, _ = matgen.generate_matrix(kind, m, n, dtype=p["dtype"], seed=p["seed"], **kw)
    return np.asarray(A)


def _spd(n, p):
    cond = p.get("cond") or 100.0
    return _gen("poev_geo", n, n, p, cond=cond)


def _herm(n, p):
    cond = p.get("cond") or 100.0
    return _gen("heev_geo", n, n, p, cond=cond)


def _cplx_mult(dtype) -> float:
    return 4.0 if np.dtype(dtype).kind == "c" else 1.0


def _rel(err, scale) -> float:
    return float(err) / max(float(scale), 1e-30)


def _result(p, error, flops, t, tol_mult: float = 1.0, ref_time=None) -> dict:
    tol = _tol(p) * tol_mult
    return {
        "error": error, "time_s": t,
        "gflops": flops * _cplx_mult(p["dtype"]) / t / 1e9 if t and flops else None,
        "ref_time_s": ref_time,
        "status": "pass" if error is not None and error <= tol else "FAILED",
        "message": "" if error is not None and error <= tol else f"err>{tol:.1e}",
    }


# ---------------------------------------------------------------------------
# BLAS-3

@_routine("gemm", "blas3")
def run_gemm(p, slate):
    """C = alpha A B + beta C; random-RHS residual check (test_gemm.cc:192-207)."""
    m, n, k = p["m"], p["n"], p["k"]
    A = _gen(p["kind"], m, k, p)
    B = np.asarray(matgen.generate_matrix(p["kind"], k, n, dtype=p["dtype"],
                                          seed=p["seed"] + 1)[0])
    C0 = np.asarray(matgen.generate_matrix(p["kind"], m, n, dtype=p["dtype"],
                                           seed=p["seed"] + 2)[0])
    alpha, beta = 2.5, 0.5
    g = _grid(p)
    Cm = slate.Matrix.from_array(C0.copy(), nb=p["nb"], grid=g)
    _, t = time_call(lambda: slate.gemm(
        alpha, slate.Matrix.from_array(A, nb=p["nb"], grid=g),
        slate.Matrix.from_array(B, nb=p["nb"], grid=g), beta, Cm),
        repeat=p["repeat"])
    C = np.asarray(Cm.array)
    w = np.random.default_rng(0).standard_normal((n,)).astype(
        np.dtype(p["dtype"]).char.lower() if np.dtype(p["dtype"]).kind == "c"
        else p["dtype"])
    y = C @ w - (alpha * (A @ (B @ w)) + beta * (C0 @ w))
    scale = (abs(alpha) * np.linalg.norm(A) * np.linalg.norm(B) +
             abs(beta) * np.linalg.norm(C0)) * np.linalg.norm(w)
    return _result(p, _rel(np.linalg.norm(y), scale), 2.0 * m * n * k, t)


@_routine("trsm", "blas3")
def run_trsm(p, slate):
    """op(T)^-1 B; identity check T (T^-1 B) == B."""
    m, n = p["m"], p["n"]
    side_left = p.get("side", "left") == "left"
    tn = m if side_left else n
    T = np.tril(_gen("rands", tn, tn, p)) + tn * np.eye(tn, dtype=p["dtype"])
    B0 = _gen("rands", m, n, p, )
    Bm = slate.Matrix.from_array(B0.copy(), nb=p["nb"])
    Tm = slate.TriangularMatrix.from_array(slate.Uplo.Lower, T, nb=p["nb"])
    _, t = time_call(lambda: slate.trsm(p.get("side", "left"), 1.0, Tm, Bm),
                     repeat=p["repeat"])
    X = np.asarray(Bm.array)
    R = T @ X - B0 if side_left else X @ T - B0
    scale = np.linalg.norm(T) * np.linalg.norm(X)
    flops = m * m * n if side_left else m * n * n
    return _result(p, _rel(np.linalg.norm(R), scale), flops, t)


@_routine("trsmA", "blas3")
def run_trsmA(p, slate):
    """Stationary-A triangular solve (src/trsmA.cc): same identity check as
    trsm through the explicit-method driver."""
    m, n = p["m"], p["n"]
    T = np.tril(_gen("rands", m, m, p)) + m * np.eye(m, dtype=p["dtype"])
    B0 = _gen("rands", m, n, p)
    Bm = slate.Matrix.from_array(B0.copy(), nb=p["nb"])
    Tm = slate.TriangularMatrix.from_array(slate.Uplo.Lower, T, nb=p["nb"])
    _, t = time_call(lambda: slate.trsmA("left", 1.0, Tm, Bm),
                     repeat=p["repeat"])
    X = np.asarray(Bm.array)
    scale = np.linalg.norm(T) * np.linalg.norm(X)
    return _result(p, _rel(np.linalg.norm(T @ X - B0), scale), m * m * n, t)


@_routine("trsmB", "blas3")
def run_trsmB(p, slate):
    """Stationary-B triangular solve (src/trsmB.cc)."""
    m, n = p["m"], p["n"]
    T = np.tril(_gen("rands", m, m, p)) + m * np.eye(m, dtype=p["dtype"])
    B0 = _gen("rands", m, n, p)
    Bm = slate.Matrix.from_array(B0.copy(), nb=p["nb"])
    Tm = slate.TriangularMatrix.from_array(slate.Uplo.Lower, T, nb=p["nb"])
    _, t = time_call(lambda: slate.trsmB("left", 1.0, Tm, Bm),
                     repeat=p["repeat"])
    X = np.asarray(Bm.array)
    scale = np.linalg.norm(T) * np.linalg.norm(X)
    return _result(p, _rel(np.linalg.norm(T @ X - B0), scale), m * m * n, t)


@_routine("trmm", "blas3")
def run_trmm(p, slate):
    """op(T) B vs dense multiply."""
    m, n = p["m"], p["n"]
    T = np.tril(_gen("rands", m, m, p))
    B0 = _gen("rands", m, n, p)
    Bm = slate.Matrix.from_array(B0.copy(), nb=p["nb"])
    Tm = slate.TriangularMatrix.from_array(slate.Uplo.Lower, T, nb=p["nb"])
    _, t = time_call(lambda: slate.trmm("left", 1.0, Tm, Bm), repeat=p["repeat"])
    err = _rel(np.linalg.norm(np.asarray(Bm.array) - T @ B0),
               np.linalg.norm(T) * np.linalg.norm(B0))
    return _result(p, err, m * m * n, t)


@_routine("herk", "blas3")
def run_herk(p, slate):
    """C = alpha A A^H + beta C on the stored triangle."""
    n, k = p["n"], p["k"]
    A = _gen("randn", n, k, p)
    C0 = _herm(n, p)
    Cm = slate.HermitianMatrix.from_array(slate.Uplo.Lower, C0.copy(), nb=p["nb"])
    _, t = time_call(lambda: slate.herk(
        1.5, slate.Matrix.from_array(A, nb=p["nb"]), 0.5, Cm), repeat=p["repeat"])
    C = np.asarray(Cm.full_array())
    expect = 1.5 * (A @ A.conj().T) + 0.5 * C0
    err = _rel(np.linalg.norm(C - expect), np.linalg.norm(expect))
    return _result(p, err, n * n * k, t)


@_routine("her2k", "blas3")
def run_her2k(p, slate):
    n, k = p["n"], p["k"]
    A = _gen("randn", n, k, p)
    B = np.asarray(matgen.generate_matrix("randn", n, k, dtype=p["dtype"],
                                          seed=p["seed"] + 1)[0])
    C0 = _herm(n, p)
    Cm = slate.HermitianMatrix.from_array(slate.Uplo.Lower, C0.copy(), nb=p["nb"])
    _, t = time_call(lambda: slate.her2k(
        1.0, slate.Matrix.from_array(A, nb=p["nb"]),
        slate.Matrix.from_array(B, nb=p["nb"]), 0.5, Cm), repeat=p["repeat"])
    C = np.asarray(Cm.full_array())
    expect = A @ B.conj().T + B @ A.conj().T + 0.5 * C0
    err = _rel(np.linalg.norm(C - expect), np.linalg.norm(expect))
    return _result(p, err, 2.0 * n * n * k, t)


@_routine("hemm", "blas3")
def run_hemm(p, slate):
    m, n = p["m"], p["n"]
    A = _herm(m, p)
    B = _gen("randn", m, n, p)
    C0 = np.zeros((m, n), p["dtype"])
    Cm = slate.Matrix.from_array(C0.copy(), nb=p["nb"])
    Am = slate.HermitianMatrix.from_array(slate.Uplo.Lower, A, nb=p["nb"])
    _, t = time_call(lambda: slate.hemm(
        "left", 1.0, Am, slate.Matrix.from_array(B, nb=p["nb"]), 0.0, Cm),
        repeat=p["repeat"])
    err = _rel(np.linalg.norm(np.asarray(Cm.array) - A @ B),
               np.linalg.norm(A) * np.linalg.norm(B))
    return _result(p, err, 2.0 * m * m * n, t)


@_routine("norm", "aux")
def run_norm(p, slate):
    """Max/One/Inf/Fro norms vs numpy on the same matrix."""
    m, n = p["m"], p["n"]
    A = _gen(p["kind"], m, n, p)
    Am = slate.Matrix.from_array(A, nb=p["nb"])
    worst = 0.0
    t_total = 0.0
    for which, npval in [("max", np.abs(A).max()),
                         ("one", np.abs(A).sum(axis=0).max()),
                         ("inf", np.abs(A).sum(axis=1).max()),
                         ("fro", np.linalg.norm(A))]:
        val, t = time_call(lambda w=which: slate.norm(w, Am), repeat=p["repeat"])
        t_total += t
        worst = max(worst, _rel(abs(float(val) - npval), npval))
    return _result(p, worst, m * n, t_total)


# ---------------------------------------------------------------------------
# linear systems

@_routine("potrf", "cholesky")
def run_potrf(p, slate):
    """‖A − L Lᴴ‖/‖A‖ reconstruction check."""
    n = p["n"]
    A = _spd(n, p)
    (L, info), t = time_call(lambda: slate.potrf(
        slate.HermitianMatrix.from_array(slate.Uplo.Lower, A.copy(),
                                         nb=p["nb"], grid=_grid(p))),
        repeat=p["repeat"])
    Lf = np.tril(np.asarray(L.array if hasattr(L, "array") else L))
    err = _rel(np.linalg.norm(A - Lf @ Lf.conj().T), np.linalg.norm(A))
    return _result(p, err, n ** 3 / 3, t, tol_mult=10 * (p.get("cond") or 100.0) ** 0.5)


@_routine("posv", "cholesky")
def run_posv(p, slate):
    n, nrhs = p["n"], p.get("nrhs", 10)
    A = _spd(n, p)
    b = _gen("randn", n, nrhs, p, )
    Bm = slate.Matrix.from_array(b.copy(), nb=p["nb"])
    _, t = time_call(lambda: slate.posv(
        slate.HermitianMatrix.from_array(slate.Uplo.Lower, A.copy(),
                                         nb=p["nb"], grid=_grid(p)),
        Bm), repeat=p["repeat"])
    x = np.asarray(Bm.array)
    err = _rel(np.linalg.norm(A @ x - b),
               np.linalg.norm(A) * np.linalg.norm(x))
    return _result(p, err, n ** 3 / 3 + 2.0 * n * n * nrhs, t)


@_routine("potri", "cholesky")
def run_potri(p, slate):
    """potrf then potri (the reference's potri consumes the factor)."""
    n = p["n"]
    A = _spd(n, p)

    def factor_invert():
        M = slate.HermitianMatrix.from_array(slate.Uplo.Lower, A.copy(), nb=p["nb"])
        L, info = slate.potrf(M)
        return slate.potri(L)

    inv, t = time_call(factor_invert, repeat=p["repeat"])
    Ainv = np.asarray(inv.full_array() if hasattr(inv, "full_array") else inv)
    if Ainv.ndim == 2 and not np.allclose(Ainv, Ainv.conj().T):
        Ainv = np.tril(Ainv) + np.tril(Ainv, -1).conj().T   # lower-stored result
    err = _rel(np.linalg.norm(A @ Ainv - np.eye(n)),
               np.linalg.norm(A) * np.linalg.norm(Ainv))
    return _result(p, err, n ** 3, t)


@_routine("getrf", "lu")
def run_getrf(p, slate):
    """‖P A − L U‖/‖A‖."""
    n = p["n"]
    A = _gen(p["kind"], n, n, p)
    (lu_, perm, info), t = time_call(lambda: slate.getrf(A.copy()),
                                     repeat=p["repeat"])
    lu_np = np.asarray(lu_)
    L = np.tril(lu_np, -1) + np.eye(n, dtype=p["dtype"])
    U = np.triu(lu_np)
    err = _rel(np.linalg.norm(A[np.asarray(perm)] - L @ U), np.linalg.norm(A))
    return _result(p, err, 2 * n ** 3 / 3, t)


@_routine("gesv", "lu")
def run_gesv(p, slate):
    n, nrhs = p["n"], p.get("nrhs", 10)
    A = _gen(p["kind"], n, n, p) + n * np.eye(n, dtype=p["dtype"])
    b = _gen("randn", n, nrhs, p)
    g = _grid(p)
    # wrapper built per call: gesv's getrf writes the LU factor back into a
    # Matrix argument, so a hoisted wrapper would poison repeat > 1 timings
    (X, perm, info), t = time_call(lambda: slate.gesv(
        slate.Matrix.from_array(A.copy(), nb=p["nb"], grid=g)
        if g is not None else A.copy(), b.copy()), repeat=p["repeat"])
    x = np.asarray(X)
    err = _rel(np.linalg.norm(A @ x - b), np.linalg.norm(A) * np.linalg.norm(x))
    return _result(p, err, 2 * n ** 3 / 3 + 2.0 * n * n * nrhs, t)


@_routine("gesv_mixed", "lu")
def run_gesv_mixed(p, slate):
    """Mixed-precision IR (src/gesv_mixed.cc: low-precision factor + IR).

    The mixed path only exists where a lower precision exists (d->s, z->c),
    so an s/c sweep row PROMOTES to its d/z counterpart (noted in the row)
    instead of skipping outright — every sweep line exercises the actual
    factor-low/refine-high pipeline.  The IR iteration count is recorded in
    the tester row (details["ir_iters"], the reference tester's iters
    column)."""
    promoted = {np.dtype(np.float32): np.float64,
                np.dtype(np.complex64): np.complex128}.get(np.dtype(p["dtype"]))
    if promoted is not None:
        # scoped x64 (jax.enable_x64) keeps the promotion local to this row
        # — the rest of the sweep stays in the caller's mode
        import jax

        with jax.enable_x64(True):
            out = _gesv_mixed_body(dict(p, dtype=promoted), slate)
        out.setdefault("details", {})["promoted"] = \
            f"s/c -> {np.dtype(promoted).char}"
        return out
    return _gesv_mixed_body(p, slate)


def _gesv_mixed_body(p, slate):
    n = p["n"]
    A = _gen(p["kind"], n, n, p) + n * np.eye(n, dtype=p["dtype"])
    b = _gen("randn", n, 1, p)
    (X, perm, info, iters), t = time_call(lambda: slate.gesv_mixed(A.copy(), b.copy()),
                                          repeat=p["repeat"])
    x = np.asarray(X)
    err = _rel(np.linalg.norm(A @ x - b), np.linalg.norm(A) * np.linalg.norm(x))
    out = _result(p, err, 2 * n ** 3 / 3, t)
    out["details"] = {"ir_iters": int(iters)}
    return out


@_routine("gesv_rbt", "lu")
def run_gesv_rbt(p, slate):
    n = p["n"]
    A = _gen(p["kind"], n, n, p) + n * np.eye(n, dtype=p["dtype"])
    b = _gen("randn", n, 1, p)
    out, t = time_call(lambda: slate.gesv_rbt(A.copy(), b.copy()), repeat=p["repeat"])
    x = np.asarray(out[0])
    err = _rel(np.linalg.norm(A @ x - b), np.linalg.norm(A) * np.linalg.norm(x))
    return _result(p, err, 2 * n ** 3 / 3, t)


@_routine("gesv_f64ir", "lu")
def run_gesv_f64ir(p, slate):
    """Emulated-f64 IR solve (ops/f64emu.py): f32 factor + exact-Ozaki
    residuals; the tester's d rows verify double-class forward error on
    hardware without f64 ALUs (gate scaled to the emulation envelope, not
    the f32 eps the suite-wide tolerance assumes)."""
    import jax.numpy as jnp

    from slate_tpu.ops.f64emu import gesv_f64ir

    n = p["n"]
    A = _gen(p["kind"], n, n, p) + n * np.eye(n, dtype=p["dtype"])
    if np.iscomplexobj(A):
        b = _gen("randn", n, 1, p) + 1j * _gen("randn", n, 1, p)
    else:
        b = _gen("randn", n, 1, p)
    (Xh, Xl, iters, info), t = time_call(
        lambda: gesv_f64ir(jnp.asarray(A), jnp.asarray(b)),
        repeat=p["repeat"])
    x = np.asarray(Xh, np.complex128 if np.iscomplexobj(A) else np.float64) \
        + np.asarray(Xl, np.complex128 if np.iscomplexobj(A) else np.float64)
    err = _rel(np.linalg.norm(A.astype(x.dtype) @ x - b),
               np.linalg.norm(A) * np.linalg.norm(x))
    out = _result(p, err, 2 * n ** 3 / 3, t)
    # double-class gate: orders below f32 eps (the dtype-derived suite
    # tolerance would under-test the emulation)
    strict = 1e-9 * max(1.0, n ** 0.5)
    out["status"] = "pass" if err is not None and err <= strict else "FAILED"
    out["message"] = "" if out["status"] == "pass" \
        else f"err>{strict:.1e} (double-class gate)"
    return out


@_routine("posv_f64ir", "chol")
def run_posv_f64ir(p, slate):
    """SPD sibling of gesv_f64ir: f32 Cholesky + emulated-f64 refinement
    (ops/f64emu.posv_f64ir), same double-class gate."""
    import jax.numpy as jnp

    from slate_tpu.ops.f64emu import posv_f64ir

    n = p["n"]
    G = _gen(p["kind"], n, n, p)
    A = G @ np.conj(G.T) + n * np.eye(n, dtype=p["dtype"])
    b = _gen("randn", n, 1, p)
    (Xh, Xl, iters, info), t = time_call(
        lambda: posv_f64ir(jnp.asarray(A), jnp.asarray(b)),
        repeat=p["repeat"])
    wide = np.complex128 if np.iscomplexobj(A) else np.float64
    x = np.asarray(Xh, wide) + np.asarray(Xl, wide)
    err = _rel(np.linalg.norm(A.astype(wide) @ x - b),
               np.linalg.norm(A) * np.linalg.norm(x))
    out = _result(p, err, n ** 3 / 3, t)
    strict = 1e-9 * max(1.0, n ** 0.5)
    out["status"] = "pass" if err is not None and err <= strict else "FAILED"
    out["message"] = "" if out["status"] == "pass" \
        else f"err>{strict:.1e} (double-class gate)"
    return out


@_routine("hesv", "indefinite")
def run_hesv(p, slate):
    n = p["n"]
    A = _herm(n, p)
    b = _gen("randn", n, 4, p)
    out, t = time_call(lambda: slate.hesv(A.copy(), b.copy(), None), repeat=p["repeat"])
    x = np.asarray(out[0])
    err = _rel(np.linalg.norm(A @ x - b), np.linalg.norm(A) * np.linalg.norm(x))
    return _result(p, err, n ** 3 / 3, t, tol_mult=20)


@_routine("gbsv", "band")
def run_gbsv(p, slate):
    n, kl, ku = p["n"], p.get("kl", 8), p.get("ku", 8)
    A = _gen("randn", n, n, p)
    band = np.triu(np.tril(A, kl), -ku) + n * np.eye(n, dtype=p["dtype"])
    b = _gen("randn", n, 2, p)
    out, t = time_call(lambda: slate.gbsv(band.copy(), b.copy(), kl=kl, ku=ku),
                       repeat=p["repeat"])
    x = np.asarray(out[0])
    err = _rel(np.linalg.norm(band @ x - b), np.linalg.norm(band) * np.linalg.norm(x))
    return _result(p, err, 2.0 * n * kl * ku, t)


@_routine("pbsv", "band")
def run_pbsv(p, slate):
    n, kd = p["n"], p.get("kd", 8)
    A = _spd(n, p)
    band = np.triu(np.tril(A, kd), -kd) + n * np.eye(n, dtype=p["dtype"])
    b = _gen("randn", n, 2, p)
    out, t = time_call(lambda: slate.pbsv(band.copy(), b.copy(), kd=kd),
                       repeat=p["repeat"])
    x = np.asarray(out[0])
    err = _rel(np.linalg.norm(band @ x - b), np.linalg.norm(band) * np.linalg.norm(x))
    return _result(p, err, n * kd * kd, t)


# ---------------------------------------------------------------------------
# least squares / QR

@_routine("geqrf", "qr")
def run_geqrf(p, slate):
    """‖A − Q R‖/‖A‖ + ‖I − QᴴQ‖."""
    m, n = p["m"], p["n"]
    A = _gen(p["kind"], m, n, p)
    fac, t = time_call(lambda: slate.geqrf(A.copy()), repeat=p["repeat"])
    Q = np.asarray(fac.Q())
    R = np.asarray(fac.R())
    k = min(m, n)
    err1 = _rel(np.linalg.norm(A - Q @ R), np.linalg.norm(A))
    err2 = np.linalg.norm(Q.conj().T @ Q - np.eye(k)) / k
    return _result(p, max(err1, err2), 2.0 * m * n * n - 2 * n ** 3 / 3, t)


@_routine("cholqr", "qr")
def run_cholqr(p, slate):
    m, n = p["m"], p["n"]
    A = _gen("randn", m, n, p)
    (Q, R), t = time_call(lambda: slate.cholqr(A.copy()), repeat=p["repeat"])
    Q, R = np.asarray(Q), np.asarray(R)
    err1 = _rel(np.linalg.norm(A - Q @ R), np.linalg.norm(A))
    err2 = np.linalg.norm(Q.conj().T @ Q - np.eye(n)) / n
    # CholeskyQR2's orthogonality envelope is ~eps*cond(A) (it is a
    # tall-panel algorithm; square randn has cond ~ n, which the generic
    # gate does not budget for — observed 4.5e-4 vs a 2.7e-4 gate at
    # n=2048 f32, exactly the theory line).  16x keeps the gate meaningful
    # while respecting the envelope on square sweep shapes.
    return _result(p, max(err1, err2), 2.0 * m * n * n, t, tol_mult=16)


@_routine("gels", "qr")
def run_gels(p, slate):
    """Normal-equations residual ‖Aᴴ(A x − b)‖ / (‖A‖² ‖x‖)."""
    m, n = p["m"], p["n"]
    A = _gen(p["kind"], m, n, p)
    b = _gen("randn", m, 2, p)
    X, t = time_call(lambda: slate.gels(A.copy(), b.copy()), repeat=p["repeat"])
    x = np.asarray(X)[:n]
    r = A @ x - b
    err = _rel(np.linalg.norm(A.conj().T @ r),
               np.linalg.norm(A) ** 2 * max(np.linalg.norm(x), 1e-10))
    # square consistent systems amplify the normal-equations residual by cond(A)
    return _result(p, err, 2.0 * m * n * n, t, tol_mult=100)


# ---------------------------------------------------------------------------
# batched serving tier (slate_tpu.serve; the reference's batch-BLAS L1 has no
# tester rows — these sweep the vmap-first drivers the serving queue packs)

def _batch_stack(gen_one, bs):
    return np.stack([gen_one(i) for i in range(bs)])


def _batched_result(p, errs, flops, t, tol_mult=1.0):
    out = _result(p, max(errs), flops, t, tol_mult=tol_mult)
    out.setdefault("details", {})["batch"] = len(errs)
    return out


@_routine("gesv_batched", "serve")
def run_gesv_batched(p, slate):
    """Batched gesv (serve.gesv_batched): max over the batch of per-element
    residuals; per-element info must be all-zero."""
    n, nrhs = p["n"], min(p.get("nrhs", 4), 4)
    bs = int(p.get("batch", 4))
    A = _batch_stack(lambda i: _gen("randn", n, n, dict(p, seed=p["seed"] + i))
                     + n * np.eye(n, dtype=p["dtype"]), bs)
    b = _batch_stack(lambda i: _gen("randn", n, nrhs,
                                    dict(p, seed=100 + p["seed"] + i)), bs)
    from slate_tpu import serve

    (X, perm, info), t = time_call(
        lambda: serve.gesv_batched(jnp.asarray(A), jnp.asarray(b)),
        repeat=p["repeat"])
    assert not np.asarray(info).any(), f"nonzero batched info {info}"
    x = np.asarray(X)
    errs = [_rel(np.linalg.norm(A[i] @ x[i] - b[i]),
                 np.linalg.norm(A[i]) * np.linalg.norm(x[i]))
            for i in range(bs)]
    return _batched_result(p, errs, bs * (2 * n**3 / 3 + 2.0 * n * n * nrhs), t)


@_routine("posv_batched", "serve")
def run_posv_batched(p, slate):
    """Batched SPD solve (serve.posv_batched) over a stack of full Hermitian
    operands."""
    n, nrhs = p["n"], min(p.get("nrhs", 4), 4)
    bs = int(p.get("batch", 4))
    A = _batch_stack(lambda i: _spd(n, dict(p, seed=p["seed"] + i)), bs)
    b = _batch_stack(lambda i: _gen("randn", n, nrhs,
                                    dict(p, seed=100 + p["seed"] + i)), bs)
    from slate_tpu import serve

    (X, info), t = time_call(
        lambda: serve.posv_batched(jnp.asarray(A), jnp.asarray(b)),
        repeat=p["repeat"])
    assert not np.asarray(info).any(), f"nonzero batched info {info}"
    x = np.asarray(X)
    errs = [_rel(np.linalg.norm(A[i] @ x[i] - b[i]),
                 np.linalg.norm(A[i]) * np.linalg.norm(x[i]))
            for i in range(bs)]
    return _batched_result(p, errs, bs * (n**3 / 3 + 2.0 * n * n * nrhs), t)


@_routine("gels_batched", "serve")
def run_gels_batched(p, slate):
    """Batched least squares (serve.gels_batched): normal-equations residual
    per element, sweeping the tall/square/wide shape grid via --tall/--wide."""
    m, n, nrhs = p["m"], p["n"], min(p.get("nrhs", 4), 4)
    bs = int(p.get("batch", 4))
    A = _batch_stack(lambda i: _gen("randn", m, n,
                                    dict(p, seed=p["seed"] + i)), bs)
    b = _batch_stack(lambda i: _gen("randn", m, nrhs,
                                    dict(p, seed=100 + p["seed"] + i)), bs)
    from slate_tpu import serve

    (X, info), t = time_call(
        lambda: serve.gels_batched(jnp.asarray(A), jnp.asarray(b)),
        repeat=p["repeat"])
    assert not np.asarray(info).any(), f"nonzero batched info {info}"
    x = np.asarray(X)
    errs = []
    for i in range(bs):
        if m >= n:
            r = A[i].conj().T @ (A[i] @ x[i] - b[i])
            errs.append(_rel(np.linalg.norm(r), np.linalg.norm(A[i]) ** 2
                             * max(np.linalg.norm(x[i]), 1e-10)))
        else:       # consistent underdetermined system: direct residual
            errs.append(_rel(np.linalg.norm(A[i] @ x[i] - b[i]),
                             np.linalg.norm(A[i]) * np.linalg.norm(x[i])))
    return _batched_result(p, errs, bs * 2.0 * m * n * min(m, n), t,
                           tol_mult=100)


# ---------------------------------------------------------------------------
# eig / svd

@_routine("heev", "eig")
def run_heev(p, slate):
    """‖A Z − Z Λ‖/‖A‖ + ‖I − ZᴴZ‖ (the reference's eig check)."""
    n = p["n"]
    A = _herm(n, p)
    g = _grid(p)
    Aop = (slate.HermitianMatrix.from_array(slate.Uplo.Lower, A.copy(),
                                            nb=p["nb"], grid=g)
           if g is not None else A.copy())
    (lam, Z), t = time_call(lambda: slate.heev(Aop), repeat=p["repeat"])
    lam, Z = np.asarray(lam), np.asarray(Z)
    err1 = _rel(np.linalg.norm(A @ Z - Z * lam[None, :]), np.linalg.norm(A))
    err2 = np.linalg.norm(Z.conj().T @ Z - np.eye(n)) / n
    out = _result(p, max(err1, err2), 9.0 * n ** 3, t)
    out["details"] = {"phases": _phases("heev")}
    return out


@_routine("heevx", "eig")
def run_heevx(p, slate):
    """Subset eigenpairs (no reference analogue): indices [n/4, n/2) via
    index-targeted bisection + thin back-transforms; residual +
    orthogonality on the k computed columns."""
    n = p["n"]
    il, iu = n // 4, n // 2
    A = _herm(n, p)
    (lam, Z), t = time_call(
        lambda: slate.heev_range(A.copy(), il=il, iu=iu),
        repeat=p["repeat"])
    lam, Z = np.asarray(lam), np.asarray(Z)
    k = iu - il
    err1 = _rel(np.linalg.norm(A @ Z - Z * lam[None, :]), np.linalg.norm(A))
    err2 = np.linalg.norm(Z.conj().T @ Z - np.eye(k)) / n
    # index-targeting gate: the one behavior heevx adds over heev
    ref = np.linalg.eigvalsh(A.astype(np.complex128 if np.iscomplexobj(A)
                                      else np.float64))
    err3 = _rel(np.max(np.abs(lam - ref[il:iu])), max(np.max(np.abs(ref)),
                                                      1e-10))
    err1 = max(err1, err3)
    # stage 1 dominates: 4/3 n^3 band reduction + O(n^2 (nb + k)) tail
    return _result(p, max(err1, err2), 4.0 * n ** 3 / 3.0, t)


@_routine("hegvx", "eig")
def run_hegvx(p, slate):
    """Generalized subset eigenpairs (no reference analogue): indices
    [n/4, n/2) of A x = lam B x; generalized residual + index gate."""
    n = p["n"]
    il, iu = n // 4, n // 2
    A = _herm(n, p)
    Bm = _gen("randn", n, n, p)
    B = (Bm @ Bm.conj().T + n * np.eye(n)).astype(p["dtype"])
    (out), t = time_call(
        lambda: slate.hegv_range(1, A.copy(), B.copy(), il=il, iu=iu),
        repeat=p["repeat"])
    lam, Z = (np.asarray(x) for x in out)
    err1 = _rel(np.linalg.norm(A @ Z - B @ Z * lam[None, :]),
                np.linalg.norm(A) + np.linalg.norm(B) * np.max(np.abs(lam)))
    import scipy.linalg as _sla
    ref = _sla.eigh(A.astype(np.complex128 if np.iscomplexobj(A)
                             else np.float64),
                    B.astype(np.complex128 if np.iscomplexobj(B)
                             else np.float64), eigvals_only=True)
    err2 = _rel(np.max(np.abs(lam - ref[il:iu])),
                max(np.max(np.abs(ref)), 1e-10))
    return _result(p, max(err1, err2), 4.0 * n ** 3 / 3.0, t)


@_routine("gesvdx", "svd")
def run_gesvdx(p, slate):
    """Top-k singular triplets (no reference analogue): GK-bisection subset
    + thin back-transforms; triplet residual on the k columns."""
    n = p["n"]
    k = max(1, n // 8)
    A = _gen("randn", n, n, p)
    (out), t = time_call(
        lambda: slate.svd_range(A.copy(), il=0, iu=k), repeat=p["repeat"])
    S, U, VT = (np.asarray(x) for x in out)
    err1 = _rel(np.linalg.norm(A @ VT.conj().T - U * S[None, :]),
                np.linalg.norm(A))
    err2 = np.linalg.norm(U.conj().T @ U - np.eye(k)) / n
    err3 = np.linalg.norm(VT @ VT.conj().T - np.eye(k)) / n
    return _result(p, max(err1, err2, err3), 8.0 * n ** 3 / 3.0, t)


@_routine("steqr", "eig")
def run_steqr(p, slate):
    """Tridiagonal QR iteration (src/steqr.cc): ‖T Q − Q Λ‖/‖T‖ +
    orthogonality, real implicit-shift sweeps at every size."""
    import numpy.random as _r
    n = p["n"]
    rng = np.random.default_rng(p["seed"])
    d = rng.standard_normal(n).astype(p["dtype"])
    e = rng.standard_normal(n - 1).astype(p["dtype"])
    T = np.diag(d.astype(np.float64)) + np.diag(e.astype(np.float64), 1) \
        + np.diag(e.astype(np.float64), -1)
    (lam, Q), t = time_call(lambda: slate.steqr(d, e), repeat=p["repeat"])
    lam, Q = np.asarray(lam, np.float64), np.asarray(Q, np.float64)
    err1 = _rel(np.linalg.norm(T @ Q - Q * lam[None, :]), np.linalg.norm(T))
    err2 = np.linalg.norm(Q.T @ Q - np.eye(n)) / n
    # ~3 sweeps/eigenvalue x n^2-class rotation+gemm work: 6 n^3 job model.
    # Accuracy envelope of accumulated QR iteration is O(sweeps*eps) =
    # O(n*eps); the suite-wide tol carries sqrt(n), so the gate needs the
    # other sqrt(n) factor
    return _result(p, max(err1, err2), 6.0 * n ** 3, t,
                   tol_mult=max(1.0, n ** 0.5) / 10.0)


@_routine("sterf", "eig")
def run_sterf(p, slate):
    """Stage-level tester for the tridiagonal VALUES solver (test_sterf.cc):
    eigenvalues of T(d, e) vs the f64 dense reference — the sweep surface
    that localizes a two-stage regression to the tridiag phase."""
    n = p["n"]
    rng = np.random.default_rng(p["seed"])
    rdt = np.dtype(p["dtype"]).char.lower()     # sterf is real-only, like LAPACK
    d = rng.standard_normal(n).astype(rdt)
    e = rng.standard_normal(n - 1).astype(rdt)
    from slate_tpu.linalg.eig import sterf

    lam, t = time_call(lambda: sterf(d, e), repeat=p["repeat"])
    lam = np.sort(np.asarray(lam, np.float64))
    T = np.diag(d.astype(np.float64)) + np.diag(e.astype(np.float64), 1) \
        + np.diag(e.astype(np.float64), -1)
    ref = np.linalg.eigvalsh(T)
    err = _rel(np.max(np.abs(lam - ref)), max(np.max(np.abs(ref)), 1e-30))
    # O(n^2) bisection work model (PWK/sterf class)
    return _result(p, err, 2.0 * n * n, t)


@_routine("he2hb", "eig")
def run_he2hb(p, slate):
    """Stage-level tester for the full->band reduction (test_he2hb.cc):
    ‖Qᴴ A Q − B‖/‖A‖ via the stacked block reflectors, plus band shape."""
    n = p["n"]
    A = _herm(n, p)
    from slate_tpu.linalg.eig import default_band_nb, he2hb, he2hb_q

    nb = default_band_nb(n, None)
    (band, Vs, Ts), t = time_call(lambda: he2hb(A.copy(), nb=nb),
                                  repeat=p["repeat"])
    band, Q = np.asarray(band), np.asarray(he2hb_q(Vs, Ts))
    err1 = _rel(np.linalg.norm(Q.conj().T @ A @ Q - band), np.linalg.norm(A))
    err2 = np.linalg.norm(Q.conj().T @ Q - np.eye(n)) / n
    r, c = np.nonzero(np.abs(band) > 0)
    bw_ok = (len(r) == 0) or (np.max(np.abs(r - c)) <= nb)
    out = _result(p, max(err1, err2), 4.0 * n ** 3 / 3.0, t, tol_mult=4)
    if not bw_ok:
        out["status"], out["message"] = "FAILED", f"bandwidth > nb={nb}"
    out["details"] = {"nb": nb}
    return out


@_routine("hb2st", "eig")
def run_hb2st(p, slate):
    """Stage-level tester for the band->tridiagonal chase (test_hb2st.cc):
    ‖B Q2 − Q2 T‖/‖B‖ + orthogonality of the accumulated Q2."""
    n = p["n"]
    kd = max(2, min(8, n // 8))
    A = _herm(n, p)
    r_idx = np.arange(n)
    band = np.where(np.abs(r_idx[:, None] - r_idx[None, :]) <= kd, A, 0)
    from slate_tpu.linalg.eig import hb2st

    (d, e, Q2), t = time_call(
        lambda: hb2st(band.copy(), kd=kd, want_vectors=True),
        repeat=p["repeat"])
    d, e, Q2 = np.asarray(d), np.asarray(e), np.asarray(Q2)
    T = np.diag(d.astype(np.float64)) + np.diag(e.astype(np.float64), 1) \
        + np.diag(e.astype(np.float64), -1)
    err1 = _rel(np.linalg.norm(band @ Q2 - Q2 @ T.astype(Q2.dtype)),
                np.linalg.norm(band))
    err2 = np.linalg.norm(Q2.conj().T @ Q2 - np.eye(n)) / n
    # chase work model: O(n^2 kd) reflector flops + O(n^3)-class Q2 gemms
    out = _result(p, max(err1, err2), 2.0 * n ** 3, t, tol_mult=4)
    out["details"] = {"kd": kd}
    return out


@_routine("hegv", "eig")
def run_hegv(p, slate):
    n = p["n"]
    A = _herm(n, p)
    B = _spd(n, dict(p, seed=p["seed"] + 3))
    (lam, Z), t = time_call(lambda: slate.hegv(1, A.copy(), B.copy()),
                            repeat=p["repeat"])
    lam, Z = np.asarray(lam), np.asarray(Z)
    err = _rel(np.linalg.norm(A @ Z - (B @ Z) * lam[None, :]),
               np.linalg.norm(A) * np.linalg.norm(Z))
    return _result(p, err, 14.0 * n ** 3, t, tol_mult=20)


@_routine("svd", "svd")
def run_svd(p, slate):
    m, n = p["m"], p["n"]
    A = _gen(p["kind"], m, n, p)
    g = _grid(p)
    Aop = (slate.Matrix.from_array(A.copy(), nb=p["nb"], grid=g)
           if g is not None else A.copy())
    (S, U, VT), t = time_call(lambda: slate.svd(Aop), repeat=p["repeat"])
    S, U, VT = np.asarray(S), np.asarray(U), np.asarray(VT)
    k = min(m, n)
    err1 = _rel(np.linalg.norm(A - (U[:, :k] * S[None, :k]) @ VT[:k]),
                np.linalg.norm(A))
    err2 = np.linalg.norm(U.conj().T @ U - np.eye(U.shape[1])) / k
    out = _result(p, max(err1, err2), 4.0 * m * n * min(m, n), t)
    out["details"] = {"phases": _phases("svd")}
    return out


@_routine("gecondest", "condest")
def run_gecondest(p, slate):
    """Condition estimate within 100x of the true cond (estimates are bounds)."""
    n = p["n"]
    cond = p.get("cond") or 100.0
    A = _gen("svd_geo", n, n, p, cond=cond)
    lu_, perm, info = slate.getrf(A.copy())
    est, t = time_call(lambda: slate.gecondest(lu_, perm, slate.norm("one", A)),
                       repeat=p["repeat"])
    true = np.linalg.cond(A, 1)
    rcond_est = float(est)
    ratio = (1.0 / max(rcond_est, 1e-30)) / true
    ok = 0.01 < ratio < 100.0
    return {"error": abs(np.log10(max(ratio, 1e-30))), "time_s": t, "gflops": None,
            "ref_time_s": None, "status": "pass" if ok else "FAILED",
            "message": "" if ok else f"est/true ratio {ratio:.2e}"}


# ---------------------------------------------------------------------------
# entry

def run_routine(name: str, params: dict) -> TestResult:
    """Run one routine at one parameter point; never raises."""
    import slate_tpu as slate
    spec = ROUTINES.get(name)
    if spec is None:
        raise KeyError(f"unknown routine '{name}'; known: {sorted(ROUTINES)}")
    from ..core.exceptions import NumericalError

    try:
        fields = spec["runner"](params, slate)
        return TestResult(routine=name, params=params, **fields)
    except NumericalError as e:
        # the taxonomy is reported, never swallowed: the row carries the
        # exact failure class (SingularMatrixError / ConvergenceError / ...)
        # plus any info index, so a sweep distinguishes "matrix was singular"
        # from tester plumbing blowing up
        info = getattr(e, "info", None)
        detail = f" info={info}" if info else ""
        return TestResult(routine=name, params=params, status="error",
                          message=f"{type(e).__name__}: {e}{detail}")
    # slate-lint: disable=SLT501 -- intentional catch-all: the tester reports
    # rows, it doesn't crash mid-sweep; the NumericalError taxonomy is already
    # reported with its class by the handler above
    except Exception as e:  # noqa: BLE001 — the tester reports, it doesn't crash
        return TestResult(routine=name, params=params, status="error",
                          message=f"{type(e).__name__}: {e}")
