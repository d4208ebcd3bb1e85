"""Cholesky family tests (reference: test/test_posv.cc — residual
||b - A x|| / (||A|| ||x|| n eps) gate; test_potri, test_trtri)."""

import numpy as np
import jax.numpy as jnp
import pytest

import slate_tpu as slate
from slate_tpu import linalg


def _spd(rng, n, cplx=False):
    a = rng.standard_normal((n, n))
    if cplx:
        a = a + 1j * rng.standard_normal((n, n))
    return a @ a.conj().T + n * np.eye(n)


@pytest.mark.parametrize("target", ["xla", "tiled"])
@pytest.mark.parametrize("uplo", ["lower", "upper"])
def test_potrf_residual(rng, target, uplo):
    n = 37
    a = _spd(rng, n)
    A = slate.HermitianMatrix.from_array(uplo, a.copy(), nb=8)
    F, info = linalg.potrf(A, {"target": target, "block_size": 8})
    assert int(info) == 0
    got = np.asarray(A.array)
    if uplo == "lower":
        L = np.tril(got)
        resid = np.linalg.norm(L @ L.T - a) / np.linalg.norm(a)
        # unstored triangle untouched
        np.testing.assert_array_equal(np.triu(got, 1), np.triu(a, 1))
    else:
        U = np.triu(got)
        resid = np.linalg.norm(U.T @ U - a) / np.linalg.norm(a)
        np.testing.assert_array_equal(np.tril(got, -1), np.tril(a, -1))
    assert resid < 1e-13


def test_potrf_complex_tiled(rng):
    n = 20
    a = _spd(rng, n, cplx=True)
    A = slate.HermitianMatrix.from_array("lower", a.copy(), nb=6)
    _, info = linalg.potrf(A, {"target": "tiled", "block_size": 6})
    assert int(info) == 0
    L = np.tril(np.asarray(A.array))
    assert np.linalg.norm(L @ L.conj().T - a) / np.linalg.norm(a) < 1e-13


def test_potrf_not_spd_info(rng):
    a = np.eye(5)
    a[3, 3] = -1.0
    A = slate.HermitianMatrix.from_array("lower", a, nb=2)
    _, info = linalg.potrf(A)
    # default path is fully jittable (no host sync): info != 0, but XLA's
    # NaN-filled factor loses the exact index
    assert int(info) != 0
    _, info = linalg.potrf(slate.HermitianMatrix.from_array("lower", a, nb=2),
                           opts={"exact_info": True})
    assert int(info) == 4  # 1-based first bad pivot (host-refined)


def test_posv_solves(rng):
    n, nrhs = 24, 3
    a = _spd(rng, n)
    b = rng.standard_normal((n, nrhs))
    A = slate.HermitianMatrix.from_array("lower", a.copy(), nb=8)
    B = slate.Matrix.from_array(b.copy(), nb=8)
    X, info = linalg.posv(A, B)
    assert int(info) == 0
    x = np.asarray(X)
    resid = np.linalg.norm(b - a @ x) / (np.linalg.norm(a) * np.linalg.norm(x) * n)
    assert resid < 1e-15
    # wrapper was updated in place too
    np.testing.assert_array_equal(np.asarray(B.array), x)


def test_trtri_trtrm_potri(rng):
    n = 16
    t = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    T = slate.TriangularMatrix.from_array("lower", t.copy(), nb=4)
    linalg.trtri(T)
    np.testing.assert_allclose(np.asarray(T.array) @ t, np.eye(n), atol=1e-10)
    # potri: inverse of SPD
    a = _spd(rng, n)
    A = slate.HermitianMatrix.from_array("lower", a.copy(), nb=4)
    linalg.potrf(A)
    linalg.potri(A)
    inv = np.asarray(A.array)
    full_inv = np.tril(inv) + np.tril(inv, -1).T
    np.testing.assert_allclose(full_inv @ a, np.eye(n), atol=1e-8)


def test_posv_mixed_converges(rng):
    n = 32
    a = _spd(rng, n)
    b = rng.standard_normal((n, 2))
    A = slate.HermitianMatrix.from_array("lower", a.copy(), nb=8)
    B = slate.Matrix.from_array(b.copy(), nb=8)
    X, info, iters = linalg.posv_mixed(A, B)
    assert int(info) == 0
    x = np.asarray(X)
    resid = np.linalg.norm(b - a @ x) / (np.linalg.norm(a) * np.linalg.norm(x))
    # IR should reach near working precision, far better than bare f32
    assert resid < 1e-12
    assert int(iters) >= 1


def test_posv_mixed_fallback_on_hard_system(rng):
    # very ill-conditioned SPD: IR in f32 stalls, fallback must still solve
    n = 16
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    d = np.logspace(0, 14, n)
    a = (q * d) @ q.T
    a = (a + a.T) / 2
    b = rng.standard_normal((n, 1))
    X, info, iters = linalg.posv_mixed(
        slate.HermitianMatrix.from_array("lower", a, nb=8),
        slate.Matrix.from_array(b.copy(), nb=8),
        {"max_iterations": 3})
    x = np.asarray(X)
    resid = np.linalg.norm(b - a @ x) / (np.linalg.norm(a) * np.linalg.norm(x))
    assert resid < 1e-8  # solved by fallback posv in f64


def test_trtri_preserves_unstored_triangle(rng):
    n = 8
    t = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    poison = t + np.triu(np.full((n, n), 7.0), 1)
    T = slate.TriangularMatrix.from_array("lower", poison.copy(), nb=4)
    linalg.trtri(T)
    got = np.asarray(T.array)
    np.testing.assert_array_equal(np.triu(got, 1), np.triu(poison, 1))
    np.testing.assert_allclose(np.tril(got) @ t, np.eye(n), atol=1e-10)


def test_potri_on_general_matrix_defaults_lower(rng):
    n = 8
    a = _spd(rng, n)
    M = slate.Matrix.from_array(a.copy(), nb=4)
    linalg.potrf(M)
    linalg.potri(M)
    inv = np.asarray(M.array)
    full_inv = np.tril(inv) + np.tril(inv, -1).T
    np.testing.assert_allclose(full_inv @ a, np.eye(n), atol=1e-8)


def _poisoned(a, uplo):
    """``a`` with its unstored strict triangle overwritten by NaN."""
    junk = np.full_like(a, np.nan)
    if uplo == "lower":
        return np.tril(a) + np.triu(junk, 1)
    return np.triu(a) + np.tril(junk, -1)


def _unstored(x, uplo):
    return np.triu(x, 1) if uplo == "lower" else np.tril(x, -1)


@pytest.mark.parametrize("target", ["xla", "tiled"])
@pytest.mark.parametrize("uplo", ["lower", "upper"])
def test_potrf_never_reads_the_unstored_triangle(rng, target, uplo):
    n = 37
    a = _spd(rng, n)
    opts = {"target": target, "block_size": 8}
    clean = slate.HermitianMatrix.from_array(uplo, a.copy(), nb=8)
    F0, info0 = linalg.potrf(clean, opts)
    poison = _poisoned(a, uplo)
    A = slate.HermitianMatrix.from_array(uplo, poison.copy(), nb=8)
    F, info = linalg.potrf(A, opts)
    assert int(info) == int(info0) == 0
    np.testing.assert_array_equal(np.asarray(F), np.asarray(F0))
    got = np.asarray(A.array)
    assert np.isnan(_unstored(got, uplo)).sum() == n * (n - 1) // 2
    np.testing.assert_array_equal(_unstored(got, uplo), _unstored(poison, uplo))
    tri = np.tril if uplo == "lower" else np.triu
    np.testing.assert_array_equal(tri(got), tri(np.asarray(clean.array)))


@pytest.mark.parametrize("target", ["xla", "tiled"])
@pytest.mark.parametrize("uplo", ["lower", "upper"])
def test_posv_never_reads_the_unstored_triangle(rng, target, uplo):
    n, nrhs = 29, 3
    a = _spd(rng, n)
    b = rng.standard_normal((n, nrhs))
    opts = {"target": target, "block_size": 8}
    X0, _ = linalg.posv(slate.HermitianMatrix.from_array(uplo, a.copy(), nb=8),
                        b.copy(), opts)
    poison = _poisoned(a, uplo)
    A = slate.HermitianMatrix.from_array(uplo, poison.copy(), nb=8)
    X, info = linalg.posv(A, b.copy(), opts)
    assert int(info) == 0
    np.testing.assert_array_equal(np.asarray(X), np.asarray(X0))
    assert np.linalg.norm(b - a @ np.asarray(X)) / np.linalg.norm(b) < 1e-12
    np.testing.assert_array_equal(_unstored(np.asarray(A.array), uplo),
                                  _unstored(poison, uplo))


@pytest.mark.parametrize("target", ["xla", "tiled"])
@pytest.mark.parametrize("uplo", ["lower", "upper"])
def test_potrf_complex_diagonal_takes_its_real_part(rng, target, uplo):
    """zpotrf semantics: the imaginary part of a Hermitian diagonal is
    ignored, so it factors as with the diagonal real-cast."""
    n = 20
    a = _spd(rng, n, cplx=True)
    opts = {"target": target, "block_size": 6}
    F0, _ = linalg.potrf(slate.HermitianMatrix.from_array(uplo, a.copy(), nb=6),
                         opts)
    noisy = a + 1j * np.diag(rng.standard_normal(n))
    F, info = linalg.potrf(
        slate.HermitianMatrix.from_array(uplo, noisy, nb=6), opts)
    assert int(info) == 0
    np.testing.assert_array_equal(np.asarray(F), np.asarray(F0))
    Fa = np.asarray(F)
    prod = Fa @ Fa.conj().T if uplo == "lower" else Fa.conj().T @ Fa
    assert np.linalg.norm(prod - a) / np.linalg.norm(a) < 1e-13


@pytest.mark.parametrize("target", ["xla", "tiled"])
def test_potrf_raw_array_factors_its_lower_triangle(rng, target):
    n = 33
    a = _spd(rng, n)
    opts = {"target": target, "block_size": 8}
    L0, _ = linalg.potrf(a.copy(), opts)
    junk = np.tril(a) + np.triu(rng.standard_normal((n, n)), 1)
    L, info = linalg.potrf(junk, opts)
    assert int(info) == 0
    np.testing.assert_array_equal(np.asarray(L), np.asarray(L0))
    np.testing.assert_allclose(np.asarray(L), np.linalg.cholesky(a),
                               rtol=1e-12, atol=1e-12)


def test_posv_compiles_no_full_matrix_prep():
    """The compiled posv on a lower-stored wrapper hands the stored array to
    the Cholesky: no transpose or scatter of the matrix, and every n x n
    operation traced from the program is the factor, its mask and store, or
    the solves."""
    import re

    import jax

    n = 512

    def posv(a, b):
        B = slate.Matrix.from_array(b)
        _, info = slate.posv(slate.HermitianMatrix.from_array("lower", a), B)
        return B.array, info

    text = jax.jit(posv).lower(
        jax.ShapeDtypeStruct((n, n), jnp.float32),
        jax.ShapeDtypeStruct((n, 16), jnp.float32)).compile().as_text()
    square = f"f32[{n},{n}]"
    ops = [m.groups() for m in re.finditer(
        r"(?m)^\s+(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", text)]
    assert ops
    assert [code for shape, code in ops if shape.startswith(square)
            and code in ("transpose", "scatter")] == []
    # top-level operations only; the compiler's relayouts of the argument
    # carry its name, not a traced op_name
    allowed = re.compile(r"/(potrf/(factor|mask|info|store)|potrs/\w+)/")
    entry = text[text.index("\nENTRY"):].splitlines()[1:]
    stray = [line.strip()[:80] for line in entry
             if f"= {square}" in line and 'op_name="jit(' in line
             and not allowed.search(line)]
    assert stray == []


def test_host_chol_info_complex_late_pivot():
    from slate_tpu.linalg.chol import _host_chol_info
    rng = np.random.default_rng(3)
    n = 12
    a = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
    a = a @ a.conj().T + n * np.eye(n)
    # make pivot 10 (0-based 9) fail: set trailing block so Schur complement dips negative
    a[9, 9] = -np.real(a[9, 9])
    info = _host_chol_info(a, nb=4)
    assert info == 10
