"""LU family tests (reference: test/test_gesv.cc — residual gate
||b - A x|| / (||A|| ||x|| n eps); test_getri; gesv_mixed / gesv_rbt testers)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import slate_tpu as slate
from slate_tpu import linalg
from slate_tpu.linalg import lu as lu_mod


def _gen(rng, m, n, cplx=False):
    a = rng.standard_normal((m, n))
    if cplx:
        a = a + 1j * rng.standard_normal((m, n))
    return a


def _check_lu(a, lu_arr, perm):
    m, n = a.shape
    k = min(m, n)
    L = np.tril(np.asarray(lu_arr), -1)[:, :k] + np.eye(m, k)
    U = np.triu(np.asarray(lu_arr))[:k, :]
    pa = a[np.asarray(perm)]
    return np.linalg.norm(pa - L @ U) / np.linalg.norm(a)


@pytest.mark.parametrize("target", ["xla", "tiled"])
def test_getrf_partial_pivot(rng, target):
    n = 29
    a = _gen(rng, n, n)
    A = slate.Matrix.from_array(a.copy(), nb=8)
    lu_arr, perm, info = linalg.getrf(A, {"target": target, "block_size": 8})
    assert int(info) == 0
    assert _check_lu(a, lu_arr, perm) < 1e-13
    assert sorted(np.asarray(perm).tolist()) == list(range(n))


def test_getrf_rectangular_tiled(rng):
    a = _gen(rng, 19, 11)
    lu_arr, perm, info = linalg.getrf(a, {"target": "tiled", "block_size": 4})
    assert _check_lu(a, lu_arr, perm) < 1e-13


def test_getrf_nopiv_diag_dominant(rng):
    n = 21
    a = _gen(rng, n, n) + n * np.eye(n)
    lu_arr, info = linalg.getrf_nopiv(a, {"block_size": 6})
    assert int(info) == 0
    L = np.tril(np.asarray(lu_arr), -1) + np.eye(n)
    U = np.triu(np.asarray(lu_arr))
    assert np.linalg.norm(a - L @ U) / np.linalg.norm(a) < 1e-12


def test_getrf_tntpiv(rng):
    n = 26
    a = _gen(rng, n, n)
    lu_arr, perm, info = linalg.getrf(a, {"method_lu": "calu", "block_size": 5})
    assert int(info) == 0
    assert _check_lu(a, lu_arr, perm) < 1e-11
    assert sorted(np.asarray(perm).tolist()) == list(range(n))


@pytest.mark.parametrize("m,n,nb,ib", [(40, 40, 10, 5), (64, 64, 16, 8),
                                       (70, 50, 16, 4), (50, 70, 32, 8)])
def test_getrf_tntpiv_two_level(rng, m, n, nb, ib):
    """Two-level CALU (outer nb trailing updates, inner ib tournament panels —
    the reference's nb/ib split, getrf_tntpiv.cc + Option::InnerBlocking)."""
    a = _gen(rng, m, n)
    lu_arr, perm, info = linalg.getrf(
        a, {"method_lu": "calu", "block_size": nb, "inner_blocking": ib})
    assert int(info) == 0
    assert _check_lu(a, lu_arr, perm) < 1e-11
    assert sorted(np.asarray(perm).tolist()) == list(range(m))


@pytest.mark.parametrize("m,n,nb,ib", [(40, 40, 10, 5), (64, 64, 16, 8),
                                       (70, 50, 16, 4)])
def test_getrf_tntpiv_pp_panel(rng, m, n, nb, ib):
    """CALU with the partial-pivot panel scheme (Options.lu_panel="pp"): one
    panel LU selects the pivots instead of the merge tree.  Same factorization
    contract — and on square full-rank inputs the selected pivot SET per
    subpanel equals classic partial pivoting's."""
    a = _gen(rng, m, n)
    lu_arr, perm, info = linalg.getrf(
        a, {"method_lu": "calu", "block_size": nb, "inner_blocking": ib,
            "lu_panel": "pp"})
    assert int(info) == 0
    assert _check_lu(a, lu_arr, perm) < 1e-11
    assert sorted(np.asarray(perm).tolist()) == list(range(m))


def test_getrf_tntpiv_pp_matches_lapack_pivots(rng):
    """With ib == nb == n (one panel), pp-CALU must reproduce classic partial
    pivoting exactly — same permutation, same factor."""
    n = 24
    a = _gen(rng, n, n)
    lu_arr, perm, info = linalg.getrf(
        a, {"method_lu": "calu", "block_size": n, "inner_blocking": n,
            "lu_panel": "pp"})
    import scipy.linalg as sla

    lu_ref, piv = sla.lu_factor(a)
    perm_ref = np.arange(n)
    for i, p in enumerate(piv):
        perm_ref[[i, p]] = perm_ref[[p, i]]
    assert np.array_equal(np.asarray(perm), perm_ref)
    assert np.allclose(np.asarray(lu_arr), lu_ref, atol=1e-12)


# shapes whose full-width panels roll into runs of more than one panel
# (more than _MIN_RUNS panels), with a ragged last panel, ib < nb, tall and
# wide
ROLLED = [(163, 163, 8, 8), (163, 163, 8, 4), (200, 150, 8, 8),
          (150, 203, 8, 4)]


@pytest.mark.parametrize("scheme", ["tournament", "pp"])
@pytest.mark.parametrize("m,n,nb,ib", ROLLED)
def test_getrf_tntpiv_rolled_panel_runs(rng, m, n, nb, ib, scheme):
    """The rolled panel loop (runs of panels, each one fori_loop on windows
    padded past the matrix): a factorization of A[perm], perm a
    permutation, for both panel schemes."""
    assert max(lu_mod._panel_runs(min(m, n) // nb)) > 1
    a = _gen(rng, m, n)
    lu_arr, perm, info = linalg.getrf(
        a, {"method_lu": "calu", "block_size": nb, "inner_blocking": ib,
            "lu_panel": scheme})
    assert int(info) == 0
    assert _check_lu(a, lu_arr, perm) < 1e-11
    assert sorted(np.asarray(perm).tolist()) == list(range(m))


@pytest.mark.parametrize("n", [24, 136])
def test_getrf_tntpiv_pp_one_panel_is_partial_pivoting(rng, n):
    """ib == nb == n: pp-CALU's one panel LU is classic partial pivoting,
    LAPACK's pivots and factor."""
    import scipy.linalg as sla

    a = _gen(rng, n, n)
    lu_arr, perm, _ = linalg.getrf(
        a, {"method_lu": "calu", "block_size": n, "inner_blocking": n,
            "lu_panel": "pp"})
    lu_ref, piv = sla.lu_factor(a)
    assert np.array_equal(np.asarray(perm), lu_mod.pivots_to_perm(piv + 1))
    assert np.allclose(np.asarray(lu_arr), lu_ref, atol=1e-12)


def _lowered_ops(n, nb=256):
    import re

    text = lu_mod._getrf_tntpiv_fn(n, n, nb, nb, "float32").lower(
        jax.ShapeDtypeStruct((n, n), jnp.float32)).as_text()
    return len(re.findall(r"= (?:stablehlo|chlo|mhlo)\.", text))


def test_getrf_tntpiv_program_does_not_grow_with_panels():
    """The rolled factorization's program is the size of its runs, not of
    its panels: n=4096 (16 panels of 256) lowers to at most 1.25x the
    operations of n=2048 (8 panels); unrolled it was twice as many."""
    assert _lowered_ops(4096) <= 1.25 * _lowered_ops(2048)


def test_getrf_tntpiv_flops_at_n2048():
    """XLA's count of the compiled factorization at n=2048, nb=256 (8 runs
    of one panel, so every loop body counts once, as it runs) lies within
    1.15x of LAPACK's 2n^3/3."""
    from slate_tpu.testing import cost_analysis_dict

    n = 2048
    comp = lu_mod._getrf_tntpiv_fn(n, n, 256, 256, "float32").lower(
        jax.ShapeDtypeStruct((n, n), jnp.float32)).compile()
    flops = cost_analysis_dict(comp)["flops"]
    assert 0.5 * 2 * n ** 3 / 3 <= flops <= 1.15 * 2 * n ** 3 / 3


def _replay_max_l(block, winners):
    """The largest |l| of each step of an f64 no-pivot LU of ``block`` with
    ``winners`` moved to its top in their order: at most 1 where they are a
    partial-pivot choice, near 1 at a near-tie."""
    rest = np.setdiff1d(np.arange(block.shape[0]), winners)
    a = block.astype(np.float64)[np.concatenate([winners, rest])]
    worst = np.empty(block.shape[1])
    for k in range(block.shape[1]):
        l = a[k + 1:, k] / a[k, k]
        worst[k] = np.max(np.abs(l))
        a[k + 1:, k + 1:] -= np.outer(l, a[k, k + 1:])
    return worst


@pytest.mark.parametrize("w", [128, 256])
@pytest.mark.parametrize("nblk", [1, 3, 32])
def test_merge_kernel_is_partial_pivoting(rng, nblk, w):
    """The Pallas merge kernel (interpreted here) picks, for each stacked
    (2w, w) block, rows partial pivoting may pick, in its order, and the
    rows XLA's LU picks, but where a near-tie lets rounding choose."""
    from slate_tpu.ops.pallas_tntpiv import merge_winners

    v2 = rng.standard_normal((nblk, 2 * w, w)).astype(np.float32)
    got = np.asarray(merge_winners(jnp.asarray(v2)))
    want = np.asarray(jax.lax.linalg.lu(jnp.asarray(v2))[2])[:, :w]
    assert got.shape == (nblk, w)
    for block, g, x in zip(v2, got, want):
        worst = _replay_max_l(block, g)
        assert worst.max() <= 1 + 1e-5
        if set(g) != set(x):
            k = np.argmax(g != x)           # where the two orders part
            assert worst[k] >= 1 - 1e-4, (k, worst[k])


@pytest.mark.parametrize("zero", [0, 1])
def test_merge_kernel_zero_half_returns_other_half(rng, zero):
    """A stack whose one half is zero (a leaf past the live rows) returns
    the other half's rows."""
    from slate_tpu.ops.pallas_tntpiv import merge_winners

    w = 128
    v2 = rng.standard_normal((2, 2 * w, w)).astype(np.float32)
    v2[:, zero * w:(zero + 1) * w] = 0
    got = np.asarray(merge_winners(jnp.asarray(v2)))
    other = set(range((1 - zero) * w, (2 - zero) * w))
    assert all(set(g) == other for g in got)


@pytest.fixture
def platform(monkeypatch):
    """Set the platform the tournament sees its operands on: "tpu" routes
    f32 merges of 128-multiple widths to the Pallas kernel (interpreted on
    this CPU).  The factorization's program is rebuilt to retrace."""
    from types import SimpleNamespace

    def use(name):
        monkeypatch.setattr(lu_mod, "_operand_device",
                            lambda a: SimpleNamespace(platform=name))
        lu_mod._getrf_tntpiv_fn.cache_clear()

    yield use
    lu_mod._getrf_tntpiv_fn.cache_clear()


def test_getrf_tntpiv_merge_kernel_matches_xla_path(rng, platform):
    """getrf_tntpiv at f32 n=1024, nb=ib=256 selects through the merge
    kernel and through XLA's LU: both factor A[perm], perm a permutation,
    with residuals within 2x of each other; the trace counts each path's
    merges under its ``impl``."""
    from slate_tpu import obs

    n = 1024
    a = rng.standard_normal((n, n)).astype(np.float32)
    merges = obs.counter("lu_tournament_merges")
    resid = {}
    for name, impl in (("tpu", "pallas"), ("cpu", "xla")):
        platform(name)
        before = merges.value(impl=impl)
        lu_arr, perm, info = linalg.getrf(
            a, {"method_lu": "calu", "block_size": 256,
                "inner_blocking": 256})
        assert merges.value(impl=impl) > before
        assert int(info) == 0
        assert sorted(np.asarray(perm).tolist()) == list(range(n))
        resid[impl] = _check_lu(a.astype(np.float64),
                                np.asarray(lu_arr, np.float64), perm)
        assert resid[impl] < 1e-5
    assert 0.5 <= resid["pallas"] / resid["xla"] <= 2.0, resid


def test_merge_kernel_not_taken_off_f32(rng, platform):
    """On a TPU, f64 merges stay with XLA's LU: the trace counts them
    under ``impl="xla"`` and none under ``"pallas"``."""
    from slate_tpu import obs

    n = 512
    a = rng.standard_normal((n, n))
    merges = obs.counter("lu_tournament_merges")
    platform("tpu")
    before = {i: merges.value(impl=i) for i in ("pallas", "xla")}
    lu_arr, perm, info = linalg.getrf(
        a, {"method_lu": "calu", "block_size": 256, "inner_blocking": 256})
    assert merges.value(impl="xla") > before["xla"]
    assert merges.value(impl="pallas") == before["pallas"]
    assert _check_lu(a, lu_arr, perm) < 1e-11


def test_getrf_bad_lu_panel_raises(rng):
    """lu_panel is validated on EVERY getrf path, not silently ignored
    (parity-audit behavior contract) — including the default PartialPiv
    path, where the knob is inert but a typo must still surface."""
    from slate_tpu.core.exceptions import SlateError

    a = _gen(rng, 16, 16)
    with pytest.raises(SlateError):
        linalg.getrf(a, {"method_lu": "calu", "lu_panel": "bogus"})
    with pytest.raises(SlateError):
        linalg.getrf(a, {"lu_panel": "bogus"})      # default method path


@pytest.mark.parametrize("method", ["partialpiv", "calu"])
def test_gesv(rng, method):
    n, nrhs = 24, 3
    a = _gen(rng, n, n)
    b = _gen(rng, n, nrhs)
    A = slate.Matrix.from_array(a.copy(), nb=8)
    B = slate.Matrix.from_array(b.copy(), nb=8)
    X, perm, info = linalg.gesv(A, B, {"method_lu": method, "target": "tiled",
                                       "block_size": 8})
    x = np.asarray(X)
    resid = np.linalg.norm(b - a @ x) / (np.linalg.norm(a) * np.linalg.norm(x) * n)
    assert resid < 1e-14


def test_getrs_trans(rng):
    n = 16
    a = _gen(rng, n, n)
    b = _gen(rng, n, 2)
    lu_arr, perm, info = linalg.getrf(a.copy())
    x = linalg.getrs(lu_arr, perm, b.copy(), trans=True)
    resid = np.linalg.norm(b - a.T @ np.asarray(x)) / np.linalg.norm(b)
    assert resid < 1e-11


def test_getri(rng):
    """getri consumes the (LU, perm) factor like the reference (src/getri.cc)."""
    n = 18
    a = _gen(rng, n, n)
    A = slate.Matrix.from_array(a.copy(), nb=6)
    lu_, perm, info = linalg.getrf(A)
    inv = linalg.getri(lu_, perm)
    np.testing.assert_allclose(np.asarray(inv) @ a, np.eye(n), atol=1e-10)


def test_gesv_mixed(rng):
    n = 32
    a = _gen(rng, n, n) + n * np.eye(n)
    b = _gen(rng, n, 2)
    X, perm, info, iters = linalg.gesv_mixed(a, b.copy())
    x = np.asarray(X)
    resid = np.linalg.norm(b - a @ x) / (np.linalg.norm(a) * np.linalg.norm(x))
    assert resid < 1e-12
    assert int(iters) >= 1


def test_gesv_mixed_gmres(rng):
    n = 24
    a = _gen(rng, n, n) + n * np.eye(n)
    b = _gen(rng, n, 1)
    X, perm, info, iters = linalg.gesv_mixed_gmres(a, b.copy())
    x = np.asarray(X)
    resid = np.linalg.norm(b - a @ x) / np.linalg.norm(b)
    assert resid < 1e-10


def test_butterfly_transform_consistency(rng):
    # U^T A V with x = V y must satisfy A x = b when A' y = U^T b
    n, depth = 16, 2
    key = jax.random.PRNGKey(0)
    ku, kv = jax.random.split(key)
    Wu = lu_mod.rbt_generate(ku, n, depth, jnp.float64)
    Wv = lu_mod.rbt_generate(kv, n, depth, jnp.float64)
    a = jnp.asarray(_gen(rng, n, n))
    at = lu_mod._butterfly_apply(Wu, a, transpose=True)
    at = lu_mod._butterfly_apply(Wv, at.T, transpose=True).T
    # dense U and V from applying to identity
    U = lu_mod._butterfly_apply(Wu, jnp.eye(n), transpose=False)
    V = lu_mod._butterfly_apply(Wv, jnp.eye(n), transpose=False)
    np.testing.assert_allclose(np.asarray(at), np.asarray(U).T @ np.asarray(a) @ np.asarray(V),
                               rtol=1e-10, atol=1e-12)


@pytest.mark.parametrize("n", [16, 19])  # 19 exercises the padding path
def test_gesv_rbt(rng, n):
    a = _gen(rng, n, n) + 2 * np.eye(n)
    b = _gen(rng, n, 2)
    X, info, iters = linalg.gesv_rbt(a, b.copy(), {"depth": 2})
    x = np.asarray(X)
    resid = np.linalg.norm(b - a @ x) / (np.linalg.norm(a) * np.linalg.norm(x))
    assert resid < 1e-12


def test_perm_to_pivots_roundtrip(rng):
    n = 12
    a = _gen(rng, n, n)
    lu_arr, perm, info = linalg.getrf(a)
    ipiv = lu_mod.perm_to_pivots(perm)
    # simulate LAPACK swaps on the original matrix rows; must equal a[perm]
    rows = np.arange(n)
    for k in range(n):
        j = ipiv[k] - 1
        rows[[k, j]] = rows[[j, k]]
    np.testing.assert_array_equal(rows, np.asarray(perm))


def test_gesv_mixed_f32_falls_back_cleanly(rng):
    # f32 has no lower factor rung (bf16 unsupported by XLA linalg): plain solve
    n = 12
    a = (np.eye(n) * n + _gen(rng, n, n)).astype(np.float32)
    b = _gen(rng, n, 1).astype(np.float32)
    X, perm, info, iters = linalg.gesv_mixed(a, b.copy())
    assert int(iters) == 0
    resid = np.linalg.norm(b - a @ np.asarray(X)) / np.linalg.norm(b)
    assert resid < 1e-4


def test_gemm_summa_matches_product():
    import slate_tpu as slate
    a = np.ones((4, 4))
    got = slate.gemm(1.0, a, a, 0.0, np.zeros((4, 4)), {"method_gemm": "summa"})
    np.testing.assert_allclose(np.asarray(got), a @ a)


@pytest.mark.parametrize("m, kind, grid, want", [
    (16384, "TPU v5 lite", False, "calu"),   # XLA's LU panel overflows VMEM
    (8192, "TPU v5 lite", False, "partialpiv"),
    (16384, "cpu", False, "partialpiv"),
    (16384, "TPU v4", False, "partialpiv"),  # scope not measured there
    (16384, "TPU v5 lite", True, "partialpiv"),  # grid-bound: distributed
])
def test_auto_lu_method_follows_vmem_fit(monkeypatch, m, kind, grid, want):
    from types import SimpleNamespace

    from slate_tpu.core.types import MethodLU

    monkeypatch.setattr(lu_mod, "_operand_device",
                        lambda a: SimpleNamespace(device_kind=kind))
    a = jnp.zeros((m, 8), jnp.float32)
    A = (slate.Matrix.from_array(a, p=2, q=1,
                                 grid=slate.parallel.ProcessGrid(2, 1))
         if grid else a)
    assert lu_mod._auto_method(A) == {"calu": MethodLU.CALU,
                                      "partialpiv": MethodLU.PartialPiv}[want]


def test_operand_device_reads_concrete_and_traced_operands():
    a = jnp.zeros((4, 4), jnp.float32)
    assert lu_mod._operand_device(a) == next(iter(a.devices()))
    assert lu_mod._operand_device(np.zeros((4, 4))) == jax.devices()[0]
    seen = []
    jax.jit(lambda x: seen.append(lu_mod._operand_device(x)) or x)(a)
    assert seen == [jax.devices()[0]]
