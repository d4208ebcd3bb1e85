"""Software f64 matmul on bf16 hardware (SURVEY §7 hard-part 6).

TPU v5e has no f64 ALUs; the d/z routine families run as f32 with
``Precision.HIGHEST`` (bf16-pass accumulation), whose envelope is
O(eps_f32·√k) per dot product.  This module supplies the *emulation flag*
the survey plans for — double-precision-class gemm semantics built from MXU
bf16 passes, for callers whose refinement loops or residual checks need
f64-class accuracy on chip.

**Ozaki-scheme splitting, made exact.**  After a per-row power-of-two
scale, each operand decomposes on a fixed-point grid:

    a = 2^e_row · Σ_i c_i · 2^(-7-8i),   c_i integer, |c_i| ≤ 256.

Integers up to 256 are exactly representable in bf16, so the slice
matrices ship to the MXU losslessly; every product c_i·c_j is an integer
with |c_i·c_j| ≤ 2^16, exact in the f32 accumulator; and a 256-length
chunk of such products sums to an integer of magnitude ≤ 2^24 — still
exactly representable in f32.  The contraction is therefore chunked at
2^(24-16) = 256, each chunk sum is EXACT, and chunk results (scaled by
their power of two, which is also exact) accumulate in double-f32
(hi, lo) via the 2Sum error-free transformation.  The only rounding in
the whole pipeline is the compensated cross-chunk accumulation and the
final read-out: measured ~1e-14 relative error at n=512 (vs ~1e-5 for
plain f32-HIGHEST), i.e. genuine double-precision-class results.

Cost: slice pairs with i+j ≥ s contribute below 2^(-8s) and are skipped,
so the flop multiplier is s(s+1)/2 ≈ 28 bf16 gemms per dgemm with the
default s=7 (56 mantissa bits ≥ f64's 53) — the classical software-f64
trade.  This is a capability/envelope layer, not the bench path (BASELINE
comparisons stay f32-HIGHEST, documented in bench.py's precision note).

Reference context: the reference's d/z tests (test/run_tests.py --type d,z)
assume hardware f64; this flag is how a TPU deployment meets those
tolerances when it must.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax

_CHUNK = 256             # 2^(24 - 16): exact f32 accumulation length


def _exact_pow2(e, dtype):
    """2^e as EXACT floats via exponent-field bit construction — XLA's
    ``exp2`` is a polynomial approximation whose f32 result can miss the
    exact power of two (observed: exp2(23.0f) = 8388612 != 2^23), which
    would silently break the error-free scaling this module depends on.
    ``e`` is clamped to the normal-exponent range: beyond it the true scale
    is not a representable normal float, and an unclamped shift corrupts
    the sign bit (rows whose magnitudes sit outside ~[2^-126, 2^127] in the
    f32 path saturate, matching what any f32 result could express)."""
    e = jnp.asarray(e)
    if jnp.dtype(dtype) == jnp.dtype(jnp.float64):
        ec = jnp.clip(e.astype(jnp.int64), -1022, 1023)
        return lax.bitcast_convert_type((ec + 1023) << 52, jnp.float64)
    ec = jnp.clip(e.astype(jnp.int32), -126, 127)
    return lax.bitcast_convert_type((ec + 127) << 23, jnp.float32)


def split_fixed_slices(x: jax.Array, s: int):
    """Error-free fixed-grid split: returns (slices, e_row) with
    ``x[i, :] = 2^e_row[i] · Σ_j slices[j][i, :] · 2^(-7-8j)`` and every
    slice an integer-valued bf16 matrix with entries in [-256, 256]."""
    x = jnp.asarray(x)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    e = jnp.where(amax > 0, jnp.floor(jnp.log2(amax)) + 1, 0.0)
    # keep both e and -e inside the normal range of the compute dtype
    lim = 1000.0 if jnp.dtype(x.dtype) == jnp.dtype(jnp.float64) else 120.0
    e = jnp.clip(e, -lim, lim)
    u = x * _exact_pow2(-e, x.dtype)     # |u| < 1 (row-normalized; exact)
    slices = []
    for _ in range(s):
        c = jnp.round(u * 128.0)         # integer in [-128, 128]... plus
        # carry headroom: after the first step |u| <= 0.5 ulp => |c| <= 64;
        # first step |u| < 1 => |c| <= 128.  Both within bf16's exact range.
        slices.append(lax.convert_element_type(c, jnp.bfloat16))
        u = (u - c / 128.0) * 256.0
    return slices, e[..., 0]


def _two_sum(a, b):
    """Knuth 2Sum: s + t == a + b exactly, s = fl(a + b)."""
    s = a + b
    bb = s - a
    t = (a - (s - bb)) + (b - bb)
    return s, t


@lru_cache(maxsize=16)
def _gemm_f64emu_fn(m: int, k: int, n: int, s: int):
    kc = -(-k // _CHUNK)
    kpad = kc * _CHUNK

    def fn(A_slices, B_slices):
        # A_slices: s × (m, k) bf16 integer grids; B_slices: s × (k, n)
        hi = jnp.zeros((m, n), jnp.float32)
        lo = jnp.zeros((m, n), jnp.float32)
        for i in range(s):
            Ai = jnp.pad(A_slices[i], ((0, 0), (0, kpad - k)))
            Ac = Ai.reshape(m, kc, _CHUNK).swapaxes(0, 1)   # (kc, m, CHUNK)
            for j in range(s - i):      # i + j >= s: below target precision
                Bj = jnp.pad(B_slices[j], ((0, kpad - k), (0, 0)))
                Bc = Bj.reshape(kc, _CHUNK, n)
                parts = jax.vmap(lambda a, b: jnp.matmul(
                    a, b, preferred_element_type=jnp.float32))(Ac, Bc)
                # exact integer chunk sums, scaled by their exact power of 2
                scale = jnp.float32(2.0 ** (-14 - 8 * (i + j)))

                def add_chunk(c, hilo, parts=parts, scale=scale):
                    h, l = hilo
                    h2, t = _two_sum(h, parts[c] * scale)
                    return h2, l + t

                hi, lo = lax.fori_loop(0, kc, add_chunk, (hi, lo))
        return hi, lo

    return jax.jit(fn)


def _gemm_f64emu_real(A, B, slices: int):
    """(hi, lo) double-f32 pair for real A @ B, exponents folded back in
    (power-of-two multiplies — exact)."""
    m, k = A.shape
    n = B.shape[-1]
    As, ea = split_fixed_slices(A, slices)
    Bs_t, eb = split_fixed_slices(B.T, slices)
    Bs = tuple(b.T for b in Bs_t)
    hi, lo = _gemm_f64emu_fn(m, k, n, slices)(tuple(As), Bs)
    # scale in the widest dtype available: under x64 the exponent SUM ea+eb
    # (up to ±2000 after clamping) still fits f64's normal range; on the
    # f32-only target the sum clamps — saturating exactly like any f32
    # representation of the true product would
    sdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    esum = ea.astype(sdt)[:, None] + eb.astype(sdt)[None, :]
    sc = _exact_pow2(esum, sdt)
    return (hi.astype(sdt) * sc).astype(sdt), (lo.astype(sdt) * sc).astype(sdt)


def _hilo_add(h, l, x):
    """Fold x into the (hi, lo) accumulator error-free (2Sum)."""
    h2, t = _two_sum(h, x)
    return h2, l + t


def gemm_f64emu(A, B, alpha=1.0, beta=0.0, C=None, slices: int = 7,
                return_hilo: bool = False):
    """Double-precision-class ``alpha·A@B + beta·C`` on bf16 hardware via the
    exact Ozaki-style splitting above (2-D operands; complex handled as four
    real products).

    The whole combination — including ``beta·C`` — happens inside the
    double-f32 (hi, lo) accumulator, so residual-style calls
    (``alpha=1, beta=-1``) keep their accuracy even when the result is tiny
    against ``A@B`` (the catastrophic-cancellation case plain f32 loses).
    alpha/beta that are signed powers of two (the residual case) fold in
    exactly; general scalars round once in f32.

    Returns f64 where available (CPU testing), else the collapsed f32 —
    already carrying the compensated accumulation; pass ``return_hilo=True``
    for the raw (hi, lo) pair.  ``slices=7`` covers 56 mantissa bits
    (≥ f64's 53); smaller values trade accuracy for speed.
    """
    from ..core.exceptions import slate_assert

    A = jnp.asarray(A)
    B = jnp.asarray(B)
    slate_assert(A.ndim == 2 and B.ndim == 2,
                 "gemm_f64emu takes 2-D operands (vmap/batch outside)")
    if jnp.iscomplexobj(A) or jnp.iscomplexobj(B):
        Ar, Ai = jnp.real(A), jnp.imag(A)
        Br, Bi = jnp.real(B), jnp.imag(B)
        rr = gemm_f64emu(Ar, Br, slices=slices, return_hilo=True)
        ii = gemm_f64emu(Ai, Bi, slices=slices, return_hilo=True)
        ri = gemm_f64emu(Ar, Bi, slices=slices, return_hilo=True)
        ir = gemm_f64emu(Ai, Br, slices=slices, return_hilo=True)
        reh, rel = _hilo_add(rr[0], rr[1] - ii[1], -ii[0])
        imh, iml = _hilo_add(ri[0], ri[1] + ir[1], ir[0])
        cdt = jnp.complex128 if jax.config.jax_enable_x64 else jnp.complex64
        prod_h = reh.astype(cdt) + 1j * imh.astype(cdt)
        prod_l = rel.astype(cdt) + 1j * iml.astype(cdt)
        prod_h, prod_l = prod_h * alpha, prod_l * alpha
        if C is not None and beta != 0:
            prod_h, prod_l = _hilo_add(prod_h, prod_l,
                                       beta * jnp.asarray(C).astype(cdt))
        if return_hilo:
            return prod_h, prod_l
        return prod_h + prod_l
    hi, lo = _gemm_f64emu_real(A, B, slices)
    af = jnp.float32(alpha)
    hi, lo = hi * af, lo * af            # exact for signed powers of two
    if C is not None and beta != 0 and jnp.iscomplexobj(C):
        # real A·B with a complex C: the product contributes only to the real
        # part, but C's imaginary part must survive (previously it was
        # silently discarded by the f32 cast).  Fold beta·Re(C) into the real
        # accumulator and carry beta·Im(C) as its own split pair.
        Cf = jnp.asarray(C)
        bf = jnp.float32(beta)
        cr_hi = jnp.real(Cf).astype(jnp.float32)
        hi, lo = _hilo_add(hi, lo, bf * cr_hi)
        ci_hi = jnp.imag(Cf).astype(jnp.float32)
        im_h, im_l = bf * ci_hi, jnp.zeros_like(ci_hi)
        if Cf.dtype == jnp.dtype(jnp.complex128):
            cr = jnp.real(Cf)
            ci = jnp.imag(Cf)
            lo = lo + bf * (cr - cr_hi.astype(cr.dtype)).astype(jnp.float32)
            im_l = im_l + bf * (ci - ci_hi.astype(ci.dtype)).astype(jnp.float32)
        cdt = jnp.complex128 if jax.config.jax_enable_x64 else jnp.complex64
        prod_h = hi.astype(cdt) + 1j * im_h.astype(cdt)
        prod_l = lo.astype(cdt) + 1j * im_l.astype(cdt)
        if return_hilo:
            return prod_h, prod_l
        return prod_h + prod_l
    if C is not None and beta != 0:
        # fold C in as its own double-f32 split, so an f64 C (CPU testing /
        # a caller-carried hilo pair collapsed to f64) loses nothing; an f32
        # C bounds the result by its own storage precision, unavoidably
        Cf = jnp.asarray(C)
        bf = jnp.float32(beta)
        c_hi = Cf.astype(jnp.float32)
        hi, lo = _hilo_add(hi, lo, bf * c_hi)
        if Cf.dtype in (jnp.float64, jnp.dtype("float64")):
            c_lo = (Cf - c_hi.astype(Cf.dtype)).astype(jnp.float32)
            lo = lo + bf * c_lo
    if return_hilo:
        return hi, lo
    out_dt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    return hi.astype(out_dt) + lo.astype(out_dt)


def _f64ir_refine(A, B2, Xh, solve32, max_iterations: int,
                  tol_factor: float):
    """Shared refinement core of gesv_f64ir / posv_f64ir: double-f32 iterate,
    residuals through the compensated gemm, stagnation-aware stop.  Returns
    (Xh, Xl, iters, info): info = 1 when the f32 factor produced non-finite
    values (singular / not SPD) — the LAPACK-style signal the *_mixed
    drivers carry — in which case the loop never runs.

    Device-side throughout: the convergence test rides a ``lax.while_loop``
    carry, so the whole solve is jittable and costs ONE host sync at the
    caller's read-out instead of a host round-trip per refinement round."""
    Xl = jnp.zeros_like(Xh)
    finite = jnp.all(jnp.isfinite(Xh))
    eps32 = float(jnp.finfo(jnp.float32).eps)
    rdt = jnp.zeros((), Xh.dtype).real.dtype
    b_hi = B2.astype(Xh.dtype)
    bnorm = jnp.max(jnp.abs(b_hi))
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm).astype(rdt)
    anorm = jnp.max(jnp.abs(A)).astype(rdt)
    xnorm = jnp.max(jnp.abs(Xh))
    xnorm = jnp.where(xnorm == 0, 1.0, xnorm).astype(rdt)
    tol = tol_factor * (eps32 ** 2) * jnp.maximum(bnorm, anorm * xnorm)

    def cond(c):
        _, _, _, it, stop = c
        return (~stop) & (it < max_iterations)

    def body(c):
        Xh, Xl, prev, it, _ = c
        rh, rl = gemm_f64emu(A, Xh.astype(A.dtype), alpha=-1.0, beta=1.0,
                             C=B2, return_hilo=True)
        rh2, rl2 = gemm_f64emu(A, Xl.astype(A.dtype), alpha=-1.0,
                               return_hilo=True)
        rh, t = _two_sum(rh, rh2)
        rl = rl + rl2 + t
        rfull = rh + rl
        rmax = jnp.max(jnp.abs(rfull)).astype(rdt)
        stop = (rmax <= tol) | (rmax > 0.9 * prev)

        def refine(_):
            D = solve32(rfull.astype(Xh.dtype))
            Xh2, tt = _two_sum(Xh, D)
            return Xh2, Xl + tt

        Xh3, Xl3 = lax.cond(stop, lambda _: (Xh, Xl), refine, None)
        return Xh3, Xl3, rmax, it + 1, stop

    init = (Xh, Xl, jnp.asarray(jnp.inf, rdt), jnp.int32(0), ~finite)
    Xh, Xl, _, iters, _ = lax.while_loop(cond, body, init)
    info = jnp.where(finite, 0, 1).astype(jnp.int32)
    return Xh, Xl, iters, info


def gesv_f64ir(A, B, max_iterations: int = 20, tol_factor: float = 4.0):
    """Solve A X = B to double-precision-class accuracy on f32 hardware:
    f32 LU factor + iterative refinement whose residuals run through the
    exact-splitting gemm — SURVEY §7's "bf16/f32 factor, f64-emulated
    refine" made concrete (the reference's gesv_mixed with the refinement
    precision EMULATED instead of assumed in hardware).

    The iterate is carried as a double-f32 (Xh, Xl) pair; each round
    computes R = B - A·(Xh + Xl) inside the compensated accumulator (both
    halves through ``gemm_f64emu``'s hilo path), solves the f32 correction
    against the cached LU, and folds it in error-free.  Standard IR theory
    then gives forward error ~ eps_emu · cond(A), i.e. ~1e-13-class
    solutions for well-conditioned systems — on hardware whose native
    solve stops at ~1e-6.

    Returns ``(Xh, Xl, iterations, info)``: the solution is ``Xh + Xl``
    evaluated in f64 (or consumed as a pair on f64-less backends); info = 1
    means the f32 factor was singular (non-finite) and no refinement ran.
    Complex inputs factor in c64 and refine through the four-real-products
    gemm path.
    """
    A = jnp.asarray(A)
    B = jnp.asarray(B)
    vec = B.ndim == 1
    B2 = B[:, None] if vec else B
    lo_dt = jnp.complex64 if jnp.iscomplexobj(A) else jnp.float32
    Af = A.astype(lo_dt)
    plu, _, perm = lax.linalg.lu(Af)

    def solve32(R):
        pb = jnp.take(R, perm, axis=0)
        y = lax.linalg.triangular_solve(plu, pb, left_side=True, lower=True,
                                        unit_diagonal=True)
        return lax.linalg.triangular_solve(plu, y, left_side=True,
                                           lower=False)

    Xh = solve32(B2.astype(lo_dt))
    Xh, Xl, iters, info = _f64ir_refine(A, B2, Xh, solve32, max_iterations,
                                        tol_factor)
    return ((Xh[:, 0], Xl[:, 0], iters, info) if vec
            else (Xh, Xl, iters, info))


def posv_f64ir(A, B, max_iterations: int = 20, tol_factor: float = 4.0):
    """SPD/HPD sibling of ``gesv_f64ir`` (the posv_mixed counterpart): f32
    Cholesky factor + f64-emulated-residual refinement.  Same double-f32
    iterate and convergence policy; returns ``(Xh, Xl, iterations, info)``
    with info = 1 when A is not (numerically) positive definite."""
    A = jnp.asarray(A)
    B = jnp.asarray(B)
    vec = B.ndim == 1
    B2 = B[:, None] if vec else B
    lo_dt = jnp.complex64 if jnp.iscomplexobj(A) else jnp.float32
    Af = A.astype(lo_dt)
    L = lax.linalg.cholesky(Af)

    def solve32(R):
        y = lax.linalg.triangular_solve(L, R, left_side=True, lower=True)
        return lax.linalg.triangular_solve(L, y, left_side=True, lower=True,
                                           conjugate_a=True, transpose_a=True)

    Xh = solve32(B2.astype(lo_dt))
    Xh, Xl, iters, info = _f64ir_refine(A, B2, Xh, solve32, max_iterations,
                                        tol_factor)
    return ((Xh[:, 0], Xl[:, 0], iters, info) if vec
            else (Xh, Xl, iters, info))
