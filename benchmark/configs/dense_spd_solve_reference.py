"""Plain reference for the dense SPD solve: the f64 scaled residual of each
answer, and the control, a plain blocked Cholesky solve whose matrix
products run at the next precision below the configuration's.

Imports nothing of the system under test.

The number compared is SLATE's tester check (``test/test_posv.cc``): the
scaled residual ``||A x - b||_F / (||A||_F ||x||_F)`` of each checked
solve.  The residual is computed in f64 on the host from the f32 operands,
a block of rows at a time (an f64 copy of the whole matrix would double
the host memory a check needs); ``||A||_F``, a normalization, may come in
f32.

The control computes the same solve as a right-looking recursive Cholesky
and blocked triangular sweeps, with every Schur-complement and off-diagonal
product at XLA's ``Precision.HIGH`` (bf16_3x on a TPU: three bf16 passes,
f32 accumulation), the step below the configuration's f32 at ``HIGHEST``.
A CPU computes every f32 product in full, so there the three passes are
spelled out (:func:`matmul_high_emulated`).  :func:`programs` puts the
control in the program's place, so that the run's own check judges it.
"""

from __future__ import annotations

import numpy as np

ROW_BLOCK = 4096


def scaled_residuals(a, xs, bs, a_norm=None):
    """``[||A x_j - b_j|| / (||A|| ||x_j||)]`` in f64 for the solves ``xs``,
    ``bs`` (each ``(n, nrhs)``) of one matrix ``a`` (host, f32).  ``a_norm``
    is ``||A||_F`` where the caller has it (a normalization, so f32 serves);
    otherwise it is summed here in f64."""
    k = len(xs)
    if k == 0:
        return []
    x = np.concatenate([np.asarray(v, np.float64) for v in xs], axis=1)
    b = np.concatenate([np.asarray(v, np.float64) for v in bs], axis=1)
    nrhs = x.shape[1] // k
    r2 = np.zeros(x.shape[1])
    a2 = 0.0
    for i in range(0, a.shape[0], ROW_BLOCK):
        blk = np.asarray(a[i:i + ROW_BLOCK], np.float64)
        r = blk @ x - b[i:i + len(blk)]
        r2 += np.einsum("ij,ij->j", r, r)
        if a_norm is None:
            a2 += float(np.einsum("ij,ij->", blk, blk))
    a_norm = np.sqrt(a2) if a_norm is None else float(a_norm)
    r = np.sqrt(r2.reshape(k, nrhs).sum(axis=1))
    xn = np.sqrt((x ** 2).reshape(x.shape[0], k, nrhs).sum(axis=(0, 2)))
    return list(r / (a_norm * xn))


# -- the control ------------------------------------------------------------

def _round_bf16(x):
    """f32 rounded to the nearest bf16 (ties to even), kept in f32, by bit
    arithmetic: XLA may drop an f32 -> bf16 -> f32 round trip of
    conversions as excess precision, which would make the control exact."""
    import jax.numpy as jnp
    from jax import lax

    u = lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return lax.bitcast_convert_type(u, jnp.float32)


def _split(x):
    hi = _round_bf16(x)
    return hi, _round_bf16(x - hi)


def matmul_high(a, b):
    """``a @ b`` at XLA's ``Precision.HIGH``: on a TPU, bf16_3x (three bf16
    passes, f32 accumulation).  Other backends ignore the precision and
    compute in full f32; there, use :func:`matmul_high_emulated`."""
    import jax.numpy as jnp
    from jax import lax

    return jnp.matmul(a, b, precision=lax.Precision.HIGH)


def matmul_high_emulated(a, b):
    """bf16_3x spelled out: hi*hi + hi*lo + lo*hi, each product of
    bf16-valued operands exact in f32, accumulated in f32, the same on
    every backend."""
    import jax.numpy as jnp
    from jax import lax

    ah, al = _split(a)
    bh, bl = _split(b)
    mm = lambda p, q: jnp.matmul(p, q, precision=lax.Precision.HIGHEST)
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


def matmul_highest(a, b):
    import jax.numpy as jnp
    from jax import lax

    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


BASE = 512


def cholesky(a, mm):
    """Lower Cholesky factor of ``a``: recursive halving, ``mm`` for the
    Schur-complement product, XLA's Cholesky at the base."""
    import jax.numpy as jnp
    from jax import lax

    n = a.shape[-1]
    if n <= BASE:
        return lax.linalg.cholesky(a, symmetrize_input=False)
    h = n // 2
    l11 = cholesky(a[:h, :h], mm)
    l21 = lax.linalg.triangular_solve(l11, a[h:, :h], left_side=False,
                                      lower=True, transpose_a=True)
    l22 = cholesky(a[h:, h:] - mm(l21, l21.T), mm)
    return jnp.block([[l11, jnp.zeros((h, n - h), a.dtype)], [l21, l22]])


def cholesky_solve(l, b, mm, block: int = 1024):
    """``x`` with ``L L^T x = b``: blocked forward and backward sweeps,
    ``mm`` for the off-diagonal products."""
    import jax.numpy as jnp
    from jax import lax

    n = l.shape[0]
    starts = list(range(0, n, block))
    y = []
    for i, s in enumerate(starts):
        e = min(s + block, n)
        r = b[s:e]
        if i:
            r = r - mm(l[s:e, :s], jnp.concatenate(y))
        y.append(lax.linalg.triangular_solve(l[s:e, s:e], r, left_side=True,
                                             lower=True))
    y = jnp.concatenate(y)
    x = [None] * len(starts)
    for i in range(len(starts) - 1, -1, -1):
        s, e = starts[i], min(starts[i] + block, n)
        r = y[s:e]
        if e < n:
            r = r - mm(l[e:, s:e].T, jnp.concatenate(x[i + 1:]))
        x[i] = lax.linalg.triangular_solve(l[s:e, s:e], r, left_side=True,
                                           lower=True, transpose_a=True)
    return jnp.concatenate(x)


def plain_posv(a, b, mm):
    return cholesky_solve(cholesky(a, mm), b, mm)


def plain_potrs(l, b, mm):
    return cholesky_solve(l, b, mm)


def programs(kind: str = "control"):
    """The reference in the program's place: the same calls, arguments and
    results as the system's programs (``posv``; ``potrf`` and ``potrs``).
    ``kind`` is ``"control"``, the timed routine's products one precision
    below the configuration's (a CPU spells the passes out), or
    ``"reference"``, at the stated precision.  The factor that potrs
    reuses is made in set-up, at the stated precision either way."""
    import jax
    import jax.numpy as jnp

    if kind == "reference":
        mm = matmul_highest
    elif jax.default_backend() == "tpu":
        mm = matmul_high
    else:
        mm = matmul_high_emulated

    def info():
        return jnp.zeros((), jnp.int32)

    return {"posv": lambda a, b: (plain_posv(a, b, mm), info()),
            "potrf": lambda a: (cholesky(a, matmul_highest), info()),
            "potrs": lambda l, b: plain_potrs(l, b, mm)}
