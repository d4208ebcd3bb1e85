"""Plain reference for the dense nonsymmetric solve: the f64 scaled residual
of each answer, and the control, a plain blocked LU solve with partial
pivoting whose matrix products run at the next precision below the
configuration's.

Imports nothing of the system under test.

The number compared is SLATE's tester check (``test/test_gesv.cc``): the
scaled residual ``||A x - b||_F / (||A||_F ||x||_F)`` of each checked solve,
the same as ``test_posv.cc``'s, so the check computes it with the dense SPD
reference's ``scaled_residuals`` (in f64 on the host, a block of rows at a
time).

The control factors ``P A = L U`` right-looking, one panel of ``PANEL``
columns at a time, each panel by a plain column-by-column partial-pivot loop
(``lax.linalg.lu`` of the whole matrix does not compile on a v5e at
n=16384), and solves with blocked sweeps.  Every trailing-update and
off-diagonal product runs at XLA's ``Precision.HIGH`` (bf16_3x on a TPU),
the step below the configuration's f32 at ``HIGHEST``; a CPU spells the
three passes out (the dense SPD reference's ``matmul_high_emulated``).
:func:`programs` puts it in the program's place, so that the run's own check
judges it.  The panel loop is rolled so the program compiles in seconds at
the cell's size.
"""

from __future__ import annotations

import os

_HERE = os.path.dirname(os.path.abspath(__file__))

#: columns of one panel of the control's LU; rows of one step of its sweeps
PANEL, SWEEP_BLOCK = 256, 1024


def _spd_reference():
    from benchlib.harness import load_module

    return load_module(os.path.join(_HERE, "dense_spd_solve_reference.py"),
                       "bench_dense_spd_solve_reference")


def _panel_lu(p, k0):
    """Partial pivoting, column by column, on the ``(n, w)`` panel ``p`` of
    columns ``k0:k0+w``: rows above ``k0`` are finished and stay put.
    Returns the factored panel and the panel's row order (``p[order]`` is
    what was factored)."""
    import jax.numpy as jnp
    from jax import lax

    n, w = p.shape
    rows = jnp.arange(n)

    def column(j, carry):
        p, order = carry
        r = k0 + j
        c = p[:, j]
        piv = jnp.argmax(jnp.where(rows >= r, jnp.abs(c), -1.0))
        swap = jnp.where(rows == r, piv, jnp.where(rows == piv, r, rows))
        p, order = p[swap], order[swap]
        l = jnp.where(rows > r, p[:, j] / p[r, j], 0.0)
        p = p.at[:, j].set(jnp.where(rows > r, l, p[:, j]))
        right = jnp.arange(w) > j
        return p - jnp.where(right[None, :], l[:, None] * p[r][None, :], 0.0), \
            order

    return lax.fori_loop(0, w, column, (p, rows))


def plain_lu(a, mm):
    """``(LU, perm)`` with ``a[perm] = L U``: right-looking blocked LU with
    partial pivoting, ``mm`` for the trailing update; the trailing update and
    the row solve run on the whole matrix with the finished part masked, so
    every panel step has one shape (n a multiple of ``PANEL``, as the
    configuration's sizes are)."""
    import jax.numpy as jnp
    from jax import lax

    n = a.shape[0]
    w = min(PANEL, n)
    rows = jnp.arange(n)

    def panel(k, carry):
        a, perm = carry
        k0 = k * w
        p, order = _panel_lu(lax.dynamic_slice_in_dim(a, k0, w, axis=1), k0)
        a, perm = a[order], perm[order]
        a = lax.dynamic_update_slice_in_dim(a, p, k0, axis=1)
        l11 = lax.dynamic_slice(a, (k0, k0), (w, w))
        u12 = lax.linalg.triangular_solve(
            l11, lax.dynamic_slice_in_dim(a, k0, w, axis=0), left_side=True,
            lower=True, unit_diagonal=True)
        right = rows >= k0 + w
        u12 = jnp.where(right[None, :], u12, 0.0)
        a = lax.dynamic_update_slice_in_dim(
            a, jnp.where(right[None, :], u12,
                         lax.dynamic_slice_in_dim(a, k0, w, axis=0)),
            k0, axis=0)
        l21 = jnp.where(right[:, None], lax.dynamic_slice_in_dim(a, k0, w,
                                                                 axis=1), 0.0)
        return a - mm(l21, u12), perm

    return lax.fori_loop(0, n // w, panel, (a, rows))


def lu_solve(lu, perm, b, mm, block: int = SWEEP_BLOCK):
    """``x`` with ``L U x = b[perm]``: blocked forward and backward sweeps,
    ``mm`` for the off-diagonal products."""
    import jax.numpy as jnp
    from jax import lax

    n = lu.shape[0]
    b = b[perm]
    starts = list(range(0, n, block))
    y = []
    for i, s in enumerate(starts):
        e = min(s + block, n)
        r = b[s:e]
        if i:
            r = r - mm(lu[s:e, :s], jnp.concatenate(y))
        y.append(lax.linalg.triangular_solve(lu[s:e, s:e], r, left_side=True,
                                             lower=True, unit_diagonal=True))
    y = jnp.concatenate(y)
    x = [None] * len(starts)
    for i in range(len(starts) - 1, -1, -1):
        s, e = starts[i], min(starts[i] + block, n)
        r = y[s:e]
        if e < n:
            r = r - mm(lu[s:e, e:], jnp.concatenate(x[i + 1:]))
        x[i] = lax.linalg.triangular_solve(lu[s:e, s:e], r, left_side=True,
                                           lower=False)
    return jnp.concatenate(x)


def programs(kind: str = "control"):
    """The reference in the program's place: the same call, arguments and
    results as the system's ``gesv`` program.  ``kind`` is ``"control"``,
    the products one precision below the configuration's (a CPU spells the
    passes out), or ``"reference"``, at the stated precision."""
    import jax
    import jax.numpy as jnp

    ref = _spd_reference()
    if kind == "reference":
        mm = ref.matmul_highest
    elif jax.default_backend() == "tpu":
        mm = ref.matmul_high
    else:
        mm = ref.matmul_high_emulated

    def gesv(a, b):
        lu, perm = plain_lu(a, mm)
        return lu_solve(lu, perm, b, mm), jnp.zeros((), jnp.int32)

    return {"gesv": gesv}
