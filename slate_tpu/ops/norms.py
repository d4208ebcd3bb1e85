"""Matrix norms over general / symmetric / triangular / band structures.

Reference analogue: ``src/internal/internal_{ge,he,sy,tr,gb,hb}norm.cc`` plus the CUDA
reductions ``src/cuda/device_{ge,he,sy,tr}norm.cu`` and the drivers ``src/norm.cc`` /
``src/colNorms.cc``.

TPU re-design: each norm is one masked XLA reduction over the HBM-resident array —
the per-tile partial-norm + host-combine structure of the reference exists only to
span GPUs and ranks, which the sharded reduction handles natively (psum over the mesh
when the array is sharded).  One-norm of a symmetric matrix uses the
half-stored form directly, like synorm/henorm do: col_sums(full) =
col_sums(stored triangle) + row_sums(strict stored triangle) transposed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.exceptions import SlateError
from ..core.types import Diag, Norm, NormScope, Uplo
from .elementwise import _mask
from . import pallas_norms as _pk

#: route 2-D unbatched norms through the Pallas streaming kernels on TPU
#: (set False to force the plain XLA reductions; tests cover both paths)
USE_PALLAS = True

_PK_WHICH = {Norm.Max: "max", Norm.One: "one", Norm.Inf: "inf", Norm.Fro: "fro"}


def _pallas_ok(A) -> bool:
    # complex dtypes stay on the XLA path: Mosaic has no complex lowering, so
    # the kernel's jnp.abs would fail to compile on the real TPU backend
    return (USE_PALLAS and getattr(A, "ndim", 0) == 2
            and not jnp.issubdtype(getattr(A, "dtype", jnp.float32),
                                   jnp.complexfloating)
            and jax.default_backend() == "tpu")


def _abs(A):
    return jnp.abs(A)


def genorm(norm, A, scope=NormScope.Matrix):
    """General-matrix norm (internal_genorm.cc, device_genorm.cu).

    scope=Columns returns the vector of column norms (the colNorms driver,
    src/colNorms.cc — only Max is supported there, like the reference).
    """
    norm = Norm.from_string(norm)
    scope = NormScope.from_string(scope) if not isinstance(scope, NormScope) else scope
    if scope == NormScope.Columns:
        if norm != Norm.Max:
            raise SlateError("colNorms supports Norm.Max only (matches reference)")
        if _pallas_ok(A):
            return _pk.col_norms_max(A)
        return jnp.max(_abs(A), axis=-2)
    if _pallas_ok(A) and norm in _PK_WHICH:
        return _pk.genorm(A, _PK_WHICH[norm])
    a = _abs(A)
    if norm == Norm.Max:
        return jnp.max(a)
    if norm == Norm.One:
        return jnp.max(jnp.sum(a, axis=-2))
    if norm == Norm.Inf:
        return jnp.max(jnp.sum(a, axis=-1))
    if norm == Norm.Fro:
        return jnp.sqrt(jnp.sum(jnp.square(a)))
    raise SlateError(f"unsupported norm {norm}")


def _masked(A, uplo, diag=Diag.NonUnit):
    mask = _mask(A.shape, uplo)
    a = jnp.where(mask, A, 0)
    if Diag.from_string(diag) == Diag.Unit:
        idx = jnp.arange(min(A.shape[-2:]))
        a = a.at[..., idx, idx].set(jnp.ones((), A.dtype))
    return a


def trnorm(norm, uplo, diag, A):
    """Trapezoid/triangular norm (internal_trnorm.cc, device_trnorm.cu).

    On TPU the triangle mask is applied in-register inside the Pallas kernel
    instead of materializing the masked matrix in HBM."""
    which = _PK_WHICH.get(Norm.from_string(norm))
    if _pallas_ok(A) and which is not None:
        lower = Uplo.from_string(uplo) == Uplo.Lower
        mode = _pk._MODE_LOWER if lower else _pk._MODE_UPPER
        return _pk.genorm(A, which, mode=mode,
                          unit_diag=Diag.from_string(diag) == Diag.Unit)
    return genorm(norm, _masked(A, uplo, diag))


def synorm(norm, uplo, A):
    """Symmetric norm from the stored triangle (internal_synorm.cc).

    One == Inf by symmetry; column sums combine the stored triangle's columns with its
    strict rows (synormOffdiag device kernel, device.hh:234-240).
    """
    norm = Norm.from_string(norm)
    lower = Uplo.from_string(uplo) == Uplo.Lower
    absA = jnp.abs(A)
    tri = jnp.tril(absA) if lower else jnp.triu(absA)          # stored triangle
    strict = jnp.tril(absA, -1) if lower else jnp.triu(absA, 1)  # excl. diagonal
    if norm == Norm.Max:
        return jnp.max(tri)
    if norm in (Norm.One, Norm.Inf):
        col = jnp.sum(tri, axis=-2) + jnp.sum(strict, axis=-1)
        return jnp.max(col)
    if norm == Norm.Fro:
        diag_sq = jnp.sum(jnp.square(jnp.abs(jnp.diagonal(A, axis1=-2, axis2=-1))))
        off_sq = jnp.sum(jnp.square(strict))
        return jnp.sqrt(2.0 * off_sq + diag_sq)
    raise SlateError(f"unsupported norm {norm}")


def henorm(norm, uplo, A):
    """Hermitian norm (internal_henorm.cc) — same combine as synorm; |.| removes the
    conjugation difference."""
    return synorm(norm, uplo, A)


def gbnorm(norm, kl, ku, A):
    """Band norm (internal_gbnorm.cc): mask outside the band then reduce."""
    m, n = A.shape[-2], A.shape[-1]
    r = jnp.arange(m)[:, None]
    c = jnp.arange(n)[None, :]
    band = (c - r <= ku) & (r - c <= kl)
    return genorm(norm, jnp.where(band, A, 0))


def hbnorm(norm, uplo, kd, A):
    """Hermitian band norm (internal_hbnorm.cc)."""
    n = A.shape[-1]
    r = jnp.arange(n)[:, None]
    c = jnp.arange(n)[None, :]
    if Uplo.from_string(uplo) == Uplo.Lower:
        band = (r - c <= kd) & (r >= c)
    else:
        band = (c - r <= kd) & (c >= r)
    return synorm(norm, uplo, jnp.where(band, A, 0))
