"""LAPACK job models: the operations and bytes a routine needs, from its
shapes alone, whatever implements it.

Flop counts follow LAPACK++ ``flops.hh`` (real arithmetic: its ``fmuls``
plus its ``fadds``, leading and lower-order terms as there).  Bytes are the
least traffic to and from device memory the routine needs, in the routine's
own dtype (pivots as int32): each operand read once and each result written
once, except where the routine must sweep an operand more than once (potrs
reads its factor's triangle in each of its two sweeps; getrs reads the unit
lower triangle of its packed factor in one and the upper in the other).
"""

from __future__ import annotations


def potrf_flops(n: int) -> float:
    return n ** 3 / 3.0 + n ** 2 / 2.0 + n / 6.0


def potrs_flops(n: int, nrhs: int) -> float:
    return 2.0 * n ** 2 * nrhs


def getrf_flops(m: int, n: int) -> float:
    k, big = min(m, n), max(m, n)
    return big * k ** 2 - k ** 3 / 3.0 - k ** 2 / 2.0 + 5.0 * k / 6.0


def getrs_flops(n: int, nrhs: int) -> float:
    return 2.0 * n ** 2 * nrhs


def geqrf_flops(m: int, n: int) -> float:
    if m >= n:
        return 2.0 * m * n ** 2 - 2.0 * n ** 3 / 3.0 + m * n + n ** 2 \
            + 14.0 * n / 3.0
    return 2.0 * n * m ** 2 - 2.0 * m ** 3 / 3.0 + 2 * m * n + 17.0 * m / 3.0


def job(routine: str, n: int, nrhs: int = 1, itemsize: int = 4,
        m: int | None = None) -> dict:
    """``{"flops": ..., "bytes": ...}`` for one call of ``routine``."""
    m = n if m is None else m
    tri = n * (n + 1) / 2.0 * itemsize          # one triangle of an n x n
    rhs = 2.0 * n * nrhs * itemsize             # B read, X written
    if routine == "posv":
        # read A's triangle, write L's
        return {"flops": potrf_flops(n) + potrs_flops(n, nrhs),
                "bytes": 2 * tri + rhs}
    if routine == "potrf":
        return {"flops": potrf_flops(n), "bytes": 2 * tri}
    if routine == "potrs":
        # read L's triangle once per sweep
        return {"flops": potrs_flops(n, nrhs), "bytes": 2 * tri + rhs}
    if routine == "getrf":
        # read A, write L\U and the pivots
        return {"flops": getrf_flops(m, n),
                "bytes": 2.0 * m * n * itemsize + 4.0 * min(m, n)}
    if routine == "getrs":
        # the factor's two triangles (one per sweep) and the pivots read
        return {"flops": getrs_flops(n, nrhs),
                "bytes": n * n * itemsize + 4.0 * n + rhs}
    if routine == "gesv":
        return {"flops": getrf_flops(n, n) + getrs_flops(n, nrhs),
                "bytes": 2.0 * n * n * itemsize + rhs}
    if routine == "gels":
        return {"flops": geqrf_flops(m, n) + 4.0 * m * n * nrhs,
                "bytes": 2.0 * m * n * itemsize
                + (m + n) * nrhs * itemsize}
    raise ValueError(f"no job model for {routine!r}")


def least_time_s(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound): the roofline's least time on a device with
    ``peaks`` and which of compute or memory bounds it."""
    t_c = flops / peaks["flops_per_s"]
    t_m = nbytes / peaks["bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
