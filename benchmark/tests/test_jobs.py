"""The job models against LAPACK++ ``flops.hh`` (``fmuls`` + ``fadds``)
and the least traffic each routine needs."""

import pytest

from benchlib.jobs import job


def lapackpp_getrf(m, n):
    if m >= n:
        fmuls = 0.5 * m * n * n - n ** 3 / 6 + 0.5 * m * n - 0.5 * n * n \
            + 2 * n / 3
        fadds = 0.5 * m * n * n - n ** 3 / 6 - 0.5 * m * n + n / 6
    else:
        fmuls = 0.5 * n * m * m - m ** 3 / 6 + 0.5 * n * m - 0.5 * m * m \
            + 2 * m / 3
        fadds = 0.5 * n * m * m - m ** 3 / 6 - 0.5 * n * m + m / 6
    return fmuls + fadds


@pytest.mark.parametrize("m, n", [(16384, 16384), (1000, 300), (300, 1000),
                                  (1, 1)])
def test_getrf_flops_and_bytes(m, n):
    j = job("getrf", n, m=m, itemsize=4)
    assert j["flops"] == pytest.approx(lapackpp_getrf(m, n), rel=1e-12)
    # A read, L\U written, the pivots (int32) written
    assert j["bytes"] == 2 * m * n * 4 + 4 * min(m, n)


def test_getrf_is_two_thirds_n_cubed():
    n = 16384
    assert job("getrf", n)["flops"] == pytest.approx(2 * n ** 3 / 3, rel=1e-4)


@pytest.mark.parametrize("n, nrhs, itemsize", [(16384, 16, 4), (512, 1, 8)])
def test_getrs_flops_and_bytes(n, nrhs, itemsize):
    j = job("getrs", n, nrhs, itemsize=itemsize)
    assert j["flops"] == 2 * n * n * nrhs
    # each sweep reads its own triangle of the packed factor (n^2 in all),
    # the pivots once, B once, and X is written once
    assert j["bytes"] == n * n * itemsize + 4 * n + 2 * n * nrhs * itemsize


def test_gesv_is_getrf_and_getrs_flops():
    n, nrhs = 4096, 16
    assert job("gesv", n, nrhs)["flops"] == pytest.approx(
        job("getrf", n)["flops"] + job("getrs", n, nrhs)["flops"])


def test_potrs_reads_its_triangle_once_per_sweep():
    n, nrhs = 1024, 8
    assert job("potrs", n, nrhs)["bytes"] == \
        2 * n * (n + 1) / 2 * 4 + 2 * n * nrhs * 4


def test_unknown_routine_is_refused():
    with pytest.raises(ValueError, match="no job model"):
        job("heev", 64)
