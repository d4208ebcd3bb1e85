"""Compiled Fortran smoke tier (reference: tools/fortran wrappers + its
Fortran examples).  Skips when no Fortran compiler is present (the dev image
carries none); CI installs gfortran and runs it for real."""

import os
import shutil
import subprocess

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE = os.path.join(_ROOT, "native")


def _fc():
    for cand in ("gfortran", "flang", "ifort"):
        if shutil.which(cand):
            return cand
    return None


@pytest.mark.skipif(_fc() is None, reason="no Fortran compiler")
def test_fortran_smoke(tmp_path):
    build = subprocess.run(["make", "-C", _NATIVE, "libslate_c_api.so"],
                           capture_output=True, text=True, timeout=180)
    assert build.returncode == 0, build.stderr[-2000:]

    exe = str(tmp_path / "smoke")
    fc = subprocess.run(
        [_fc(), os.path.join(_ROOT, "tools", "fortran", "slate_tpu.f90"),
         os.path.join(_ROOT, "tools", "fortran", "smoke.f90"),
         "-J", str(tmp_path), "-L", _NATIVE, "-lslate_c_api",
         f"-Wl,-rpath,{_NATIVE}", "-o", exe],
        capture_output=True, text=True, timeout=120)
    assert fc.returncode == 0, fc.stderr[-2000:]

    env = dict(os.environ)
    env.update({"SLATE_TPU_ROOT": _ROOT, "JAX_PLATFORMS": "cpu"})
    run = subprocess.run([exe], capture_output=True, text=True, timeout=600,
                         env=env)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    assert "FORTRAN PASS" in run.stdout


def test_fortran_module_in_sync_with_header():
    """The committed slate_tpu.f90 is exactly what gen_fortran.py emits from
    the current C header — full-surface coverage (57 interfaces vs the
    round-4 handwritten 4), no drift."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "gen_fortran", os.path.join(_ROOT, "tools", "fortran",
                                    "gen_fortran.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    decls = gen.parse(gen.HEADER)
    assert len(decls) >= 50, "header scrape lost declarations"
    names = {d[1] for d in decls}
    # every C-API entry point the smoke program and conformance tier use
    for required in ("slate_dgesv", "slate_dgetrf", "slate_dgetrs",
                     "slate_dsyev", "slate_zgemm", "slate_matrix_gesvd"):
        assert required in names, required
    with open(os.path.join(_ROOT, "tools", "fortran", "slate_tpu.f90")) as f:
        committed = f.read()
    assert gen.emit(decls) == committed, \
        "slate_tpu.f90 is stale — rerun tools/fortran/gen_fortran.py"


@pytest.mark.skipif(_fc() is None, reason="no Fortran compiler")
def test_fortran_blas_example(tmp_path):
    """examples/fortran/ex05_blas.f90 (reference examples/fortran/ex05):
    Fortran gemm through the generated module + embedded runtime."""
    build = subprocess.run(["make", "-C", _NATIVE, "libslate_c_api.so"],
                           capture_output=True, text=True, timeout=180)
    assert build.returncode == 0, build.stderr[-2000:]
    exe = str(tmp_path / "ex05f")
    fc = subprocess.run(
        [_fc(), os.path.join(_ROOT, "tools", "fortran", "slate_tpu.f90"),
         os.path.join(_ROOT, "examples", "fortran", "ex05_blas.f90"),
         "-J", str(tmp_path), "-L", _NATIVE, "-lslate_c_api",
         f"-Wl,-rpath,{_NATIVE}", "-o", exe],
        capture_output=True, text=True, timeout=120)
    assert fc.returncode == 0, fc.stderr[-2000:]
    env = dict(os.environ)
    env.update({"SLATE_TPU_ROOT": _ROOT, "JAX_PLATFORMS": "cpu"})
    run = subprocess.run([exe], capture_output=True, text=True, timeout=300,
                         env=env)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    assert "ex05 OK" in run.stdout
