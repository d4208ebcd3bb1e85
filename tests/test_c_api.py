"""C API tier (reference unit_test/test_c_api.cc + src/c_api): compiles a real
C program against include/slate_tpu.h, links the embedded-runtime shared
library, and runs it in a clean process."""

import os
import shutil
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE = os.path.join(_ROOT, "native")
_LIB = os.path.join(_NATIVE, "libslate_c_api.so")


def _have_toolchain():
    return shutil.which("gcc") is not None and shutil.which("make") is not None


@pytest.mark.skipif(not _have_toolchain(), reason="no C toolchain")
def test_c_api_end_to_end(tmp_path):
    build = subprocess.run(["make", "-C", _NATIVE, "libslate_c_api.so"],
                           capture_output=True, text=True, timeout=180)
    assert build.returncode == 0, build.stderr[-2000:]

    exe = str(tmp_path / "c_api_check")
    cc = subprocess.run(
        ["gcc", os.path.join(_ROOT, "tests", "c_api_check.c"),
         "-I", os.path.join(_ROOT, "include"), "-L", _NATIVE,
         "-lslate_c_api", f"-Wl,-rpath,{_NATIVE}", "-lm", "-o", exe],
        capture_output=True, text=True, timeout=120)
    assert cc.returncode == 0, cc.stderr[-2000:]

    env = dict(os.environ)
    env.update({"SLATE_TPU_ROOT": _ROOT, "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    run = subprocess.run([exe], capture_output=True, text=True, timeout=600,
                         env=env)
    sys.stdout.write(run.stdout)
    assert run.returncode == 0, run.stdout[-3000:] + run.stderr[-2000:]
    assert "C_API PASS" in run.stdout


@pytest.mark.skipif(not _have_toolchain(), reason="no C toolchain")
def test_c_blas_example(tmp_path):
    """examples/c/ex05_blas.c (reference examples/c_api/ex05_blas.c):
    a C gemm against a naive reference through the embedded runtime."""
    build = subprocess.run(["make", "-C", _NATIVE, "libslate_c_api.so"],
                           capture_output=True, text=True, timeout=180)
    assert build.returncode == 0, build.stderr[-2000:]
    exe = str(tmp_path / "ex05")
    cc = subprocess.run(
        ["gcc", os.path.join(_ROOT, "examples", "c", "ex05_blas.c"),
         "-I", os.path.join(_ROOT, "include"), "-L", _NATIVE,
         "-lslate_c_api", f"-Wl,-rpath,{_NATIVE}", "-lm", "-o", exe],
        capture_output=True, text=True, timeout=120)
    assert cc.returncode == 0, cc.stderr[-2000:]
    env = dict(os.environ)
    env.update({"SLATE_TPU_ROOT": _ROOT, "JAX_PLATFORMS": "cpu"})
    run = subprocess.run([exe], capture_output=True, text=True, timeout=300,
                         env=env)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    assert "ex05 OK" in run.stdout
